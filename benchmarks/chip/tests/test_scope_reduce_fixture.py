"""scope_reduce.py on a small recorded trace and its compiled step's text:
three steps of the program's own flagship train step at a tiny size on
one TPU v5e chip (tools/record_fixture_scopes.py, recorded on the chip
in PR 24): two layers under the scan, both Pallas kernels, AdamW, batches
through ``device_prefetch``.

The expected nanoseconds were taken once with an independent reduction
(a one-nanosecond boolean timeline per kind, each instruction classified
by a direct grep of its fused computation in the text) and agree with
the reducer's to the nanosecond. And the fixture that was there reads
as it did."""

import os

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
TRACE = os.path.join(HERE, "fixture_scopes.xplane.pb")
TEXT = os.path.join(HERE, "fixture_scopes.hlo.txt")
OLD_TRACE = os.path.join(HERE, "fixture_trace.xplane.pb")
HOST_PREFIXES = ("bench.", "hvd.")


@pytest.fixture(scope="module")
def trace():
    import trace_reduce
    return trace_reduce.load(TRACE, None, HOST_PREFIXES)


@pytest.fixture(scope="module")
def scopes():
    import scope_reduce
    with open(TEXT) as f:
        return scope_reduce.parse_hlo(f.read())


def test_what_the_fixture_holds(trace, scopes):
    import scope_reduce as sr
    assert sorted(trace.devices) == [0]
    dev = trace.devices[0]
    assert len(dev.ops) == 1353          # 451 instructions a step
    assert sum(e.opcode == "while" for e in dev.ops) == 6   # fwd, bwd scan
    assert [e.name for e in trace.host_spans] == [
        "bench.input", "hvd.input.source", "hvd.input.place",
        "bench.dispatch", "bench.wait"] * 3
    # text and trace are of one executable
    assert sr.has_scopes(scopes)
    assert sr.unmatched(trace, scopes) == 0
    # both kernels carry their phase, by metadata and not by name
    kernels = {e.name for e in dev.ops if e.name.startswith("hvd_")}
    assert {n.split(".")[0] for n in kernels} == {
        "hvd_flash_attention", "hvd_fused_xent"}
    for name in kernels:
        want = "hvd.attention.core" if "flash" in name else "hvd.head"
        assert want in scopes[name].phases and scopes[name].kind == "fwd"


def test_the_five_kinds_on_the_recorded_step(trace, scopes):
    import scope_reduce as sr
    import trace_reduce as tr
    got = {k: sr.reduce(trace, scopes, {"kind": k})
           for k in ("fwd", "bwd", "opt", "mixed", "unscoped")}
    assert got == {"fwd": 115187.0, "bwd": 134282.0, "opt": 21648.0,
                   "mixed": 6767.0, "unscoped": 82487.0}
    dev = trace.devices[0]
    # a partition: every compute instruction in exactly one kind; on this
    # trace no two instructions overlap, so the kinds are the compute
    # cover; the rest of the busy time is what only the two scans cover
    assert sum(got.values()) == 360371.0 == tr.length(tr.compute(dev))
    assert tr.reduce_device(dev, None, "busy") == 362527.0
    # at this size XLA's own copies are a quarter of the step; the mixed
    # fusions are the gradients that meet their update outside the scan:
    # the tied embedding's and the final norm's
    mixed = sr.top_instructions(trace, scopes, {"kind": "mixed"}, 3)
    assert [m[0] for m in mixed] == ["fusion (f32[512,256],..)",
                                     "fusion (f32[256],..)"]


def test_phases_on_the_recorded_step(trace, scopes):
    import scope_reduce as sr
    got = {p: sr.reduce(trace, scopes, {"phase": "hvd." + p})
           for p in ("embed", "layers", "attention", "attention.core",
                     "mlp", "head", "optimizer", "grad_sync", "moe")}
    assert got == {
        "embed": 37405.0, "layers": 224899.0, "attention": 137207.0,
        "attention.core": 81805.0, "mlp": 43878.0, "head": 22608.0,
        # opt + the mixed fusion
        "optimizer": 21648.0 + 6767.0,
        # one chip: no psum, so no such scope in the program; and no MoE
        "grad_sync": None, "moe": None}
    # the core is inside the block, the block inside the stack
    assert got["attention.core"] < got["attention"] < got["layers"]


def test_host_spans_and_idle_gaps(trace):
    import scope_reduce as sr
    import trace_reduce as tr
    assert sr.host_span_median_ms(trace, "hvd.input.source") == 0.13591
    assert sr.host_span_median_ms(trace, "hvd.input.place") == 0.57841
    assert sr.host_span_median_ms(trace, "hvd.input.decode") is None
    # the program's spans lie inside the benchmark's
    spans = trace.host_spans
    for i in range(0, len(spans), 5):
        outer, source, place = spans[i:i + 3]
        assert outer.start <= source.start and source.end <= place.start
        assert place.end <= outer.end
    gaps = sr.idle_gaps(trace, 4)
    assert [g[0] for g in gaps] == [
        "bench.wait", "bench.dispatch", "bench.input/hvd.input.place",
        "bench.input/hvd.input.place"]
    # the same gaps as trace_reduce's, which knows only the outer name
    outer_only = tr.Trace(trace.devices, [
        s for s in spans if s.name.startswith("bench.")])
    assert [[g[0].split("/")[0], g[1]] for g in gaps] == \
        tr.idle_gaps(outer_only, 4)


def test_read_metric_on_the_fixture(trace):
    import scope_reduce as sr
    with open(TEXT) as f:
        ctx = {"trace": trace, "hlo_text": f.read(), "trace_steps": 3}
    read = {"trace_scope": {"kind": "bwd"}, "per_step": True, "scale": 1e-6}
    assert sr.read_metric(read, ctx) == pytest.approx(134282.0 / 3 / 1e6)
    assert sr.read_metric({"host_span": "hvd.input.place",
                           "reduce": "median_ms"}, ctx) == 0.57841


def test_the_traces_own_metadata_agrees_with_the_text(trace, scopes):
    """The fallback: the tf_op stat of the event metadata, read from the
    protobuf's wire format. Where the text gives an instruction an
    op_name of its own the trace gives the same (it also hands a scan's
    name down to instructions of its body that have none); what the
    fallback cannot see is a fusion's body."""
    import scope_reduce as sr
    from_trace = sr.trace_op_names(TRACE)
    with open(TEXT) as f:
        own = {}
        for line in f:
            m = sr._INSTRUCTION.match(line)
            op = sr._OP_NAME.search(line) if m else None
            if op:
                own[m.group(1)] = op.group(1).replace("\\'", "'")
    executed = {e.name for e in trace.devices[0].ops}
    # 330 distinct instructions ran; XLA's own copies have no metadata
    named = executed & set(from_trace)
    assert len(executed) == 330 and len(named) == 159
    assert sum(n in own for n in named) == 151
    assert all(from_trace[n] == own[n] for n in named if n in own)
    fallback = sr.scopes_from_trace(TRACE)
    assert sr.has_scopes(fallback)
    kernel = next(n for n in executed if n.startswith("hvd_flash"))
    assert fallback[kernel] == scopes[kernel]
    # no body: the mixed fusion reads as its root's direction only
    mixed = [n for n in executed if scopes[n].kind == "mixed"]
    assert mixed and all(fallback[n].kind != "mixed" for n in mixed)


def test_the_fixture_that_was_there_reads_as_it_did():
    """Host spans loaded with both prefixes and gaps named by
    scope_reduce change nothing where the program has no spans of its
    own; a trace without text gives no scope metric and does not raise."""
    import scope_reduce as sr
    import trace_reduce as tr
    old, new = tr.load(OLD_TRACE), tr.load(OLD_TRACE, None, HOST_PREFIXES)
    assert new == old
    assert sr.idle_gaps(new, 5) == tr.idle_gaps(old, 5)
    assert sr.read_metric({"trace_scope": {"kind": "fwd"}},
                          {"trace": new, "hlo_text": None,
                           "trace_steps": 4}) is None
    # its program (PR 23's) has no hvd.* scope: nothing to report
    assert not sr.has_scopes(sr.scopes_from_trace(OLD_TRACE))
    assert sr.reduce(new, sr.scopes_from_trace(OLD_TRACE),
                     {"kind": "unscoped"}) is None
