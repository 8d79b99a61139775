"""scope_reduce.py on an HLO text and a trace written by hand: a scan
(``while``) with a forward and a backward fusion in its body, a
weight-gradient fusion with the AdamW update fused in (mixed), a
backward fusion that recomputes a forward cast, a plain update, a
collective and XLA's own prefetch copies."""

import pytest

HLO = '''HloModule jit_step, is_scheduled=true

FileNames
1 "/x/transformer.py"

%region_0.1 (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %add.1 = f32[] add(%a, %b), metadata={op_name="jit(step)/jvp(hvd.head)/reduce_sum"}
}

%fused_computation.1 (p0: bf16[8,8]) -> bf16[8,8] {
  %p0 = bf16[8,8] parameter(0)
  ROOT %dot.1 = bf16[8,8] dot(%p0, %p0), metadata={op_name="jit(step)/jvp()/while/body/closed_call/hvd.attention/dot_general" stack_frame_id=3}
}

%fused_computation.2 (p0: bf16[8,8]) -> bf16[8,8] {
  %p0.1 = bf16[8,8] parameter(0)
  %convert.1 = f32[8,8] convert(%p0.1), metadata={op_name="jit(step)/jvp()/while/body/closed_call/hvd.attention/hvd.attention.core/convert_element_type"}
  ROOT %dot.2 = bf16[8,8] dot(%convert.1, %p0.1), metadata={op_name="jit(step)/transpose(jvp())/while/body/closed_call/hvd.attention/hvd.attention.core/dot_general"}
}

%nested (p: f32[8,8]) -> f32[8,8] {
  %p = f32[8,8] parameter(0)
  ROOT %mul.9 = f32[8,8] multiply(%p, %p), metadata={op_name="jit(step)/hvd.optimizer/mul"}
}

%fused_computation.3 (p0: bf16[8,8], p1: f32[8,8]) -> f32[8,8] {
  %p0.2 = bf16[8,8] parameter(0)
  %p1.2 = f32[8,8] parameter(1)
  %dot.3 = f32[8,8] dot(%p0.2, %p0.2), metadata={op_name="jit(step)/transpose(jvp(Bert))/layer_0/hvd.mlp/ffn_in/dot_general"}
  %fusion.9 = f32[8,8] fusion(%p1.2), kind=kLoop, calls=%nested
  ROOT %add.3 = f32[8,8] add(%dot.3, %fusion.9)
}

%fused_computation.4 (p0: f32[8,8]) -> f32[8,8] {
  %p0.3 = f32[8,8] parameter(0)
  ROOT %sqrt.1 = f32[8,8] sqrt(%p0.3), metadata={op_name="jit(step)/hvd.optimizer/sqrt"}
}

%body (t: (s32[], bf16[8,8])) -> (s32[], bf16[8,8]) {
  %t = (s32[], bf16[8,8]) parameter(0)
  %x = bf16[8,8] get-tuple-element(%t), index=1
  %fusion.1 = bf16[8,8] fusion(%x), kind=kOutput, calls=%fused_computation.1, metadata={op_name="jit(step)/jvp()/while/body/closed_call/hvd.attention/dot_general"}
  %fusion.2 = bf16[8,8] fusion(%fusion.1), kind=kOutput, calls=%fused_computation.2
  %hvd_flash_attention.6 = (bf16[8,8], f32[8]) custom-call(%fusion.2), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jvp()/while/body/closed_call/hvd.attention/hvd.attention.core/hvd_flash_attention/pallas_call"}, backend_config={"custom_call_config":{"body":"op_name=\\"not/hvd.mlp/this\\""}}
  ROOT %tuple.1 = (s32[], bf16[8,8]) tuple(%x, %fusion.2)
}

ENTRY %main.1 (w: bf16[8,8], m: f32[8,8]) -> f32[8,8] {
  %w = bf16[8,8] parameter(0)
  %m = f32[8,8] parameter(1)
  %copy-start.1 = (bf16[8,8], bf16[8,8], u32[]) copy-start(%w)
  %copy-done.1 = bf16[8,8] copy-done(%copy-start.1)
  %while.1 = (s32[], bf16[8,8]) while(%copy-done.1), condition=%cond, body=%body
  %all-reduce.1 = bf16[8,8] all-reduce(%w), to_apply=%region_0.1, metadata={op_name="jit(step)/hvd.grad_sync/psum"}
  %fusion.3 = f32[8,8] fusion(%all-reduce.1, %m), kind=kOutput, calls=%fused_computation.3
  ROOT %fusion.4 = f32[8,8] fusion(%fusion.3), kind=kLoop, calls=%fused_computation.4
}
'''


def _trace(ops, host=()):
    import trace_reduce as tr
    # the opcode as the trace gives it; a fusion's and a kernel's by name
    def opcode(name):
        if name.startswith("fusion"):
            return "fusion"
        if name.startswith("hvd_"):
            return "custom-call"
        return name.rsplit(".", 1)[0]
    return tr.Trace({0: tr.DeviceTrace(
        [tr.Event(n, s, d, opcode(n)) for n, s, d in ops], [])},
        [tr.Event(n, s, d) for n, s, d in host])


# one step: the prefetch, the scan (two iterations of forward fusion,
# backward fusion, kernel), the all-reduce, the mixed fusion, the update
OPS = [
    ("copy-start.1", 0, 1), ("copy-done.1", 1, 2),
    ("while.1", 3, 60),
    ("fusion.1", 3, 10), ("fusion.2", 13, 7), ("hvd_flash_attention.6", 20, 5),
    ("fusion.1", 30, 10), ("fusion.2", 39, 9),       # overlap by 1 ns
    ("hvd_flash_attention.6", 50, 5),
    ("all-reduce.1", 63, 20),
    ("fusion.3", 83, 30), ("fusion.4", 113, 7),
]


def test_classify_peels_wrappers_and_matches_whole_components():
    import scope_reduce as sr
    c = sr.classify
    assert c("jit(step)/jvp(hvd.head)/mul") == sr.Scopes(
        frozenset({"hvd.head"}), frozenset({"fwd"}))
    assert c("jit(step)/transpose(jvp(hvd.head))/mul").kind == "bwd"
    # flax: the wrapper is on the outermost module, the phase stands plain
    s = c("jit(step)/transpose(jvp(Bert))/layer_3/hvd.attention/attention/"
          "hvd.attention.core/div")
    assert s.phases == {"hvd.attention", "hvd.attention.core"}
    assert s.kind == "bwd"
    # a whole component, not a substring
    assert c("jit(f)/jvp(hvd.attention.core)/add").phases == {
        "hvd.attention.core"}
    assert c("jit(step)/hvd.optimizer/add").kind == "opt"
    assert c("jit(step)/hvd.grad_sync/psum").kind == "bwd"
    assert c("jit(step)/jvp()/while/body/dynamic_slice") == sr.NO_SCOPE
    assert c("jit(step)/my_hvd.head_thing/add") == sr.NO_SCOPE


def test_parse_hlo_gives_a_fusion_its_bodys_scopes():
    import scope_reduce as sr
    scopes = sr.parse_hlo(HLO)
    kinds = {n: scopes[n].kind for n in (
        "fusion.1", "fusion.2", "fusion.3", "fusion.4",
        "hvd_flash_attention.6", "copy-done.1", "while.1", "all-reduce.1")}
    assert kinds == {
        "fusion.1": "fwd",
        # a forward cast recomputed inside a backward fusion: backward
        "fusion.2": "bwd",
        # a weight gradient with the update (through a nested fusion)
        "fusion.3": "mixed",
        "fusion.4": "opt",
        "hvd_flash_attention.6": "fwd",
        "copy-done.1": "unscoped",
        # a container's own line carries no scope: its body's do
        "while.1": "unscoped",
        "all-reduce.1": "bwd",
    }
    assert scopes["fusion.2"].directions == {"fwd", "bwd"}
    assert scopes["fusion.3"].phases == {"hvd.mlp", "hvd.optimizer"}
    # the kernel's op_name is its metadata's, not a string in its body
    assert scopes["hvd_flash_attention.6"].phases == {
        "hvd.attention", "hvd.attention.core"}
    assert sr.has_scopes(scopes)
    assert not sr.has_scopes(sr.parse_hlo(HLO.replace("hvd.", "xyz.")))
    assert not sr.has_scopes(None) and not sr.has_scopes({})


def test_the_five_kinds_partition_the_compute_cover():
    import scope_reduce as sr
    import trace_reduce as tr
    trace, scopes = _trace(OPS), sr.parse_hlo(HLO)
    got = {k: sr.reduce(trace, scopes, {"kind": k})
           for k in ("fwd", "bwd", "opt", "mixed", "unscoped")}
    assert got == {
        "fwd": 10 + 5 + 10 + 5,     # fusion.1 and the kernel, twice
        "bwd": 7 + 9,               # fusion.2; the all-reduce is left out
        "opt": 7, "mixed": 30,
        "unscoped": 1 + 2,          # the prefetch; never the while
    }
    # every compute instruction is in exactly one kind ...
    dev = trace.devices[0]
    assert sum(e.dur for e in sr.compute_events(dev)) == sum(got.values())
    # ... so the kinds add up to the compute cover but for what
    # neighbouring instructions overlap (fusion.1 and fusion.2, 1 ns)
    assert sum(got.values()) - tr.length(tr.compute(dev)) == 1
    # and with the exposed collective to the busy time but for what only
    # the container covers (the scan's own 5 + 2 + 8 ns between and after
    # its body's instructions)
    exposed = tr.reduce_device(dev, tr.COLLECTIVES.pattern, "exposed")
    assert exposed == 20
    busy = tr.reduce_device(dev, None, "busy")
    assert busy - (sum(got.values()) - 1 + exposed) == 15


def test_a_phase_takes_both_directions_and_the_kernel():
    import scope_reduce as sr
    trace, scopes = _trace(OPS), sr.parse_hlo(HLO)
    assert sr.reduce(trace, scopes, {"phase": "hvd.attention.core"}) == \
        7 + 5 + 9 + 5
    assert sr.reduce(trace, scopes, {"phase": "hvd.attention"}) == \
        (10 + 7 + 5) + (10 + 9 + 5) - 1
    assert sr.reduce(trace, scopes, {"phase": "hvd.mlp"}) == 30
    # a phase the program does not have, a program without scopes, no trace
    assert sr.reduce(trace, scopes, {"phase": "hvd.moe"}) is None
    assert sr.reduce(trace, sr.parse_hlo(HLO.replace("hvd.", "xyz.")),
                     {"kind": "unscoped"}) is None
    assert sr.reduce(None, scopes, {"kind": "fwd"}) is None
    with pytest.raises(ValueError):
        sr.reduce(trace, scopes, {"direction": "fwd"})
    assert sr.unmatched(trace, scopes) == 0
    assert sr.unmatched(_trace([("fusion.77", 0, 1)]), scopes) == 1
    assert sr.top_instructions(trace, scopes, {"kind": "fwd"}, 1) == [
        ["fusion", 20e-9]]


def test_read_metric_dispatches_the_two_kinds():
    import scope_reduce as sr
    host = [("bench.input", 0, 100), ("hvd.input.source", 1, 30),
            ("hvd.input.place", 32, 60), ("bench.input", 200, 100),
            ("hvd.input.source", 201, 50), ("hvd.input.place", 252, 40)]
    ctx = {"trace": _trace(OPS, host), "hlo_text": HLO, "trace_steps": 2}
    assert sr.read_metric({"trace_scope": {"kind": "fwd"}, "per_step": True,
                           "scale": 1e-6}, ctx) == pytest.approx(15e-6)
    assert sr.read_metric({"trace_scope": {"phase": "hvd.mlp"}}, ctx) == 30
    assert sr.read_metric({"host_span": "hvd.input.source",
                           "reduce": "median_ms"}, ctx) == 40e-6
    assert sr.read_metric({"host_span": "hvd.input.place",
                           "reduce": "median_ms"}, ctx) == 50e-6
    # nothing to read: no raise, no value
    assert sr.read_metric({"host_span": "hvd.checkpoint.stall",
                           "reduce": "median_ms"}, ctx) is None
    for empty in ({"trace": None, "hlo_text": HLO, "trace_steps": 2},
                  {"trace": _trace(OPS), "hlo_text": None, "trace_steps": 2},
                  {"trace": _trace(OPS), "trace_steps": 2}):
        assert sr.read_metric({"trace_scope": {"kind": "fwd"}},
                              empty) is None
    with pytest.raises(ValueError):
        sr.read_metric({"span": "bench.wait", "reduce": "median_ms"}, ctx)


def test_idle_gaps_are_named_after_the_programs_span_inside_the_benchs():
    import scope_reduce as sr
    ops = [("fusion.1", 0, 100), ("fusion.1", 200, 100),
           ("fusion.1", 400, 100), ("fusion.1", 530, 10)]
    host = [("bench.wait", 0, 98),
            # the device waits while the batch is placed
            ("bench.input", 100, 100), ("hvd.input.source", 101, 10),
            ("hvd.input.place", 112, 86),
            # a program span that covers less than half of the gap
            ("bench.input", 300, 100), ("hvd.input.source", 301, 30),
            ("bench.wait", 500, 30)]
    assert sr.idle_gaps(_trace(ops, host), 3) == [
        ["bench.input/hvd.input.place", 100e-9],
        ["bench.input", 100e-9],
        ["bench.wait", 30e-9]]
    # no span at all
    assert sr.idle_gaps(_trace(ops), 1) == [["host:none", 100e-9]]
