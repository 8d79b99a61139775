"""``readers/step_owners.py`` on a text and a trace written by hand, and on
the recorded fixture: the eight reasons partition the compute events'
summed durations to the nanosecond; a fusion of a recomputed ``dot`` with
backward elementwise work is ``recompute``, a backward fusion with a
recomputed ``tanh`` is ``bwd`` and shows in the bound; XLA's unnamed copies
and prefetch halves take their consumer's owner, into a tuple and out
through the ``get-tuple-element`` of the same place, into a loop's body and
from its root on to the loop; one that nothing reads is ``unowned`` and
listed by name; a ``.remat`` name is ``recompute``; self and subtree nest;
the reader's ``read`` gives None where there is nothing to read. On the
recorded step each of ``fwd``, ``opt``, ``mixed`` is what ``scope_reduce``
gives that kind less what is now ``move`` or ``wait``, by an independent
reading of the text. The tool walks a cell at its tiny sizes."""

import io
import json
import os
import re
import subprocess
import sys

import pytest

CHIP = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(CHIP))
HERE = os.path.join(CHIP, "tests")

import scope_reduce as sr                           # noqa: E402
import trace_reduce as tr                           # noqa: E402
from readers import step_owners as so               # noqa: E402

LAYERS = "jit(step)/jvp(hvd.layers)/while/body/closed_call/checkpoint"
BACK = "jit(step)/transpose(jvp(hvd.layers))/while/body/closed_call/checkpoint"
AGAIN = BACK + "/rematted_computation"

HLO = f'''HloModule jit_step, is_scheduled=true

%fwd_body (p0: bf16[8,8]) -> bf16[8,8] {{
  %p0 = bf16[8,8] parameter(0)
  ROOT %dot.1 = bf16[8,8] dot(%p0, %p0), metadata={{op_name="{LAYERS}/hvd.mlp/dot_general" stack_frame_id=3}}
}}

%again_body (p0: bf16[8,8], p1: bf16[8,8]) -> bf16[8,8] {{
  %p0.1 = bf16[8,8] parameter(0)
  %p1.1 = bf16[8,8] parameter(1)
  %dot.2 = bf16[8,8] dot(%p0.1, %p0.1), metadata={{op_name="{AGAIN}/hvd.mlp/dot_general"}}
  ROOT %mul.2 = bf16[8,8] multiply(%dot.2, %p1.1), metadata={{op_name="{BACK}/hvd.mlp/mul"}}
}}

%bwd_body (p0: bf16[8,8]) -> bf16[8,8] {{
  %p0.2 = bf16[8,8] parameter(0)
  %tanh.3 = bf16[8,8] tanh(%p0.2), metadata={{op_name="{AGAIN}/hvd.attention/hvd.attention.core/tanh"}}
  %mul.3 = bf16[8,8] multiply(%tanh.3, %p0.2), metadata={{op_name="{BACK}/hvd.attention/hvd.attention.core/mul"}}
  ROOT %bitcast.3 = bf16[64] bitcast(%mul.3), metadata={{op_name="{BACK}/reshape"}}
}}

%move_body (p0: bf16[8,8], p1: s32[]) -> bf16[8,8] {{
  %p0.3 = bf16[8,8] parameter(0)
  %p1.3 = s32[] parameter(1)
  %one = s32[] constant(1)
  %add.4 = s32[] add(%p1.3, %one)
  %slice.4 = bf16[8,8] dynamic-slice(%p0.3, %add.4, %add.4), dynamic_slice_sizes={{8,8}}
  ROOT %transpose.4 = bf16[8,8] transpose(%slice.4), dimensions={{1,0}}, metadata={{op_name="{LAYERS}/hvd.attention/hvd.attention.core/transpose"}}
}}

%cast_body (p0: f32[8,8]) -> bf16[8,8] {{
  %p0.5 = f32[8,8] parameter(0)
  ROOT %convert.5 = bf16[8,8] convert(%p0.5)
}}

%remat_body (p0: bf16[8,8]) -> bf16[8,8] {{
  %p0.6 = bf16[8,8] parameter(0)
  ROOT %tanh.6 = bf16[8,8] tanh(%p0.6), metadata={{op_name="{BACK}/hvd.attention/tanh"}}
}}

%nested (p: f32[8,8]) -> f32[8,8] {{
  %p = f32[8,8] parameter(0)
  ROOT %mul.9 = f32[8,8] multiply(%p, %p), metadata={{op_name="jit(step)/hvd.optimizer/mul"}}
}}

%mixed_body (p0: bf16[8,8], p1: f32[8,8]) -> f32[8,8] {{
  %p0.7 = bf16[8,8] parameter(0)
  %p1.7 = f32[8,8] parameter(1)
  %dot.7 = f32[8,8] dot(%p0.7, %p0.7), metadata={{op_name="{BACK}/hvd.mlp/dot_general"}}
  %fusion.9 = f32[8,8] fusion(%p1.7), kind=kLoop, calls=%nested
  ROOT %add.7 = f32[8,8] add(%dot.7, %fusion.9)
}}

%opt_body (p0: f32[8,8]) -> f32[8,8] {{
  %p0.8 = f32[8,8] parameter(0)
  ROOT %sqrt.8 = f32[8,8] sqrt(%p0.8), metadata={{op_name="jit(step)/hvd.optimizer/sqrt"}}
}}

%body (t: (s32[], bf16[8,8], bf16[8,8])) -> (s32[], bf16[8,8], bf16[8,8]) {{
  %t = (s32[], bf16[8,8], bf16[8,8]) parameter(0)
  %i = s32[] get-tuple-element(%t), index=0
  %x = bf16[8,8] get-tuple-element(%t), index=1
  %y = bf16[8,8] get-tuple-element(%t), index=2
  %copy.5 = s32[] copy(%i)
  %three = s32[] constant(3)
  %fusion.2 = bf16[8,8] fusion(%x, %x), kind=kOutput, calls=%again_body
  %fusion.4 = bf16[8,8] fusion(%y, %three), kind=kLoop, calls=%move_body
  %copy.8 = bf16[8,8] copy(%fusion.4)
  ROOT %tuple.2 = (s32[], bf16[8,8], bf16[8,8]) tuple(%copy.5, %fusion.2, %copy.8)
}}

ENTRY %main.1 (w: bf16[8,8], m: f32[8,8]) -> f32[8,8] {{
  %w = bf16[8,8] parameter(0)
  %m = f32[8,8] parameter(1)
  %zero = s32[] constant(0)
  %copy-start.1 = (bf16[8,8], bf16[8,8], u32[]) copy-start(%w)
  %copy-done.1 = bf16[8,8] copy-done(%copy-start.1)
  %copy.7 = bf16[8,8] copy(%w)
  %tuple.1 = (bf16[8,8], bf16[8,8]) tuple(%copy-done.1, %copy.7)
  %gte.0 = bf16[8,8] get-tuple-element(%tuple.1), index=0
  %gte.1 = bf16[8,8] get-tuple-element(%tuple.1), index=1
  %fusion.1 = bf16[8,8] fusion(%gte.0), kind=kOutput, calls=%fwd_body
  %fusion.5 = bf16[64] fusion(%gte.1), kind=kLoop, calls=%bwd_body
  %copy.9 = bf16[8,8] copy(%w)
  %fusion.6 = bf16[8,8] fusion(%m), kind=kLoop, calls=%cast_body
  %copy.6 = bf16[8,8] copy(%fusion.1)
  %tuple.3 = (s32[], bf16[8,8], bf16[8,8]) tuple(%zero, %fusion.6, %copy.6)
  %while.1 = (s32[], bf16[8,8], bf16[8,8]) while(%tuple.3), condition=%cond, body=%body, metadata={{op_name="jit(step)/transpose(jvp(hvd.layers))/while"}}
  %fusion.6.remat = bf16[8,8] fusion(%fusion.1), kind=kLoop, calls=%remat_body
  %all-reduce.1 = bf16[8,8] all-reduce(%w), to_apply=%region_0.1, metadata={{op_name="jit(step)/hvd.grad_sync/psum"}}
  %fusion.7 = f32[8,8] fusion(%all-reduce.1, %m), kind=kOutput, calls=%mixed_body
  ROOT %fusion.8 = f32[8,8] fusion(%fusion.7), kind=kLoop, calls=%opt_body
}}
'''

MLP = ("hvd.layers", "hvd.mlp")
CORE = ("hvd.layers", "hvd.attention", "hvd.attention.core")
#: (instruction, duration): distinct powers of two, so that a sum names its
#: parts; the loop's body runs twice
DURATIONS = [
    ("copy-start.1", 1), ("copy-done.1", 2), ("copy.7", 4), ("fusion.1", 8),
    ("fusion.5", 16), ("copy.9", 32), ("fusion.6", 64), ("copy.6", 128),
    ("while.1", 0),
    ("fusion.2", 256), ("fusion.4", 512), ("copy.8", 1024),
    ("copy.5", 16384),
    ("fusion.2", 256), ("fusion.4", 512), ("copy.8", 1024),
    ("copy.5", 16384),
    ("fusion.6.remat", 2048), ("all-reduce.1", 100), ("fusion.7", 4096),
    ("fusion.8", 8192),
]


def _ops(durations):
    """One after the other; the loop covers its two passes."""
    ops, at = [], 0
    for name, dur in durations:
        ops.append((name, at, dur or 2 * (256 + 512 + 1024 + 16384)))
        at += dur
    return ops


OPS = _ops(DURATIONS)
MOVED = 4 + 32 + 128 + 2 * (512 + 1024 + 16384)
WANT = {     # instruction -> (owner, reason)
    "copy-start.1": (MLP, "wait"), "copy-done.1": (MLP, "wait"),
    "copy.7": (CORE, "move"),            # place 1 of the tuple: fusion.5's
    "fusion.1": (MLP, "fwd"),
    "fusion.5": (CORE, "bwd"),           # the recomputed tanh is a guest
    "copy.9": ((), "move"),              # nothing reads it
    "fusion.6": (MLP, "other"),          # a cast: place 1 of the loop's
                                         # state, which fusion.2 reads
    "copy.6": (CORE, "move"),            # place 2: fusion.4 reads it
    "fusion.2": (MLP, "recompute"),      # the dot decides
    "fusion.4": (CORE, "move"),          # scoped, and a relayout all the same
    "copy.8": (CORE, "move"),            # to the body's root at place 2:
                                         # the next pass's fusion.4 reads it
    "copy.5": (("hvd.layers",), "move"),  # the counter: the loop's own
    "fusion.6.remat": (("hvd.layers", "hvd.attention"), "recompute"),
    "fusion.7": (MLP, "mixed"), "fusion.8": (("hvd.optimizer",), "opt"),
}


def _opcode(name):
    if name.startswith("fusion"):
        return "fusion"
    return re.sub(r"\.\d+$", "", name)


def _trace(ops=OPS, devices=1):
    return tr.Trace({d: tr.DeviceTrace(
        [tr.Event(n, s, dur, _opcode(n), "bf16[8,8]") for n, s, dur in ops],
        []) for d in range(devices)}, [])


def _found(ops=OPS, devices=1, steps=1):
    return so.attribute(_trace(ops, devices), HLO, sr.parse_hlo(HLO), steps)


def test_every_instruction_gets_its_owner_and_its_reason():
    program, scopes = so.Program(HLO), sr.parse_hlo(HLO)
    got = {name: (program.owner(program.instructions[name]),
                  program.reason(program.instructions[name],
                                 scopes[name].kind)) for name in WANT}
    assert got == WANT
    # the rule, piece by piece
    deciding = {n: program.deciding(program.instructions[n]).name
                for n in ("fusion.2", "fusion.5", "fusion.4", "fusion.7",
                          "copy.7")}
    assert deciding == {"fusion.2": "dot.2",     # the matmul, not the root
                        "fusion.5": "mul.3",     # a bitcast root: its operand
                        "fusion.4": "transpose.4", "fusion.7": "dot.7",
                        "copy.7": "copy.7"}


def test_the_eight_reasons_partition_the_compute_events_to_the_nanosecond():
    found = _found()
    compute = [(n, d) for n, _s, d in OPS
               if n not in ("while.1", "all-reduce.1")]
    assert found.compute_ns == sum(d for _n, d in compute) \
        == sum(found.rows.values())
    by_reason = {r: found.select_ns([r]) for r in so.REASONS}
    assert by_reason == {
        "wait": 1 + 2, "move": MOVED,
        "opt": 8192, "mixed": 4096, "fwd": 8, "recompute": 2 * 256 + 2048,
        "bwd": 16, "other": 64}
    assert sum(by_reason.values()) == found.compute_ns
    assert found.unmatched == 0
    # scope_reduce's kinds against the reasons: what bwd is made of, and
    # what each kind loses to move and wait
    assert found.kinds == {
        ("unscoped", "wait"): 3, ("unscoped", "move"): MOVED - 1024,
        ("unscoped", "other"): 64, ("fwd", "fwd"): 8, ("fwd", "move"): 1024,
        ("bwd", "bwd"): 16, ("bwd", "recompute"): 512 + 2048,
        ("mixed", "mixed"): 4096, ("opt", "opt"): 8192}
    # ms a step, mean over the devices
    two = _found(devices=2, steps=4)
    assert two.compute_ns == 2 * found.compute_ns
    assert two.ms(two.select_ns(["opt"])) == 8192 / 4 / 1e6


def test_a_guest_is_bounded_and_the_unowned_are_named():
    found = _found()
    # fusion.5 holds a recomputed tanh and is a backward fusion: its whole
    # time is the most the rule can have kept from ``recompute``
    assert found.decided_otherwise == {"bwd": 16}
    assert found.unowned == {"copy.9 copy bf16[8,8]": 32}
    doc = so.table(found)
    assert doc["unowned"] == [["copy.9 copy bf16[8,8]", 32 / 1e6]]
    assert doc["unowned_ms"] == 32 / 1e6
    assert doc["other"] == [["fusion.6 fusion bf16[8,8]", 64 / 1e6]]
    assert doc["recomputed_decided_otherwise_ms"] == {"bwd": 16 / 1e6}
    assert doc["reasons_sum_ns"] == doc["compute_events_sum_ns"]
    assert doc["owner_by_reason_ms"]["hvd.layers/hvd.mlp"] == {
        "wait": 3 / 1e6, "fwd": 8 / 1e6, "other": 64 / 1e6,
        "recompute": 512 / 1e6, "mixed": 4096 / 1e6}
    assert json.loads(json.dumps(doc)) == doc
    out = io.StringIO()
    so.say(found, out)
    said = out.getvalue()
    assert "copy.9 copy" in said and "'bwd': 0.0" in said
    assert "hvd.optimizer" not in said      # 0.008 ms: under ROW_MS
    assert said.splitlines()[1].split() == ["owner", *so.REASONS]


def test_self_and_subtree_nest():
    found = _found()
    every = sum(found.rows.values())

    def ns(phase, match, reasons=None):
        return found.select_ns(reasons, phase, match)
    assert ns(None, "subtree") == every
    assert ns("unowned", "self") == 32
    assert ns("hvd.layers", "subtree") == every - 32 - 8192
    assert ns("hvd.layers", "self") == 2 * 16384           # copy.5
    assert ns("hvd.attention", "self") == 2048             # fusion.6.remat
    assert ns("hvd.attention.core", "self") \
        == ns("hvd.attention.core", "subtree") \
        == 4 + 16 + 128 + 2 * (512 + 1024)
    assert ns("hvd.attention", "subtree") == ns("hvd.attention", "self") \
        + ns("hvd.attention.core", "subtree")
    assert ns("hvd.layers", "subtree") == ns("hvd.layers", "self") \
        + ns("hvd.attention", "subtree") + ns("hvd.mlp", "subtree")
    assert ns("hvd.attention.core", "subtree", ["move"]) \
        == MOVED - 32 - 2 * 16384
    assert ns("hvd.mlp", "subtree", ["recompute", "bwd"]) == 512


def test_an_instruction_the_text_does_not_know_and_a_merged_path():
    found = _found(OPS + [("fusion.77", 70000, 5), ("copy-done.77", 70005, 7),
                          ("copy.77", 70012, 9)])
    assert found.unmatched == 3
    assert found.rows[(), "other"] == 5 and found.rows[(), "wait"] == 7
    assert found.rows[(), "move"] == 32 + 9
    program = so.Program(HLO)
    # XLA joins the paths of call sites it merged: each phase once, in
    # order; hvd.recompute is a reason, and no part of an owner
    assert program.path(
        "jit(step)/transpose(jvp(hvd.layers))/while/jit(step)/transpose("
        "jvp(hvd.mtp))/hvd.layers/while/hvd.mlp/hvd.recompute/mul") == (
            ("hvd.layers", "hvd.mtp", "hvd.mlp"), True)
    assert program.path("jit(step)/jvp(hvd.head)/mul") == (("hvd.head",),
                                                            False)
    assert program.path("jit(step)/my_hvd.head_thing/add") == ((), False)


def test_read_gives_none_where_there_is_nothing_to_read():
    read = {"reader": "step_owners", "value": "ms", "reason": "recompute"}
    assert so.read(read, {}) is None
    assert so.read(read, {"trace": _trace(), "trace_steps": 1}) is None
    no_scopes = "\n".join(line for line in HLO.splitlines()
                          if "hvd." not in line)
    assert so.read(read, {"trace": _trace(), "hlo_text": no_scopes,
                          "trace_steps": 1}) is None
    ctx = {"trace": _trace(), "hlo_text": HLO, "trace_steps": 2}
    with pytest.raises(ValueError, match="knows the reasons"):
        so.read({**read, "reason": "rematted"}, ctx)
    with pytest.raises(ValueError, match="reads 'ms'"):
        so.read({"reader": "step_owners", "value": "share"}, ctx)


def test_read_through_the_harness_and_one_table_a_run(capsys):
    import run as harness
    ctx = {"trace": _trace(), "hlo_text": HLO, "trace_steps": 2}

    def value(**select):
        return harness.read_layer_metric(
            {"reader": "step_owners", "value": "ms", **select}, ctx)
    assert value(reason="recompute") == (512 + 2048) / 2 / 1e6
    assert value(reason=["move", "wait"]) == (MOVED + 3) / 2 / 1e6
    assert value(phase="unowned", match="self") == 32 / 2 / 1e6
    assert value(phase="hvd.attention.core", match="subtree",
                 reason="move") == (MOVED - 32 - 2 * 16384) / 2 / 1e6
    assert value(phase="hvd.mlp", match="self") == (
        3 + 8 + 64 + 512 + 4096) / 2 / 1e6
    assert value(phase="hvd.mlp") == value(phase="hvd.mlp", match="self")
    # a phase the program does not have: nothing to read; one it has and
    # no instruction of that reason: 0
    assert value(phase="hvd.ssm", reason="recompute") is None
    assert value(phase="hvd.optimizer", reason="recompute") == 0.0
    assert so.LAST is ctx["step_owners"]
    assert capsys.readouterr().err.count("ms a step by owner and reason") == 1


# -- the recorded step --------------------------------------------------------

@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "fixture_scopes.hlo.txt")) as f:
        text = f.read()
    trace = tr.load(os.path.join(HERE, "fixture_scopes.xplane.pb"), None,
                    ("bench.", "hvd."))
    return trace, text, sr.parse_hlo(text)


def _bodies(text):
    """computation -> its instructions' (opcode, whether the result is a
    scalar), nested fusions' in their place, by a reading of the text that
    shares nothing with the reader's."""
    lines, current = {}, None
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .*\{\s*$", line)
        if head:
            current = lines.setdefault(head.group(1), [])
        elif line.startswith("}"):
            current = None
        elif current is not None and " = " in line:
            current.append(line)

    def body(name):
        out = []
        for line in lines.get(name, ()):
            called = re.search(r"\bcalls=%?([\w.\-]+)", line)
            if called:
                out += body(called.group(1))
            else:
                _n, opcode, result = tr.parse_instruction(line.strip())
                out.append((opcode, result.endswith("[]")))
        return out
    calls = {}
    for members in lines.values():
        for line in members:
            called = re.search(r"\bcalls=%?([\w.\-]+)", line)
            if called:
                calls[tr.parse_instruction(line.strip().replace(
                    "ROOT ", ""))[0]] = body(called.group(1))
    return calls


def test_the_recorded_step_s_kinds_less_what_moves_and_waits(recorded):
    trace, text, scopes = recorded
    found = so.attribute(trace, text, scopes, 3)
    events = sr.compute_events(trace.devices[0])
    assert found.compute_ns == sum(e.dur for e in events) \
        == sum(found.rows.values())
    assert found.unmatched == 0 and found.devices == 1
    calls = _bodies(text)

    def waits(e):
        return e.opcode.endswith(("-start", "-done"))

    def moves(e):
        if e.name in calls:
            return all(op in so.MOVES or scalar for op, scalar
                       in calls[e.name])
        return e.opcode in so.MOVES
    for kind in ("fwd", "opt", "mixed", "bwd", "unscoped"):
        of_kind = [e for e in events if scopes[e.name].kind == kind]
        stays = sum(e.dur for e in of_kind if not waits(e) and not moves(e))
        reasons = {"bwd": ["bwd", "recompute"],
                   "unscoped": ["other"]}.get(kind, [kind])
        assert found.select_ns(reasons) == stays, kind
        assert sum(ns for (k, _r), ns in found.kinds.items()
                   if k == kind) == sum(e.dur for e in of_kind), kind
    assert found.select_ns(["wait"]) == sum(e.dur for e in events
                                            if waits(e))
    assert found.select_ns(["move"]) == sum(
        e.dur for e in events if not waits(e) and moves(e))
    # nothing is checkpointed in the recorded step
    assert found.select_ns(["recompute"]) == 0
    assert found.decided_otherwise == {}


def test_the_recorded_step_in_numbers(recorded):
    """Taken once from this reader (PR 51) and held: a change to the rule
    shows here. Nanoseconds over the three steps."""
    trace, text, scopes = recorded
    found = so.attribute(trace, text, scopes, 3)
    assert {r: found.select_ns([r]) for r in so.REASONS} == RECORDED_REASONS
    assert sorted(found.unowned) == RECORDED_UNOWNED
    core = "hvd.attention.core"
    assert found.select_ns(None, core, "subtree") == RECORDED_CORE_SUBTREE
    assert found.select_ns(None, "hvd.attention", "subtree") \
        == found.select_ns(None, "hvd.attention", "self") \
        + found.select_ns(None, core, "subtree")
    assert found.select_ns(None, "hvd.layers", "subtree") == sum(
        ns for (owner, _r), ns in found.rows.items()
        if owner[:1] == ("hvd.layers",))
    assert found.seconds < 5


RECORDED_REASONS = {"wait": 74430, "move": 43758, "opt": 21648,
                    "mixed": 6767, "fwd": 105335, "recompute": 0,
                    "bwd": 108093, "other": 340}
RECORDED_UNOWNED = []       # every unnamed copy and prefetch finds a reader
RECORDED_CORE_SUBTREE = 84038


# -- the tool -----------------------------------------------------------------

def test_the_tool_walks_a_cell_at_its_tiny_sizes(tmp_path):
    """``tools/step_owners.py --rehearse``: ``run.py`` calls the reader for
    every metric that names it and none raises; on the CPU a trace has no
    device plane, so there is nothing to attribute and no value."""
    run = subprocess.run(
        [sys.executable, os.path.join(CHIP, "tools", "step_owners.py"),
         "--workload", "granite-4.0-h-micro.s4096", "--seed", "2147483659",
         "--seconds", "0.5", "--rehearse", "--out", str(tmp_path)],
        env={**os.environ, "PYTHONPATH": ROOT, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=600)
    assert run.returncode == 0, run.stderr[-2000:]
    lines = run.stdout.strip().splitlines()
    result, report = json.loads(lines[-2]), json.loads(lines[-1])
    assert result["correct"] is True and result["failed"] == 0
    assert report["metrics"] == result["metrics"]
    assert report["owners"] is None
    assert report["phases"]["reduce_s"] >= 0
    assert not [k for k in result["metrics"] if "recompute" in k]
    with open(os.path.join(
            tmp_path, "granite-4.0-h-micro.s4096.seed2147483659"
            ".owners.json")) as f:
        assert json.load(f) == report
