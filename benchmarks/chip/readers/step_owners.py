"""The traced step's device time, each executed instruction to one owner and
one reason.

    {"reader": "step_owners", "value": "ms",
     "reason": "<one of REASONS>" | [..],     (left out: every reason)
     "phase": "<hvd.* phase>" | "unowned",    (left out: every owner)
     "match": "self" | "subtree"}             (with a phase; default subtree)

ms a step, mean over the devices; None without a trace, the compiled step's
text or a program with scopes, and for a phase the program does not have.

``scope_reduce``'s phase metrics are *covers*: every instruction with the
scope in itself or its fused body, whole. Covers overlap (a fusion that
holds one instruction of each of two phases counts for both) and say
nothing of why the instruction ran. This reader gives every executed
compute instruction (``scope_reduce.compute_events``: no container, no
collective) to exactly one **owner**, a path of the program's phases
(``hvd.layers/hvd.ssm/hvd.ssm.conv``), and exactly one **reason**, and sums
the events' own durations: so the reasons partition the compute events'
summed durations, and a phase's *self* time (owner ends in it) and
*subtree* time (owner holds it) nest without counting anything twice. It
reads what ``ctx`` has today: ``hlo_text``, ``trace``, ``trace_steps``
(and ``scopes``, ``scope_reduce.parse_hlo`` of the text, where the harness
has parsed it already).

**The deciding instruction** of an executed instruction, one rule for owner
and reason alike: itself where it calls no computation. Of a fusion, the
first ``dot`` / ``convolution`` / ``custom-call`` of its body (nested
fusions' bodies included: the matmul or the kernel sets the time), else the
body's root, a tuple or bitcast root's first operand; where that carries no
``hvd.*`` name (a copy XLA put there) its first operand, and so on down;
where that ends at a parameter, the last instruction of the body that
carries one.

**Owner**: the ``hvd.*`` components of the deciding instruction's
``op_name`` in their order, ``jvp(..)`` / ``transpose(..)`` peeled as
``scope_reduce.classify`` peels them, ``hvd.recompute`` left out (a reason,
no part of the model). An instruction with no ``hvd.*`` name anywhere
(XLA's copies, async copy and slice pairs, casts) takes the owner of the
instruction that consumes it: its users in the text, breadth first through
the instructions that have no name either (``get-tuple-element``,
``bitcast``, ``copy``, the halves of async pairs, ``tuple``, a chain of
XLA's own slices and casts) to the first one with an owner, at most
``WALK`` instructions, into a tuple and out through the
``get-tuple-element`` of the same place alone, into a loop's body and from
its root on to the loop; failing that its producer's, the same way back;
failing that it is ``unowned``.

**Reason**, the first that applies:

``wait``       a half of an asynchronous pair on the compute stream
               (``copy-start`` / ``copy-done``, ``slice-start`` /
               ``slice-done``, any ``*-start`` / ``*-done``): XLA's prefetch
``move``       a ``copy`` or ``transpose`` or another of ``MOVES`` unfused,
               or a fusion whose body holds nothing but ``MOVES`` and
               scalar index arithmetic: a relayout, scoped or not
``opt`` ``mixed`` ``fwd``   ``scope_reduce.Scopes.kind``, as the step.* kinds
``recompute``  kind ``bwd`` and the deciding instruction's path holds
               ``rematted_computation`` (``jax.checkpoint``'s second run of
               a function) or ``hvd.recompute`` (a hand-written backward
               that runs forward work again), or the instruction's name
               holds ``.remat`` (XLA's own rematerialisation)
``bwd``        kind ``bwd`` otherwise
``other``      kind ``unscoped``: arithmetic nobody named

With the first read of a run goes one table on standard error: owner x
reason, every row over ``ROW_MS`` a step; the ``unowned`` instructions by
name; and, as a bound on the rule, the time of instructions that hold
recomputed names but were decided otherwise. ``tools/step_owners.py``
prints all of it as JSON.
"""

from __future__ import annotations

import dataclasses
import re
import sys
import time
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import scope_reduce
import trace_reduce
from scope_reduce import (_CALLS, _COMPUTATION, _OP_NAME, _WRAPPERS,
                          NO_SCOPE, PHASE_PREFIX, compute_events)

try:        # the vocabulary is the program's, where it has it
    from horovod_tpu.profiling.scopes import RECOMPUTE, RECOMPUTED
except ImportError:     # a program from before it: JAX writes the one anyway
    RECOMPUTE, RECOMPUTED = "hvd.recompute", "rematted_computation"

WAIT, MOVE, RECOMPUTE_REASON, OTHER = "wait", "move", "recompute", "other"
REASONS = (WAIT, MOVE, scope_reduce.OPT, scope_reduce.MIXED,
           scope_reduce.FWD, RECOMPUTE_REASON, scope_reduce.BWD, OTHER)
UNOWNED = "unowned"
#: what a body may hold and still be a relayout
MOVES = frozenset((
    "parameter", "copy", "transpose", "bitcast", "reshape", "slice",
    "dynamic-slice", "dynamic-update-slice", "concatenate", "pad",
    "broadcast", "tuple", "constant", "get-tuple-element"))
#: what decides a fusion's time where its body has one
HEAVY = frozenset(("dot", "convolution", "custom-call"))
WALK = 256          # instructions one walk may visit
ROW_MS = 0.25       # rows of the table on standard error

_INSTRUCTION = re.compile(r"^\s+(ROOT )?%?([\w.\-]+) = (.*)$")
_OPCODE = re.compile(r" ([a-z][\w\-]*)\(")
_OPERAND = re.compile(r"%([\w.\-]+)")
_SCALAR = re.compile(r"^\w+\[\]")
_HALF = re.compile(r"-(start|done|update)$")
_INDEX = re.compile(r"\bindex=(\d+)")
#: the computations a container runs (a fusion's is ``calls=``)
_RUNS = re.compile(r"\b(?:body|to_apply|true_computation"
                   r"|false_computation)=%?([\w.\-]+)"
                   r"|\bbranch_computations=\{([^}]*)\}")


@dataclasses.dataclass(eq=False, slots=True)
class Instruction:
    name: str
    opcode: str
    operands: Tuple[str, ...]
    op_name: str            # its own, "" where it has none
    calls: Optional[str]    # the computation a fusion (or async pair) runs
    scalar: bool            # its result is one element
    root: bool
    computation: str = ""
    index: Optional[int] = None     # a get-tuple-element's


class Program:
    """The compiled step's text: every instruction with its opcode,
    operands and own ``op_name``, the computations' members in the text's
    order, and each instruction's users."""

    def __init__(self, text: str):
        self.instructions: Dict[str, Instruction] = {}
        self.members: Dict[str, List[Instruction]] = {}
        self.users: Dict[str, List[str]] = {}
        #: computation -> the while / conditional / call that runs it, and
        #: the computations each runs (a loop's body; not its condition)
        self.callers: Dict[str, str] = {}
        self.runs: Dict[str, List[str]] = {}
        current = computation = None
        for line in text.splitlines():
            if current is None:
                m = _COMPUTATION.match(line)
                if m:
                    computation = m.group(1)
                    current = self.members.setdefault(computation, [])
                continue
            if line.startswith("}"):
                current = None
                continue
            m = _INSTRUCTION.match(line)
            if not m:
                continue
            root, name, rest = m.groups()
            op = _OPCODE.search(rest)
            if op is None:
                continue
            end = rest.find("), ", op.end())
            operands = tuple(_OPERAND.findall(
                rest, op.end(), end if end >= 0 else len(rest)))
            meta = _OP_NAME.search(rest, max(end, 0))
            called = _CALLS.search(rest, max(end, 0))
            inst = Instruction(
                name, op.group(1), operands, meta.group(1) if meta else "",
                called.group(1) if called else None,
                bool(_SCALAR.match(rest)), bool(root), computation)
            if inst.opcode == "get-tuple-element":
                inst.index = int(_INDEX.search(rest, max(end, 0)).group(1))
            self.instructions[name] = inst
            current.append(inst)
            for operand in operands:
                self.users.setdefault(operand, []).append(name)
            if inst.opcode in trace_reduce.CONTAINERS:
                for one, several in _RUNS.findall(rest, max(end, 0)):
                    for run in [one] if one else several.split(","):
                        self.callers[run.strip().lstrip("%")] = name
                        self.runs.setdefault(name, []).append(
                            run.strip().lstrip("%"))
        self._bodies: Dict[str, List[Instruction]] = {}
        self._paths: Dict[str, Tuple[Tuple[str, ...], bool]] = {}
        self._owners: Dict[str, Tuple[str, ...]] = {}

    # -- one op_name ---------------------------------------------------------

    def path(self, op_name: str) -> Tuple[Tuple[str, ...], bool]:
        """(the ``hvd.*`` components in order, ``hvd.recompute`` left out;
        whether the path says the work is done again)."""
        if op_name not in self._paths:
            parts = [_WRAPPERS.sub("", p) for p in op_name.split("/")]
            # each once: XLA joins the paths of call sites it merged (the
            # expert kernels' searchsorted loop, one for all layers)
            self._paths[op_name] = (
                tuple(dict.fromkeys(
                    p for p in parts
                    if p.startswith(PHASE_PREFIX) and p != RECOMPUTE)),
                RECOMPUTED in parts or RECOMPUTE in parts)
        return self._paths[op_name]

    def named(self, inst: Instruction) -> bool:
        return bool(self.path(inst.op_name)[0])

    # -- a fusion's body -----------------------------------------------------

    def body(self, computation: str) -> List[Instruction]:
        """The computation's instructions in the text's order, a nested
        fusion's in its place."""
        if computation not in self._bodies:
            self._bodies[computation] = out = []      # guards a cycle
            for inst in self.members.get(computation, ()):
                if inst.calls is not None:
                    out.extend(self.body(inst.calls))
                else:
                    out.append(inst)
        return self._bodies[computation]

    def _root(self, computation: str) -> Optional[Instruction]:
        members = self.members.get(computation)
        if not members:
            return None
        return next((i for i in members if i.root), members[-1])

    def deciding(self, inst: Instruction) -> Instruction:
        """The module docstring's rule. An instruction whose body names
        nothing decides for itself."""
        if inst.calls is None:
            return inst
        body = self.body(inst.calls)
        heavy = next((b for b in body if b.opcode in HEAVY
                      and self.named(b)), None)
        if heavy is not None:
            return heavy
        node = self._root(inst.calls)
        for _ in range(WALK):
            if node is None or node.opcode == "parameter":
                break
            if node.calls is not None:
                node = self._root(node.calls)
            elif node.operands and (node.opcode in ("tuple", "bitcast")
                                    or not self.named(node)):
                node = self.instructions.get(node.operands[0])
            else:
                break
        if node is not None and self.named(node):
            return node
        return next((b for b in reversed(body) if self.named(b)), inst)

    def holds_recomputed(self, inst: Instruction) -> bool:
        """Whether any instruction of it is named as done again."""
        if self.path(inst.op_name)[1] or ".remat" in inst.name:
            return True
        return inst.calls is not None and any(
            self.path(b.op_name)[1] for b in self.body(inst.calls))

    # -- owner ---------------------------------------------------------------

    def _next(self, node: Instruction, place: Optional[int], forward: bool):
        """The instructions the walk goes on to from ``node``, each with
        the place in a tuple at which the value then lies (None: it is no
        tuple's element): into a ``tuple`` at the operand's place, through
        a loop and into its body at the same place, out through the
        ``get-tuple-element`` of that place alone; backwards the same."""
        if not forward:
            if node.opcode == "tuple" and place is not None:
                return [(node.operands[place], None)] \
                    if place < len(node.operands) else []
            if node.opcode == "get-tuple-element":
                place = node.index
            elif node.opcode not in trace_reduce.CONTAINERS:
                place = None
            return [(name, place) for name in node.operands]
        nexts = list(self.users.get(node.name, ()))
        if node.root and node.computation in self.callers:
            # what a loop's body hands on, the loop hands on
            nexts.append(self.callers[node.computation])
        out = []
        for name in nexts:
            user = self.instructions.get(name)
            if user is None:
                continue
            if user.opcode == "get-tuple-element":
                if place is None or user.index == place:
                    out.append((name, None))
            elif user.opcode == "tuple":
                out.append((name, user.operands.index(node.name)))
            elif user.opcode in trace_reduce.CONTAINERS:
                # read after the loop, or inside it
                out.append((name, place))
                out.extend((i.name, place) for run in self.runs.get(name, ())
                           for i in self.members.get(run, ())
                           if i.opcode == "parameter")
            else:
                out.append((name, None))
        return out

    def _walk(self, start: Instruction, forward: bool) -> Tuple[str, ...]:
        """Breadth first from ``start`` to the first instruction with a
        name of its own: over users (``forward``) or operands, through
        the instructions that have none. A loop's name (``hvd.layers``)
        says less than that of the instruction inside or after it that
        reads the value: it stands where the walk finds no such."""
        seen, queue, loop = {start.name}, [(start, None)], ()
        for node, place in queue:
            for name, at in self._next(node, place, forward):
                other = self.instructions.get(name)
                if other is None or name in seen or len(seen) >= WALK:
                    continue
                seen.add(name)
                own = (self._owners.get(name)
                       or self.path(self.deciding(other).op_name)[0])
                if own and other.opcode not in trace_reduce.CONTAINERS:
                    return own
                loop = loop or own
                queue.append((other, at))
        return loop

    def owner(self, inst: Instruction) -> Tuple[str, ...]:
        if inst.name not in self._owners:
            self._owners[inst.name] = (
                self.path(self.deciding(inst).op_name)[0]
                or self._walk(inst, True) or self._walk(inst, False))
        return self._owners[inst.name]

    # -- reason --------------------------------------------------------------

    def is_move(self, inst: Instruction) -> bool:
        if inst.opcode in ("copy", "transpose"):
            return True
        if inst.calls is None:
            return inst.opcode in MOVES
        return all(b.opcode in MOVES or b.scalar
                   for b in self.body(inst.calls))

    def reason(self, inst: Instruction, kind: str) -> str:
        if _HALF.search(inst.opcode):
            return WAIT
        if self.is_move(inst):
            return MOVE
        if kind == scope_reduce.UNSCOPED:
            return OTHER
        if kind != scope_reduce.BWD:
            return kind
        again = (".remat" in inst.name
                 or self.path(self.deciding(inst).op_name)[1])
        return RECOMPUTE_REASON if again else scope_reduce.BWD


@dataclasses.dataclass
class Attribution:
    """Nanoseconds summed over the traced steps and the devices: exact
    sums of the events' integer durations."""
    rows: Dict[Tuple[Tuple[str, ...], str], float]   # (owner, reason) -> ns
    kinds: Dict[Tuple[str, str], float]   # (scope_reduce kind, reason) -> ns
    unowned: Dict[str, float]             # "<name> <opcode> <type>" -> ns
    other: Dict[str, float]               # the same for reason ``other``
    decided_otherwise: Dict[str, float]   # reason given -> ns, see table()
    compute_ns: float                     # the compute events' durations
    unmatched: int                        # events the text does not name
    program_phases: frozenset             # every phase the text names
    devices: int
    steps: int
    seconds: float                        # what the attribution cost

    def ms(self, ns: float) -> float:
        """ms a step, mean over the devices."""
        return ns / self.devices / self.steps / 1e6

    def select_ns(self, reasons: Optional[Iterable[str]] = None,
                  phase: Optional[str] = None, match: str = "subtree"):
        reasons = None if reasons is None else set(reasons)
        total = 0.0
        for (owner, reason), ns in self.rows.items():
            if reasons is not None and reason not in reasons:
                continue
            if phase == UNOWNED:
                if owner:
                    continue
            elif phase is not None and not (
                    owner and owner[-1] == phase if match == "self"
                    else phase in owner):
                continue
            total += ns
        return total


def attribute(trace, text: str, scopes: Optional[dict],
              steps: int) -> Attribution:
    t0 = time.perf_counter()
    program = Program(text)
    if scopes is None:
        scopes = scope_reduce.parse_hlo(text)
    rows: Dict[Tuple[Tuple[str, ...], str], float] = {}
    kinds: Dict[Tuple[str, str], float] = {}
    unowned: Dict[str, float] = {}
    other: Dict[str, float] = {}
    otherwise: Dict[str, float] = {}
    placed: Dict[str, tuple] = {}   # name -> owner, reason, kind, guest, known
    compute_ns, unmatched = 0.0, 0
    for dev in trace.devices.values():
        for e in compute_events(dev):
            if e.name not in placed:
                inst = program.instructions.get(e.name)
                kind = scopes.get(e.name, NO_SCOPE).kind
                if inst is None:     # the text is another executable's
                    inst = Instruction(e.name, e.opcode, (), "", None,
                                       False, False)
                reason = program.reason(inst, kind)
                placed[e.name] = (
                    program.owner(inst), reason, kind,
                    reason != RECOMPUTE_REASON
                    and program.holds_recomputed(inst),
                    e.name in program.instructions)
            owner, reason, kind, held, known = placed[e.name]
            unmatched += not known
            compute_ns += e.dur
            rows[owner, reason] = rows.get((owner, reason), 0.0) + e.dur
            kinds[kind, reason] = kinds.get((kind, reason), 0.0) + e.dur
            for named, wanted in ((unowned, not owner),
                                  (other, reason == OTHER)):
                if wanted:
                    key = f"{e.name} {e.opcode} {e.text}".strip()
                    named[key] = named.get(key, 0.0) + e.dur
            if held:
                otherwise[reason] = otherwise.get(reason, 0.0) + e.dur
    return Attribution(rows, kinds, unowned, other, otherwise, compute_ns,
                       unmatched,
                       frozenset().union(*(s.phases for s in scopes.values())),
                       len(trace.devices), steps,
                       time.perf_counter() - t0)


def _join(owner: Sequence[str]) -> str:
    return "/".join(owner) or UNOWNED


def table(found: Attribution) -> dict:
    """Everything the attribution knows, in ms a step: ``owner_by_reason``
    (owner path -> reason -> ms, every row), the reasons' totals and their
    sum beside the compute events' summed durations (equal: the partition),
    ``kind_by_reason`` (``scope_reduce``'s kind of each instruction against
    the reason it got here: what a step.* kind loses to ``move`` and
    ``wait``, and how ``bwd`` splits), the ``unowned`` instructions by
    name and the two dozen largest of reason ``other``,
    ``recomputed_decided_otherwise_ms`` (instructions with a recomputed
    name in their body whose deciding instruction has none, by the reason
    they got: the most the deciding-instruction rule can have kept from
    ``recompute``), ``unmatched_instructions`` and the seconds the reader
    took."""
    by_owner: Dict[str, Dict[str, float]] = {}
    for (owner, reason), ns in found.rows.items():
        by_owner.setdefault(_join(owner), {})[reason] = found.ms(ns)
    by_kind: Dict[str, Dict[str, float]] = {}
    for (kind, reason), ns in found.kinds.items():
        by_kind.setdefault(kind, {})[reason] = found.ms(ns)
    reasons = {r: found.ms(found.select_ns([r])) for r in REASONS}
    return {
        "reasons_ms": reasons,
        "reasons_sum_ns": sum(found.rows.values()),
        "compute_events_sum_ns": found.compute_ns,
        "compute_events_sum_ms": found.ms(found.compute_ns),
        "unowned_ms": found.ms(found.select_ns(phase=UNOWNED)),
        "owner_by_reason_ms": dict(sorted(
            by_owner.items(), key=lambda kv: -sum(kv[1].values()))),
        "kind_by_reason_ms": by_kind,
        "unowned": [[k, found.ms(v)] for k, v in sorted(
            found.unowned.items(), key=lambda kv: -kv[1])],
        "other": [[k, found.ms(v)] for k, v in sorted(
            found.other.items(), key=lambda kv: -kv[1])[:24]],
        "recomputed_decided_otherwise_ms": {
            r: found.ms(v)
            for r, v in sorted(found.decided_otherwise.items())},
        "unmatched_instructions": found.unmatched,
        "devices": found.devices, "steps": found.steps,
        "attribute_s": found.seconds}


def say(found: Attribution, out=None) -> None:
    """The table on standard error."""
    out = out or sys.stderr
    doc = table(found)
    width = max([len(o) for o in doc["owner_by_reason_ms"]] + [5])
    print(f"readers/step_owners.py: ms a step by owner and reason (rows over "
          f"{ROW_MS} ms; {found.devices} device(s), {found.steps} steps, "
          f"{found.seconds:.2f} s to attribute)", file=out)
    print(f"{'owner':<{width}} " + " ".join(f"{r:>9}" for r in REASONS),
          file=out)
    for owner, by in doc["owner_by_reason_ms"].items():
        if sum(by.values()) >= ROW_MS:
            print(f"{owner:<{width}} " + " ".join(
                f"{by.get(r, 0.0):9.3f}" for r in REASONS), file=out)
    print(f"{'all':<{width}} " + " ".join(
        f"{doc['reasons_ms'][r]:9.3f}" for r in REASONS), file=out)
    def rounded(pairs):
        return [[k, round(v, 3)] for k, v in pairs]
    print(f"readers/step_owners.py: the reasons sum to "
          f"{found.ms(doc['reasons_sum_ns']):.6f} ms, the compute events to "
          f"{doc['compute_events_sum_ms']:.6f}; {found.unmatched} unmatched; "
          f"unowned {doc['unowned_ms']:.3f} ms: "
          f"{rounded(doc['unowned'][:12])}; the largest of other: "
          f"{rounded(doc['other'][:6])}; held recomputed names but decided "
          f"otherwise: "
          f"{dict(rounded(doc['recomputed_decided_otherwise_ms'].items()))}",
          file=out)


#: the last run's attribution, for ``tools/step_owners.py``
LAST: Optional[Attribution] = None


def _attribution(ctx: dict) -> Optional[Attribution]:
    """One attribution a run, kept in ``ctx``; None where there is no
    device trace, no text or a program without scopes."""
    global LAST
    if "step_owners" not in ctx:
        trace, text = ctx.get("trace"), ctx.get("hlo_text")
        found = None
        if trace is not None and trace.devices and text:
            if "scopes" not in ctx:
                ctx["scopes"] = scope_reduce.parse_hlo(text)
            if scope_reduce.has_scopes(ctx["scopes"]):
                found = attribute(trace, text, ctx["scopes"],
                                  ctx["trace_steps"])
                say(found)
        ctx["step_owners"] = LAST = found
    return ctx["step_owners"]


def read(read: dict, ctx: dict) -> Optional[float]:
    if read.get("value") != "ms":
        raise ValueError(f"step_owners reads 'ms', not {read.get('value')!r}")
    reasons = read.get("reason")
    if isinstance(reasons, str):
        reasons = [reasons]
    if reasons is not None and not set(reasons) <= set(REASONS):
        raise ValueError(f"step_owners knows the reasons {REASONS}, not "
                         f"{reasons}")
    match = read.get("match", "subtree")
    if match not in ("self", "subtree"):
        raise ValueError(f"step_owners matches self or subtree, not {match!r}")
    found = _attribution(ctx)
    if found is None:
        return None
    phase = read.get("phase")
    if phase not in (None, UNOWNED) and phase not in found.program_phases:
        return None
    return found.ms(found.select_ns(reasons, phase, match))
