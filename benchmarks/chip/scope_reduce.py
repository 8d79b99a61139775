"""Device time by what the program calls its parts: joins the compiled
step's HLO text with a profiler trace.

The program names the phases of its train step with ``jax.named_scope``
(``horovod_tpu/profiling/scopes.py``: ``hvd.embed``, ``hvd.attention``,
``hvd.attention.core``, ``hvd.mlp``, ``hvd.head``, ``hvd.grad_sync``,
``hvd.optimizer``). A scope is metadata: it becomes one component of the
``op_name`` path on every HLO instruction traced under it, *inside* fused
computations too, and differentiation writes the direction into the same
path (``.../transpose(jvp(Bert))/layer_3/hvd.mlp/ffn_in/dot_general``).
The trace's ``XLA Ops`` events carry the instruction's name, and
``jax.profiler.ProfileData`` gives no more than that; the compiled step's
text (``compiled.as_text()``) has the same names with their ``op_name``.
So:

* :func:`parse_hlo` reads the text into ``instruction name -> Scopes``:
  the phases and directions of the instruction itself and, for a fusion,
  of every instruction of its fused computation;
* :func:`reduce` covers the trace's compute instructions (containers and
  collectives left out, as in ``trace_reduce.compute``) that a selection
  takes, with ``trace_reduce``'s own ``union`` / ``length``, mean over
  the devices;
* :func:`host_span_median_ms` reads the program's host spans
  (``hvd.input.source``, ``hvd.input.place``) from the trace's host plane;
* :func:`idle_gaps` names an idle gap after the program's span inside the
  benchmark's (``bench.input/hvd.input.place``);
* :func:`read_metric` is the dispatch for the ``read`` kinds
  ``trace_scope`` and ``host_span`` of a metric file. SCOPES.md has the
  kinds, a worked example, and why ``run.py`` does not call it yet.

This module knows no phase by name but the two whose direction is not
in the path (``DIRECTION_OF``): any path component that starts with
``hvd.`` is a phase, so a model's new phase needs a metric file, no code.
A program without scopes (an older commit) gives ``None`` everywhere.

An executable read from JAX's persistent compilation cache carries the
metadata of the build that *wrote* it: the cache key leaves metadata out
(``jax_compilation_cache_include_metadata_in_key`` is off), so a cache
warmed by a commit without scopes hands this commit a step without them
(BERT's, on the chip in PR 24; a program with a Pallas kernel compiles
anew, because the kernel's serialised body carries the name stack).
"""

from __future__ import annotations

import dataclasses
import re
import statistics
from typing import Dict, FrozenSet, Iterable, List, Optional, Tuple

import trace_reduce
from trace_reduce import COLLECTIVES, DeviceTrace, Event, Trace

PHASE_PREFIX = "hvd."
FWD, BWD, OPT, MIXED, UNSCOPED = "fwd", "bwd", "opt", "mixed", "unscoped"
#: phases whose direction the path does not say: the update is its own,
#: the gradient synchronisation belongs to the backward pass
DIRECTION_OF = {"hvd.optimizer": OPT, "hvd.grad_sync": BWD}

_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
_WRAPPERS = re.compile(r"^(?:\w+\()+|\)+$")


@dataclasses.dataclass(frozen=True)
class Scopes:
    """What one executed instruction works for: the phases and the
    directions of its scoped instructions (its own, and a fusion's
    body's). An instruction whose body holds scoped and unscoped
    instructions goes by the scoped ones."""
    phases: FrozenSet[str] = frozenset()
    directions: FrozenSet[str] = frozenset()

    @property
    def kind(self) -> str:
        """Exactly one of fwd / bwd / opt / mixed / unscoped. ``mixed``
        is update work fused with model work (a weight gradient and its
        AdamW update in one fusion). Forward names beside backward ones
        make a *backward* instruction: XLA duplicates cheap forward
        producers (casts, the softmax's subtract, gelu's tanh) into the
        backward fusions that consume them, a third of BERT-Large's
        fusions, and they run where the cotangents are, in the backward
        pass."""
        if not self.directions:
            return UNSCOPED
        if OPT in self.directions:
            return OPT if len(self.directions) == 1 else MIXED
        return BWD if BWD in self.directions else FWD

    def merged(self, other: "Scopes") -> "Scopes":
        return Scopes(self.phases | other.phases,
                      self.directions | other.directions)


NO_SCOPE = Scopes()


def classify(op_name: str) -> Scopes:
    """One ``op_name`` path. A phase is a whole path component with its
    ``jvp(..)`` / ``transpose(..)`` wrappers peeled off, so
    ``hvd.attention`` is not found in ``hvd.attention.core``. The
    direction is the path's: differentiation wraps the *outermost*
    component of the name stack, which is the phase only where no module
    name stands outside it."""
    phases = frozenset(
        c for c in (_WRAPPERS.sub("", part) for part in op_name.split("/"))
        if c.startswith(PHASE_PREFIX))
    if not phases:
        return NO_SCOPE
    named = [DIRECTION_OF[p] for p in phases if p in DIRECTION_OF]
    direction = named[0] if named else (
        BWD if "transpose(" in op_name else FWD)
    return Scopes(phases, frozenset([direction]))


def parse_hlo(text: str) -> Dict[str, Scopes]:
    """``instruction name -> Scopes`` for every instruction of every
    computation of a compiled module's text. A fusion (anything with
    ``calls=``) takes on the scopes of its called computation's
    instructions, through nested fusions."""
    own: Dict[str, Scopes] = {}            # instruction -> its op_name's
    calls: Dict[str, str] = {}             # instruction -> computation
    members: Dict[str, List[str]] = {}     # computation -> instructions
    current: Optional[List[str]] = None
    for line in text.splitlines():
        if current is None:
            m = _COMPUTATION.match(line)
            if m:
                current = members.setdefault(m.group(1), [])
            continue
        if line.startswith("}"):
            current = None
            continue
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        name = m.group(1)
        current.append(name)
        # the metadata stands before a custom call's (long) backend_config
        op = _OP_NAME.search(line)
        own[name] = classify(op.group(1)) if op else NO_SCOPE
        called = _CALLS.search(line)
        if called:
            calls[name] = called.group(1)

    of_computation: Dict[str, Scopes] = {}

    def computation_scopes(comp: str) -> Scopes:
        if comp not in of_computation:
            of_computation[comp] = NO_SCOPE       # guards a cycle
            total = NO_SCOPE
            for inst in members.get(comp, ()):
                total = total.merged(instruction_scopes(inst))
            of_computation[comp] = total
        return of_computation[comp]

    def instruction_scopes(inst: str) -> Scopes:
        if inst in calls:
            return own[inst].merged(computation_scopes(calls[inst]))
        return own[inst]

    return {inst: instruction_scopes(inst) for inst in own}


# -- the fallback: the trace's own metadata ----------------------------------

def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        shift += 7
        if byte < 0x80:
            return value, i


def _fields(buf: bytes):
    """(field number, wire type, value) of one protobuf message: varints
    as ints, length-delimited fields as bytes; fixed-width fields are
    skipped. Enough for xplane.proto, and no TensorFlow import."""
    i = 0
    while i < len(buf):
        key, i = _varint(buf, i)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
            yield number, wire, value
        elif wire == 2:
            size, i = _varint(buf, i)
            yield number, wire, buf[i:i + size]
            i += size
        else:
            i += {1: 8, 5: 4}[wire]


def trace_op_names(path: str) -> Dict[str, str]:
    """``instruction name -> op_name`` from the ``tf_op`` stat on the
    event metadata of an ``.xplane.pb``'s device planes (XSpace.planes=1;
    XPlane.name=2, event_metadata=4, stat_metadata=5; XEventMetadata
    .name=2, .stats=5; XStat.metadata_id=1, .str_value=5, .ref_value=7).
    The fallback where the compiled step's text is not to be had (a
    capture taken elsewhere): it names an instruction's own op_name, a
    fusion's root's, not its body's, so it cannot tell a mixed fusion."""
    with open(path, "rb") as f:
        space = f.read()
    names: Dict[str, str] = {}
    for number, _w, plane in _fields(space):
        if number != 1:
            continue
        parts = list(_fields(plane))
        if not any(n == 2 and trace_reduce.DEVICE_PLANE.match(
                v.decode("utf-8", "replace")) for n, w, v in parts if w == 2):
            continue

        def entries(field):      # a map entry: key=1, value=2
            for n, w, v in parts:
                if n == field and w == 2:
                    yield dict((k, val) for k, _w2, val in _fields(v))[2]
        stat_names = {}
        for meta in entries(5):
            doc = {k: v for k, _w2, v in _fields(meta)}
            stat_names[doc.get(1, 0)] = doc.get(2, b"").decode("utf-8",
                                                               "replace")
        for meta in entries(4):
            text, op_name = "", None
            for k, w, v in _fields(meta):
                if k == 2 and w == 2:
                    text = v.decode("utf-8", "replace")
                elif k == 5 and w == 2:
                    stat = {sk: sv for sk, _w2, sv in _fields(v)}
                    if stat_names.get(stat.get(1)) == "tf_op":
                        op_name = (stat[5].decode("utf-8", "replace")
                                   if 5 in stat else stat_names.get(
                                       stat.get(7), ""))
            if op_name:
                # "<op_name>:<op type>"
                names[trace_reduce.parse_instruction(text)[0]] = \
                    op_name.rsplit(":", 1)[0]
    return names


def scopes_from_trace(path: str) -> Dict[str, Scopes]:
    return {name: classify(op) for name, op in trace_op_names(path).items()}


def has_scopes(scopes: Optional[Dict[str, Scopes]]) -> bool:
    return bool(scopes) and any(s.phases for s in scopes.values())


# -- the device side ---------------------------------------------------------

def compute_events(dev: DeviceTrace) -> List[Event]:
    """The instructions ``trace_reduce.compute`` covers: neither a
    container nor a collective."""
    return [e for e in dev.ops
            if not e.is_container and not COLLECTIVES.search(e.key)]


def selects(scopes: Scopes, select: dict) -> bool:
    """``{"kind": "fwd|bwd|opt|mixed|unscoped"}`` partitions the compute
    instructions; ``{"phase": "hvd.head"}`` takes every instruction with
    the phase in its body, whatever its direction."""
    if "kind" in select:
        return scopes.kind == select["kind"]
    if "phase" in select:
        return select["phase"] in scopes.phases
    raise ValueError(f"a trace_scope selects a kind or a phase, not "
                     f"{sorted(select)}")


def cover_ns(dev: DeviceTrace, scopes: Dict[str, Scopes],
             select: dict) -> float:
    """Nanoseconds of one device covered by the selected compute
    instructions. An instruction the text does not know is unscoped."""
    return trace_reduce.length(trace_reduce.union(
        (e.start, e.end) for e in compute_events(dev)
        if selects(scopes.get(e.name, NO_SCOPE), select)))


def reduce(trace: Optional[Trace], scopes: Optional[Dict[str, Scopes]],
           select: dict) -> Optional[float]:
    """Mean over the trace's devices of :func:`cover_ns`; None where
    there is no device trace, the program carries no scope at all, or
    not the selected phase."""
    if trace is None or not trace.devices or not has_scopes(scopes):
        return None
    if "phase" in select and not any(
            select["phase"] in s.phases for s in scopes.values()):
        return None
    return statistics.mean(cover_ns(d, scopes, select)
                           for d in trace.devices.values())


def top_instructions(trace: Trace, scopes: Dict[str, Scopes], select: dict,
                     n: int = 8) -> List[Tuple[str, float]]:
    """The selected instructions that took most device time, grouped as
    ``trace_reduce.top_ops`` groups them: [group, seconds over the traced
    window averaged over the devices]."""
    totals: Dict[str, float] = {}
    for dev in trace.devices.values():
        for e in compute_events(dev):
            if selects(scopes.get(e.name, NO_SCOPE), select):
                key = f"{re.sub(r'[.0-9]+$', '', e.name)} {e.text}".strip()
                totals[key] = totals.get(key, 0.0) + e.dur
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9 / len(trace.devices)] for k, v in ranked]


def unmatched(trace: Trace, scopes: Dict[str, Scopes]) -> int:
    """Executed compute instructions the text does not name: 0 when text
    and trace are of the same executable."""
    return sum(e.name not in scopes for dev in trace.devices.values()
               for e in compute_events(dev))


# -- the host side -----------------------------------------------------------

def host_span_median_ms(trace: Optional[Trace], name: str) -> Optional[float]:
    durations = [e.dur for e in getattr(trace, "host_spans", ())
                 if e.name == name]
    return statistics.median(durations) / 1e6 if durations else None


def _most_cover(spans: Iterable[Event], s: float, e: float):
    best, cover = None, 0.0
    for span in spans:
        c = min(e, span.end) - max(s, span.start)
        if c > cover:
            best, cover = span, c
    return best, cover


def idle_gaps(trace: Trace, n: int = 5) -> List[Tuple[str, float]]:
    """``trace_reduce.idle_gaps`` with the program's word: the longest
    gaps between busy intervals, each named after the benchmark's span
    that covers most of it and, where a program span (``hvd.*``) inside
    that span covers most of the gap, ``<bench span>/<program span>``."""
    outer = [s for s in trace.host_spans
             if not s.name.startswith(PHASE_PREFIX)]
    inner = [s for s in trace.host_spans if s.name.startswith(PHASE_PREFIX)]
    gaps = []
    for dev in trace.devices.values():
        b = trace_reduce.busy(dev)
        gaps += [(b[i][1], b[i + 1][0]) for i in range(len(b) - 1)]
    out = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        span, _ = _most_cover(outer, s, e)
        name = span.name if span is not None else "host:none"
        if span is not None:
            within, cover = _most_cover(
                (i for i in inner
                 if i.start >= span.start and i.end <= span.end), s, e)
            if within is not None and cover > (e - s) / 2:
                name = f"{name}/{within.name}"
        out.append([name, (e - s) / 1e9])
    return out


# -- a metric file's "read" --------------------------------------------------

def read_metric(read: dict, ctx: dict) -> Optional[float]:
    """The value of a metric whose ``read`` is one of

    ``{"trace_scope": {"kind": ..} | {"phase": ..}, "per_step": bool,
    "scale": x}``  device time of the selected instructions, from
    ``ctx["trace"]`` and ``ctx["hlo_text"]`` (the compiled step's text);

    ``{"host_span": "<hvd.* span>", "reduce": "median_ms"}``  median of
    the program's host span over the traced steps;

    or None where there is nothing to read (no trace, no text, a program
    without that scope or span)."""
    if "trace_scope" in read:
        if "scopes" not in ctx:
            text = ctx.get("hlo_text")
            ctx["scopes"] = parse_hlo(text) if text else None
        value = reduce(ctx.get("trace"), ctx["scopes"], read["trace_scope"])
        if value is None:
            return None
        if read.get("per_step"):
            value /= ctx["trace_steps"]
        return value * read.get("scale", 1.0)
    if "host_span" in read:
        if read["reduce"] != "median_ms":
            raise ValueError(f"unknown span reduce {read['reduce']!r}")
        return host_span_median_ms(ctx.get("trace"), read["host_span"])
    raise ValueError(f"scope_reduce reads trace_scope and host_span, not "
                     f"{sorted(read)}")
