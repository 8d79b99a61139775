"""From a profiler trace (``*.xplane.pb``) to numbers. The one place
where device time is reduced; every per-layer metric with a
``trace_ops`` source and the ``breakdown`` go through it.

What a TPU trace holds (JAX 0.9, looked at by hand on a v5e, PR 23):

* one plane ``/device:TPU:<n>`` per chip with the lines ``Steps``,
  ``XLA Modules`` (one event per executed program), ``XLA Ops`` (one
  event per executed HLO instruction; the name is the instruction's text,
  ``%fusion.12 = f32[..] fusion(...)``) and ``Async XLA Ops`` (one event
  per asynchronous pair, from its ``-start`` to its ``-done``:
  copies, slices, and collectives when XLA makes them asynchronous);
* on ``XLA Ops`` a ``while`` / ``conditional`` / ``call`` covers the
  events of its body, and a fusion may overlap a neighbour, so durations
  are never summed for busy time: busy is the union of the intervals;
* one plane ``/host:CPU`` with a line per thread; the line of the thread
  that ran the loop (named after the process) holds the
  ``jax.profiler.TraceAnnotation`` spans, on the same clock as the
  device planes (nanoseconds from the start of the session).

Only ``jax.profiler.ProfileData`` is needed to read it.
"""

from __future__ import annotations

import dataclasses
import re
import statistics
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]          # [start_ns, end_ns)

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, ASYNC_LINE = "XLA Ops", "Async XLA Ops"
HOST_PLANE = "/host:CPU"
#: instructions that only contain others: in the busy union, out of sums
CONTAINERS = ("while", "conditional", "call")
#: what counts as a collective wherever a metric asks for "compute". A
#: pattern is searched in "<name> <opcode>": GSPMD names its all-reduce
#: ``all-reduce.3``, a shard_map program names it after the JAX primitive
#: (``psum.7``) and only the opcode says what it is
COLLECTIVES = re.compile(
    r"(^| )(all-reduce|reduce-scatter|all-gather|collective-permute"
    r"|all-to-all)")


@dataclasses.dataclass(frozen=True)
class Event:
    name: str         # short name: "fusion.12", "hvd_flash_attention.6"
    start: float      # ns
    dur: float        # ns
    opcode: str = ""  # "fusion", "all-reduce", "custom-call", "while"
    text: str = ""    # the instruction's result type, for the breakdown

    @property
    def end(self) -> float:
        return self.start + self.dur

    @property
    def key(self) -> str:
        """What a metric's pattern is searched in."""
        return f"{self.name} {self.opcode}"

    @property
    def is_container(self) -> bool:
        return self.opcode in CONTAINERS


@dataclasses.dataclass
class DeviceTrace:
    ops: List[Event]            # the "XLA Ops" line
    async_ops: List[Event]      # the "Async XLA Ops" line


@dataclasses.dataclass
class Trace:
    devices: Dict[int, DeviceTrace]
    host_spans: List[Event]


def parse_instruction(hlo_text: str) -> Tuple[str, str, str]:
    """``%fusion.12 = f32[8]{0} fusion(...)`` -> (``fusion.12``,
    ``fusion``, ``f32[8]``): short name, opcode, result type (a tuple's
    first element stands for it). A name that is no instruction (a host
    span) comes back as it is."""
    head, _, rest = hlo_text.partition(" = ")
    if rest.startswith("("):            # a tuple type: to its closing paren
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        result, tail = rest[:i + 1], rest[i + 1:]
    else:
        result, _, tail = rest.partition(" ")
    result = re.sub(r"\{[^{}]*\}", "", result)
    first = re.match(r"\((\w+\[[^\]]*\]), ", result)
    if first:
        result = f"({first.group(1)},..)"
    return head.lstrip("%"), tail.strip().split("(")[0], result[:48]


def load(path: str, device_ids: Optional[Sequence[int]] = None,
         host_prefix: str = "bench.") -> Trace:
    """Read an ``.xplane.pb``. Keeps the device planes in ``device_ids``
    (all when None) and the host spans whose name starts with
    ``host_prefix``."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            n = int(m.group(1))
            if device_ids is not None and n not in device_ids:
                continue
            lines = {ln.name: ln for ln in plane.lines}

            def events(line_name):
                out = []
                for e in getattr(lines.get(line_name), "events", ()):
                    name, opcode, text = parse_instruction(e.name)
                    out.append(Event(name, e.start_ns, e.duration_ns,
                                     opcode, text))
                return out
            devices[n] = DeviceTrace(events(OPS_LINE), events(ASYNC_LINE))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host += [Event(e.name, e.start_ns, e.duration_ns)
                         for e in line.events
                         if e.name.startswith(host_prefix)]
    return Trace(devices, sorted(host, key=lambda e: e.start))


# -- interval arithmetic ------------------------------------------------------

def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Disjoint, sorted intervals covering the same points."""
    out: List[Interval] = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def length(disjoint: Iterable[Interval]) -> float:
    return sum(e - s for s, e in disjoint)


def subtract(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """The part of ``a`` not covered by ``b``; both disjoint and sorted."""
    out, j = [], 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k, cur = j, s
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def _spans(events: Iterable[Event]) -> List[Interval]:
    return [(e.start, e.end) for e in events]


# -- one device ----------------------------------------------------------------

def busy(dev: DeviceTrace) -> List[Interval]:
    """Where any instruction ran, containers included."""
    return union(_spans(dev.ops))


def window(dev: DeviceTrace) -> Interval:
    """First instruction's start to last instruction's end."""
    if not dev.ops:
        return (0.0, 0.0)
    return (min(e.start for e in dev.ops), max(e.end for e in dev.ops))


def matching(dev: DeviceTrace, pattern: str,
             with_async: bool = False) -> List[Event]:
    """Instructions in whose "<name> <opcode>" the pattern is found;
    containers never match."""
    rx = re.compile(pattern)
    pool = dev.ops + dev.async_ops if with_async else dev.ops
    return [e for e in pool if rx.search(e.key) and not e.is_container]


def compute(dev: DeviceTrace) -> List[Interval]:
    """Where an instruction that is neither a container nor a collective
    ran: what a collective can hide behind."""
    return union(_spans(
        e for e in dev.ops
        if not e.is_container and not COLLECTIVES.search(e.key)))


def reduce_device(dev: DeviceTrace, pattern: Optional[str],
                  how: str) -> float:
    """One number in nanoseconds (``idle`` in percent) for one device.

    sum      summed durations of the matching instructions on ``XLA Ops``
    union    length covered by the matching instructions, their
             asynchronous spans included (a collective's time in flight)
    exposed  the part of that cover during which no compute ran
    busy     length of the busy union
    idle     100 * (1 - busy / window)
    """
    if how == "busy":
        return length(busy(dev))
    if how == "idle":
        w = window(dev)
        return 100.0 * (1.0 - length(busy(dev)) / (w[1] - w[0])) \
            if w[1] > w[0] else 0.0
    if how == "sum":
        return sum(e.dur for e in matching(dev, pattern))
    cover = union(_spans(matching(dev, pattern, with_async=True)))
    if how == "union":
        return length(cover)
    if how == "exposed":
        return length(subtract(cover, compute(dev)))
    raise ValueError(f"unknown reduce {how!r}")


# -- all devices ----------------------------------------------------------------

def across(values: Sequence[float], how: str = "mean") -> float:
    return {"mean": statistics.mean, "median": statistics.median}[how](values)


def reduce(trace: Trace, pattern: Optional[str], how: str,
           over: str = "mean") -> Optional[float]:
    """``reduce_device`` over the trace's devices; None when the trace
    holds no device or, for a pattern, when nothing matched anywhere."""
    if not trace.devices:
        return None
    if pattern is not None and not any(
            matching(d, pattern, with_async=True)
            for d in trace.devices.values()):
        return None
    return across([reduce_device(d, pattern, how)
                   for d in trace.devices.values()], over)


def busy_and_window_s(trace: Trace) -> Tuple[float, float]:
    """(busy seconds averaged over the devices, traced window seconds:
    the longest device's first start to last end)."""
    busy_ns = across([length(busy(d)) for d in trace.devices.values()])
    spans = [window(d) for d in trace.devices.values()]
    return busy_ns / 1e9, max(e - s for s, e in spans) / 1e9


def top_ops(trace: Trace, n: int = 10) -> List[Tuple[str, float]]:
    """The instructions that took most device time, containers left out.
    An unrolled model has one instruction per layer for the same work, so
    instructions are grouped by name without its number and by result
    type: [that group, seconds over the traced window averaged over the
    devices]."""
    totals: Dict[str, float] = {}
    for dev in trace.devices.values():
        for e in dev.ops:
            if not e.is_container:
                key = f"{re.sub(r'[.0-9]+$', '', e.name)} {e.text}".strip()
                totals[key] = totals.get(key, 0.0) + e.dur
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9 / len(trace.devices)] for k, v in ranked]


def idle_gaps(trace: Trace, n: int = 5) -> List[Tuple[str, float]]:
    """The longest gaps between busy intervals on any device, each named
    after the host span that covers most of it (``host:none`` when no
    span does): [span name, seconds]."""
    gaps = []
    for dev in trace.devices.values():
        b = busy(dev)
        gaps += [(b[i][1], b[i + 1][0]) for i in range(len(b) - 1)]
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:n]
    out = []
    for s, e in gaps:
        best, cover = "host:none", 0.0
        for span in trace.host_spans:
            if span.start >= e:
                break
            c = min(e, span.end) - max(s, span.start)
            if c > cover:
                best, cover = span.name, c
        out.append([best, (e - s) / 1e9])
    return out
