"""The host's pauses in the window, and who they were.

    {"reader": "host_pauses", "value": "<one of VALUES>"}

Two sources. From the harness's own clock (``ctx["spans_seconds"]``,
``ctx["step_done_at_s"]``; any program):

``pause_max_ms``   the most that one of the window's steps'
                   ``bench.input + bench.dispatch`` took beyond their
                   median, ms: the size of the host's largest pause
``stall_pct``      100 x how far the window's last known completion lies
                   behind its place on the median step's clock / the
                   window: what the device lost, to a pause of the host
                   longer than the steps in flight or to anything below
                   the host loop (``bench.wait`` is 98 % of a window: a
                   process that is not running is most likely there, and
                   ``pause_max_ms`` cannot see it)

From the program's host log (``horovod_tpu.profiling.host_log``: the ring of
``hvd.input.*`` spans, ``hvd.host.gc`` and ``hvd.host.compile`` records on
``time.perf_counter()``; a program without it gives None):

``gc_pause_ms``    summed ``hvd.host.gc`` records that began in the window

With it goes one line on standard error, for the one who asks which pause a
run met: the largest pause's step, how much of it a record of the program
names (a collection, a compile, the step's own source / place span beyond its
median) and which; the sum of all steps' excesses (half the steps lie above
the median by jitter, so it has a floor that is no pause); the window's
collections by generation and those before it.

**Placing the ring in the window.** ``ctx`` has no ``perf_counter`` time, the
ring has nothing else. Every ``next(batches)`` of ``run.py`` is one
``put_next`` of ``device_prefetch``: one ``hvd.input.source`` and one
``hvd.input.place`` record, inside ``bench.input``. After the window a traced
run takes ``ctx["trace_steps"]`` batches and one more for ``compiled_step``,
so of ``P`` batches in the ring the window's step ``i`` of ``N`` is batch
``P - 1 - trace_steps - N + i``, and that batch's source record begins where
the step's ``bench.input`` does. **Checked, not trusted:** in every step
source + place must fit inside that step's ``bench.input``, the window's last
batch must begin before its closing and the next one after it. Where a check
fails ``gc_pause_ms`` is None and standard error says which step failed.
"""

from __future__ import annotations

import statistics
import sys

VALUES = ("pause_max_ms", "stall_pct", "gc_pause_ms")
SLACK_S = 50e-6      # what two clocks read a few lines apart may differ by
SOURCE, PLACE = "hvd.input.source", "hvd.input.place"
GC, COMPILE = "hvd.host.gc", "hvd.host.compile"


def _excess(values):
    middle = statistics.median(values)
    return [max(0.0, v - middle) for v in values]


def _step_seconds(ctx):
    """``bench.input + bench.dispatch`` of each of the window's steps."""
    spans = ctx.get("spans_seconds") or {}
    inputs, dispatches = spans.get("bench.input"), spans.get("bench.dispatch")
    if not inputs or not dispatches or len(inputs) != len(dispatches):
        return None
    return [a + b for a, b in zip(inputs, dispatches)]


def _completions(ctx):
    """(the times the window's steps were known complete, without
    ``run_steps``' closing fill; the window's seconds)."""
    done = list(ctx.get("step_done_at_s") or ())
    if not done:
        return None, None
    window_s = done[-1]
    while done and done[-1] == window_s:      # the fill, not a reading
        done.pop()
    return done, window_s


def _stall_pct(ctx):
    """What the device lost, as a share of the window. A step's completion
    is *known* no earlier than it happened, and later where the host was
    busy when it did: a pause the steps in flight hid shows as one long gap
    and one short (153 + 38 ms for two steps of 95, PERF.md, PR 35), which
    a sum of the gaps' excesses would count and the device never felt. A
    device that ran dry never catches up: so the loss is how far the last
    completion lies behind its place on the median step's clock, less the
    least any completion did."""
    done, window_s = _completions(ctx)
    if not done or len(done) < 3 or window_s <= 0:
        return None
    step = statistics.median(b - a for a, b in zip(done, done[1:]))
    behind = [t - done[0] - k * step for k, t in enumerate(done)]
    return 100.0 * (behind[-1] - min(behind)) / window_s


def _covered(intervals, a, b):
    """Length of [a, b] that the union of ``intervals`` covers."""
    total, reach = 0.0, a
    for s, e in sorted(intervals):
        s, e = max(s, reach), min(e, b)
        if e > s:
            total, reach = total + e - s, e
    return total


class _NotPlaced(Exception):
    """The ring cannot be placed in the window; the message says why."""


def _aligned(ctx, records):
    """The window's steps as ``[(source record, place record)]`` and the
    window's opening and closing on the ring's clock."""
    inputs = (ctx.get("spans_seconds") or {}).get("bench.input")
    done = ctx.get("step_done_at_s")
    if not inputs or not done or "trace_steps" not in ctx:
        raise _NotPlaced("the run recorded no window")
    batches, source = [], None
    for r in records:
        if r[0] == SOURCE:
            source = r
        elif r[0] == PLACE and source is not None:
            batches.append((source, r))
            source = None
    n = len(inputs)
    first = len(batches) - 1 - ctx["trace_steps"] - n
    if first < 0:
        raise _NotPlaced(
            f"the ring holds {len(batches)} batches, the window's {n} "
            f"steps, {ctx['trace_steps']} traced and one more need "
            f"{n + ctx['trace_steps'] + 1}")
    steps = batches[first:first + n]
    for i, ((_s, _t0, source_s, _m), (_p, _t1, place_s, _m2)) in \
            enumerate(steps):
        if source_s + place_s > inputs[i] + SLACK_S:
            raise _NotPlaced(
                f"step {i}: {SOURCE} + {PLACE} "
                f"{1e3 * (source_s + place_s):.3f} ms exceed its "
                f"bench.input {1e3 * inputs[i]:.3f} ms")
    opened = steps[0][0][1]
    closed = opened + done[-1]
    if steps[-1][0][1] > closed:
        raise _NotPlaced(
            f"step {n - 1}'s batch begins "
            f"{steps[-1][0][1] - closed:.6f} s after the window closed")
    if batches[first + n][0][1] < closed - SLACK_S:
        raise _NotPlaced(
            f"the first batch after the window begins "
            f"{closed - batches[first + n][0][1]:.6f} s before it closed")
    return steps, opened, closed


def _from_the_ring(ctx):
    """``gc_pause_ms`` and the line on standard error; None, and a line
    that says why, where the ring cannot be placed in the window."""
    try:
        from horovod_tpu.profiling import host_log
        records = host_log.records()
    except (ImportError, AttributeError):
        return None          # a program without the host log: nothing to read
    try:
        steps, opened, closed = _aligned(ctx, records)
    except _NotPlaced as why:
        print(f"readers/host_pauses.py: {why}; the ring is not placed in "
              "the window and gc_pause_ms is left out", file=sys.stderr)
        return None
    collections = [r for r in records if r[0] == GC]
    before = [r for r in collections if r[1] < opened]
    in_window = [r for r in collections if opened <= r[1] < closed]
    named = [r for r in records
             if r[0] in (GC, COMPILE) and r[1] + r[2] > opened]
    seconds = _step_seconds(ctx)
    over = _excess(seconds)
    i = over.index(max(over))
    (_n, s0, s_s, _m), (_n2, p0, p_s, _m2) = steps[i]
    a, b = s0 - SLACK_S, s0 + seconds[i] + SLACK_S
    spans = [(r[1], r[1] + r[2]) for r in named]
    # the step's own spans beyond their medians count for what a named
    # record inside them does not already explain
    source_over = _excess([s[2] for s, _p in steps])[i]
    place_over = _excess([p[2] for _s, p in steps])[i]
    by_name = (_covered(spans, a, b)
               + max(0.0, source_over - _covered(spans, s0, s0 + s_s))
               + max(0.0, place_over - _covered(spans, p0, p0 + p_s)))
    who = [(r[0], r[3]) for r in named if r[1] < b and r[1] + r[2] > a]
    who += [(name, f"+{1e3 * more:.3f} ms") for name, more in (
        (SOURCE, source_over), (PLACE, place_over)) if more]
    waits = (ctx.get("spans_seconds") or {}).get("bench.wait") or [0.0]
    done, _w = _completions(ctx)
    gaps = [b - a for a, b in zip(done, done[1:])] or [0.0]
    by_generation = [sum(1 for r in in_window
                         if (r[3] or {}).get("generation") == g)
                     for g in (0, 1, 2)]
    full = (", which is all it holds: the oldest are gone"
            if len(records) >= host_log.RING_RECORDS else "")
    print(f"readers/host_pauses.py: the window's largest pause, step {i}: "
          f"{1e3 * over[i]:.3f} ms over the median, "
          f"{1e3 * min(over[i], by_name):.3f} ms of it named {who[:6]}; all "
          f"steps' excesses {1e3 * sum(over):.3f} ms; collections in the "
          f"window by generation {by_generation}, before it "
          f"{len(before)} in {sum(r[2] for r in before):.3f} s (of the "
          f"ring's {len(records)} records{full}); longest bench.wait "
          f"{1e3 * max(waits):.1f} ms, longest gap between completions "
          f"{1e3 * max(gaps):.1f} ms after step {gaps.index(max(gaps))}",
          file=sys.stderr)
    return 1e3 * sum(r[2] for r in in_window)


def read(read: dict, ctx: dict):
    which = read["value"]
    if which not in VALUES:
        raise ValueError(f"host_pauses reads one of {VALUES}, not {which!r}")
    if which == "stall_pct":
        return _stall_pct(ctx)
    seconds = _step_seconds(ctx)
    if seconds is None:
        return None
    if which == "pause_max_ms":
        return 1e3 * max(_excess(seconds))
    return _from_the_ring(ctx)
