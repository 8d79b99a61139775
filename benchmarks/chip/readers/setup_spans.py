"""Set-up's seconds by owner, from the program's host log.

    {"reader": "setup_spans", "value": "<one of VALUES>"}

The program (``horovod_tpu.profiling``: ``host_log``, ``compile_watch``,
``scopes``) writes one ring of ``(name, start, duration, meta)`` records on
``time.perf_counter()``. Read here, each a *first* record of its kind (the
harness lowers the step once more after the window, a later ``hvd.init()`` is
a re-mesh's):

``step_trace_s``          the timed step's trace, Python to jaxpr: the first
                          ``hvd.host.compile`` record with ``event`` ``trace``
                          that no other trace lay around (``nested`` unset)
                          and ``function`` the one that ``ctx["hlo_text"]``'s
                          ``HloModule jit_<name>`` names
``step_lower_s``          that function's first ``lower`` record: jaxpr to
                          StableHLO, a kernel's Mosaic lowering inside it
``step_trace_kernels_s``  cover of the ``hvd.host.trace/kernel/<kernel>`` spans
                          that began inside the step's trace: the Pallas call
                          sites, a kernel's body traced where its call is
                          bound, forward and (from autodiff's transposition)
                          backward
``step_trace_model_s``    cover of the ``hvd.host.trace/hvd.<phase>`` spans
                          inside it, less what the kernel spans cover of
                          them: the model's own Python. What is left of
                          ``step_trace_s`` after these two ran in no span of
                          the program's: autodiff, transposition, JAX's own
                          machinery
``program_import_s``      ``hvd.host.import``: this package's import, first
                          line to last
``init_s``                ``hvd.host.init``: one ``hvd.init()``

A run that recorded no set-up (no ``ctx["counters"]``), a program without
the host log, a program without the record, or a full ring (its oldest
records are gone, so none is known to be a first) gives None and no raise; a step whose trace bound no kernel (its jitted call
sites traced before, in the reference check's program) reads 0. With
``step_trace_s`` goes one line on standard error: how many records the ring
held at the read and how many of them set-up wrote, how many nested traces
were folded into ``compile_watch.by_function()``, and its three functions
with the most seconds (process totals, the lowering after the window among
them).
"""

from __future__ import annotations

import re
import sys

from readers import host_pauses

VALUES = ("step_trace_s", "step_lower_s", "step_trace_model_s",
          "step_trace_kernels_s", "program_import_s", "init_s")
COMPILE, TRACE = "hvd.host.compile", "hvd.host.trace"
IMPORT, INIT = "hvd.host.import", "hvd.host.init"
KERNEL = TRACE + "/kernel/"
PHASE = TRACE + "/hvd."


def _first(records, name, **meta):
    for r in records:
        if r[0] == name and all((r[3] or {}).get(k) == v
                                for k, v in meta.items()):
            return r
    return None


def _step_function(ctx):
    """The timed step's name as the program's records say it."""
    named = re.search(r"HloModule jit_(\w+)", ctx.get("hlo_text") or "")
    return named.group(1) if named else None


def _inside(records, prefix, a, b):
    """[(start, end)] of the spans named ``prefix...`` that began in
    [a, b)."""
    return [(r[1], r[1] + r[2]) for r in records
            if r[0].startswith(prefix) and a <= r[1] < b]


def _say(ctx, records) -> None:
    """The line on standard error: set-up's records in the ring (those
    before the window's opening, as ``host_pauses`` places it), the nested
    traces that are seconds of ``by_function()`` and no record, and its
    three functions with the most top-level seconds."""
    try:
        from horovod_tpu.profiling import compile_watch
        table = compile_watch.by_function()
    except (ImportError, AttributeError):
        return
    try:
        opened = host_pauses._aligned(ctx, records)[1]
        setup = f"{sum(r[1] < opened for r in records)} before the window"
    except host_pauses._NotPlaced:
        setup = "the window not placed"
    folded = sum(e["nested_traces"] for e in table.values()) - sum(
        bool((r[3] or {}).get("nested")) for r in records)

    def seconds(entry):
        return sum(v for k, v in entry.items() if k.endswith("_seconds")
                   and not k.startswith("nested"))
    largest = sorted(table.items(), key=lambda kv: -seconds(kv[1]))[:3]
    print(f"readers/setup_spans.py: the ring holds {len(records)} records, "
          f"{setup}; {folded} nested traces are no record; by_function()'s "
          "three largest: " + "; ".join(
              f"{name} " + ", ".join(
                  f"{k[:-len('_seconds')]} {v:.3f} s"
                  for k, v in entry.items()
                  if k.endswith("_seconds") and v)
              for name, entry in largest), file=sys.stderr)


def read(read: dict, ctx: dict):
    which = read["value"]
    if which not in VALUES:
        raise ValueError(f"setup_spans reads one of {VALUES}, not {which!r}")
    if "counters" not in ctx:
        return None              # the run recorded no set-up
    try:
        from horovod_tpu.profiling import host_log
        records = host_log.records()
    except (ImportError, AttributeError):
        return None              # a program without the host log
    if len(records) >= host_log.RING_RECORDS:
        return None              # the oldest are gone: no first is known
    if which in ("program_import_s", "init_s"):
        found = _first(records, IMPORT if which == "program_import_s"
                       else INIT)
        return None if found is None else found[2]
    function = _step_function(ctx)
    if function is None:
        return None
    if which == "step_lower_s":
        found = _first(records, COMPILE, event="lower", function=function)
        return None if found is None else found[2]
    trace = _first(records, COMPILE, event="trace", function=function,
                   nested=None)
    if trace is None:
        return None
    if which == "step_trace_s":
        _say(ctx, records)
        return trace[2]
    a, b = trace[1], trace[1] + trace[2]
    if not _inside(records, TRACE + "/", a, b):
        return None              # a program from before the spans
    kernels = _inside(records, KERNEL, a, b)
    if which == "step_trace_kernels_s":
        return host_pauses._covered(kernels, a, b)
    phases = _inside(records, PHASE, a, b)
    return host_pauses._covered(phases + kernels, a, b) \
        - host_pauses._covered(kernels, a, b)
