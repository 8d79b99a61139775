"""One traced run of a cell with the phase metrics of ``phase_metrics/``.

    python3 benchmarks/chip/tools/phase_report.py --workload <cell> \
        --seed <n> --seconds <s>

``run.py --trace 1`` plus what it cannot read yet: the cell's set-up as
``run.py`` makes it (reference check included, so that the compile
counters cover the same programs), a short untraced window, the traced
steps with the program's ``hvd.*`` host spans kept beside ``bench.*``,
the compiled step's text, and every metric of ``phase_metrics/`` read
through ``scope_reduce.read_metric`` or ``run.read_layer_metric``. The
last line of standard output is one JSON object: ``metrics``, the
``identity`` (the five kinds + the exposed collective against
``step.device_ms``, and the time only a container covers), ``tracing``
(what the instrumentation costs when it is on) and ``breakdown``. The
record goes to ``<out>/<cell>.seed<n>.phases.json``.

Why a tool and not ``run.py``: SCOPES.md. ``--rehearse`` walks the tiny
sizes on the CPU, where a trace has no device plane: the host spans and
the counters are read, the device metrics are left out.
"""

from __future__ import annotations

import argparse
import glob
import importlib
import itertools
import json
import os
import statistics
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [HERE, os.path.dirname(os.path.dirname(HERE))]

import run as harness       # noqa: E402  (set-up is counted from here)

KINDS = ("fwd", "bwd", "opt", "mixed", "unscoped")
HOST_PREFIXES = ("bench.", "hvd.")


def read_metric(read: dict, ctx: dict):
    """The dispatch ``run.read_layer_metric`` would gain."""
    import scope_reduce
    if "trace_scope" in read or "host_span" in read:
        return scope_reduce.read_metric(read, ctx)
    return harness.read_layer_metric(read, ctx)


def phase_values(cell: str, ctx: dict) -> dict:
    values = {}
    for path in sorted(glob.glob(os.path.join(HERE, "phase_metrics",
                                              "*.json"))):
        spec = harness.read_json(path)
        if "workloads" in spec and cell not in spec["workloads"]:
            continue
        value = read_metric(spec["read"], ctx)
        if value is not None:
            values[os.path.basename(path)[:-len(".json")]] = value
    return values


def take_trace(cell, batches, job, devices, watch):
    """``run.take_trace`` with the program's host spans kept and the
    traced steps timed. Returns (trace or None, seconds of the steps)."""
    import jax
    import trace_reduce
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    with tempfile.TemporaryDirectory(prefix="chipbench_trace_") as trace_dir:
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        try:
            seconds = harness.run_steps(
                cell, batches, harness.Spans(annotate=True),
                job["max_ahead"],
                lambda n, _s: n >= job["trace_steps"], watch)[2]
        finally:
            jax.profiler.stop_trace()
        found = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        trace = trace_reduce.load(found[0], [d.id for d in devices],
                                  HOST_PREFIXES) if found else None
    return trace, seconds


def identity(trace, scopes, values: dict, steps: int) -> dict:
    """The five kinds + the exposed collective against the busy time, in
    ms a step, and the time only a container covers (between the
    instructions of a scan's body), which is in neither."""
    import trace_reduce as tr
    per_step = lambda ns: ns / steps / 1e6                     # noqa: E731
    device_ms = per_step(tr.reduce(trace, None, "busy", "mean"))
    exposed = tr.reduce(trace, tr.COLLECTIVES.pattern, "exposed")
    exposed_ms = per_step(exposed) if exposed is not None else 0.0
    kinds_ms = sum(values.get(f"step.{k}_ms", 0.0) for k in KINDS)
    container_only_ms = per_step(statistics.mean(
        tr.length(tr.busy(d)) - tr.length(tr.union(
            (e.start, e.end) for e in d.ops if not e.is_container))
        for d in trace.devices.values()))
    overlap_ms = kinds_ms - per_step(statistics.mean(
        tr.length(tr.compute(d)) for d in trace.devices.values()))
    return {"step.device_ms_mean": device_ms, "kinds_ms": kinds_ms,
            "collective.exposed_ms": exposed_ms,
            "kinds_plus_exposed_over_device":
                (kinds_ms + exposed_ms) / device_ms,
            "container_only_ms": container_only_ms,
            "kinds_overlap_ms": overlap_ms}


def direction_sets(trace, scopes, steps: int) -> dict:
    """ms a step by the exact set of directions in an instruction's body
    ("bwd+fwd": a backward fusion with forward names in it), summed
    durations, mean over devices."""
    import scope_reduce as sr
    totals = {}
    for dev in trace.devices.values():
        for e in sr.compute_events(dev):
            key = "+".join(sorted(scopes.get(e.name, sr.NO_SCOPE)
                                  .directions)) or "unscoped"
            totals[key] = totals.get(key, 0.0) + e.dur
    return {k: v / len(trace.devices) / steps / 1e6
            for k, v in sorted(totals.items())}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=5.0,
                    help="the untraced window the traced steps are "
                         "compared with")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--out", default=os.path.join(harness.ROOT,
                                                  "chiprun_out", "bench"))
    args = ap.parse_args(argv)
    _bench, entry, config, job = harness.load_cell(args.workload,
                                                   args.rehearse)
    chips = entry["chips"]
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={chips}").strip()
    os.environ.setdefault("HVD_TPU_PROFILE_ON_ANOMALY", "0")
    try:
        return report(args, entry, config, job)
    finally:
        hvd = sys.modules.get("horovod_tpu")
        if hvd is not None:
            hvd.shutdown()


def report(args, entry, config, job) -> int:
    import jax
    chips, seed = entry["chips"], args.seed
    devices = jax.devices()
    if not args.rehearse and devices[0].platform != "tpu":
        raise harness.BenchFailure(f"JAX found no TPU (devices: {devices})")
    if len(devices) < chips:
        raise harness.BenchFailure(f"the cell needs {chips} chip(s)")
    devices = devices[:chips]

    import horovod_tpu as hvd
    import scope_reduce
    from horovod_tpu.data.data_loader import device_prefetch
    from horovod_tpu.profiling import compile_watch
    from horovod_tpu.utils import compile_cache
    if not args.rehearse:
        compile_cache.enable()
    hvd.init()
    if not compile_watch.ensure_installed():
        raise harness.BenchFailure("compile metrics are disabled")
    adapter = importlib.import_module(f"adapters.{config['adapter']}")
    reference = importlib.import_module(f"reference.{config['adapter']}")
    watch = lambda: compile_watch.totals()["compiles"]     # noqa: E731

    # -- set-up, as run.py makes it ------------------------------------------
    mesh = hvd.build_mesh(devices=devices, **job["mesh"])
    cell = adapter.Cell(config, job, mesh, seed)
    jax.block_until_ready(cell.params)
    check = harness.reference_check(adapter, reference, cell, config, job,
                                    seed)
    cell.init_optimizer(harness.make_optimizer(job))
    batches = device_prefetch(
        (adapter.host_batch(config, job, seed, i,
                            job["batch_per_chip"] * chips)
         for i in itertools.count()),
        cell.batch_sharding(), buffer_size=job["prefetch"])
    jax.block_until_ready(cell.step(next(batches)))
    jax.block_until_ready(harness.run_steps(
        cell, batches, harness.Spans(), job["max_ahead"],
        lambda n, _s: n >= job["warmup_steps"] - 1, watch)[0])
    totals = compile_watch.totals()
    setup_s = time.perf_counter() - harness._T_START

    # -- an untraced window, then the traced steps ----------------------------
    compiles_before = watch()
    losses, _done, window_s, _c = harness.run_steps(
        cell, batches, harness.Spans(), job["max_ahead"],
        lambda _n, s: s >= args.seconds, watch)
    t = time.perf_counter()
    trace, traced_s = take_trace(cell, batches, job, devices, watch)
    trace_and_load_s = time.perf_counter() - t
    compiles_in_window = watch() - compiles_before

    t = time.perf_counter()
    hlo_text = cell.compiled_step(next(batches)).as_text()
    text_s = time.perf_counter() - t
    t = time.perf_counter()
    scopes = scope_reduce.parse_hlo(hlo_text)
    parse_s = time.perf_counter() - t

    # the new totals are None on a program without them: left out
    lower = [totals.get("trace_seconds"), totals.get("lower_seconds")]
    counters = {
        "compiles_in_window": compiles_in_window,
        "setup_compile_s": totals["seconds_total"],
        "setup_trace_lower_s": None if None in lower else sum(lower),
        "setup_cache_read_s": totals.get("cache_read_seconds"),
        "setup_persistent_cache_misses":
            totals.get("persistent_cache_misses")}
    steps = job["trace_steps"]
    t = time.perf_counter()
    values = phase_values(args.workload, {
        "trace": trace, "hlo_text": hlo_text, "scopes": scopes,
        "trace_steps": steps, "counters": counters})
    reduce_s = time.perf_counter() - t

    result = {
        "cell": args.workload, "seed": seed, "rehearsal": args.rehearse,
        "device": {"platform": devices[0].platform,
                   "kind": devices[0].device_kind, "count": len(devices)},
        "reference_ok": check["ok"], "setup_s": setup_s,
        "compile_totals_over_setup": totals,
        "compile_cache": jax.config.jax_compilation_cache_dir,
        "metrics": values,
        "tracing": {
            "untraced_step_s": window_s / len(losses),
            "traced_step_s": traced_s / steps,
            "trace_and_load_s": trace_and_load_s,
            "hlo_text_s": text_s, "hlo_text_bytes": len(hlo_text),
            "parse_s": parse_s, "reduce_s": reduce_s},
        "program_has_scopes": scope_reduce.has_scopes(scopes),
    }
    if trace is not None and trace.devices:
        result["identity"] = identity(trace, scopes, values, steps)
        result["breakdown"] = {
            "direction_sets_ms": direction_sets(trace, scopes, steps),
            "unmatched_instructions": scope_reduce.unmatched(trace, scopes),
            "unscoped": scope_reduce.top_instructions(
                trace, scopes, {"kind": "unscoped"}, 20),
            "mixed": scope_reduce.top_instructions(
                trace, scopes, {"kind": "mixed"}),
            "idle_gaps": scope_reduce.idle_gaps(trace, 5)}
    os.makedirs(args.out, exist_ok=True)
    path = os.path.join(args.out,
                        f"{args.workload}.seed{seed}.phases.json")
    with open(path, "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except harness.BenchFailure as e:
        print(f"benchmarks/chip/tools/phase_report.py: {e}", file=sys.stderr)
        sys.exit(2)
