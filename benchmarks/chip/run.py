"""The chip benchmark's one command.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

One run of one cell of ``BENCHMARK.json``: set-up (weights from the seed,
reference check, optimizer state, compile or cache read, warm-up), a
measured window of ``--seconds`` of free-running train steps, and with
``--trace 1`` a profiler trace of a few more steps. The last line of
standard output is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics``, ``device`` (and ``breakdown`` with ``--trace 1``). With
``--trace 0`` the metrics are the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics. Everything else worth keeping goes to
earlier lines and to ``<out>/<cell>.seed<n>.trace<t>.json``.

It exits non-zero and prints no result where JAX finds no TPU or fewer
chips than the cell names: there is no CPU fallback. ``--rehearse`` is the
harness's own off-chip walk: the cell's ``tiny`` sizes on the CPU (on as
many virtual devices as the cell has chips), every number under a
``rehearsal.`` name, never under a metric's.

The harness knows no cell, configuration or model by name: it finds
``configs/<config>.json``, ``workloads/<traffic>.json``,
``adapters/<adapter>.py``, ``reference/<adapter>.py`` and
``layer_metrics/<metric>.json`` by the names in ``BENCHMARK.json``.
README.md says how a later PR adds each.
"""

from __future__ import annotations

import time

_T_START = time.perf_counter()     # set-up is counted from here

import argparse
import contextlib
import glob
import importlib
import itertools
import json
import math
import os
import statistics
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
GB = 1e9


class BenchFailure(Exception):
    """The run cannot give a result; exit non-zero, print no result."""


def read_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_cell(name: str, tiny: bool):
    """(cell entry, configuration, job) by the names in BENCHMARK.json."""
    bench = read_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise BenchFailure(f"no workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    files = {c["name"]: c["file"] for c in bench["configs"]}
    config = read_json(ROOT, files[cell["config"]])
    job = read_json(HERE, "workloads", cell["traffic"] + ".json")
    if tiny:
        config = {**config, **config["tiny"]}
        job = {**job, **job["tiny"]}
    return bench, cell, config, job


def make_optimizer(job: dict):
    import optax
    opt = dict(job["optimizer"])
    return getattr(optax, opt.pop("name"))(**opt)


def step_bytes(mem) -> dict:
    """Per-device bytes of a compiled step from ``memory_analysis()``:
    arguments + outputs - aliased (donated in place) + temporaries."""
    doc = {k: getattr(mem, f"{k}_size_in_bytes")
           for k in ("argument", "output", "alias", "temp")}
    doc["total"] = (doc["argument"] + doc["output"] - doc["alias"]
                    + doc["temp"])
    return doc


def metrics_of(bench: dict, kind: str, cell: str) -> list:
    return [m for m in bench[kind]
            if "workloads" not in m or cell in m["workloads"]]


# ---------------------------------------------------------------------------
# spans: the host loop's own, on the host clock, and inside a trace as
# TraceAnnotations so that they sit on the trace's clock too
# ---------------------------------------------------------------------------

class Spans:
    def __init__(self, annotate: bool = False):
        self.seconds = {}       # name -> [duration, ...]
        if annotate:
            import jax
            self._annotation = jax.profiler.TraceAnnotation
        else:
            self._annotation = lambda _name: contextlib.nullcontext()

    def median_ms(self) -> dict:
        return {k: 1e3 * statistics.median(v)
                for k, v in self.seconds.items()}

    @contextlib.contextmanager
    def __call__(self, name: str):
        with self._annotation(name):
            t0 = time.perf_counter()
            try:
                yield
            finally:
                self.seconds.setdefault(name, []).append(
                    time.perf_counter() - t0)


def run_steps(cell, batches, spans: Spans, max_ahead: int, stop, watch):
    """Free-running steps until ``stop(n_dispatched, elapsed)``: a fresh
    batch every step, no readback, at most ``max_ahead`` steps dispatched
    beyond the last one known complete. Opens on an idle device and closes
    when the last dispatched step is complete. Returns the losses (still
    on the device), the host time each step was known complete at, the
    window's seconds and the steps during which a compile was counted."""
    import jax
    losses, done_at, compiled_in = [], [], []
    t0 = time.perf_counter()
    while True:
        with spans("bench.input"):
            batch = next(batches)
        before = watch()
        with spans("bench.dispatch"):
            losses.append(cell.step(batch))
        if watch() != before:
            compiled_in.append(len(losses) - 1)
        if len(losses) - len(done_at) > max_ahead:
            with spans("bench.wait"):
                jax.block_until_ready(losses[len(done_at)])
            done_at.append(time.perf_counter() - t0)
        if stop(len(losses), time.perf_counter() - t0):
            break
    with spans("bench.wait"):
        jax.block_until_ready(losses[-1])
    seconds = time.perf_counter() - t0
    done_at += [seconds] * (len(losses) - len(done_at))
    return losses, done_at, seconds, compiled_in


# ---------------------------------------------------------------------------
# correctness, outside the window
# ---------------------------------------------------------------------------

def reference_check(adapter, reference, cell, config, job, seed: int) -> dict:
    """The program's loss and three gradient leaves against the plain
    reference's, on seeded sequences and the cell's own parameters."""
    import jax
    import jax.numpy as jnp
    host = adapter.host_batch(config, job, seed, -1, cell.check_sequences())
    batch = jax.device_put(host, cell.check_sharding())
    got_loss, got = cell.program_loss_and_grads(batch)
    want_loss, want = reference.loss_and_grads(
        cell.plain_params(), cell.leaf_paths, batch,
        adapter.shapes(config, job))

    @jax.jit
    def errors(got_loss, got, want_loss, want):
        def rel_l2(g, w):
            g, w = g.astype(jnp.float32), w.astype(jnp.float32)
            return jnp.linalg.norm((g - w).ravel()) / jnp.linalg.norm(
                w.ravel())
        return (jnp.abs(got_loss - want_loss) / jnp.abs(want_loss),
                {k: rel_l2(got[k], want[k]) for k in want})
    loss_rel, grad_rel = jax.device_get(
        errors(got_loss, got, want_loss, want))
    tol = reference.TOLERANCE
    doc = {"loss_program": float(got_loss), "loss_reference": float(want_loss),
           "loss_rel": float(loss_rel),
           "grad_rel_l2": {k: float(v) for k, v in grad_rel.items()},
           "tolerance": tol, "sequences": cell.check_sequences()}
    doc["ok"] = bool(
        math.isfinite(doc["loss_rel"]) and doc["loss_rel"] <= tol["loss_rel"]
        and all(math.isfinite(v) and v <= tol["grad_rel_l2"]
                for v in doc["grad_rel_l2"].values()))
    return doc


def replica_checksums(cell) -> dict:
    """Bit sums of the named leaves on every device that holds a replica;
    replicas of one leaf must agree exactly."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def bit_sum(x):
        return jnp.sum(jax.lax.bitcast_convert_type(x, jnp.uint32),
                       dtype=jnp.uint32)
    out = {}
    for name, leaf in cell.named_leaves().items():
        if leaf.sharding.is_fully_replicated:
            out[name] = [int(bit_sum(s.data))
                         for s in leaf.addressable_shards]
    return out


# ---------------------------------------------------------------------------
# per-layer metrics: one small reader per source, the metric is data
# ---------------------------------------------------------------------------

def roofline_function(name: str):
    for module in ("roofline", f"roofline_{name}"):
        try:
            return getattr(importlib.import_module(module), name)
        except (ImportError, AttributeError):
            continue
    raise BenchFailure(f"no roofline function {name!r}")


def read_layer_metric(read: dict, ctx: dict):
    """The metric's value from what this run recorded, or None where
    there is nothing to read."""
    import trace_reduce
    if "span" in read:
        if read["reduce"] != "median_ms":
            raise BenchFailure(f"unknown span reduce {read['reduce']!r}")
        return ctx["spans_median_ms"].get(read["span"])
    if "counter" in read:
        return ctx["counters"].get(read["counter"])
    if "trace_ops" in read:
        trace = ctx.get("trace")
        if trace is None:
            return None
        if "roofline" in read:
            busy_ns = trace_reduce.reduce(trace, read["trace_ops"], "sum")
            if not busy_ns:
                return None
            need = roofline_function(read["roofline"])(ctx["shapes"])
            peaks = ctx["peaks"]
            least_s = max(need["flops"] / peaks["bf16_flops_per_s"],
                          need["bytes"] / peaks["hbm_bytes_per_s"])
            return 100.0 * least_s / (busy_ns / 1e9 / ctx["trace_steps"])
        value = trace_reduce.reduce(trace, read["trace_ops"], read["reduce"],
                                    read.get("across", "mean"))
        if value is None:
            return None
        if read.get("per_step"):
            value /= ctx["trace_steps"]
        return value * read.get("scale", 1.0)
    raise BenchFailure(f"unknown metric source {sorted(read)}")


def layer_values(bench: dict, cell: str, ctx: dict) -> dict:
    values = {}
    for m in metrics_of(bench, "per_layer", cell):
        spec = read_json(HERE, "layer_metrics", m["name"] + ".json")
        value = read_layer_metric(spec["read"], ctx)
        if value is not None:
            values[m["name"]] = value
    return values


def take_trace(cell, batches, job, devices, watch):
    """A few more steady steps under the profiler, the host loop's spans
    written as annotations. Returns (the reduced trace or None, the
    steps' losses)."""
    import jax
    import trace_reduce
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0     # the spans are annotations
    with tempfile.TemporaryDirectory(prefix="chipbench_trace_") as trace_dir:
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        try:
            losses = run_steps(
                cell, batches, Spans(annotate=True), job["max_ahead"],
                lambda n, _s: n >= job["trace_steps"], watch)[0]
        finally:
            jax.profiler.stop_trace()
        found = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        trace = trace_reduce.load(found[0], [d.id for d in devices]) \
            if found else None
    return trace, losses


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="the cell's tiny sizes off the chip; numbers under "
                         "rehearsal.* names")
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out",
                                                  "bench"))
    args = ap.parse_args(argv)

    bench, entry, config, job = load_cell(args.workload, args.rehearse)
    chips = entry["chips"]
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={chips}").strip()
    # the benchmark's trace is the only profiler session in the process
    os.environ.setdefault("HVD_TPU_PROFILE_ON_ANOMALY", "0")
    sys.path[:0] = [HERE, ROOT]

    try:
        return measure(args, bench, entry, config, job)
    finally:
        hvd = sys.modules.get("horovod_tpu")
        if hvd is not None:
            hvd.shutdown()


def measure(args, bench, entry, config, job) -> int:
    import jax
    import numpy as np
    chips, seed = entry["chips"], args.seed
    devices = jax.devices()
    d0 = devices[0]
    if not args.rehearse and d0.platform != "tpu":
        raise BenchFailure(
            f"JAX found no TPU (devices: {devices}); the benchmark never "
            "measures on another platform. --rehearse walks the cell off "
            "the chip at tiny sizes.")
    if len(devices) < chips:
        raise BenchFailure(f"the cell needs {chips} chip(s), JAX reports "
                           f"{len(devices)}")
    devices = devices[:chips]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices)}
    peaks = read_json(HERE, "peaks.json").get(d0.device_kind)
    if peaks is None and not args.rehearse:
        raise BenchFailure(f"no row for {d0.device_kind!r} in peaks.json")

    def say(event: str, **fields) -> None:
        print(json.dumps({"event": event, "cell": args.workload, **device,
                          "rehearsal": args.rehearse, **fields}), flush=True)

    import horovod_tpu as hvd
    from horovod_tpu.data.data_loader import device_prefetch
    from horovod_tpu.profiling import compile_watch
    from horovod_tpu.utils import compile_cache
    if not args.rehearse:   # the rehearsal's CPU programs are not kept
        compile_cache.enable()
    hvd.init()
    if not compile_watch.ensure_installed():
        raise BenchFailure("compile metrics are disabled: compiles inside "
                           "the window could not be counted")
    adapter = importlib.import_module(f"adapters.{config['adapter']}")
    reference = importlib.import_module(f"reference.{config['adapter']}")
    watch = lambda: compile_watch.totals()["compiles"]     # noqa: E731
    split = {"import_init_s": time.perf_counter() - _T_START}
    say("start", seed=seed, seconds=args.seconds, trace=args.trace,
        compile_cache=jax.config.jax_compilation_cache_dir,
        config=entry["config"], traffic=entry["traffic"])

    def lap(name: str, t0: float) -> float:
        split[name] = time.perf_counter() - t0
        return time.perf_counter()

    # -- set-up: weights, reference check, optimizer, compile, warm-up ----
    t = time.perf_counter()
    mesh = hvd.build_mesh(devices=devices, **job["mesh"])
    cell = adapter.Cell(config, job, mesh, seed)
    jax.block_until_ready(cell.params)
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(cell.params))
    t = lap("weights_s", t)

    check = reference_check(adapter, reference, cell, config, job, seed)
    say("reference_check", **check)
    t = lap("reference_s", t)

    cell.init_optimizer(make_optimizer(job))
    global_batch = job["batch_per_chip"] * chips
    batches = device_prefetch(
        (adapter.host_batch(config, job, seed, i, global_batch)
         for i in itertools.count()),
        cell.batch_sharding(), buffer_size=job["prefetch"])
    first = cell.step(next(batches))     # compiles, or reads the cache
    jax.block_until_ready(first)
    t = lap("optimizer_and_first_step_s", t)
    warm = [first] + run_steps(
        cell, batches, Spans(), job["max_ahead"],
        lambda n, _s: n >= job["warmup_steps"] - 1, watch)[0]
    jax.block_until_ready(warm)
    t = lap("warmup_s", t)
    compile_totals = compile_watch.totals()
    setup_s = time.perf_counter() - _T_START
    say("setup", setup_s=setup_s, split=split, n_params=n_params,
        compile_seconds=compile_totals["seconds_total"],
        compiles=compile_totals["compiles"])

    # -- the window ---------------------------------------------------------
    spans = Spans()
    compiles_before = watch()
    losses, done_at, window_s, compiled_in = run_steps(
        cell, batches, spans, job["max_ahead"],
        lambda _n, s: s >= args.seconds, watch)
    compiles_in_window = watch() - compiles_before
    attempted = len(losses)
    tokens_per_step = adapter.tokens_per_step(job, chips)
    tokens_per_s_per_chip = attempted * tokens_per_step / window_s / chips

    # -- after the window: trace, losses, replicas, the step's memory -------
    t_after = time.perf_counter()
    trace, traced = take_trace(cell, batches, job, devices, watch) \
        if args.trace else (None, [])
    trace_s = time.perf_counter() - t_after
    loss_values = [float(x) for x in np.asarray(jax.device_get(
        warm + losses + traced), np.float64)]
    window_losses = loss_values[len(warm):len(warm) + attempted]
    not_finite = sum(not math.isfinite(x) for x in window_losses)
    failed = not_finite + len(compiled_in)
    checksums = replica_checksums(cell) if chips > 1 else {}
    replicas_agree = all(len(set(v)) == 1 for v in checksums.values())

    # lowering again reuses the jitted step's executable: no second compile
    compiled = step_bytes(cell.compiled_step(next(batches)).memory_analysis())
    live_peak = max(((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                     for d in devices), default=0)
    # memory_stats() counts live arrays and misses a program's temporaries
    # on this chip, so the fullest chip held at least the compiled step
    device["memory_peak_bytes"] = int(max(live_peak, compiled["total"]))

    correct = bool(check["ok"] and not_finite == 0
                   and compiles_in_window == 0 and replicas_agree
                   and all(math.isfinite(x) for x in loss_values))

    if args.trace:
        values = layer_values(bench, args.workload, {
            "spans_median_ms": spans.median_ms(), "trace": trace,
            "peaks": peaks, "trace_steps": job["trace_steps"],
            "shapes": adapter.shapes(config, job),
            "counters": {"compiles_in_window": compiles_in_window,
                         "setup_compile_s": compile_totals["seconds_total"]}})
    else:
        values = {"tokens_per_s_per_chip": tokens_per_s_per_chip,
                  "setup_s": setup_s,
                  "hbm_compiled_gb": compiled["total"] / GB}
        if peaks is not None:
            values["mfu_pct"] = (
                100.0 * tokens_per_s_per_chip
                * adapter.flops_per_token(config, job)
                / peaks["bf16_flops_per_s"])
        values = {m["name"]: values[m["name"]]
                  for m in metrics_of(bench, "end_to_end", args.workload)
                  if m["name"] in values}
    units = {m["name"]: m["unit"]
             for m in bench["end_to_end"] + bench["per_layer"]}

    prefix = "rehearsal." if args.rehearse else ""
    result = {
        "correct": correct, "attempted": attempted, "failed": failed,
        "metrics": {prefix + n: {"value": v, "unit": units[n]}
                    for n, v in values.items()},
        "device": device,
    }
    if trace is not None and trace.devices:
        import trace_reduce
        busy_s, traced_window_s = trace_reduce.busy_and_window_s(trace)
        device["busy_s"], device["window_s"] = busy_s, traced_window_s
        result["breakdown"] = {
            "device_ops": trace_reduce.top_ops(trace, 10),
            "idle_gaps": trace_reduce.idle_gaps(trace, 5)}

    record = {
        "cell": args.workload, "seed": seed, "seconds": args.seconds,
        "trace": args.trace, "rehearsal": args.rehearse, "result": result,
        "setup_split": split, "window_s": window_s,
        "trace_and_reduce_s": trace_s,
        "tokens_per_step": tokens_per_step, "n_params": n_params,
        "reference_check": check, "replica_checksums": checksums,
        "compiles_in_window": compiles_in_window,
        "steps_with_a_compile": compiled_in,
        "step_done_at_s": done_at, "losses": loss_values,
        "warmup_steps": len(warm),
        "host_spans_median_ms": spans.median_ms(),
        "compiled_step_bytes": compiled,
        "memory_stats_peak_bytes": live_peak,
        "after_window_s": time.perf_counter() - t_after,
    }
    os.makedirs(args.out, exist_ok=True)
    path = os.path.join(
        args.out, f"{args.workload}.seed{seed}.trace{args.trace}.json")
    with open(path, "w") as f:
        json.dump(record, f, indent=1)
    say("window", window_s=window_s, steps=attempted,
        step_s=window_s / attempted, first_loss=window_losses[0],
        last_loss=window_losses[-1], compiles_in_window=compiles_in_window,
        replicas_agree=replicas_agree, record=os.path.relpath(path, ROOT),
        host_spans_median_ms=record["host_spans_median_ms"],
        compiled_step_bytes=record["compiled_step_bytes"],
        memory_stats_peak_bytes=live_peak)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except BenchFailure as e:
        print(f"benchmarks/chip/run.py: {e}", file=sys.stderr)
        sys.exit(2)
