"""The chip benchmark's one command.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

One run of one cell of ``BENCHMARK.json``: set-up (weights from the seed,
reference check, optimizer state, compile or cache read, warm-up), a
measured window of ``--seconds`` of free-running train steps, and with
``--trace 1`` a profiler trace of a few more steps. The last line of
standard output is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics``, ``device`` (and ``breakdown`` with ``--trace 1``), then
``compared``: each number ``correct`` was decided from beside its limit,
which are also the last lines of standard error. With
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
``adapters/<adapter>.py``, ``reference/<adapter>.py``,
``layer_metrics/<metric>.json`` (and the ``roofline_<function>.py`` or
``readers/<name>.py`` such a file names) by the names in
``BENCHMARK.json``. README.md says how a later PR adds each.
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


def hold_fit(compiled: dict, peaks: dict) -> dict:
    """The compiled step (:func:`step_bytes`) against the chip: the most
    the compiler admits a program on this device (``peaks.json``) less what
    the step takes, which must leave the margin ``peaks.json`` states, else
    the run gives no result. Memory is a fit and no rate: no parent is
    asked, and what a change spends inside the fit is a per-layer reading
    (``hbm.compiled_gb``). The margin is for the next change: the heap's
    packing alone moves a step's bytes (README.md, "The fit")."""
    limit = peaks["hbm_compile_limit_bytes"]
    margin = peaks["hbm_fit_margin_bytes"]
    headroom = limit - compiled["total"]
    if headroom < margin:
        raise BenchFailure(
            f"the compiled step takes {compiled['total'] / GB:.3f} GB a "
            f"device and the compiler admits {limit / GB:.3f}: "
            f"{headroom / GB:.3f} GB of headroom, under the margin of "
            f"{margin / GB:.3f} GB the benchmark holds every cell to")
    return {"limit_bytes": limit, "margin_bytes": margin,
            "headroom_bytes": headroom}


def record_path(out: str, cell: str, seed: int, trace: int) -> str:
    return os.path.join(out, f"{cell}.seed{seed}.trace{trace}.json")


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


def reader_function(name: str):
    """``readers/<name>.py:read(read, ctx)``: a reader the harness lacks is
    a file of a later PR, found by name as a roofline function is."""
    try:
        return importlib.import_module(f"readers.{name}").read
    except (ImportError, AttributeError) as e:
        raise BenchFailure(f"no reader {name!r} (readers/{name}.py with a "
                           f"function read(read, ctx)): {e}")


def least_seconds(need: dict, calls_a_step: float, peaks: dict,
                  what: str = "the kernel") -> float:
    """The least seconds a step for the calls a trace held, from a roofline
    function's answer ``need`` (roofline.py): its work as often as the traced
    program ran a pass's ``calls``, a whole number of passes and at least
    one, else the run fails; a function that states no ``calls`` counts a
    whole step and is scaled by nothing."""
    times = 1.0
    if "calls" in need:
        passes = calls_a_step / need["calls"]
        if passes < 1 or abs(passes - round(passes)) > 1e-9:
            raise BenchFailure(
                f"{what}: the trace holds {calls_a_step} calls a step, a "
                f"forward pass makes {need['calls']}: not a whole number of "
                "passes")
        times = round(passes) / need.get("passes", 1)
    return times * max(need["flops"] / peaks["bf16_flops_per_s"],
                       need["bytes"] / peaks["hbm_bytes_per_s"])


def roofline_share(read: dict, ctx: dict):
    """A kernel's share of its roofline, in percent: the least time the chip
    could take for the calls THE TRACE HOLDS over their summed device time.

    The function (``roofline.py``) counts ``flops`` and ``bytes`` and says
    how many ``calls`` of its kernel one forward pass makes; the harness
    counts the kernel's instructions on ``XLA Ops`` and takes the function's
    work as often as the traced program ran those calls (a checkpointed
    block runs its forward kernel twice: two passes' calls and two passes'
    work; a program that keeps the activations: one and one). What is not a
    whole number of passes, or less than one, is a kernel that lost a layer
    or a count that no longer describes the model: the run fails
    (:func:`least_seconds`). The run's record keeps what was read
    (``phases.rooflines``)."""
    import trace_reduce
    trace, pattern, steps = ctx["trace"], read["trace_ops"], ctx["trace_steps"]
    busy_ns = trace_reduce.reduce(trace, pattern, "sum")
    if not busy_ns:
        return None
    reading = {"ms_a_step": busy_ns / 1e6 / steps, "calls_a_step":
               trace_reduce.reduce(trace, pattern, "count") / steps}
    ctx.setdefault("rooflines", {})[read["roofline"]] = reading
    least_s = least_seconds(
        roofline_function(read["roofline"])(ctx["shapes"]),
        reading["calls_a_step"], ctx["peaks"],
        f"{read['roofline']} over {pattern!r}")
    reading["least_ms_a_step"] = 1e3 * least_s
    return 100.0 * least_s / (busy_ns / 1e9 / steps)


def read_layer_metric(read: dict, ctx: dict):
    """The metric's value from what this run recorded (``ctx``, README.md),
    or None where there is nothing to read."""
    import scope_reduce
    import trace_reduce
    if "reader" in read:
        return reader_function(read["reader"])(read, ctx)
    if "trace_scope" in read or "host_span" in read:
        return scope_reduce.read_metric(read, ctx)
    if "span" in read:
        if read["reduce"] != "median_ms":
            raise BenchFailure(f"unknown span reduce {read['reduce']!r}")
        return ctx.get("spans_median_ms", {}).get(read["span"])
    if "counter" in read:
        value = ctx.get("counters", {}).get(read["counter"])
        if value is not None and "per" in read:     # bytes as GB, ..
            value /= read["per"]
        return value
    if "trace_ops" in read:
        trace = ctx.get("trace")
        if trace is None:
            return None
        if "roofline" in read:
            return roofline_share(read, ctx)
        value = trace_reduce.reduce(trace, read["trace_ops"], read["reduce"],
                                    read.get("across", "mean"))
        if value is None:
            return None
        if read.get("per_step"):
            value /= ctx["trace_steps"]
        return value * read.get("scale", 1.0)
    raise BenchFailure(f"unknown metric source {sorted(read)}")


def setup_counters(totals: dict, split: dict, compiles_in_window) -> dict:
    """``ctx["counters"]``: every numeric total of ``compile_watch`` over
    set-up as ``setup_<key>``, the laps of set-up as ``setup_split_<lap>``,
    and the names the first metric files read. A program without a total
    leaves its counter, and so the metric, out."""
    counters = {f"setup_{k}": v for k, v in totals.items()
                if isinstance(v, (int, float)) and not isinstance(v, bool)}
    counters.update({f"setup_split_{k}": v for k, v in split.items()})
    named = {"compiles_in_window": compiles_in_window,
             "setup_compile_s": totals["seconds_total"],
             "setup_cache_read_s": totals.get("cache_read_seconds"),
             "setup_persistent_cache_misses":
                 totals.get("persistent_cache_misses")}
    counters.update({k: v for k, v in named.items() if v is not None})
    return counters


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
        # the program's host spans (hvd.*) beside the harness's (bench.*)
        trace = trace_reduce.load(found[0], [d.id for d in devices],
                                  ("bench.", "hvd.")) if found else None
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
    compiled_step = cell.compiled_step(next(batches))
    compiled = step_bytes(compiled_step.memory_analysis())
    # (a rehearsal has no chip, so no row of peaks.json and no limit to hold)
    fit = hold_fit(compiled, peaks) if peaks is not None else None
    stats = [d.memory_stats() or {} for d in devices]
    live_peak = max((s.get("peak_bytes_in_use", 0) for s in stats), default=0)
    # memory_stats() counts live arrays and misses a program's temporaries
    # on this chip, so the fullest chip held at least the compiled step
    device["memory_peak_bytes"] = int(max(live_peak, compiled["total"]))

    correct = bool(check["ok"] and not_finite == 0
                   and compiles_in_window == 0 and replicas_agree
                   and all(math.isfinite(x) for x in loss_values))

    phases = None
    if args.trace:
        import scope_reduce
        laps = [time.perf_counter()]
        hlo_text = compiled_step.as_text()
        laps.append(time.perf_counter())
        scopes = scope_reduce.parse_hlo(hlo_text)
        laps.append(time.perf_counter())
        ctx = {
            "spans_median_ms": spans.median_ms(),
            "spans_seconds": spans.seconds, "step_done_at_s": done_at,
            "trace": trace, "hlo_text": hlo_text, "scopes": scopes,
            "peaks": peaks, "trace_steps": job["trace_steps"],
            "shapes": adapter.shapes(config, job),
            "counters": {
                **setup_counters(compile_totals, split, compiles_in_window),
                **{f"step_bytes_{k}": v for k, v in compiled.items()}}}
        values = layer_values(bench, args.workload, ctx)
        laps.append(time.perf_counter())
        phases = {"hlo_text_bytes": len(hlo_text),
                  "program_has_scopes": scope_reduce.has_scopes(scopes),
                  "rooflines": ctx.get("rooflines", {})}
        if trace is not None and trace.devices:
            phases.update(scope_reduce.report(trace, scopes,
                                              job["trace_steps"]))
            laps.append(time.perf_counter())
        phases.update(zip(("hlo_text_s", "parse_s", "reduce_s", "report_s"),
                          (b - a for a, b in zip(laps, laps[1:]))))
    else:
        values = {"tokens_per_s_per_chip": tokens_per_s_per_chip,
                  "setup_s": setup_s}
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
        import scope_reduce
        import trace_reduce
        busy_s, traced_window_s = trace_reduce.busy_and_window_s(trace)
        device["busy_s"], device["window_s"] = busy_s, traced_window_s
        # a gap goes by the program's span where one covers it
        result["breakdown"] = {
            "device_ops": trace_reduce.top_ops(trace, 10),
            "idle_gaps": scope_reduce.idle_gaps(trace, 5)}

    # every number `correct` compared, beside its limit: last in the line
    # and last on standard error, so that a refused run says why
    tol = check["tolerance"]
    result["compared"] = {
        "loss_rel": [check["loss_rel"], tol["loss_rel"]],
        **{f"grad_rel_l2.{k}": [v, tol["grad_rel_l2"]]
           for k, v in check["grad_rel_l2"].items()},
        "nonfinite_losses": [sum(not math.isfinite(x)
                                 for x in loss_values), 0],
        "compiles_in_window": [compiles_in_window, 0],
        "replica_leaves_disagreeing": [
            sum(len(set(v)) != 1 for v in checksums.values()), 0]}

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
        "compiled_step_bytes": compiled, "hbm_fit": fit,
        "memory_stats_peak_bytes": live_peak,
        # (what peaks.json's hbm_compile_limit_bytes was read from)
        "memory_stats_bytes_limit": [s.get("bytes_limit") for s in stats],
        "after_window_s": time.perf_counter() - t_after,
    }
    if phases is not None:
        record["phases"] = phases
    os.makedirs(args.out, exist_ok=True)
    path = record_path(args.out, args.workload, seed, args.trace)
    with open(path, "w") as f:
        json.dump(record, f, indent=1)
    say("window", window_s=window_s, steps=attempted,
        step_s=window_s / attempted, first_loss=window_losses[0],
        last_loss=window_losses[-1], compiles_in_window=compiles_in_window,
        replicas_agree=replicas_agree, record=os.path.relpath(path, ROOT),
        host_spans_median_ms=record["host_spans_median_ms"],
        compiled_step_bytes=compiled, hbm_fit=fit,
        memory_stats_peak_bytes=live_peak)
    print(json.dumps(result), flush=True)
    for name, (value, limit) in result["compared"].items():
        print(f"compared {name} {value!r} limit {limit!r}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except BenchFailure as e:
        print(f"benchmarks/chip/run.py: {e}", file=sys.stderr)
        sys.exit(2)
