"""Live fleet-scrape worker (launched by test_core_multiprocess.py):
the ISSUE 7 acceptance — a 2-process job where ONLY rank 0's
``/metrics/fleet`` is scraped and it carries correctly merged samples
from EVERY rank (counter sums, gauge aggregation, per-rank step-time
breakdown), surviving one elastic ``shutdown -> init`` re-mesh (tree
re-registered, merged counters keep accumulating, same ports rebound).
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ["JAX_PLATFORMS"] = "cpu"

import urllib.request  # noqa: E402

import jax  # noqa: E402

import jax.numpy as jnp  # noqa: E402

import horovod_tpu as hvd  # noqa: E402
from horovod_tpu.common.basics import _state  # noqa: E402
from horovod_tpu.train.callbacks import TelemetryCallback  # noqa: E402

STEPS_GEN1 = 3
STEPS_GEN2 = 2


def scrape(port, path):
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=15) as r:
        return r.status, r.read().decode()


def parse(text):
    out = {}
    for line in text.splitlines():
        if line and not line.startswith("#"):
            key, val = line.rsplit(" ", 1)
            out[key] = float(val)
    return out


def run_steps(n):
    telemetry = TelemetryCallback(units_per_step=32, unit="examples")
    # goodput mode (HVD_TEST_GOODPUT=1, window=2 via env): rank 1 slow
    # ON PURPOSE — an inter-step stall books as its input_wait, so the
    # merged view must name rank 1 the worst goodput rank while rank
    # 0's own blocking allreduce wait stays inside its step envelope
    import time
    gp_stall = 0.05 if (os.environ.get("HVD_TEST_GOODPUT")
                        and hvd.rank() == 1) else 0.0
    for _ in range(n):
        if gp_stall:
            time.sleep(gp_stall)
        telemetry.on_step_begin()
        hvd.allreduce(jnp.ones(8), op=hvd.Sum, name="fleet_grad")
        telemetry.on_step_end()


def push_and_settle():
    """Deterministic aggregation: every rank flushes its tree node
    (children push upstream synchronously), fenced by barriers so rank
    0 holds every rank's doc before the scrape."""
    agg = _state.metrics_exporter.fleet
    assert agg is not None, "fleet aggregator missing on the exporter"
    if hvd.rank() != 0:
        agg.flush()  # POSTs this subtree to the parent's exporter
    hvd.barrier()


def assert_fleet_view(base_port, expected_steps, generation_label):
    status, body = scrape(base_port, "/metrics/fleet")
    assert status == 200, (status, body)
    series = parse(body)
    size = hvd.size()
    # counter sums across EVERY rank, through the tree
    assert series["hvd_steps_total"] == expected_steps, \
        (generation_label, series["hvd_steps_total"], expected_steps)
    assert series['hvd_collective_calls_total{kind="allreduce"}'] >= \
        expected_steps, (generation_label, body)
    # tree health: every rank reporting
    assert series["hvd_fleet_size"] == size
    assert series["hvd_fleet_ranks_reporting"] == size, \
        (generation_label, body)
    # per-rank step-time breakdown for every rank
    for r in range(size):
        key = f'hvd_fleet_rank_step_time_seconds{{rank="{r}"}}'
        assert key in series and series[key] > 0, (generation_label, key)
    assert series["hvd_fleet_step_time_max"] >= \
        series["hvd_fleet_step_time_min"] > 0
    assert series["hvd_fleet_straggler_rank"] in set(range(size))
    # gauge aggregation: throughput declares agg=sum — the fleet value
    # must be >= any single rank's contribution (both ranks just ran)
    own = parse(scrape(base_port + hvd.local_rank(),
                       "/metrics")[1])["hvd_examples_per_second"]
    assert series["hvd_examples_per_second"] >= own * 0.999, \
        (generation_label, series["hvd_examples_per_second"], own)
    # histogram merge: bucket counts add across ranks
    assert series["hvd_step_time_seconds_count"] == expected_steps
    # goodput mode: every rank's ledger closed a window (window=2 via
    # env) and the merged view carries the per-rank productive fraction
    # plus the worst-offender pair (docs/OBSERVABILITY.md "Goodput
    # ledger") — and they AGREE with each other
    if os.environ.get("HVD_TEST_GOODPUT"):
        fr = {}
        for r in range(size):
            key = f'hvd_fleet_rank_goodput_fraction{{rank="{r}"}}'
            assert key in series, (generation_label, sorted(series))
            fr[r] = series[key]
            assert 0 < fr[r] <= 1, (generation_label, fr)
        worst = int(series["hvd_fleet_goodput_worst_rank"])
        assert abs(series["hvd_fleet_goodput_min"]
                   - min(fr.values())) < 1e-6, (generation_label, series)
        assert abs(fr[worst] - min(fr.values())) < 1e-6, \
            (generation_label, fr, worst)
        # rank 1 stalls between steps: it must be the worst offender
        assert worst == 1, (generation_label, fr)


def main():
    rank = int(os.environ["HOROVOD_RANK"])
    size = int(os.environ["HOROVOD_SIZE"])
    base_port = int(os.environ["HVD_TPU_METRICS_PORT"])

    # ---- generation 1 ----
    hvd.init()
    run_steps(STEPS_GEN1)
    push_and_settle()
    if rank == 0:
        assert_fleet_view(base_port, STEPS_GEN1 * size, "gen1")
    hvd.barrier()

    # ---- elastic re-mesh: shutdown -> init ----
    hvd.shutdown()
    hvd.init()
    assert _state.metrics_exporter is not None, \
        "exporter did not rebind after re-mesh"
    assert _state.metrics_exporter.fleet is not None, \
        "fleet tree not re-registered after re-mesh"

    run_steps(STEPS_GEN2)
    push_and_settle()
    if rank == 0:
        # the process-global registry accumulates across the re-mesh:
        # merged counters now carry BOTH generations from BOTH ranks
        assert_fleet_view(base_port, (STEPS_GEN1 + STEPS_GEN2) * size,
                          "gen2")
    hvd.barrier()
    hvd.shutdown()
    print(f"fleet worker {rank}: OK", flush=True)


if __name__ == "__main__":
    main()
