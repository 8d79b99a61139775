"""Live metrics-exporter worker (launched by test_core_multiprocess.py):
hvd.init() with HVD_TPU_METRICS_PORT set, drive cached allreduces and
telemetry steps, then scrape this worker's own ``/metrics`` over HTTP —
the in-process equivalent of ``curl localhost:$HVD_TPU_METRICS_PORT/metrics``
— and assert the Prometheus text carries the engine cache-hit rate, the
step-time histogram buckets, and the throughput gauge
(docs/OBSERVABILITY.md acceptance surface)."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ["JAX_PLATFORMS"] = "cpu"

import urllib.request  # noqa: E402

import jax  # noqa: E402

import jax.numpy as jnp  # noqa: E402

import horovod_tpu as hvd  # noqa: E402
from horovod_tpu.train.callbacks import TelemetryCallback  # noqa: E402


def scrape(port, path):
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=15) as r:
        return r.status, r.read().decode()


def main():
    rank = int(os.environ["HOROVOD_RANK"])
    hvd.init()
    port = int(os.environ["HVD_TPU_METRICS_PORT"]) + hvd.local_rank()

    # repeated same-name allreduces: first negotiates (cache miss), the
    # rest hit the response cache -> cache_hit_rate becomes defined
    for _ in range(6):
        hvd.allreduce(jnp.ones(8), op=hvd.Sum, name="cached")

    # train-loop telemetry feeding the same registry the exporter serves
    telemetry = TelemetryCallback(units_per_step=32, unit="examples")
    for _ in range(3):
        telemetry.on_step_begin()
        hvd.allreduce(jnp.ones(4), op=hvd.Sum, name="step_grad")
        telemetry.on_step_end()

    status, body = scrape(port, "/metrics")
    assert status == 200, (status, body)
    assert "hvd_engine_cache_hit_rate" in body, body
    assert "hvd_step_time_seconds_bucket" in body, body
    assert "hvd_examples_per_second" in body, body
    assert "hvd_steps_total 3" in body, body
    assert 'hvd_collective_calls_total{kind="allreduce"}' in body, body

    status, health = scrape(port, "/healthz")
    assert status == 200 and '"status": "ok"' in health, health
    assert f'"rank": {rank}' in health, health

    # one-call dict view must agree with the scrape surface
    snap = hvd.metrics_snapshot()
    assert snap["engine"].get("cache_hits", 0) > 0, snap["engine"]
    assert snap["derived"]["cache_hit_rate"] > 0, snap["derived"]
    assert "hvd_step_time_seconds" in snap["registry"], list(snap["registry"])
    assert "ranks" in snap["stragglers"], snap["stragglers"]

    hvd.barrier()
    hvd.shutdown()

    # after shutdown the exporter must be down (no leaked server thread)
    try:
        scrape(port, "/healthz")
        raise AssertionError("exporter still serving after shutdown")
    except (OSError, urllib.error.URLError):
        pass
    print(f"metrics worker {rank}: OK", flush=True)


if __name__ == "__main__":
    main()
