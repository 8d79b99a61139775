"""Minimal pyspark stand-in for exercising ``horovod_tpu.spark.run``
without a Spark installation (reference analog: the Spark integration
tests in ``test/integration/test_spark.py`` run against a local-mode
SparkContext; this image has no pyspark, so the barrier-scheduling
surface that ``spark.run`` actually touches is reimplemented here over
subprocesses + a filesystem rendezvous).

Surface implemented (exactly what ``horovod_tpu/spark/__init__.py`` uses):

- ``pyspark.sql.SparkSession.builder.getOrCreate()``
- ``session.sparkContext.defaultParallelism``
- ``sc.parallelize(range(n), n).barrier().mapPartitions(fn).collect()``
- inside each task (a real subprocess, like a Spark executor):
  ``pyspark.BarrierTaskContext.get()`` with ``partitionId()``,
  ``getTaskInfos()`` (``.address``), ``allGather(str)``, ``barrier()``.

The task function is shipped to the worker subprocess with cloudpickle —
the same serialization Spark uses — so closure capture is exercised for
real, and every task runs ``hvd.init()`` in its own process over the
real TCP core, as on a genuine cluster.
"""

from __future__ import annotations

import os
import shutil
import subprocess
import sys
import tempfile
import time


class TaskInfo:
    def __init__(self, address: str):
        self.address = address


class BarrierTaskContext:
    """File-rendezvous barrier context; one instance per worker process.

    Rounds are numbered per process; ``allGather`` writes
    ``<sync>/<round>_<rank>`` and polls until all ``size`` files exist.
    Deterministic and dependency-free, which is all a test needs.
    """

    _instance = None

    def __init__(self):
        self._rank = int(os.environ["FAKE_SPARK_RANK"])
        self._size = int(os.environ["FAKE_SPARK_SIZE"])
        self._sync = os.environ["FAKE_SPARK_SYNC_DIR"]
        self._round = 0

    @classmethod
    def get(cls):
        if cls._instance is None:
            cls._instance = cls()
        return cls._instance

    def partitionId(self) -> int:
        return self._rank

    def getTaskInfos(self):
        return [TaskInfo("127.0.0.1:0") for _ in range(self._size)]

    def allGather(self, message: str = ""):
        rnd = self._round
        self._round += 1
        my = os.path.join(self._sync, f"{rnd}_{self._rank}")
        with open(my + ".tmp", "w") as f:
            f.write(message)
        os.rename(my + ".tmp", my)  # atomic publish
        deadline = time.time() + 120
        paths = [os.path.join(self._sync, f"{rnd}_{r}")
                 for r in range(self._size)]
        while not all(os.path.exists(p) for p in paths):
            if time.time() > deadline:
                raise RuntimeError(f"fake barrier round {rnd} timed out")
            time.sleep(0.01)
        out = []
        for p in paths:
            with open(p) as f:
                out.append(f.read())
        return out

    def barrier(self) -> None:
        self.allGather("")


class _FakeBarrierRDD:
    def __init__(self, n: int):
        self._n = n
        self._fn = None

    def mapPartitions(self, fn):
        self._fn = fn
        return self

    def collect(self):
        import cloudpickle

        tmp = tempfile.mkdtemp(prefix="fake_spark_")
        sync = os.path.join(tmp, "sync")
        os.makedirs(sync)
        fn_path = os.path.join(tmp, "task_fn.pkl")
        with open(fn_path, "wb") as f:
            cloudpickle.dump(self._fn, f)

        procs = []
        for rank in range(self._n):
            env = dict(os.environ)
            env.update({
                "FAKE_SPARK_RANK": str(rank),
                "FAKE_SPARK_SIZE": str(self._n),
                "FAKE_SPARK_SYNC_DIR": sync,
                # worker processes must resolve THIS fake pyspark first
                "PYTHONPATH": os.pathsep.join(
                    [os.path.dirname(os.path.dirname(
                        os.path.abspath(__file__)))] +
                    [p for p in env.get("PYTHONPATH", "").split(os.pathsep)
                     if p]),
            })
            out_path = os.path.join(tmp, f"out_{rank}.pkl")
            # the worker bootstrap pins the CPU JAX platform the same way
            # every worker script in tests/ does (hvd_worker.py): unit-test
            # workers never touch a chip
            procs.append((rank, out_path, subprocess.Popen(
                [sys.executable, "-c",
                 "import os, sys\n"
                 "os.environ.setdefault(\n"
                 "    'XLA_FLAGS', '--xla_force_host_platform_device_count=1')\n"
                 "os.environ['JAX_PLATFORMS'] = 'cpu'\n"
                 "import cloudpickle\n"
                 "fn_path, out_path, rank = sys.argv[1:4]\n"
                 "with open(fn_path, 'rb') as f:\n"
                 "    fn = cloudpickle.load(f)\n"
                 "result = list(fn(iter([int(rank)])))\n"
                 "with open(out_path, 'wb') as f:\n"
                 "    cloudpickle.dump(result, f)\n",
                 fn_path, out_path, str(rank)],
                env=env)))

        results = []
        failed = []
        try:
            for rank, out_path, p in procs:
                rc = p.wait(timeout=180)
                if rc != 0:
                    failed.append((rank, rc))
                    continue
                with open(out_path, "rb") as f:
                    results.extend(cloudpickle.load(f))
        finally:
            # never leak workers: a task wedged in the barrier poll would
            # otherwise outlive the test session
            for _, _, p in procs:
                if p.poll() is None:
                    p.kill()
                    p.wait(timeout=30)
            shutil.rmtree(tmp, ignore_errors=True)
        if failed:
            raise RuntimeError(f"fake spark tasks failed: {failed}")
        return results


class Row:
    """Minimal pyspark.sql.Row: attribute access + ``asDict()``."""

    def __init__(self, **kwargs):
        self.__dict__["_fields"] = dict(kwargs)

    def asDict(self):
        return dict(self._fields)

    def __getattr__(self, item):
        try:
            return self.__dict__["_fields"][item]
        except KeyError:
            raise AttributeError(item) from None

    def __repr__(self):
        inner = ", ".join(f"{k}={v!r}" for k, v in self._fields.items())
        return f"Row({inner})"


class _FakeDataFrame:
    """Partitioned DataFrame stand-in: rows live in ``n`` contiguous
    partitions; ``rdd.mapPartitionsWithIndex`` ships the function to one
    SUBPROCESS PER PARTITION (cloudpickle, like a Spark executor) and
    ``collect`` returns only what the function yields — so estimator
    code that materializes data through executors is exercised for real,
    and a ``toPandas()`` regression (driver collect) is observable via
    ``toPandas_calls``."""

    def __init__(self, pdf, n_partitions: int = 2):
        self._pdf = pdf.reset_index(drop=True)
        self._n = n_partitions
        self.toPandas_calls = 0

    def repartition(self, n: int) -> "_FakeDataFrame":
        return _FakeDataFrame(self._pdf, n)

    @property
    def rdd(self):
        return _FakeDataFrameRDD(self._pdf, self._n)

    def toPandas(self):
        self.toPandas_calls += 1
        return self._pdf.copy()


class _FakeDataFrameRDD:
    def __init__(self, pdf, n: int):
        self._pdf, self._n = pdf, n

    def getNumPartitions(self) -> int:
        return self._n

    def mapPartitionsWithIndex(self, fn):
        return _FakeDataFrameJob(self._pdf, self._n, fn)


class _FakeDataFrameJob:
    def collect(self):
        import cloudpickle
        import numpy as np

        tmp = tempfile.mkdtemp(prefix="fake_spark_df_")
        try:
            bounds = np.array_split(np.arange(len(self._pdf)), self._n)
            payloads = []
            for idx, rows_idx in enumerate(bounds):
                rows = [Row(**rec) for rec in self._pdf.iloc[rows_idx]
                        .to_dict(orient="records")]
                path = os.path.join(tmp, f"task_{idx}.pkl")
                with open(path, "wb") as f:
                    cloudpickle.dump((self._fn, idx, rows), f)
                payloads.append((idx, path))

            procs = []
            for idx, path in payloads:
                env = dict(os.environ)
                env["PYTHONPATH"] = os.pathsep.join(
                    [os.path.dirname(os.path.dirname(
                        os.path.abspath(__file__)))] +
                    [p for p in env.get("PYTHONPATH", "").split(os.pathsep)
                     if p])
                out_path = os.path.join(tmp, f"out_{idx}.pkl")
                procs.append((idx, out_path, subprocess.Popen(
                    [sys.executable, "-c",
                     "import sys\n"
                     "import cloudpickle\n"
                     "task_path, out_path = sys.argv[1:3]\n"
                     "with open(task_path, 'rb') as f:\n"
                     "    fn, idx, rows = cloudpickle.load(f)\n"
                     "result = list(fn(idx, iter(rows)))\n"
                     "with open(out_path, 'wb') as f:\n"
                     "    cloudpickle.dump(result, f)\n",
                     path, out_path],
                    env=env)))
            results = []
            failed = []
            for idx, out_path, p in procs:
                rc = p.wait(timeout=120)
                if rc != 0:
                    failed.append((idx, rc))
                    continue
                with open(out_path, "rb") as f:
                    results.extend(cloudpickle.load(f))
            if failed:
                raise RuntimeError(f"fake spark df tasks failed: {failed}")
            return results
        finally:
            shutil.rmtree(tmp, ignore_errors=True)

    def __init__(self, pdf, n: int, fn):
        self._pdf, self._n, self._fn = pdf, n, fn


class _FakeSparkContext:
    defaultParallelism = 2

    def parallelize(self, data, n):
        return _FakeParallelized(n)


class _FakePlainRDD:
    """Non-barrier mapPartitions: each partition is a subprocess fed its
    partition's data, no sync-dir rendezvous (used by run_elastic's agent
    tasks, which coordinate through the driver KV instead)."""

    def __init__(self, n: int, fn):
        self._n = n
        self._fn = fn

    def collect(self):
        import cloudpickle

        tmp = tempfile.mkdtemp(prefix="fake_spark_plain_")
        fn_path = os.path.join(tmp, "task_fn.pkl")
        with open(fn_path, "wb") as f:
            cloudpickle.dump(self._fn, f)
        procs = []
        for rank in range(self._n):
            env = dict(os.environ)
            env["PYTHONPATH"] = os.pathsep.join(
                [os.path.dirname(os.path.dirname(
                    os.path.abspath(__file__)))] +
                [p for p in env.get("PYTHONPATH", "").split(os.pathsep)
                 if p])
            out_path = os.path.join(tmp, f"out_{rank}.pkl")
            procs.append((rank, out_path, subprocess.Popen(
                [sys.executable, "-c",
                 "import os, sys\n"
                 "os.environ.setdefault(\n"
                 "    'XLA_FLAGS', '--xla_force_host_platform_device_count=1')\n"
                 "os.environ['JAX_PLATFORMS'] = 'cpu'\n"
                 "import cloudpickle\n"
                 "fn_path, out_path, rank = sys.argv[1:4]\n"
                 "with open(fn_path, 'rb') as f:\n"
                 "    fn = cloudpickle.load(f)\n"
                 "result = list(fn(iter([int(rank)])))\n"
                 "with open(out_path, 'wb') as f:\n"
                 "    cloudpickle.dump(result, f)\n",
                 fn_path, out_path, str(rank)],
                env=env)))
        results = []
        failed = []
        try:
            for rank, out_path, p in procs:
                rc = p.wait(timeout=300)
                if rc != 0:
                    failed.append((rank, rc))
                    continue
                with open(out_path, "rb") as f:
                    results.extend(cloudpickle.load(f))
        finally:
            for _, _, p in procs:
                if p.poll() is None:
                    p.kill()
                    p.wait(timeout=30)
            shutil.rmtree(tmp, ignore_errors=True)
        if failed:
            raise RuntimeError(f"fake spark tasks failed: {failed}")
        return results


class _FakeParallelized:
    def __init__(self, n: int):
        self._n = n

    def barrier(self):
        return _FakeBarrierRDD(self._n)

    def mapPartitions(self, fn):
        return _FakePlainRDD(self._n, fn)
