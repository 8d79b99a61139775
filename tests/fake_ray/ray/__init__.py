"""Minimal ray stand-in for exercising ``horovod_tpu.ray.RayExecutor``
without a Ray installation (reference analog: the Ray integration tests in
``test/integration/test_ray.py`` run against ``ray.init(local_mode=...)``;
this image has no ray, so the actor surface the executor actually touches
is reimplemented here over subprocesses + a framed-pipe RPC).

Surface implemented (exactly what ``horovod_tpu/ray/__init__.py`` uses):

- ``@ray.remote(num_cpus=...)`` on a class → ``.remote(*args)`` actor
  construction; actor method ``.remote(...)`` calls returning futures
- ``ray.get(future | [futures])``
- ``ray.kill(actor)``
- ``ray.nodes()`` (for ``RayHostDiscovery``) — returns ``_FAKE_NODES``,
  settable by the test

Each actor is a REAL subprocess (like a Ray worker): the class cell and
every call travel via cloudpickle, and method calls are dispatched
asynchronously — a future is created when ``.remote()`` is called and the
response is read only at ``ray.get``, so concurrent ``execute`` calls that
rendezvous in ``hvd.init()`` across actors make progress, exactly as on a
real Ray cluster.
"""

from __future__ import annotations

import os
import struct
import subprocess
import sys

_FAKE_NODES = []  # tests assign dicts shaped like ray.nodes() entries

_ACTOR_MAIN = r"""
import os, struct, sys
# protocol rides a dup of stdout; user-level prints go to stderr so they
# can never corrupt frames
proto_out = os.fdopen(os.dup(1), "wb")
os.dup2(2, 1)
# unit-test actors run on the CPU platform and never touch a chip
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=1")
os.environ["JAX_PLATFORMS"] = "cpu"
import cloudpickle
proto_in = os.fdopen(0, "rb")

def read_frame():
    hdr = proto_in.read(4)
    if len(hdr) < 4:
        sys.exit(0)
    (n,) = struct.unpack(">I", hdr)
    return cloudpickle.loads(proto_in.read(n))

def write_frame(obj):
    blob = cloudpickle.dumps(obj)
    proto_out.write(struct.pack(">I", len(blob)) + blob)
    proto_out.flush()

cls, args, kwargs = read_frame()
obj = cls(*args, **kwargs)
while True:
    name, cargs, ckwargs = read_frame()
    try:
        write_frame(("ok", getattr(obj, name)(*cargs, **ckwargs)))
    except BaseException as e:  # report, keep serving
        write_frame(("err", f"{type(e).__name__}: {e}"))
"""


class _Future:
    def __init__(self, actor, index: int):
        self._actor = actor
        self._index = index

    def get(self):
        return self._actor._read_until(self._index)


class _Actor:
    def __init__(self, cls, args, kwargs):
        import cloudpickle

        # bufsize=0: reads must go straight to the pipe so select() in
        # _read_until never misses data parked in a Python-level buffer
        self._proc = subprocess.Popen(
            [sys.executable, "-c", _ACTOR_MAIN],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, bufsize=0,
            env=dict(os.environ))
        self._sent = 0
        self._received = 0
        self._results = {}
        self._write(cloudpickle.dumps((cls, args, kwargs)))

    def _write(self, blob: bytes) -> None:
        self._proc.stdin.write(struct.pack(">I", len(blob)) + blob)
        self._proc.stdin.flush()

    def _call(self, name, args, kwargs) -> _Future:
        import cloudpickle

        self._write(cloudpickle.dumps((name, args, kwargs)))
        fut = _Future(self, self._sent)
        self._sent += 1
        return fut

    def _read_exact(self, n: int, deadline: float) -> bytes:
        """Read exactly n bytes from the (unbuffered) actor pipe, failing
        at the deadline: a stalled actor (wedged rendezvous) must fail
        the test, not hang the pytest session."""
        import select
        import time

        buf = b""
        while len(buf) < n:
            remaining = deadline - time.time()
            if remaining <= 0 or not select.select(
                    [self._proc.stdout], [], [], remaining)[0]:
                self._kill()
                raise RuntimeError("fake ray actor call timed out")
            chunk = self._proc.stdout.read(n - len(buf))
            if not chunk:
                raise RuntimeError(
                    f"fake ray actor died (rc={self._proc.poll()})")
            buf += chunk
        return buf

    def _read_until(self, index: int, deadline_s: float = 180.0):
        import time

        import cloudpickle

        deadline = time.time() + deadline_s
        while self._received <= index:
            (n,) = struct.unpack(">I", self._read_exact(4, deadline))
            status, value = cloudpickle.loads(self._read_exact(n, deadline))
            self._results[self._received] = (status, value)
            self._received += 1
        status, value = self._results.pop(index)
        if status == "err":
            raise RuntimeError(f"fake ray actor call failed: {value}")
        return value

    def __getattr__(self, name):
        if name.startswith("_"):
            raise AttributeError(name)
        actor = self

        class _Method:
            @staticmethod
            def remote(*args, **kwargs):
                return actor._call(name, args, kwargs)

        return _Method()

    def _kill(self) -> None:
        if self._proc.poll() is None:
            self._proc.kill()
            self._proc.wait(timeout=30)


class _RemoteClass:
    def __init__(self, cls):
        self._cls = cls

    def remote(self, *args, **kwargs):
        return _Actor(self._cls, args, kwargs)


def remote(*args, **kwargs):
    if len(args) == 1 and not kwargs and isinstance(args[0], type):
        return _RemoteClass(args[0])  # bare @ray.remote

    def deco(cls):
        return _RemoteClass(cls)

    return deco  # @ray.remote(num_cpus=...)


def get(x):
    if isinstance(x, (list, tuple)):
        return [f.get() for f in x]
    return x.get()


def kill(actor) -> None:
    actor._kill()


def nodes():
    return list(_FAKE_NODES)
