"""The host log: what paused the host, when, and for how long.

One bounded ring of ``(name, start, duration, meta)`` records on
``time.perf_counter()``, always on (as :mod:`.compile_watch` is), with
four writers and no other:

* :class:`Span`, what :func:`horovod_tpu.profiling.annotate` returns: a
  ``jax.profiler.TraceAnnotation`` (so the span lies on the device
  planes' clock while a profiler session is open) that also leaves a
  record here, session or none. ``data_loader.put_next`` opens
  ``scopes.INPUT_SOURCE`` and ``scopes.INPUT_PLACE`` once a batch, so those
  two are the program's own step clock in an untraced run; ``hvd.init()``
  is one ``scopes.HOST_INIT``; ``scopes.scope`` (a device phase) and
  ``compile_watch.kernel_trace`` (a Pallas call site) open
  ``scopes.HOST_TRACE`` spans, which a function leaves while it is traced
  and never once it is an executable;
* the ``gc.callbacks`` entry of :func:`install_gc_callback` (installed by
  ``hvd.init()``, removed by ``hvd.shutdown()``): every garbage
  collection is a ``scopes.HOST_GC`` record, ``meta`` its ``generation``
  and ``collected``; generations 1 and 2 are ``TraceAnnotation`` s too,
  on the trace's clock;
* :mod:`.compile_watch`'s duration listener: every lowering, backend
  compile, persistent-cache read and top-level trace JAX times is a
  ``scopes.HOST_COMPILE`` record (``meta``: ``event``, ``function``), written
  at its end with ``start = now - duration``; a record only, because a
  ``TraceAnnotation`` cannot be written after the fact. A trace *inside*
  another (``jax.numpy``'s own jitted functions, thousands a step) is
  seconds of its function in ``compile_watch.by_function()`` and no record,
  unless a ``scopes.HOST_TRACE`` span ended inside it (:func:`ended_since`:
  a jitted call site of this program's; ``meta["nested"]`` is then true);
* ``horovod_tpu/__init__.py``: one ``scopes.HOST_IMPORT`` record, the
  package's import from its first line to its last.

:func:`records` reads the ring back. Names come from :mod:`.scopes`.
"""

from __future__ import annotations

import collections
import gc
import time
from typing import List, Optional, Tuple

import jax

from horovod_tpu.profiling import scopes

#: the ring's length. The fastest job measured (BERT-Large at batch 8, a
#: 24 ms step) leaves about three records a step, ~1300 in a 10 s window:
#: this holds a dozen such windows, in ~3 MB
RING_RECORDS = 16384

Record = Tuple[str, float, float, Optional[dict]]

_RING: "collections.deque[Record]" = collections.deque(maxlen=RING_RECORDS)
_clock = time.perf_counter


def record(name: str, start: float, duration: float,
           meta: Optional[dict] = None) -> None:
    """Append one record (``deque.append`` is atomic)."""
    _RING.append((name, start, duration, meta))


def records() -> List[Record]:
    """The ring's records in the order they ended."""
    return list(_RING)


def ended_since(prefix: str, start: float) -> bool:
    """Whether a record named ``prefix...`` ended at or after ``start``.
    Looks back from the newest record only as far as records that ended
    after ``start`` (records lie in the order they ended)."""
    for back in range(1, len(_RING) + 1):
        try:
            name, t0, duration, _meta = _RING[-back]
        except IndexError:      # another thread's append moved the ring
            return False
        if t0 + duration < start:
            return False
        if name.startswith(prefix):
            return True
    return False


def clear() -> None:
    """Tests only."""
    _RING.clear()


class Span(jax.profiler.TraceAnnotation):
    """A ``TraceAnnotation`` that leaves a record in the ring as well."""

    __slots__ = ("_name", "_t0")

    def __init__(self, name: str):
        super().__init__(name)
        self._name = name

    def __enter__(self):
        super().__enter__()
        self._t0 = _clock()
        return self

    def __exit__(self, exc_type, exc_value, traceback):
        t1 = _clock()
        _RING.append((self._name, self._t0, t1 - self._t0, None))
        return super().__exit__(exc_type, exc_value, traceback)


# -- garbage collections ------------------------------------------------------

# the collection under way: [perf_counter at "start", its open annotation
# or None]. The collector does not re-enter itself, so one slot is enough
_gc_open = None


def _on_gc(phase: str, info: dict) -> None:
    """``gc.callbacks`` entry; must never raise into the collector. A
    generation-0 collection (tens of microseconds, about two a step in a
    BERT-Large loop) is a record; the older generations, whose
    collections are the ones long enough to starve a device, are
    ``TraceAnnotation`` s as well."""
    global _gc_open
    try:
        if phase == "start":
            _gc_open = [_clock(), None]
            if info.get("generation", 0) > 0:
                span = jax.profiler.TraceAnnotation(
                    scopes.HOST_GC, generation=info["generation"])
                span.__enter__()
                _gc_open[1] = span
        elif _gc_open is not None:
            t1 = _clock()
            (t0, span), _gc_open = _gc_open, None
            _RING.append((scopes.HOST_GC, t0, t1 - t0,
                          {"generation": info.get("generation", -1),
                           "collected": info.get("collected", 0)}))
            if span is not None:
                span.__exit__(None, None, None)
    except Exception:
        pass


def install_gc_callback() -> None:
    """Idempotent: one entry in ``gc.callbacks``, however often called."""
    if _on_gc not in gc.callbacks:
        gc.callbacks.append(_on_gc)


def uninstall_gc_callback() -> None:
    global _gc_open
    while _on_gc in gc.callbacks:
        gc.callbacks.remove(_on_gc)
    _gc_open = None

