"""Compile observability: XLA compile time, tracing-cache misses, and
the ``recompile_storm`` detector.

The classic silent TPU perf killer is not a slow op — it is a *re*compile
storm: an input pipeline that drifts shapes (a ragged last batch, a
padding bug, a python-scalar hyperparameter traced as a constant) makes
``jit`` miss its tracing cache every step, and the job spends minutes in
XLA while the step-time metrics only show mush.  This module turns the
compiler into a first-class metrics source:

* ``hvd_compile_seconds{function=...}`` — per-function backend-compile
  time histogram (label set bounded; overflow lands on ``other``);
* ``hvd_compile_total`` — backend compilations;
* ``hvd_compile_cache_miss_total`` — tracing-cache misses (every
  "Compiling f" event: jit found no cached trace for the call);
* ``recompile_storm`` findings through the anomaly engine
  (:mod:`horovod_tpu.metrics.anomaly`) — the SAME function compiled
  more than ``HVD_TPU_RECOMPILE_STORM`` times past its
  ``HVD_TPU_RECOMPILE_WARMUP`` expected compiles, with the offending
  function named in the finding and the flight event (and, via the
  anomaly->profile hook, a device trace of the storm itself).

Sources:

* ``jax.monitoring`` duration events
  (``/jax/core/compile/backend_compile_duration``) time the actual XLA
  backend compile — *or the read of the persistent cache in its place*:
  JAX times ``compile_or_get_cached`` as a whole, so on a cache hit the
  event covers the retrieval (deserialising the executable and loading
  it onto the devices) and ``compiles`` / ``seconds_total`` count it like
  a compile. :func:`totals` therefore also splits a program's way to the
  device by JAX's other compile events: ``trace_seconds``
  (``jaxpr_trace_duration``: Python to jaxpr), ``lower_seconds``
  (``jaxpr_to_mlir_module_duration``: jaxpr to StableHLO),
  ``cache_read_seconds`` (``cache_retrieval_time_sec``, recorded on hits
  only and a part of ``seconds_total``), ``persistent_cache_hits`` and
  ``persistent_cache_misses`` (``/jax/compilation_cache/cache_hits`` and
  ``cache_misses``; JAX counts a miss when it *writes* the entry, so a
  compile too quick or too small to be kept is neither). Each timed
  event is also a ``scopes.HOST_COMPILE`` record of the host log
  (:mod:`.host_log`): *when* it happened, not only for how long;
* the same events' ``fun_name`` (JAX passes it to the listener for a trace,
  ``step``, and for a lowering and a backend compile, ``jit(step)``): one
  name, ``step``, in every record and in :func:`by_function`, which keeps
  each function's count and seconds of all four kinds. A cache read
  carries no name and is given to the backend compile it lies inside;
* **nesting.** JAX times every jitted function traced *inside* another (an
  inner ``jax.jit``, every ``jax.numpy`` function), so ``trace_seconds``
  counts an inner trace again in each trace around it. A trace that ends
  while another is open (``jax._src.core.trace_state_clean()`` is false)
  is *nested*: seconds of its function, apart from its top-level ones, and
  no record of the host log (``host_log.py`` says when it is one all the
  same). ``trace_lower_cover_seconds`` is the union of the top-level
  traces' and the lowerings' intervals on their thread, kept as the events
  end: one that lies inside a later one (a trace that a lowering made)
  turns nested after the fact, which is also all that is left should JAX
  move that private name;
* :func:`kernel_trace`, around every Pallas call site: the kernel's body is
  traced where the call is bound, a ``scopes.HOST_TRACE`` span and
  ``kernel_traces`` / ``kernel_trace_seconds`` of :func:`totals`;
* the ``jax_log_compiles`` log line ("Compiling jit(<name>) with global
  shapes...") counts a function's tracing-cache misses for the storm
  detector, and names a compile where an event came without a name.
  When this module enabled the flag itself it also stops those records
  propagating to the root logger (they become metrics, not stderr
  noise); a user who pre-enabled the flag keeps their output.

Everything degrades gracefully: if a jax upgrade renames the logger or
reshapes the message, compiles are still counted (monitoring events) —
only the per-function attribution goes to ``unknown``.
"""

from __future__ import annotations

import collections
import contextlib
import logging
import re
import threading
import time
from typing import Dict, Optional

from horovod_tpu.profiling import host_log, scopes

MAX_FUNCTION_LABELS = 32
DEFAULT_RECOMPILE_WARMUP = 2
DEFAULT_RECOMPILE_STORM = 3

# jax's lowering log line; the WARNING level is jax's own choice for
# log_compiles output (jax._src.interpreters.pxla)
# the installed jax writes "Compiling jit(train_step) with global
# shapes..."; the function's own name is what the metrics carry
_COMPILING_RE = re.compile(
    r"^Compiling (?:jit\()?([^\s()]+)\)? with global shapes")
_PXLA_LOGGER = "jax._src.interpreters.pxla"
# also logs at WARNING under log_compiles ("Finished tracing...",
# "Finished XLA compilation...") — silenced alongside when WE own the
# flag, or every compile would print three stderr lines
_DISPATCH_LOGGER = "jax._src.dispatch"

_LOCK = threading.Lock()
_TLS = threading.local()

_installed = False
_handler: Optional[logging.Handler] = None
_null_handler: Optional[logging.Handler] = None
_we_enabled_flag = False
_prev_propagate: Dict[str, bool] = {}
_registry = None
# jax.monitoring has no listener removal, so the listeners are
# registered at most once per process and gated on ``_installed`` —
# an uninstall/ensure_installed cycle must NOT add a second listener
# (every compile would count twice)
_listener_registered = False

_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# jax's timed compile events -> (the word for its kind in the host log, the
# key of totals() its seconds add to). Each leaves a ``scopes.HOST_COMPILE``
# record in the host log when it ends: its interval, its kind and the
# function's name (JAX 0.9 passes ``fun_name`` to the duration listener for
# the three it times in ``dispatch.log_elapsed_time``; a cache read carries
# none). A persistent-cache *write* has no event of its own: it lies inside
# the backend compile (``compile_or_get_cached``) and cannot be told from it
_DURATION_EVENTS = {
    _BACKEND_COMPILE_EVENT: ("backend_compile", "seconds_total"),
    "/jax/core/compile/jaxpr_trace_duration": ("trace", "trace_seconds"),
    "/jax/core/compile/jaxpr_to_mlir_module_duration":
        ("lower", "lower_seconds"),
    "/jax/compilation_cache/cache_retrieval_time_sec":
        ("cache_read", "cache_read_seconds"),
}
_EVENT_TOTALS = {
    "/jax/compilation_cache/cache_hits": "persistent_cache_hits",
    "/jax/compilation_cache/cache_misses": "persistent_cache_misses",
}

# per-function compile counts + storm bookkeeping
_compiles: Dict[str, int] = {}
_flagged_at: Dict[str, int] = {}
_label_set: set = set()
_ZERO_TOTALS = {"compiles": 0, "cache_misses": 0, "seconds_total": 0.0,
                "trace_seconds": 0.0, "lower_seconds": 0.0,
                "cache_read_seconds": 0.0, "persistent_cache_hits": 0,
                "persistent_cache_misses": 0,
                "trace_lower_cover_seconds": 0.0, "kernel_traces": 0,
                "kernel_trace_seconds": 0.0}
_totals = dict(_ZERO_TOTALS)

# by_function(): name -> counts and seconds a kind, at most
# MAX_FUNCTION_LABELS names and ``OTHER``
OTHER = "other"
_KINDS = ("trace", "nested_trace", "lower", "backend_compile", "cache_read")
_ZERO_FUNCTION = {k: v for kind in _KINDS
                  for k, v in ((kind + "s", 0), (kind + "_seconds", 0.0))}
_functions: Dict[str, dict] = {}
# thread -> its top-level traces and lowerings that a later event may yet
# turn out to lie around: (start, end, function, kind, its seconds, those of
# them that the cover counts)
_open: Dict[int, collections.deque] = {}
OPEN_INTERVALS = 1024
SLACK_S = 50e-6     # what two clocks read a few lines apart may differ by
_WRAPPED_RE = re.compile(r"^\w+\((.+)\)$")     # jit(step), pmap(step)

try:
    from jax._src.core import trace_state_clean as _no_trace_open
except ImportError:        # every trace then counts as top-level at its end
    def _no_trace_open() -> bool:
        return True


def _envi(name: str, default: int) -> int:
    from horovod_tpu.common.config import env_int
    return env_int(name, default)


def enabled() -> bool:
    from horovod_tpu.common.config import env_bool
    return env_bool("COMPILE_METRICS", True)


def _reg():
    global _registry
    if _registry is None:
        from horovod_tpu.metrics.registry import default_registry
        _registry = default_registry()
    return _registry


def _function_label(name: str) -> str:
    """Bound the label cardinality: a storm of distinct names (e.g. a
    lambda per step) must not turn the registry into a leak."""
    with _LOCK:
        if name in _label_set:
            return name
        if len(_label_set) < MAX_FUNCTION_LABELS:
            _label_set.add(name)
            return name
    return "other"


def _note_compiling(name: str) -> None:
    """A tracing-cache miss for ``name`` (about to trace + compile)."""
    _TLS.last_name = name
    with _LOCK:
        _totals["cache_misses"] += 1
    try:
        _reg().counter(
            "hvd_compile_cache_miss_total",
            help="jit tracing-cache misses (each one traces and "
                 "compiles)").inc()
    except Exception:
        pass
    _check_storm(name)


def _check_storm(name: str) -> None:
    warmup = max(0, _envi("RECOMPILE_WARMUP", DEFAULT_RECOMPILE_WARMUP))
    storm = max(1, _envi("RECOMPILE_STORM", DEFAULT_RECOMPILE_STORM))
    with _LOCK:
        n = _compiles.get(name, 0) + 1
        if len(_compiles) < 4096 or name in _compiles:
            _compiles[name] = n
        _function(name)        # by_function() says it, seconds or none yet
        recompiles = n - warmup
        last = _flagged_at.get(name, 0)
        if recompiles <= 0 or recompiles - last < storm:
            return
        _flagged_at[name] = recompiles
    # outside the lock: reporting fans out to counter + flight +
    # (possibly) a profile capture
    try:
        from horovod_tpu.metrics.anomaly import report_finding
        report_finding("recompile_storm", function=name, compiles=n,
                       recompiles=recompiles)
    except Exception:
        pass


def _function_name(fun_name) -> Optional[str]:
    """JAX's ``fun_name`` without the API's wrapper: ``jit(step)`` and
    ``step`` are one function."""
    if not fun_name:
        return None
    wrapped = _WRAPPED_RE.match(fun_name)
    return wrapped.group(1) if wrapped else fun_name


def _function(name: Optional[str]) -> dict:
    """``name``'s entry of :func:`by_function` (hold ``_LOCK``). A full table
    folds the entry with the fewest seconds into ``OTHER``: set-up runs
    dozens of one-line ``jax.numpy`` programs before the step that matters,
    and first come, first kept would keep those."""
    name = name or "unknown"
    entry = _functions.get(name)
    if entry is None:
        if len(_functions) >= MAX_FUNCTION_LABELS + (OTHER in _functions):
            least = min((k for k in _functions if k != OTHER),
                        key=lambda k: sum(v for f, v in _functions[k].items()
                                          if f.endswith("_seconds")))
            other = _functions.setdefault(OTHER, dict(_ZERO_FUNCTION))
            for field, value in _functions.pop(least).items():
                other[field] += value
        entry = _functions[name] = dict(_ZERO_FUNCTION)
    return entry


def _count(name: Optional[str], kind: str, seconds: float,
           events: int = 1) -> None:
    entry = _function(name)
    entry[kind + "s"] += events
    entry[kind + "_seconds"] += seconds


def _top_level(kind: str, name: Optional[str], start: float,
               seconds: float) -> None:
    """A trace or a lowering that ended with no trace open (hold ``_LOCK``):
    seconds of its function and, less what this thread's cover already
    holds of it, of the cover. Earlier ones that lie inside it were nested
    after all."""
    intervals = _open.setdefault(
        threading.get_ident(), collections.deque(maxlen=OPEN_INTERVALS))
    while intervals and intervals[-1][0] >= start - SLACK_S:
        _s, _e, inner, inner_kind, inside, covered = intervals.pop()
        if (inner or "unknown") not in _functions:
            inner = OTHER                   # folded away since
        _count(inner, inner_kind, -inside, -1)
        _count(inner, "nested_trace", inside)
        _totals["trace_lower_cover_seconds"] -= covered
    end = start + seconds
    covered = min(seconds, max(0.0, end - intervals[-1][1])) \
        if intervals else seconds
    intervals.append((start, end, name, kind, seconds, covered))
    _count(name, kind, seconds)
    _totals["trace_lower_cover_seconds"] += covered


def _on_backend_compile(seconds: float, name: Optional[str]) -> None:
    name = name or getattr(_TLS, "last_name", None) or "unknown"
    with _LOCK:
        _totals["compiles"] += 1
        _totals["seconds_total"] += float(seconds)
    try:
        reg = _reg()
        reg.counter("hvd_compile_total",
                    help="XLA backend compilations").inc()
        reg.histogram(
            "hvd_compile_seconds",
            help="XLA backend compile time per compilation",
            labels={"function": _function_label(name)}).observe(seconds)
    except Exception:
        pass


class _CompileLogHandler(logging.Handler):
    def emit(self, record: logging.LogRecord) -> None:
        if not _installed:
            return
        try:
            m = _COMPILING_RE.match(record.getMessage())
            if m:
                _note_compiling(m.group(1))
        except Exception:
            pass  # observability must never break compilation


def ensure_installed(registry=None) -> bool:
    """Idempotent; returns True when the hooks are (already) live.
    Gated on ``HVD_TPU_COMPILE_METRICS`` (default on)."""
    global _installed, _handler, _null_handler, _we_enabled_flag, \
        _prev_propagate, _registry, _listener_registered
    if not enabled():
        return False
    with _LOCK:
        if _installed:
            return True
        _installed = True
    if registry is not None:
        _registry = registry
    try:
        import jax
        import jax.monitoring

        def _dur_listener(event: str, duration: float, **kw) -> None:
            if not _installed or event not in _DURATION_EVENTS:
                return
            kind, key = _DURATION_EVENTS[event]
            duration = float(duration)
            end = time.perf_counter()
            start = end - duration
            name = _function_name(kw.get("fun_name"))
            meta = {"event": kind, "function": name}
            if kind == "trace" and not _no_trace_open():
                with _LOCK:
                    _totals[key] += duration
                    _count(name, "nested_trace", duration)
                if not host_log.ended_since(scopes.HOST_TRACE, start):
                    return
                meta["nested"] = True
            elif kind in ("trace", "lower"):
                with _LOCK:
                    _totals[key] += duration
                    _top_level(kind, name, start, duration)
            elif kind == "cache_read":
                # inside its function's backend compile, which ends later
                # and says whose it was
                _TLS.cache_read = (start, duration, meta)
                with _LOCK:
                    _totals[key] += duration
            else:
                read = getattr(_TLS, "cache_read", None)
                _TLS.cache_read = None
                _on_backend_compile(duration, name)
                with _LOCK:
                    _count(name, kind, duration)
                    if read is not None and read[0] >= start - SLACK_S:
                        read[2]["function"] = name
                        _count(name, "cache_read", read[1])
            host_log.record(scopes.HOST_COMPILE, start, duration, meta)

        def _event_listener(event: str, **_kw) -> None:
            if _installed and event in _EVENT_TOTALS:
                with _LOCK:
                    _totals[_EVENT_TOTALS[event]] += 1

        if not _listener_registered:
            jax.monitoring.register_event_duration_secs_listener(
                _dur_listener)
            jax.monitoring.register_event_listener(_event_listener)
            _listener_registered = True
        lg = logging.getLogger(_PXLA_LOGGER)
        _handler = _CompileLogHandler(level=logging.DEBUG)
        lg.addHandler(_handler)
        if lg.level > logging.WARNING or lg.level == logging.NOTSET:
            lg.setLevel(logging.WARNING)
        if not jax.config.jax_log_compiles:
            jax.config.update("jax_log_compiles", True)
            _we_enabled_flag = True
            # we turned the firehose on; keep it out of stderr.  The
            # NullHandler matters: with propagate=False and NO handler,
            # stdlib logging falls back to the bare-format lastResort
            # stderr handler for WARNING records
            _null_handler = logging.NullHandler()
            for name in (_PXLA_LOGGER, _DISPATCH_LOGGER):
                lgr = logging.getLogger(name)
                _prev_propagate[name] = lgr.propagate
                lgr.propagate = False
                lgr.addHandler(_null_handler)
    except Exception as e:
        from horovod_tpu.common.logging import get_logger
        get_logger().warning("compile observability unavailable: %r", e)
    return True


def uninstall() -> None:
    """Tests only: disable the hooks and restore jax's flag/propagation.
    The monitoring listener stays registered (jax has no single-listener
    removal) but goes inert behind the ``_installed`` flag."""
    global _installed, _handler, _null_handler, _we_enabled_flag
    with _LOCK:
        if not _installed:
            return
        _installed = False
    lg = logging.getLogger(_PXLA_LOGGER)
    if _handler is not None:
        lg.removeHandler(_handler)
        _handler = None
    if _we_enabled_flag:
        try:
            import jax
            jax.config.update("jax_log_compiles", False)
        except Exception:
            pass
        for name, prop in _prev_propagate.items():
            lgr = logging.getLogger(name)
            lgr.propagate = prop
            if _null_handler is not None:
                lgr.removeHandler(_null_handler)
        _we_enabled_flag = False
        _prev_propagate.clear()
        _null_handler = None


def totals() -> dict:
    """Process-lifetime compile totals. ``compiles`` / ``seconds_total``
    are JAX's backend-compile events (``hvd_compile_total``: measured,
    not the wall clock of a phase that also ran the first step) and on a
    persistent-cache hit hold the read in the compile's place;
    ``cache_misses`` is jit's *tracing*-cache misses. ``trace_seconds``, ``lower_seconds``,
    ``cache_read_seconds``, ``persistent_cache_hits`` and
    ``persistent_cache_misses`` are the module docstring's split;
    ``trace_seconds`` counts a nested trace once more in every trace around
    it, ``trace_lower_cover_seconds`` (the top-level traces and the
    lowerings, as a union) counts every second once. ``kernel_traces`` and
    ``kernel_trace_seconds``: :func:`kernel_trace`."""
    with _LOCK:
        return dict(_totals)


def by_function() -> Dict[str, dict]:
    """``{function: {...}}``, at most ``MAX_FUNCTION_LABELS`` names (those
    with the most seconds) and ``OTHER``: ``compiles`` (the function's
    tracing-cache misses, what the storm detector counts) and, for each of
    ``trace``, ``nested_trace``, ``lower``, ``backend_compile`` and
    ``cache_read``, ``<kind>s`` and ``<kind>_seconds``. A function's
    ``trace_seconds`` and ``lower_seconds`` are its top-level ones: over all
    functions they add up to ``trace_lower_cover_seconds`` (to the clocks'
    slack); ``nested_trace_seconds`` are its traces inside another
    function's, which that one's seconds hold already."""
    with _LOCK:
        table = {name: {"compiles": _compiles.get(name, 0), **entry}
                 for name, entry in _functions.items()}
        if OTHER in table:
            table[OTHER]["compiles"] = sum(_compiles.values()) - sum(
                e["compiles"] for n, e in table.items() if n != OTHER)
        return table


@contextlib.contextmanager
def kernel_trace(name: str):
    """Around a Pallas call site, ``name`` the kernel's instruction name: the
    kernel's body is traced when the call is bound, so this is what one
    kernel shape costs a program's trace. One span of the host log,
    ``scopes.HOST_TRACE`` + ``/kernel/<name>``, and one more of
    ``kernel_traces``, its seconds onto ``kernel_trace_seconds``."""
    t0 = time.perf_counter()
    try:
        with host_log.Span(f"{scopes.HOST_TRACE}/kernel/{name}"):
            yield
    finally:
        if _installed:
            with _LOCK:
                _totals["kernel_traces"] += 1
                _totals["kernel_trace_seconds"] += time.perf_counter() - t0


def kernel_call(pallas_call, *args, name: str, **kwargs):
    """``pallas_call(*args, name=name, **kwargs)`` whose call on its operands
    runs inside :func:`kernel_trace`: a call site changes one word."""
    call = pallas_call(*args, name=name, **kwargs)

    def bound(*operands):
        with kernel_trace(name):
            return call(*operands)
    return bound


def reset_counts() -> None:
    """Forget per-function storm bookkeeping, totals, and the label
    budget (tests, elastic re-init); the registry instruments are
    cumulative and stay.  Resetting the label set lets a fresh
    generation attribute ITS functions by name — without it a
    long-lived process saturates ``MAX_FUNCTION_LABELS`` once and every
    later function lands on ``other`` forever.  Re-used names attach to
    their existing series, so cardinality stays bounded per reset
    epoch."""
    with _LOCK:
        _compiles.clear()
        _flagged_at.clear()
        _label_set.clear()
        _functions.clear()
        _open.clear()
        _totals.update(_ZERO_TOTALS)
