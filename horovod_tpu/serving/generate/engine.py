"""The generative decode engine: ONE jit'd fixed-shape decode step over
a static slot array, fed by continuous token-level batching.

The request-level serving loop (replica.py) answers a whole request per
forward; autoregressive decode breaks that granularity — sequences
finish at different times, and a request-level batch strands chip time
on every early finisher.  This engine decodes at TOKEN granularity:

* a static array of ``HVD_TPU_GEN_SLOTS`` decode slots; the compiled
  step (:func:`~horovod_tpu.models.decode.decode_step_paged`)
  always runs over all of them, with an active mask — membership churn
  is host bookkeeping between steps and NEVER changes a compiled shape
  (the compile-stability guard in tests/test_generate.py asserts
  exactly one decode-step compile under heavy join/leave churn);
* K/V history lives in the paged pool (:mod:`.pages`): admission
  allocates a request's WORST-CASE pages up front, eviction returns
  them the same step boundary the sequence leaves;
* prompts prefill in fixed ``HVD_TPU_PREFILL_CHUNK``-token chunks, one
  chunk per engine iteration per sequence, interleaved with live
  decode steps — a long prompt never stalls the decode batch
  (prefill/decode split);
* the admission edge is the SAME bounded
  :class:`~horovod_tpu.serving.batcher.DynamicBatcher` contract as
  request-level serving (explicit 429 sheds, drain semantics), run
  with ``max_wait_s=0`` — holding a batch window open would stall the
  decode loop for nothing, the slot scheduler IS the batching.

Every request's path is traced (submit→admit→prefill→each decode
step→finish, PR-15 spans) and metered per phase
(``hvd_serving_prefill/decode_seconds_total``, slot occupancy, page
pool, TTFT/ITL — docs/OBSERVABILITY.md).
"""

from __future__ import annotations

import threading
import time
from typing import Any, Callable, List, Optional, Sequence, Tuple

import numpy as np

from horovod_tpu.common.config import env_int
from horovod_tpu.common.logging import get_logger
from horovod_tpu.serving import metrics as smetrics
from horovod_tpu.serving.batcher import DeadlineError, DynamicBatcher
from horovod_tpu.serving.generate.pages import PagePool, plan_kv_pages
from horovod_tpu.serving.generate.scheduler import (DECODE, DONE, PREFILL,
                                                    GenRequest,
                                                    SlotScheduler)


def _jit_step_fns(cfg) -> Tuple[Callable, Callable]:
    """The two compiled entry points, as NAMED module-visible closures:
    compile_watch attributes compiles by function name, and the
    one-compile guarantee is asserted against ``gen_decode_step``."""
    import jax

    from horovod_tpu.models.decode import (decode_step_paged,
                                           prefill_chunk_paged)

    def gen_decode_step(params, k_pages, v_pages, page_table, lengths,
                        last_token, active):
        return decode_step_paged(params, k_pages, v_pages, page_table,
                                 lengths, last_token, active, cfg)

    def gen_prefill_chunk(params, k_pages, v_pages, page_row, tokens,
                          pos0, valid):
        return prefill_chunk_paged(params, k_pages, v_pages, page_row,
                                   tokens, pos0, valid, cfg)

    return jax.jit(gen_decode_step), jax.jit(gen_prefill_chunk)


class GenerateEngine:
    """Continuous-batching decode engine over one model's weights.

    Thread model: :meth:`submit` runs on any thread (handler threads —
    it only touches the bounded admission queue); ALL slot/page/array
    mutation happens in :meth:`step_once`, called either by the
    background loop (:meth:`start`) or directly by tests/bench drivers
    for deterministic single-threaded stepping.
    """

    def __init__(self, params: Any, cfg,
                 n_slots: Optional[int] = None,
                 page_bytes: Optional[int] = None,
                 prefill_chunk: Optional[int] = None,
                 max_ctx: Optional[int] = None,
                 batcher: Optional[DynamicBatcher] = None) -> None:
        import jax.numpy as jnp

        from horovod_tpu.models.decode import (flatten_decode_params,
                                               kv_cache_spec)
        self.cfg = cfg
        self.n_slots = int(n_slots or env_int("GEN_SLOTS", 4))
        self.prefill_chunk = int(prefill_chunk
                                 or env_int("PREFILL_CHUNK", 16))
        self.max_ctx = int(max_ctx or cfg.max_seq)
        n_layers, kv_width, kv_dtype = kv_cache_spec(cfg)
        self.plan = plan_kv_pages(n_layers, kv_width, kv_dtype,
                                  self.n_slots, self.max_ctx, page_bytes)
        self.pool = PagePool(self.plan)
        self.scheduler = SlotScheduler(self.n_slots, self.pool,
                                       self.prefill_chunk, self.max_ctx)
        # max_wait_s=0: the window must close instantly — the slot
        # scheduler is the batching, the queue is only admission control
        self.batcher = batcher or DynamicBatcher(
            max_batch_size=self.n_slots, max_wait_s=0.0)
        self.params = flatten_decode_params(params)
        self._decode_fn, self._prefill_fn = _jit_step_fns(cfg)
        shape = (n_layers, self.plan.total_pages + 1,
                 self.plan.page_tokens, kv_width)
        self._k_pages = jnp.zeros(shape, jnp.float32)
        self._v_pages = jnp.zeros(shape, jnp.float32)
        # host mirrors of the decode step's per-slot inputs; rows of
        # the page table default to the scratch page id
        self._page_table = np.full(
            (self.n_slots, self.plan.pages_per_slot),
            self.plan.total_pages, dtype=np.int32)
        self._lengths = np.zeros((self.n_slots,), np.int32)
        self._last_token = np.zeros((self.n_slots,), np.int32)
        self._active = np.zeros((self.n_slots,), bool)
        self.decode_steps_total = 0
        self.prefill_chunks_total = 0
        # weight-swap pause gate: the replica's hot swap clears it
        # around the params flip so the decode loop holds at a step
        # boundary; the held time is charged to every live sequence's
        # ``swap_pause`` ledger stage
        self._swap_gate = threading.Event()
        self._swap_gate.set()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    # -- limits -------------------------------------------------------------
    @property
    def max_request_tokens(self) -> int:
        """Hard per-request bound: prompt + max_new must fit one slot's
        page table AND the model context."""
        return min(self.max_ctx, self.plan.slot_tokens)

    # -- lifecycle ----------------------------------------------------------
    def start(self) -> "GenerateEngine":
        self._thread = threading.Thread(target=self._run,
                                        name="hvd-gen-engine",
                                        daemon=True)
        self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5.0)

    def _run(self) -> None:
        while not self._stop.is_set():
            self.step_once(idle_wait_s=0.05)

    # -- intake -------------------------------------------------------------
    def submit(self, req_id: str, prompt, max_new: int,
               deadline_s: Optional[float] = None, trace=None,
               on_token=None) -> GenRequest:
        """Admit one generation request (any thread).  Raises
        :class:`~horovod_tpu.serving.batcher.SheddedError` /
        :class:`~horovod_tpu.serving.batcher.DrainingError` exactly like
        request-level admission, and :class:`ValueError` when the worst
        case cannot fit a slot.  The caller blocks on
        ``req.pending.wait()`` for the terminal result."""
        req = GenRequest(req_id, prompt, int(max_new), trace=trace,
                         on_token=on_token)
        if req.max_new < 1:
            raise ValueError(f"request {req_id}: max_new must be >= 1")
        if req.prompt_len < 1:
            raise ValueError(f"request {req_id}: empty prompt")
        if req.worst_case_tokens > self.max_request_tokens:
            raise ValueError(
                f"request {req_id}: prompt+max_new "
                f"({req.worst_case_tokens}) exceeds the per-slot "
                f"capacity ({self.max_request_tokens})")
        req.pending = self.batcher.submit(req_id, req,
                                          deadline_s=deadline_s)
        return req

    def generate(self, prompt, max_new: int, req_id: str = "local",
                 deadline_s: Optional[float] = None) -> dict:
        """Blocking convenience wrapper (the engine loop must be
        running, or another thread stepping)."""
        req = self.submit(req_id, prompt, max_new, deadline_s=deadline_s)
        wait_s = (req.pending.deadline - time.monotonic()) + 1.0
        return req.pending.wait(timeout=max(wait_s, 0.1))

    # -- drain --------------------------------------------------------------
    def drain(self) -> None:
        self.batcher.drain()

    def drained(self) -> bool:
        """Admission stopped AND every admitted sequence answered."""
        return self.batcher.draining and self.batcher.drained() \
            and not self.scheduler.busy()

    def wait_drained(self, timeout_s: float = 30.0) -> bool:
        end = time.monotonic() + timeout_s
        while not self.drained():
            if time.monotonic() >= end:
                return False
            time.sleep(0.01)
        return True

    # -- weight-swap pause --------------------------------------------------
    def begin_swap(self) -> None:
        """Hold the decode loop at the next step boundary (the replica's
        hot weight swap brackets the params flip with begin/end)."""
        self._swap_gate.clear()

    def end_swap(self) -> None:
        self._swap_gate.set()

    def _swap_wait(self) -> None:
        if self._swap_gate.is_set():
            return
        t0 = time.monotonic()
        self._swap_gate.wait()
        pause = time.monotonic() - t0
        if pause <= 0:
            return
        # charge the pause to every LIVE sequence's ledger (waiting
        # requests keep accruing slot/page wait through the scheduler)
        for req in list(self.scheduler.slots):
            if req is not None:
                req.swap_pause_s += pause

    # -- the step -----------------------------------------------------------
    def step_once(self, idle_wait_s: float = 0.0) -> bool:
        """One engine iteration: pull admissions, sweep deadlines,
        admit into slots, ONE prefill chunk per prefilling sequence,
        ONE batched decode step, deliver finishes.  Returns True when
        any work happened."""
        self._swap_wait()
        pulled = self._pull_admissions(idle_wait_s)
        self._sweep_deadlines()
        admitted = self.scheduler.admit()
        for req in admitted:
            self._on_admitted(req)
        worked = pulled or bool(admitted)
        worked = self._prefill_tick() or worked
        worked = self._decode_tick() or worked
        smetrics.set_slot_occupancy(self.scheduler.occupied(),
                                    self.n_slots)
        smetrics.set_gen_waiting(self.scheduler.waiting_count())
        return worked

    def _pull_admissions(self, idle_wait_s: float) -> bool:
        # when slots/queue hold live work the pull must not block; only
        # a fully idle engine waits in next_batch
        timeout = 0.0 if self.scheduler.busy() else float(idle_wait_s)
        batch = self.batcher.next_batch(timeout_s=timeout)
        if not batch:
            return False
        for pending in batch:
            req: GenRequest = pending.payload
            req.pending = pending
            self.scheduler.add_waiting(req)
        # the queue's job ends at hand-off; sequence lifetime is the
        # scheduler's (drain completion = drained() above, which also
        # requires the scheduler to be empty)
        self.batcher.batch_done()
        return True

    def _sweep_deadlines(self) -> None:
        now = time.monotonic()
        for req in list(self.scheduler.slots):
            if req is None or req.pending is None:
                continue
            if req.pending.deadline <= now:
                smetrics.inc_shed("deadline")
                self._finish(req, "deadline", error=DeadlineError(
                    f"request {req.id}: deadline expired mid-generation "
                    f"after {len(req.tokens)} tokens"))

    def _on_admitted(self, req: GenRequest) -> None:
        row = self._page_table[req.slot]
        row[:] = self.plan.total_pages          # scratch-fill the tail
        row[:len(req.pages)] = req.pages
        self._lengths[req.slot] = 0
        self._last_token[req.slot] = 0
        self._active[req.slot] = False          # active only once decoding
        self._span(req, "gen_admit",
                   dur_s=req.admitted_at - req.submitted_at,
                   slot=req.slot, pages=len(req.pages),
                   queued_s=round(req.admitted_at - req.submitted_at, 6))

    # -- prefill ------------------------------------------------------------
    def _prefill_tick(self) -> bool:
        import jax.numpy as jnp
        worked = False
        for req in self.scheduler.prefilling():
            chunk = self.scheduler.next_prefill_chunk(req)
            if chunk is None:     # defensive; PREFILL implies a chunk
                continue
            start, length = chunk
            tokens = np.zeros((self.prefill_chunk,), np.int32)
            tokens[:length] = req.prompt[start:start + length]
            t0 = time.monotonic()
            nxt, self._k_pages, self._v_pages = self._prefill_fn(
                self.params, self._k_pages, self._v_pages,
                jnp.asarray(self._page_table[req.slot]),
                jnp.asarray(tokens), np.int32(start), np.int32(length))
            nxt = int(nxt)
            dur = time.monotonic() - t0
            smetrics.observe_prefill(dur)
            self.prefill_chunks_total += 1
            req.prefill_s += dur
            req.prefill_pos += length
            req.prefill_chunks += 1
            self._span(req, "gen_prefill", dur_s=dur,
                       chunk=req.prefill_chunks, chunk_start=start,
                       tokens=length)
            worked = True
            if req.prefill_pos >= req.prompt_len:
                # the last chunk's last valid logits ARE the first
                # emitted token: prefill ends with TTFT, decode
                # continues from it
                req.state = DECODE
                self._lengths[req.slot] = req.prompt_len
                self._last_token[req.slot] = nxt
                self._active[req.slot] = True
                self._emit(req, nxt)
                smetrics.count_gen_tokens(1)
                smetrics.observe_ttft(
                    req.first_token_at - req.submitted_at)
                if len(req.tokens) >= req.max_new:
                    self._finish(req, "length")
        return worked

    # -- decode -------------------------------------------------------------
    def _decode_tick(self) -> bool:
        import jax.numpy as jnp
        decoding = self.scheduler.decoding()
        if not decoding:
            return False
        t0 = time.monotonic()
        nxt, self._k_pages, self._v_pages = self._decode_fn(
            self.params, self._k_pages, self._v_pages,
            jnp.asarray(self._page_table), jnp.asarray(self._lengths),
            jnp.asarray(self._last_token), jnp.asarray(self._active))
        nxt = np.asarray(nxt)
        dur = time.monotonic() - t0
        self.decode_steps_total += 1
        for req in decoding:
            s = req.slot
            tok = int(nxt[s])
            req.decode_steps += 1
            req.decode_s += dur  # each rider experiences the full step
            self._lengths[s] += 1
            self._last_token[s] = tok
            self._emit(req, tok)
            self._span(req, "gen_decode_step", dur_s=dur,
                       step=req.decode_steps, token=tok,
                       batch=len(decoding))
            if len(req.tokens) >= req.max_new:
                self._finish(req, "length")
        smetrics.observe_decode(dur, len(decoding))
        smetrics.observe_batch(len(decoding), top=self.n_slots)
        return True

    # -- delivery -----------------------------------------------------------
    def _emit(self, req: GenRequest, token: int) -> None:
        now = time.monotonic()
        prev = req.last_token_at
        req.emit(token, now)
        if prev:
            smetrics.observe_itl(now - prev)

    def _finish(self, req: GenRequest, reason: str,
                error: Optional[BaseException] = None) -> None:
        s = req.slot
        self.scheduler.evict(req, reason)
        if s is not None:
            self._active[s] = False
            self._lengths[s] = 0
            self._last_token[s] = 0
            self._page_table[s, :] = self.plan.total_pages
        smetrics.inc_gen_finished(reason)
        now = time.monotonic()
        stages = {k: round(v, 6) for k, v in req.stages().items()}
        self._span(req, "gen_finish",
                   dur_s=now - req.submitted_at, reason=reason,
                   tokens_emitted=len(req.tokens),
                   prefill_chunks=req.prefill_chunks,
                   decode_steps=req.decode_steps,
                   ttft_s=round((req.first_token_at - req.submitted_at)
                                if req.first_token_at else 0.0, 6),
                   **{f"stage_{k}": v for k, v in stages.items()
                      if v > 0})
        if req.pending is None:
            return
        if error is not None:
            req.pending.set_error(error)
            return
        ttft = (req.first_token_at - req.submitted_at) \
            if req.first_token_at else 0.0
        req.pending.set_result({
            "tokens": list(req.tokens),
            "tokens_emitted": len(req.tokens),
            "finish_reason": reason,
            "prompt_tokens": req.prompt_len,
            "prefill_chunks": req.prefill_chunks,
            "decode_steps": req.decode_steps,
            "ttft_s": round(ttft, 6),
            "total_s": round(now - req.submitted_at, 6),
            # the generate-plane slice of the request ledger — the
            # replica handler adds its own stages and the router closes
            # the books (docs/OBSERVABILITY.md "Serving request ledger")
            "stages": stages,
        })

    def _span(self, req: GenRequest, name: str, dur_s: float,
              **attrs) -> None:
        if req.trace is None:
            return
        try:
            from horovod_tpu import tracing
            tracing.record_span(
                "serving", name, tracing.child(req.trace, "serving"),
                start=time.time() - max(dur_s, 0.0), dur_s=dur_s,
                request=req.id, **attrs)
        except Exception:
            pass  # tracing must never take down the decode loop


# -- request-level baseline ---------------------------------------------------
def request_level_generate(engine: GenerateEngine,
                           requests: Sequence[Tuple[Any, int]],
                           traced: bool = False,
                           on_token_factory: Optional[Callable] = None
                           ) -> List[GenRequest]:
    """The request-granular discipline the continuous engine replaces,
    driven through the SAME compiled step functions so the comparison
    is apples-to-apples: admit a full gang of ``n_slots`` requests,
    decode until the gang's LONGEST sequence finishes — early
    finishers strand their slot — and only then admit the next gang.

    ``traced``/``on_token_factory`` attach the SAME per-request
    instrumentation the bench puts on the continuous run (a trace
    context per request, an ``on_token_factory(i)`` callback per
    request) so neither side wins on untracked overhead.

    The engine must NOT be running its background loop.  Returns the
    finished :class:`GenRequest` objects in submission order; compare
    ``engine.decode_steps_total`` deltas (and wall time) against a
    continuous run of the same request set."""
    if engine._thread is not None and engine._thread.is_alive():
        raise RuntimeError("baseline needs exclusive manual stepping")

    def _trace():
        if not traced:
            return None
        from horovod_tpu import tracing
        return tracing.new_trace("serving")

    reqs = [GenRequest(f"gang-{i}", prompt, int(max_new), trace=_trace(),
                       on_token=(on_token_factory(i)
                                 if on_token_factory else None))
            for i, (prompt, max_new) in enumerate(requests)]
    for lo in range(0, len(reqs), engine.n_slots):
        gang = reqs[lo:lo + engine.n_slots]
        for r in gang:
            engine.scheduler.add_waiting(r)
        guard = 0
        while any(r.state != DONE for r in gang):
            engine.step_once()
            guard += 1
            if guard > 100_000:
                raise RuntimeError("baseline failed to converge")
    return reqs


# -- demo model ---------------------------------------------------------------
def demo_gen_setup(vocab: int = 64, d_model: int = 32, n_layers: int = 2,
                   n_heads: int = 2, max_seq: int = 64,
                   seed: int = 0) -> Tuple[Any, Any]:
    """A deterministic tiny dense transformer — the generate-mode
    analog of :func:`~horovod_tpu.serving.replica.demo_params`.
    Returns ``(params, cfg)`` sized for the CPU test mesh; fp32 so the
    token-parity contract is exact."""
    import jax.numpy as jnp

    from horovod_tpu.models.transformer import (TransformerConfig,
                                                init_params)
    cfg = TransformerConfig(vocab_size=vocab, d_model=d_model,
                            n_heads=n_heads, n_layers=n_layers,
                            d_ff=2 * d_model, max_seq=max_seq,
                            n_experts=0, dtype=jnp.float32,
                            param_dtype=jnp.float32, remat=False)
    params = init_params(np.random.RandomState(seed), cfg, n_stages=1)
    return params, cfg
