"""Token-level continuous batching (ISSUE 17, docs/SERVING.md
"Continuous batching & KV paging").

Fast battery: the KV page plan (byte-budget precedence, geometry) and
page pool (all-or-nothing allocation, low-first ids, high-water /
fragmentation accounting), the slot scheduler (FIFO page-gated
admission with head-of-line blocking, prefill chunk math, eviction
returning pages at the step boundary, drop_waiting), engine admission
validation, the TOKEN-EXACT parity contract (staggered continuous
decode bit-identical to sequential decode and to the dense
full-recompute oracle), the one-compile-under-churn guard, the
continuous-vs-gang decode-step win, deadline/drain semantics, the
replica ``/generate`` path (roundtrip, duplicate replay, concurrent
duplicates joining one in-flight decode, 400 on oversized prompts),
router ``submit_generate`` exactly-once accounting with
``tokens_emitted`` on the audit line, lifecycle trace-span coverage,
and the per-phase metrics.

Everything here runs in-process on the 8-virtual-device CPU mesh; the
demo model is a tiny fp32 dense transformer so parity is exact.
"""

import json
import threading
import time

import numpy as np
import pytest


@pytest.fixture(autouse=True)
def _chaos_clean():
    from horovod_tpu import chaos
    chaos.uninstall()
    yield
    chaos.uninstall()


def _demo():
    from horovod_tpu.serving.generate import demo_gen_setup
    return demo_gen_setup()


def _engine(**over):
    from horovod_tpu.serving.generate import GenerateEngine
    params, cfg = _demo()
    kw = dict(n_slots=4, page_bytes=4096, prefill_chunk=8)
    kw.update(over)
    return GenerateEngine(params, cfg, **kw)


def _run_to_done(engine, reqs, guard=50_000):
    from horovod_tpu.serving.generate.scheduler import DONE
    n = 0
    while any(r.state != DONE for r in reqs):
        engine.step_once()
        n += 1
        assert n < guard, "engine failed to converge"


# -- page plan + pool ---------------------------------------------------------
def test_page_plan_geometry_and_budget_precedence(monkeypatch):
    from horovod_tpu.serving.generate.pages import (plan_kv_pages,
                                                    resolve_page_bytes)
    # explicit argument wins over everything
    assert resolve_page_bytes(1234) == 1234
    monkeypatch.setenv("HVD_TPU_KV_PAGE_BYTES", "2048")
    assert resolve_page_bytes(None) == 2048
    monkeypatch.delenv("HVD_TPU_KV_PAGE_BYTES")
    # env unset: the bucket-planner fallback, capped to page scale
    from horovod_tpu.serving.generate.pages import DEFAULT_PAGE_BYTES_CAP
    assert 1 <= resolve_page_bytes(None) <= DEFAULT_PAGE_BYTES_CAP
    # geometry: 1 layer x width 8 x fp32 x (K AND V) = 64 B/token;
    # a 256 B budget holds 4 tokens/page, 16-token ctx needs 4 pages
    plan = plan_kv_pages(1, 8, np.float32, slots=3, max_ctx=16,
                         page_bytes=256)
    assert plan.page_tokens == 4
    assert plan.pages_per_slot == 4
    assert plan.total_pages == 12
    assert plan.slot_tokens == 16
    assert plan.token_bytes == 64
    assert plan.page_bytes == 256
    assert plan.pages_for(1) == 1
    assert plan.pages_for(4) == 1
    assert plan.pages_for(5) == 2
    # the plan is cached per fingerprint (pure metadata)
    assert plan_kv_pages(1, 8, np.float32, slots=3, max_ctx=16,
                         page_bytes=256) is plan


def test_page_pool_all_or_nothing_and_accounting():
    from horovod_tpu.serving.generate.pages import PagePool, plan_kv_pages
    plan = plan_kv_pages(1, 8, np.float32, slots=2, max_ctx=16,
                         page_bytes=256)  # 8 pages total
    pool = PagePool(plan)
    a = pool.alloc(3)
    assert a == [0, 1, 2]           # low-first, contiguous when fresh
    b = pool.alloc(4)
    assert pool.in_use == 7
    # all-or-nothing: 2 > 1 free -> None, and NOTHING was taken
    assert pool.alloc(2) is None
    assert pool.in_use == 7
    assert pool.alloc(1) == [7]
    assert pool.high_water == 8
    pool.free(a)
    assert pool.in_use == 5
    # freeing re-sorts so hand-out stays low-first after churn
    assert pool.alloc(1) == [0]
    pool.free(b + [7, 0])
    assert pool.in_use == 0
    assert pool.fragmentation() == 0.0  # one contiguous free run
    assert pool.high_water == 8         # sticky across frees
    stats = pool.stats()
    assert stats["capacity"] == 8 and stats["page_tokens"] == 4


def test_page_pool_fragmentation_reports_shredded_free_set():
    from horovod_tpu.serving.generate.pages import PagePool, plan_kv_pages
    plan = plan_kv_pages(1, 8, np.float32, slots=2, max_ctx=32,
                         page_bytes=64)  # 64 B/token -> 1 tok/page
    pool = PagePool(plan)
    pages = pool.alloc(plan.total_pages)
    # free every OTHER page: the free set is all 1-page runs
    pool.free(pages[::2])
    assert pool.fragmentation() > 0.4


# -- slot scheduler -----------------------------------------------------------
def _sched(n_slots=2, pool_pages=4, page_tokens=4, prefill_chunk=4):
    from horovod_tpu.serving.generate.pages import PagePool, plan_kv_pages
    from horovod_tpu.serving.generate.scheduler import SlotScheduler
    plan = plan_kv_pages(1, 8, np.float32, slots=pool_pages,
                         max_ctx=page_tokens,
                         page_bytes=64 * page_tokens)
    assert plan.total_pages == pool_pages \
        and plan.page_tokens == page_tokens
    pool = PagePool(plan)
    return SlotScheduler(n_slots, pool, prefill_chunk,
                         max_ctx=pool_pages * page_tokens), pool


def test_scheduler_fifo_admission_is_page_gated_head_of_line():
    from horovod_tpu.serving.generate.scheduler import (PREFILL, WAITING,
                                                        GenRequest)
    sched, pool = _sched(n_slots=3, pool_pages=5, page_tokens=4)
    big = GenRequest("big", [1] * 8, 8)       # worst case 16 -> 4 pages
    small = GenRequest("small", [1], 1)       # worst case 2 -> 1 page
    held = pool.alloc(2)                      # only 3 pages remain
    sched.add_waiting(big)
    sched.add_waiting(small)
    # the head can't be covered: the LINE blocks — small is NOT
    # admitted around it (that would starve big forever under load)
    assert sched.admit() == []
    assert big.state == WAITING and small.state == WAITING
    pool.free(held)
    admitted = sched.admit()                  # FIFO order, both fit now
    assert [r.id for r in admitted] == ["big", "small"]
    assert big.state == PREFILL and big.slot == 0 and len(big.pages) == 4
    assert small.slot == 1 and len(small.pages) == 1
    assert sched.occupied() == 2 and sched.busy()


def test_scheduler_slots_gate_admission_too():
    from horovod_tpu.serving.generate.scheduler import GenRequest
    sched, _pool = _sched(n_slots=1, pool_pages=4, page_tokens=4)
    first = GenRequest("first", [1], 1)
    second = GenRequest("second", [1], 1)
    sched.add_waiting(first)
    sched.add_waiting(second)
    assert [r.id for r in sched.admit()] == ["first"]
    assert sched.admit() == []                # no free slot
    sched.evict(first, "length")
    assert [r.id for r in sched.admit()] == ["second"]


def test_scheduler_prefill_chunking_and_eviction_returns_pages():
    from horovod_tpu.serving.generate.scheduler import (DONE,
                                                        GenRequest)
    sched, pool = _sched(n_slots=2, pool_pages=4, page_tokens=4,
                         prefill_chunk=4)
    req = GenRequest("r", list(range(10)), 2)  # 10-token prompt
    sched.add_waiting(req)
    assert sched.admit() == [req]
    assert sched.chunks_for(req.prompt_len) == 3
    chunks = []
    while True:
        c = sched.next_prefill_chunk(req)
        if c is None:
            break
        chunks.append(c)
        req.prefill_pos += c[1]
    assert chunks == [(0, 4), (4, 4), (8, 2)]
    in_use = pool.in_use
    assert in_use == 3                         # ceil(12 / 4)
    sched.evict(req, "length")
    assert req.state == DONE and req.finish_reason == "length"
    assert req.pages == [] and pool.in_use == 0
    assert not sched.busy()


def test_scheduler_drop_waiting_only_removes_queued():
    from horovod_tpu.serving.generate.scheduler import GenRequest
    sched, _pool = _sched()
    req = GenRequest("w", [1], 1)
    sched.add_waiting(req)
    assert sched.waiting_count() == 1
    assert sched.drop_waiting(req) is True
    assert sched.drop_waiting(req) is False    # already gone
    assert sched.waiting_count() == 0


# -- engine: admission validation --------------------------------------------
def test_engine_rejects_what_cannot_fit_a_slot():
    eng = _engine()
    cap = eng.max_request_tokens
    assert cap >= 8
    with pytest.raises(ValueError):            # prompt+max_new too big
        eng.submit("big", [1] * cap, max_new=1)
    with pytest.raises(ValueError):
        eng.submit("empty", [], max_new=4)
    with pytest.raises(ValueError):
        eng.submit("zero", [1, 2], max_new=0)
    # the boundary case fits
    req = eng.submit("edge", [1] * (cap - 1), max_new=1)
    _run_to_done(eng, [req])
    assert len(req.tokens) == 1


def test_engine_max_new_one_finishes_at_prefill():
    """TTFT happens at prefill end: the last chunk's logits ARE the
    first token, so max_new=1 never enters the decode batch — and the
    prefill-emitted token still lands in gen_tokens_total (it is a
    real emission; skipping it under-counts by one per request)."""
    from horovod_tpu.metrics import default_registry
    eng = _engine()
    req = eng.submit("one", [3, 1, 4, 1, 5], max_new=1)
    before = eng.decode_steps_total
    ctr = default_registry().get("hvd_serving_gen_tokens_total")
    tok_before = ctr.value if ctr is not None else 0.0
    _run_to_done(eng, [req])
    assert req.finish_reason == "length"
    assert len(req.tokens) == 1
    assert eng.decode_steps_total == before    # zero decode steps
    ctr = default_registry().get("hvd_serving_gen_tokens_total")
    assert ctr is not None and ctr.value == tok_before + 1.0


# -- the parity contract ------------------------------------------------------
def _reqset(rng, n, max_prompt=20, max_new_hi=8):
    """Mixed-length prompts/budgets off one seeded stream."""
    out = []
    for _ in range(n):
        plen = int(rng.randint(1, max_prompt + 1))
        out.append(([int(t) for t in rng.randint(0, 64, size=plen)],
                    int(rng.randint(1, max_new_hi + 1))))
    return out


def test_token_parity_continuous_vs_sequential_vs_oracle():
    """THE acceptance contract: a staggered continuous run emits
    BIT-IDENTICAL tokens to a one-at-a-time sequential run of the same
    engine, and both match the dense full-recompute oracle — paging,
    slot churn, prefill chunking and co-batching must be numerically
    invisible."""
    from horovod_tpu.models.decode import reference_greedy_decode
    params, cfg = _demo()
    reqset = _reqset(np.random.RandomState(7), 5)

    # continuous: stagger submissions mid-flight
    eng = _engine()
    reqs = []
    for i, (prompt, max_new) in enumerate(reqset[:2]):
        reqs.append(eng.submit(f"c{i}", prompt, max_new))
    for _ in range(3):
        eng.step_once()                        # first two are mid-decode
    for i, (prompt, max_new) in enumerate(reqset[2:], start=2):
        reqs.append(eng.submit(f"c{i}", prompt, max_new))
    _run_to_done(eng, reqs)

    # sequential: same engine geometry, one sequence at a time
    seq_eng = _engine()
    for i, ((prompt, max_new), creq) in enumerate(zip(reqset, reqs)):
        sreq = seq_eng.submit(f"s{i}", prompt, max_new)
        _run_to_done(seq_eng, [sreq])
        assert sreq.tokens == creq.tokens, \
            f"request {i}: continuous diverged from sequential"
        assert creq.finish_reason == "length"
        # The dense oracle recompiles per unique sequence length, so
        # anchor against it on a sample rather than every request.
        if i < 2:
            oracle = reference_greedy_decode(params, cfg, prompt, max_new)
            assert creq.tokens == oracle, \
                f"request {i}: paged decode diverged from the dense oracle"


# -- compile stability --------------------------------------------------------
def test_decode_step_compiles_exactly_once_under_churn():
    """The static-slot contract: sequences joining/leaving every few
    steps is host bookkeeping — the jit'd step functions compile
    EXACTLY once each across heavy churn."""
    from horovod_tpu.profiling import compile_watch
    compile_watch.ensure_installed()
    compile_watch.reset_counts()
    eng = _engine(n_slots=3)
    reqs = [eng.submit(f"n{i}", [1 + i] * (1 + (i * 5) % 17),
                       max_new=1 + i % 6)
            for i in range(12)]
    _run_to_done(eng, reqs)
    counts = {name: entry["compiles"]
              for name, entry in compile_watch.by_function().items()}
    assert counts.get("gen_decode_step", 0) == 1, counts
    assert counts.get("gen_prefill_chunk", 0) == 1, counts


# -- continuous vs request-level gang ----------------------------------------
def test_continuous_needs_strictly_fewer_decode_steps_than_gang():
    """The throughput claim in its deterministic form: over a mixed
    request set, continuous slot reuse spends strictly fewer compiled
    decode steps than the request-level gang discipline (early
    finishers stranding their slot), at identical per-step cost — and
    emits the identical tokens."""
    from horovod_tpu.serving.generate import request_level_generate
    reqset = _reqset(np.random.RandomState(11), 12, max_new_hi=10)

    eng = _engine()
    reqs = [eng.submit(f"c{i}", p, m) for i, (p, m) in enumerate(reqset)]
    _run_to_done(eng, reqs)
    continuous_steps = eng.decode_steps_total

    base = request_level_generate(eng, reqset)
    gang_steps = eng.decode_steps_total - continuous_steps
    assert continuous_steps < gang_steps, \
        (continuous_steps, gang_steps)
    for creq, breq in zip(reqs, base):
        assert creq.tokens == breq.tokens


# -- deadline / drain ---------------------------------------------------------
def test_engine_deadline_expires_mid_generation():
    from horovod_tpu.serving.batcher import DeadlineError
    eng = _engine()
    req = eng.submit("late", [1, 2, 3], max_new=50, deadline_s=0.05)
    eng.step_once()                            # admit + prefill
    time.sleep(0.1)
    eng.step_once()                            # sweep fires
    assert req.finish_reason == "deadline"
    with pytest.raises(DeadlineError):
        req.pending.wait(timeout=1.0)
    # the slot and pages came back
    assert eng.scheduler.occupied() == 0
    assert eng.pool.in_use == 0


def test_engine_drain_refuses_new_and_finishes_admitted():
    from horovod_tpu.serving.batcher import DrainingError
    eng = _engine()
    req = eng.submit("inflight", [5, 6], max_new=3)
    eng.step_once()
    eng.drain()
    with pytest.raises(DrainingError):
        eng.submit("refused", [1], max_new=1)
    assert not eng.drained()                   # still decoding
    _run_to_done(eng, [req])
    assert req.finish_reason == "length"
    assert eng.drained()


# -- metrics ------------------------------------------------------------------
def test_generate_metrics_register_all_documented_names():
    from horovod_tpu.metrics.registry import default_registry
    eng = _engine()
    req = eng.submit("m0", [1] * 10, max_new=3)
    _run_to_done(eng, [req])
    reg = default_registry()
    for name in ("hvd_serving_prefill_seconds_total",
                 "hvd_serving_prefill_chunks_total",
                 "hvd_serving_decode_seconds_total",
                 "hvd_serving_decode_steps_total",
                 "hvd_serving_gen_tokens_total",
                 "hvd_serving_slot_occupancy",
                 "hvd_serving_gen_waiting",
                 "hvd_serving_kv_pages_in_use",
                 "hvd_serving_kv_pages_total",
                 "hvd_serving_kv_page_bytes",
                 "hvd_serving_ttft_seconds",
                 "hvd_serving_itl_seconds"):
        assert reg.get(name) is not None, f"{name} never registered"
    finished = reg.get("hvd_serving_gen_finished_total",
                       labels={"reason": "length"})
    assert finished is not None and finished.value >= 1


# -- replica /generate --------------------------------------------------------
def _post(port, doc, path="/generate", timeout=30.0):
    import urllib.error
    import urllib.request
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", data=json.dumps(doc).encode(),
        method="POST")
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


@pytest.fixture
def gen_replica():
    from horovod_tpu.serving import ReplicaServer
    r = ReplicaServer(replica_id="g0", mode="generate").start()
    yield r
    r.stop()


def test_replica_generate_roundtrip_and_duplicate_replay(gen_replica):
    code, resp = _post(gen_replica.port,
                       {"id": "g1", "prompt": [1, 2, 3], "max_new": 4})
    assert code == 200, resp
    assert resp["tokens_emitted"] == 4 and len(resp["tokens"]) == 4
    assert resp["finish_reason"] == "length"
    assert resp["prompt_tokens"] == 3
    # a duplicate (retry after timeout) replays the CACHED stream —
    # one id never decodes twice, even with a different payload
    code2, resp2 = _post(gen_replica.port,
                         {"id": "g1", "prompt": [9, 9], "max_new": 2})
    assert code2 == 200 and resp2["tokens"] == resp["tokens"]
    # an oversized prompt is a definitive 400, not a retryable fault
    cap = gen_replica.engine.max_request_tokens
    code3, resp3 = _post(gen_replica.port,
                         {"id": "g2", "prompt": [1] * cap,
                          "max_new": 8})
    assert code3 == 400 and "capacity" in resp3["error"]


def test_replica_concurrent_duplicates_join_one_decode(gen_replica):
    """The hedge-dedupe bugfix: duplicates of one id arriving WHILE it
    decodes join the live in-flight request before any second decode
    could start — every copy returns the identical token stream."""
    results = []
    lock = threading.Lock()

    def fire():
        code, resp = _post(gen_replica.port,
                           {"id": "dup", "prompt": [4, 2], "max_new": 6})
        with lock:
            results.append((code, resp))

    threads = [threading.Thread(target=fire) for _ in range(3)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30.0)
    assert len(results) == 3
    tokens = {tuple(resp["tokens"]) for code, resp in results}
    assert all(code == 200 for code, _ in results)
    assert len(tokens) == 1, "duplicates decoded divergent streams"
    # every copy reports the SAME single decode's accounting
    assert {resp["decode_steps"] for _c, resp in results} == {5}
    assert all(resp["tokens_emitted"] == 6 for _c, resp in results)


def test_infer_mode_replica_404s_generate():
    from horovod_tpu.serving import ReplicaServer
    r = ReplicaServer(dim=4, replica_id="i0").start()
    try:
        code, resp = _post(r.port, {"id": "x", "prompt": [1],
                                    "max_new": 1})
        assert code == 404 and "mode=infer" in resp["error"]
    finally:
        r.stop()


# -- router + tracing ---------------------------------------------------------
def test_router_generate_exactly_once_audit_and_trace_spans(gen_replica):
    """One request through router -> replica -> engine: the ``ok``
    audit line carries ``tokens_emitted``, the books close
    exactly-once, and ONE trace id covers the whole lifecycle —
    submit (request/dispatch), admission (gen_admit), every prefill
    chunk (gen_prefill), every decode step (gen_decode_step), and the
    finish (gen_finish)."""
    from horovod_tpu.diagnostics.flight_recorder import recorder
    from horovod_tpu.serving.router import Router
    from horovod_tpu.tracing.reader import spans_from_events
    router = Router([("127.0.0.1", gen_replica.port)], max_inflight=8)
    try:
        resp = router.submit_generate([7, 7, 7], max_new=5,
                                      req_id="traced-1")
    finally:
        router.close()
    assert resp["tokens_emitted"] == 5
    ok = [e for e in router.log.entries if e["outcome"] == "ok"]
    assert len(ok) == 1 and ok[0]["tokens_emitted"] == 5
    acct = router.log.accounting()
    assert acct["unanswered"] == [] and acct["answered_twice"] == []
    trace = ok[0]["trace"]
    assert trace and resp.get("trace") == trace
    spans, _points = spans_from_events(recorder().events(),
                                       trace_id=trace)
    names = [s["name"] for s in spans]
    for expected in ("request", "dispatch", "serve", "gen_admit",
                     "gen_prefill", "gen_finish"):
        assert expected in names, (expected, names)
    assert names.count("gen_decode_step") == 4  # token 1 is prefill's
    finish = [s for s in spans if s["name"] == "gen_finish"][0]
    assert finish["attrs"]["tokens_emitted"] == 5
