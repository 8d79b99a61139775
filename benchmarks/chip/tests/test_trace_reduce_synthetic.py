"""The interval arithmetic of trace_reduce.py on traces written by hand."""

import pytest


def _trace(ops, async_ops=(), host=()):
    import trace_reduce as tr
    # the opcode as the trace gives it; here the name without its number
    ev = lambda rows: [tr.Event(n, s, d, n.split(".")[0])      # noqa: E731
                       for n, s, d in rows]
    return tr.Trace({0: tr.DeviceTrace(ev(ops), ev(async_ops))},
                    ev(host))


def test_union_and_subtract():
    import trace_reduce as tr
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 8), (4, 4)]) == [
        (0, 3), (5, 8)]
    assert tr.length([(0, 3), (5, 8)]) == 6
    assert tr.subtract([(0, 10)], [(2, 3), (5, 7)]) == [
        (0, 2), (3, 5), (7, 10)]
    assert tr.subtract([(0, 4), (6, 9)], [(3, 7)]) == [(0, 3), (7, 9)]
    assert tr.subtract([(0, 4)], []) == [(0, 4)]
    assert tr.subtract([(2, 3)], [(0, 10)]) == []


def test_parse_instruction():
    import trace_reduce as tr
    assert tr.parse_instruction(
        "%fusion.12 = f32[8,4]{1,0:T(8,128)} fusion(f32[8]{0} %p)") == (
            "fusion.12", "fusion", "f32[8,4]")
    assert tr.parse_instruction(
        "%hvd_flash_attention.6 = (bf16[32,2048,128]{2,1,0:T(8,128)(2,1)},"
        " f32[32,1,2048]{2,1,0}) custom-call(bf16[32]{0} %x), "
        "custom_call_target=\"tpu_custom_call\"") == (
            "hvd_flash_attention.6", "custom-call", "(bf16[32,2048,128],..)")
    # a shard_map program names its all-reduce after the JAX primitive
    assert tr.parse_instruction(
        "%psum.7 = bf16[256,2048]{1,0:T(8,128)(2,1)} all-reduce(bf16[256,"
        "2048]{1,0} %fusion.1), channel_id=1") == (
            "psum.7", "all-reduce", "bf16[256,2048]")
    assert tr.parse_instruction(
        "%while.1 = (s32[]{:T(128)}, f32[2]{0}) while((s32[]) %t)")[:2] == (
            "while.1", "while")
    assert tr.parse_instruction("bench.wait") == ("bench.wait", "", "")


def test_busy_idle_sum_union_exposed():
    import trace_reduce as tr
    # a while over its body, a compute op, an asynchronous all-reduce that
    # hides partly behind compute, then a gap and one more op
    t = _trace(
        ops=[("while.1", 0, 100),                  # container: busy only
             ("fusion.1", 0, 40), ("hvd_flash_attention.2", 40, 30),
             ("all-reduce-start.1", 70, 1), ("fusion.2", 71, 19),
             ("all-reduce-done.1", 90, 10),
             ("fusion.3", 150, 50)],
        async_ops=[("all-reduce-start.1", 70, 30)],
        host=[("bench.wait", 0, 120), ("bench.dispatch", 120, 40)])
    assert tr.reduce(t, None, "busy") == 150          # [0,100) + [150,200)
    assert tr.reduce(t, None, "idle") == pytest.approx(25.0)   # 50 of 200
    assert tr.reduce(t, "hvd_flash_attention", "sum") == 30
    assert tr.reduce(t, "^fusion", "sum") == 40 + 19 + 50
    coll = tr.COLLECTIVES.pattern
    assert tr.reduce(t, coll, "union") == 30          # [70,100)
    # compute under it: fusion.2 [71,90); exposed [70,71) + [90,100)
    assert tr.reduce(t, coll, "exposed") == 11
    assert tr.reduce(t, "no_such_op", "sum") is None
    assert tr.busy_and_window_s(t) == (150e-9, 200e-9)
    # the one gap [100,150): bench.wait covers 20 ns, bench.dispatch 30 ns
    assert tr.idle_gaps(t) == [["bench.dispatch", 50e-9]]
    top = dict(tr.top_ops(t, 3))
    assert top["fusion"] == pytest.approx(109e-9)     # grouped, no while
    assert "while" not in top


def test_across_devices():
    import trace_reduce as tr
    one = lambda dur: tr.DeviceTrace(                          # noqa: E731
        [tr.Event("fusion.1", 0, dur, "fusion")], [])
    t = tr.Trace({0: one(10), 1: one(30), 2: one(80)}, [])
    assert tr.reduce(t, None, "busy", "mean") == 40
    assert tr.reduce(t, None, "busy", "median") == 30
    assert tr.reduce(tr.Trace({}, []), None, "busy") is None
