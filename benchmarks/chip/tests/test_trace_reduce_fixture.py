"""trace_reduce.py on a small recorded trace: four steps of a tiny
program on two TPU v5e devices (tools/record_fixture.py, recorded on the
chip in PR 23) with one Pallas kernel (``hvd_flash_attention.1``), one
collective (a shard_map ``psum``: instruction ``psum.7``, opcode
all-reduce) and the host loop's ``bench.*`` annotations.

The expected numbers were added up by hand from the trace's events
(nanoseconds), and the busy unions checked against a one-nanosecond
boolean timeline."""

import os

import pytest

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "fixture_trace.xplane.pb")
COLLECTIVE = "(^| )(all-reduce|reduce-scatter|all-gather" \
             "|collective-permute|all-to-all)"


@pytest.fixture(scope="module")
def trace():
    import trace_reduce
    return trace_reduce.load(FIXTURE)


def test_what_the_trace_holds(trace):
    assert sorted(trace.devices) == [0, 1]
    for dev in trace.devices.values():
        assert len(dev.ops) == 32       # 8 instructions a step
        assert {e.opcode for e in dev.ops} == {
            "copy-start", "copy-done", "copy", "fusion", "custom-call",
            "all-reduce"}
    assert [e.name for e in trace.host_spans] == [
        "bench.input", "bench.dispatch", "bench.wait"] * 4


def test_per_name_sums(trace):
    import trace_reduce as tr
    d0, d1 = trace.devices[0], trace.devices[1]
    # the kernel's four calls on each device
    assert tr.reduce_device(d0, "hvd_flash_attention", "sum") == \
        4183 + 4182 + 4184 + 4182 == 16731
    assert tr.reduce_device(d1, "hvd_flash_attention", "sum") == \
        4184 + 4183 + 4183 + 4184 == 16734
    assert tr.reduce(trace, "hvd_flash_attention", "sum") == 16732.5
    # the collective is found by its opcode: its name is psum.7
    assert tr.reduce_device(d0, COLLECTIVE, "sum") == \
        28246 + 28402 + 28312 + 28242 == 113202
    assert tr.reduce_device(d1, COLLECTIVE, "sum") == \
        26264 + 26270 + 26227 + 26227 == 104988
    assert tr.reduce(trace, "^psum", "sum") == 109095.0
    assert tr.reduce(trace, "no_such_kernel", "sum") is None


def test_busy_union_and_idle_share(trace):
    import trace_reduce as tr
    d0, d1 = trace.devices[0], trace.devices[1]
    # device 0: four steps of 36584 + 36741 + 36656 + 36579 ns from first
    # start to last end, less the 1-4 ns holes between instructions
    assert tr.reduce_device(d0, None, "busy") == 146525
    assert tr.reduce_device(d1, None, "busy") == 138599
    assert tr.window(d0) == (145549172.0, 152788496.0)       # 7239324 ns
    assert tr.reduce_device(d0, None, "idle") == pytest.approx(
        100 * (1 - 146525 / 7239324))
    assert tr.reduce(trace, None, "busy") == 142562.0
    assert tr.reduce(trace, None, "idle") == pytest.approx(98.030464, 1e-7)
    assert tr.busy_and_window_s(trace) == (142562e-9, 7239324e-9)


def test_exposed_collective_time(trace):
    import trace_reduce as tr
    # nothing else runs while the psum does (the one asynchronous copy
    # ends 1.6 us before it starts), so all of it is exposed
    assert tr.reduce(trace, COLLECTIVE, "union") == 109095.0
    assert tr.reduce(trace, COLLECTIVE, "exposed") == 109095.0
    # a pattern that takes the two fusions of a step as well: they do
    # not overlap the psum, so the cover is the three summed
    d0 = trace.devices[0]
    assert tr.reduce_device(d0, "^psum|^fusion", "union") == \
        113202 + (1571 + 1571 + 1570 + 1571) + (1828 + 1828 + 1830 + 1829)


def test_breakdown(trace):
    import trace_reduce as tr
    top = tr.top_ops(trace, 2)
    assert top[0] == ["psum bf16[256,2048]", pytest.approx(109095e-9)]
    assert top[1] == ["hvd_flash_attention (bf16[2,256,128],..)",
                      pytest.approx(16732.5e-9)]
    gaps = tr.idle_gaps(trace, 3)
    # between steps the device waits while the host makes the next batch
    assert [g[0] for g in gaps] == ["bench.input"] * 3
    assert gaps[0][1] == pytest.approx(2802447e-9)
