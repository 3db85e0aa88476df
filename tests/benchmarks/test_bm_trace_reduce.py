"""The reduction from trace events to numbers, on a small synthetic list."""

import pytest

from benchmarks import trace_reduce as R

FUSION = "%fusion.3 = bf16[4,8]{1,0:T(8,128)(2,1)} fusion(bf16[4,8]{1,0} %p0), kind=kLoop"
KERNEL = "%closed_call.2 = (bf16[8,128]{1,0:T(8,128)(2,1)S(1)}, f32[8,1]{1,0}) custom-call(bf16[8,128]{1,0} %x), custom_call_target=\"tpu_custom_call\""
WHILE = "%while.17 = (s32[]{:T(128)}, bf16[4,8]{1,0}) while((s32[], bf16[4,8]) %t), condition=%c, body=%b"
ALLREDUCE = "%all-reduce.1 = bf16[4,8]{1,0} all-reduce(bf16[4,8]{1,0} %g), replica_groups={}"

# device 0: a while [0,4) wrapping fusion [0,1) and kernel [1,3); idle [4,6);
# all-reduce [6,8) with a fusion [7,9) overlapping its second half
DEV0 = [(WHILE, 0.0, 4.0), (FUSION, 0.0, 1.0), (KERNEL, 1.0, 2.0),
        (ALLREDUCE, 6.0, 2.0), (FUSION, 7.0, 2.0)]
DEV1 = [(FUSION, 0.0, 5.0)]
SPANS = [("bm:feed", 3.9, 1.5), ("bm:fetch", 5.0, 1.2)]


def test_short_names_and_wrappers():
    assert R.short_name(FUSION) == "%fusion.3 fusion"
    assert R.short_name(KERNEL) == "%closed_call.2 custom-call"
    assert R.short_name(WHILE) == "%while.17 while"
    assert R.is_wrapper(WHILE) and not R.is_wrapper(KERNEL)
    assert R.short_name("plain") == "plain"


def test_busy_is_the_union_of_intervals():
    assert R.busy_seconds(DEV0) == pytest.approx(4.0 + 3.0)
    assert R.union([(0, 1), (0.5, 2), (3, 4), (4, 4)]) == [(0, 2), (3, 4)]
    assert R.subtract([(0, 10)], [(2, 3), (5, 7)]) == [(0, 2), (3, 5), (7, 10)]


def test_op_seconds_and_top_ops_leave_wrappers_out():
    assert R.op_seconds(DEV0, r" custom-call\(") == pytest.approx(2.0)
    assert R.op_count(DEV0, r" custom-call\(") == 1
    assert R.op_seconds(DEV0, "while") == 0.0
    top = R.top_ops(DEV0)
    assert top[0] == ["%fusion.3 fusion", pytest.approx(3.0)]
    assert "%while.17 while" not in [k for k, _ in top]


def test_idle_gaps_are_named_by_the_host_span_open_then():
    gaps = R.idle_gaps(DEV0, SPANS, 0.0, 10.0)
    # idle [4,6): feed overlaps 1.4, fetch 1.0 -> feed; idle [9,10): no span
    assert gaps == [["bm:feed", pytest.approx(2.0)], ["(no span)", pytest.approx(1.0)]]


def test_exposed_collective_time():
    # all-reduce [6,8); compute covers [7,9): one second is exposed
    assert R.exposed_collective_seconds(DEV0) == pytest.approx(1.0)
    assert R.exposed_collective_seconds(DEV1) == 0.0


def test_reduce_trace_clips_to_the_window_and_averages_devices():
    out = R.reduce_trace({"/device:TPU:0": DEV0, "/device:TPU:1": DEV1},
                         SPANS, 0.5, 8.5)
    # dev0 busy in [0.5,8.5]: [0.5,4) + [6,8.5) = 6.0; dev1: [0.5,5) = 4.5
    assert out["busy_s"] == pytest.approx((6.0 + 4.5) / 2)
    assert out["window_s"] == pytest.approx(8.0)
    assert len(out["device_ops"]) <= 10 and len(out["idle_gaps"]) <= 10
    assert out["idle_gaps"][0][0] == "bm:feed"
    with pytest.raises(ValueError):
        R.reduce_trace({}, [], 0.0, 1.0)


def test_the_decode_program_is_the_most_frequent_module():
    mods = [("jit_step(1)", 0.0, 1.0), ("jit_step(2)", 1.0, 0.1),
            ("jit_step(2)", 2.0, 0.1), ("jit_step(2)", 3.0, 0.1)]
    assert R.most_frequent_module(mods) == "jit_step(2)"
    assert R.most_frequent_module([]) is None
    ev = [(KERNEL, 0.5, 0.2), (KERNEL, 1.05, 0.01), (KERNEL, 3.02, 0.01)]
    inside = R.within(ev, R.union((s, s + d) for n, s, d in mods if n == "jit_step(2)"))
    assert [e[1] for e in inside] == [1.05, 3.02]
