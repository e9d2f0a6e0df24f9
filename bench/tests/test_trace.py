"""The reduction from a profiler trace to busy time, idle share, kernel
time and exposed collective time, on a small recorded trace and on hand-made
ones."""

import json
import os

import pytest

from bench import trace as T

DATA = os.path.join(os.path.dirname(__file__), "data")


def ms(x):
    return int(x * 1e6)


def hand_trace():
    """Window 0-100 ms; device 0 runs a kernel 10-30, a fusion 25-40 and an
    all-gather 50-70 of which 60-65 overlaps a fusion; device 1 is busy
    0-50.  Host spans: call 0-45, pack 45-100."""
    return dict(
        devices={
            "/device:TPU:0": [["while.4", ms(10), ms(30)],
                              ["_seg_fused_kernel", ms(10), ms(20)],
                              ["fusion.1", ms(25), ms(15)],
                              ["all-gather.3", ms(50), ms(20)],
                              ["fusion.2", ms(60), ms(5)]],
            "/device:TPU:1": [["fusion.9", ms(-10), ms(60)]],
        },
        host=[["bench.window", ms(0), ms(100)], ["bench.call", ms(0), ms(45)],
              ["bench.pack", ms(45), ms(55)]])


def test_busy_union_idle_share_and_kernel_time():
    s = T.summarize(hand_trace(), chips=2)
    assert s["window_s"] == pytest.approx(0.1)
    # device 0: 10-40 and 50-70 busy = 50 ms; device 1: 0-50 = 50 ms
    assert s["busy_s"] == pytest.approx(0.05)
    assert T.idle_pct(s) == pytest.approx(50.0)
    # the kernel ran 20 ms of device 0's 50 busy ms; ops are averaged
    # over the two devices
    assert s["ops"]["_seg_fused_kernel"] == pytest.approx(0.01)
    assert T.op_share(s, ("seg_fused",)) == pytest.approx(0.2)
    assert T.op_share(s, ("no_such_kernel",)) is None


def test_exposed_collective_time():
    s = T.summarize(hand_trace(), chips=2)
    # all-gather 50-70 minus the fusion 60-65: 15 ms on device 0, none on
    # device 1, averaged over two devices
    assert s["collective_s"] == pytest.approx(0.01)
    assert s["exposed_s"] == pytest.approx(0.0075)


def test_idle_gaps_are_labelled_by_host_spans():
    s = T.summarize(hand_trace(), chips=1)
    gaps = sorted(s["gaps"], key=lambda g: -g[1])
    # device 0 idles 0-10 (call), 40-50 (pack: midpoint 45), 70-100 (pack)
    assert gaps[0] == ["pack", pytest.approx(0.03)]
    assert sorted(g[0] for g in gaps) == ["call", "pack", "pack"]
    b = T.breakdown(s)
    assert b["idle_gaps"][0][0] == "pack" and len(b["device_ops"]) == 4


def test_control_flow_ops_count_as_busy_not_as_op_time():
    s = T.summarize(hand_trace(), chips=1)
    assert "while.4" not in s["ops"] and s["calls"] == 1
    assert T.op_name("%fusion.12 = s32[4]{0} fusion(s32[4]{0} %p)") == \
        "fusion.12"


def test_chips_beyond_the_cell_are_left_out():
    s = T.summarize(hand_trace(), chips=1)
    assert s["devices"] == 1 and s["busy_s"] == pytest.approx(0.05)


def test_no_device_reads_nothing():
    tr = dict(devices={}, host=[["bench.window", 0, ms(10)]])
    assert T.idle_pct(T.summarize(tr, chips=1)) is None


def test_interval_arithmetic():
    assert T.merge([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert T.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert T.subtract([(0, 4), (6, 9)], [(3, 7)]) == [(0, 3), (7, 9)]


RECORDED = sorted(f for f in os.listdir(DATA) if f.startswith("trace_"))


@pytest.mark.parametrize("name", RECORDED)
def test_recorded_trace(name):
    """A trace recorded on a v5e (the start of a traced window): the
    reduction's numbers against a plain recount of the same events."""
    tr = json.load(open(os.path.join(DATA, name)))
    chips = len(tr["devices"])
    s = T.summarize(tr, chips)
    (w0, w1), = [(a, a + d) for n, a, d in tr["host"] if n == "bench.window"]
    assert s["window_s"] == pytest.approx((w1 - w0) / 1e9)
    busy_all = []
    for dev in sorted(tr["devices"], key=T._device_order):
        # recount chip by chip: the busy union by a sweep over sorted
        # endpoints, clipped to the window
        ivs = [(max(a, w0), min(a + d, w1)) for _, a, d in tr["devices"][dev]
               if a < w1 and a + d > w0]
        marks = sorted([(a, 1) for a, _ in ivs] + [(b, -1) for _, b in ivs])
        busy, depth, last = 0, 0, None
        for t, step in marks:
            if depth > 0:
                busy += t - last
            depth, last = depth + step, t
        busy_all.append(busy)
    assert s["busy_s"] == pytest.approx(sum(busy_all) / len(busy_all) / 1e9)
    idle0 = sum(d for _, d in s["gaps"]) * 1e9
    assert busy_all[0] + idle0 == pytest.approx(w1 - w0, rel=1e-9)
    assert 0 <= T.idle_pct(s) <= 100
    assert s["exposed_s"] <= s["collective_s"] + 1e-12
    leaf = sum(v for k, v in s["ops"].items())
    assert leaf > 0 and not any(T.is_container(k) for k in s["ops"])
