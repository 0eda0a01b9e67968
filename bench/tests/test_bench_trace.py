"""The reduction from a trace to busy time, idle share, kernel time and
idle gaps by host span, on hand-made events and on a small trace
recorded on the chip (``bench/fixtures/trace_small.json.gz``)."""
import gzip
import json

import numpy as np
import pytest

from bench import trace_reduce as tr
from bench.spec import BENCH_DIR

DEV = [("fusion.1", 0, 10), ("_pd_kernel", 5, 10), ("fusion.2", 30, 5),
       ("copy", 60, 20)]
HOST = [("bench.window", 0, 100), ("bench.step", 0, 40),
        ("bench.submit", 41, 4), ("bench.wait_arrival", 46, 30)]


def test_union_and_busy():
    assert tr.union([(5, 15), (0, 10), (30, 35)], 0, 100) == [(0, 15),
                                                              (30, 35)]
    assert tr.union([(90, 120)], 0, 100) == [(90, 100)]
    assert tr.busy_ns(DEV, 0, 100) == 15 + 5 + 20


def test_gaps():
    assert tr.gaps(DEV, 0, 100) == [(15, 30), (35, 60), (80, 100)]
    assert tr.gaps([], 0, 10) == [(0, 10)]


def test_op_seconds_clips_to_the_window():
    s = tr.op_seconds(DEV, 0, 70)
    assert s["copy"] == pytest.approx(10e-9)
    k = tr.op_seconds(DEV, 0, 100, lambda n: "_pd_kernel" in n)
    assert k == {"_pd_kernel": pytest.approx(10e-9)}


def test_gaps_by_host_charges_the_open_span():
    g = tr.gaps_by_host(DEV, HOST, 0, 100)
    # 15-30 and 35-40 under bench.step; 40-41 none; 41-45 submit; 45-46
    # none; 46-60 wait; 80-100 none
    assert g["bench.step"] == pytest.approx(20e-9)
    assert g["bench.submit"] == pytest.approx(4e-9)
    assert g["bench.wait_arrival"] == pytest.approx(14e-9)
    assert g["none"] == pytest.approx(22e-9)


def test_summary():
    s = tr.summary({"device": DEV, "host": HOST})
    assert s["window_s"] == pytest.approx(100e-9)
    assert s["busy_s"] == pytest.approx(40e-9)
    assert s["device_ops"][0][0] == "copy"
    assert len(s["idle_gaps"]) <= 10


def test_no_window_span_is_an_error():
    with pytest.raises(ValueError):
        tr.window([("bench.step", 0, 1)])


def _chip_trace():
    with gzip.open(BENCH_DIR / "fixtures" / "trace_small.json.gz", "rt") as f:
        d = json.load(f)
    return {"device": [tuple(e) for e in d["device"]],
            "host": [tuple(e) for e in d["host"]]}


def test_chip_trace_busy_matches_a_raster():
    t = _chip_trace()
    lo, hi = tr.window(t["host"])
    # busy time on a 1 us raster, computed without merging intervals
    grid = np.zeros(int((hi - lo) // 1000) + 1, bool)
    for _, s, d in t["device"]:
        a, b = int((max(s, lo) - lo) // 1000), int((min(s + d, hi) - lo)
                                                   // 1000)
        grid[a:b + 1] = True
    assert tr.busy_ns(t["device"], lo, hi) / 1e3 == pytest.approx(
        grid.sum(), rel=0.02)
    s = tr.summary(t)
    idle = sum(v for _, v in s["idle_gaps"])
    assert idle + s["busy_s"] == pytest.approx(s["window_s"], rel=1e-6)
    assert 0 < s["busy_s"] < s["window_s"]


def test_chip_trace_finds_the_paged_kernel():
    from bench.metrics import paged_attn_roofline as m
    t = _chip_trace()
    lo, hi = tr.window(t["host"])
    k = tr.op_seconds(t["device"], lo, hi, m.is_kernel)
    calls = [e for e in t["device"] if m.is_kernel(e[0])]
    # one decode span of 8 steps over 40 layers
    assert len(calls) == 8 * 40
    assert 0 < sum(k.values()) < tr.busy_ns(t["device"], lo, hi) * 1e-9


def test_leaves_drop_the_ops_that_hold_others():
    ev = [("while", 0, 100), ("fusion", 10, 20), ("kernel", 40, 30),
          ("copy", 120, 5)]
    assert [e[0] for e in tr.leaves(ev)] == ["fusion", "kernel", "copy"]
    s = tr.summary({"device": ev, "host": [("bench.window", 0, 200)]})
    assert [n for n, _ in s["device_ops"]] == ["kernel", "fusion", "copy"]
    assert s["busy_s"] == pytest.approx(105e-9)
