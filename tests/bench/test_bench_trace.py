"""The trace -> metric reduction, on small traces."""
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from bench import tracereduce as tr  # noqa: E402


def _trace():
    # device ops overlap at [5, 10); host spans nest window > objective >
    # plan; times in ns
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [["jit_seg", 0, 40]]},
            {"name": "XLA Ops", "events": [
                ["fusion.1", 0, 10], ["bitflip_kernel", 5, 10],
                ["fusion.2", 30, 10], ["fusion.1", 60, 10]]}]},
        {"name": "/host:CPU", "lines": [
            {"name": "python", "events": [
                ["bench.window", 0, 50], ["bench.search.objective", 0, 44],
                ["bench.search.plan", 20, 8], ["other", 0, 50]]}]}]}


def test_busy_is_the_union_of_device_ops_inside_the_window():
    s = tr.summarize(_trace(), {"bitflip": "bitflip"})
    assert s["window_s"] == pytest.approx(50e-9)
    # [0, 15) and [30, 40); the op at 60 lies outside the window
    assert s["busy_s"] == pytest.approx(25e-9)
    assert s["devices"] == 1


def test_kernel_time_by_stable_name():
    s = tr.summarize(_trace(), {"bitflip": "bitflip", "none": "absent"})
    assert s["kernel_s"]["bitflip"] == pytest.approx(10e-9)
    assert s["kernel_s"]["none"] == 0.0
    ops = dict(s["breakdown"]["device_ops"])
    assert ops["fusion.1"] == pytest.approx(10e-9)
    assert ops["fusion.2"] == pytest.approx(10e-9)


def test_idle_gaps_go_to_the_innermost_host_span():
    s = tr.summarize(_trace())
    gaps = dict(s["breakdown"]["idle_gaps"])
    # gap [15, 30) has its midpoint inside the plan span; gap [40, 50)
    # only inside the window
    assert gaps == {"bench.search.plan x1": pytest.approx(15e-9),
                    "bench.window x1": pytest.approx(10e-9)}


def test_no_window_span_is_an_error():
    t = _trace()
    t["planes"][1]["lines"][0]["events"] = [["other", 0, 50]]
    with pytest.raises(ValueError):
        tr.summarize(t)


def test_no_device_op_in_the_window_is_an_error():
    t = _trace()
    t["planes"][0]["lines"][1]["events"] = [["fusion.1", 60, 10]]
    with pytest.raises(ValueError):
        tr.summarize(t)


def _recorded():
    """The first 40 ms of a traced window of the search cell on a TPU v5e
    (device operations and the benchmark's host spans; operation names
    cut to 160 characters, the window and objective spans cut to the
    40 ms)."""
    with open(os.path.join(HERE, "bench_trace_v5e.json")) as f:
        return json.load(f)


def _raster(intervals, lo, hi, step=100.0):
    import numpy as np

    n = int((hi - lo) // step) + 1
    on = np.zeros(n, bool)
    for a, b in intervals:
        on[int((max(a, lo) - lo) // step):int((min(b, hi) - lo) // step)] = 1
    return on


def test_recorded_trace_busy_matches_a_raster():
    import numpy as np

    t = _recorded()
    s = tr.summarize(t, {"custom": r"custom-call"})
    lo, hi = tr.window_bounds(t)
    dev = tr.device_planes(t)[0]
    ops = [(e[1], e[1] + e[2]) for ln in dev["lines"]
           if ln["name"] == "XLA Ops" for e in ln["events"]]
    on = _raster(ops, lo, hi)
    assert s["window_s"] == pytest.approx((hi - lo) * 1e-9)
    assert s["busy_s"] == pytest.approx(on.sum() * 100e-9, rel=2e-3)
    assert 0 < s["busy_s"] < s["window_s"]
    # idle time, attributed by span, adds up to the window minus busy
    idle = sum(v for _, v in s["breakdown"]["idle_gaps"])
    assert idle == pytest.approx(s["window_s"] - s["busy_s"], rel=1e-9)
    labels = [k for k, _ in s["breakdown"]["idle_gaps"]]
    assert all(k.startswith("bench.") for k in labels)
    assert any(k.startswith("bench.search.stack") for k in labels)
    # top operations are sorted and are real op names of the trace
    tops = s["breakdown"]["device_ops"]
    assert [v for _, v in tops] == sorted((v for _, v in tops),
                                          reverse=True)
    names = {e[0] for ln in dev["lines"] for e in ln["events"]}
    assert {k for k, _ in tops} <= names


def test_recorded_trace_kernel_time_counts_the_kernel_not_its_consumers():
    """The recorded window holds ``bitflip`` kernels and the fusions that
    dequantize their output, whose text names the kernel as an operand;
    only the kernel's own events count."""
    from bench.drivers.search import KERNELS

    t = _recorded()
    lo, hi = tr.window_bounds(t)
    own = consumers = 0.0
    for ln in tr.device_planes(t)[0]["lines"]:
        if ln["name"] != "XLA Ops":
            continue
        for name, s, d in ln["events"]:
            if "bitflip" not in name or s + d <= lo or s >= hi:
                continue
            if name.split(" = ")[0].find("bitflip") >= 0:
                own += min(s + d, hi) - max(s, lo)
            else:
                consumers += min(s + d, hi) - max(s, lo)
    assert own > 0 and consumers > 0
    got = tr.summarize(t, KERNELS)["kernel_s"]["bitflip"]
    assert got == pytest.approx(own * 1e-9)
