"""The program's spans and modules in a trace -> the idle split and the
chunk-assembly metrics, on small traces."""
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from bench import harness  # noqa: E402
from bench import programtrace as pt  # noqa: E402
from bench import tracereduce as tr  # noqa: E402

NEW = ("idle_between_programs.search", "idle_dispatch.search",
       "idle_stack.search", "assembly_device_share.search",
       "stack_fallback_share.search", "idle_recompute.search",
       "idle_bookkeeping.search", "idle_outside_engine.search")
# the readers of GROUPS, which partition the between-program idle
PARTS = ("idle_dispatch.search", "idle_stack.search",
         "idle_recompute.search", "idle_bookkeeping.search",
         "idle_outside_engine.search")


def _trace():
    # modules [0, 40) seg, [60, 70) an eager gather, [80, 95) unit; ops
    # leave idle [10, 30) inside the seg and [85, 90) inside the unit;
    # between programs: [40, 60) under a stack span, [70, 80) under a
    # gather-path stack, [95, 100) under the objective alone; times in ns
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [
                ["jit_engine_seg_0_4(11)", 0, 40], ["jit_gather(12)", 60, 10],
                ["jit_engine_unit_9(3)", 80, 15]]},
            {"name": "XLA Ops", "events": [
                ["f1", 0, 10], ["f2", 30, 10], ["g", 60, 10], ["u", 80, 5],
                ["u2", 90, 5], ["late", 120, 5]]}]},
        {"name": "/host:CPU", "lines": [
            {"name": "python3", "events": [
                ["bench.window", 0, 100],
                ["repro.objective#call=1,rows=60#", 0, 100],
                ["repro.engine.dispatch", 40, 30],
                ["repro.engine.stack#path=stack#", 42, 10],
                ["repro.engine.call", 55, 10],
                ["repro.engine.stack#path=gather#", 72, 3],
                ["repro.engine.stack#path=stack#", 110, 3]]}]}]}


def _ctx(t):
    return {"trace": tr.summarize(t), "program": pt.reduce(t)}


def _read(name, ctx):
    return harness.load_reader(name)(ctx)


def test_modules_and_the_idle_split_on_a_hand_made_plane():
    s = pt.reduce(_trace())
    assert s["module_s"] == pytest.approx({
        "jit_engine_seg_0_4": 40e-9, "jit_gather": 10e-9,
        "jit_engine_unit_9": 15e-9})
    assert s["idle_between_s"] == pytest.approx(35e-9)
    assert s["idle_by_program_span"] == {
        "repro.engine.stack": [pytest.approx(30e-9), 2],
        "repro.objective": [pytest.approx(5e-9), 1],
        pt.INSIDE: [pytest.approx(25e-9), 2]}
    assert dict(pt.breakdown(s)) == {
        "repro.engine.stack x2": pytest.approx(30e-9),
        pt.INSIDE + " x2": pytest.approx(25e-9),
        "repro.objective x1": pytest.approx(5e-9)}
    # the stack span that begins after the window is not counted
    assert s["stack_paths"] == {"stack": 1, "gather": 1}
    assert s["engine_modules"] == 2 and s["program_spans"] == 6


def test_each_new_reader_on_a_hand_made_plane():
    ctx = _ctx(_trace())
    got = {m: _read(m, ctx) for m in NEW}
    assert got == {"idle_between_programs.search": pytest.approx(35.0),
                   "idle_dispatch.search": 0.0,
                   "idle_stack.search": pytest.approx(30.0),
                   "assembly_device_share.search": pytest.approx(25.0),
                   "stack_fallback_share.search": pytest.approx(50.0),
                   "idle_recompute.search": 0.0,
                   "idle_bookkeeping.search": 0.0,
                   "idle_outside_engine.search": pytest.approx(5.0)}
    idle = _read("device_idle_share.search", ctx)
    assert (got["idle_dispatch.search"] + got["idle_stack.search"]
            <= got["idle_between_programs.search"] <= idle)
    assert sum(got[m] for m in PARTS) == pytest.approx(
        got["idle_between_programs.search"])


def test_recompute_takes_the_gaps_nested_in_it():
    """Gaps under a recompute's own dispatch and stack spans, and under
    a recompute nested in it, go to the recompute; the five groups
    still add up to the between-program idle."""
    mods = [["jit_engine_unit_1(1)", 0, 10], ["jit_engine_unit_2(2)", 30, 10],
            ["jit_engine_unit_2(2)", 60, 10], ["jit_engine_unit_3(3)", 85, 5]]
    t = {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": mods},
            {"name": "XLA Ops", "events": [[n, s, d] for n, s, d in mods]}]},
        {"name": "/host:CPU", "lines": [
            {"name": "python3", "events": [
                ["bench.window", 0, 100],
                ["repro.objective#call=1,rows=4#", 0, 100],
                ["repro.engine.plan", 12, 13],
                ["repro.engine.recompute", 45, 35],
                ["repro.engine.dispatch", 46, 12],
                ["repro.engine.stack#path=stack#", 47, 8],
                ["repro.engine.recompute", 56, 1],
                ["repro.engine.dispatch", 75, 4]]}]}]}
    red = pt.reduce(t)
    assert red["idle_by_program_span"] == {
        "repro.engine.plan": [pytest.approx(20e-9), 1],
        "repro.engine.recompute": [pytest.approx(35e-9), 2],
        "repro.objective": [pytest.approx(10e-9), 1]}
    ctx = _ctx(t)
    got = {m: _read(m, ctx) for m in PARTS}
    assert got == {"idle_dispatch.search": 0.0, "idle_stack.search": 0.0,
                   "idle_recompute.search": pytest.approx(35.0),
                   "idle_bookkeeping.search": pytest.approx(20.0),
                   "idle_outside_engine.search": pytest.approx(10.0)}
    assert sum(got.values()) == pytest.approx(
        _read("idle_between_programs.search", ctx))


def test_a_trace_of_a_program_without_spans_or_names_reads_nothing():
    t = _trace()
    t["planes"][1]["lines"][0]["events"] = [["bench.window", 0, 100]]
    t["planes"][0]["lines"][0]["events"] = [["jit_seg(1)", 0, 95]]
    ctx = _ctx(t)
    got = {m: _read(m, ctx) for m in NEW}
    # modules still run, so the between-program idle is read
    assert got.pop("idle_between_programs.search") == pytest.approx(5.0)
    assert got == dict.fromkeys(got)
    t["planes"][0]["lines"].pop(0)
    assert _read("idle_between_programs.search", _ctx(t)) is None


def test_metadata_survives_the_name():
    name = pt.encode("repro.objective", [("call", 3), ("rows", 60)])
    assert name == "repro.objective#call=3,rows=60#"
    assert pt.decode(name) == ("repro.objective",
                               {"call": "3", "rows": "60"})
    assert pt.decode("repro.engine.call") == ("repro.engine.call", {})


def test_readers_find_the_runs_own_trace(tmp_path, monkeypatch):
    """A trace recorded here (host only: no device plane) is loaded once
    for every reader, with the program's spans and their metadata; a
    trace of another window is not read."""
    import jax

    from repro import obs

    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("bench.window"):
            with obs.span("engine.stack", path="gather"):
                jax.numpy.ones(4).block_until_ready()
    monkeypatch.setattr(pt, "TRACE_DIR", str(tmp_path))
    t = pt.load(tr.find_xplane(str(tmp_path)))
    lo, hi = tr.window_bounds(t)
    names = [n for n, *_ in pt.program_spans(t)]
    assert names == ["repro.engine.stack"]
    ctx = {"trace": {"window_s": (hi - lo) * 1e-9, "busy_s": 0.0}}
    assert _read("stack_fallback_share.search", ctx) == 0.0
    assert ctx["program"]["stack_paths"] == {"gather": 1}
    assert _read("idle_between_programs.search", ctx) is None
    assert _read("assembly_device_share.search", ctx) is None
    other = {"trace": {"window_s": 2 * (hi - lo) * 1e-9, "busy_s": 0.0}}
    assert _read("stack_fallback_share.search", other) is None


def _recorded():
    """31 ms of a traced window of the search cell on a TPU v5e, eight
    dispatches of the 18th objective call in the window, both assembly
    paths among them: the device's modules and operations, the
    program's and the benchmark's host spans; operation names cut to
    160 characters, the ``bench.*`` spans cut to the excerpt."""
    with open(os.path.join(HERE, "bench_program_trace_v5e.json")) as f:
        return json.load(f)


def test_recorded_trace_idle_split_adds_up():
    t = _recorded()
    s, red = tr.summarize(t), pt.reduce(t)
    idle = s["window_s"] - s["busy_s"]
    assert 0 < red["idle_between_s"] <= idle
    split = sum(v for _, v in pt.breakdown(red))
    assert split == pytest.approx(idle, rel=1e-2)
    labels = {k.rsplit(" x", 1)[0] for k, _ in pt.breakdown(red)}
    assert pt.INSIDE in labels
    assert "repro.engine.stack" in labels
    assert labels - {pt.INSIDE} <= {k for g in pt.GROUPS.values()
                                    for k in g}
    assert red["engine_modules"] > 0
    assert set(red["stack_paths"]) == {"gather", "stack"}
    ctx = {"trace": s, "program": red}
    got = {m: _read(m, ctx) for m in NEW}
    assert all(v is not None and 0 <= v <= 100 for v in got.values()), got
    assert (got["idle_dispatch.search"] + got["idle_stack.search"]
            <= got["idle_between_programs.search"]
            <= _read("device_idle_share.search", ctx) + 1e-9)
    assert sum(got[m] for m in PARTS) == pytest.approx(
        got["idle_between_programs.search"])
