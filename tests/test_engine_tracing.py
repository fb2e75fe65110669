"""Spans and executable names of the staged engine.

The contracts under test (``repro.obs``, ``core/eval_engine`` and
``core/objectives``):

  * a fused staged evaluation under ``jax.profiler.trace`` records the
    eight ``repro.*`` spans, ``repro.engine.stack`` and
    ``repro.engine.call`` nested in ``repro.engine.dispatch``, and that
    nested in ``repro.objective``;
  * every eviction recompute and every chunk assembly is spanned, the
    assembly with the path it took (``gather`` or ``stack``), and the
    unit runs ``stats()`` counts are those of the planned segments and
    the recomputes;
  * every executable the engine dispatches carries a stable name,
    ``engine_seg_<start>_<length>`` or ``engine_unit_<i>``, on every
    backend;
  * tracing changes no ΔAcc bit.
"""
import glob

import numpy as np
import pytest

SCALE = np.array([1.0, 0.1])
SPANS = ("objective", "engine.plan", "engine.dispatch", "engine.stack",
         "engine.call", "engine.store", "engine.recompute", "engine.gather")


def _setup():
    import jax
    import jax.numpy as jnp
    from repro.models.cnn import CNN_MODELS

    model = CNN_MODELS["alexnet"]
    rng = np.random.default_rng(0)
    params = model.init(jax.random.PRNGKey(2), num_classes=8, width=0.125,
                        img=8)
    x = jnp.asarray(rng.normal(size=(2, 8, 8, 3)), jnp.float32)
    y = jnp.asarray(rng.integers(0, 8, size=(2,)))
    return model, params, x, y


def _objective(backend="generic", **kw):
    from repro.core import (POD_TIERS_4, CostModel, FaultSpec,
                            InferenceAccuracyEvaluator, ObjectiveFn)
    from repro.models.cnn import quantize_unit_params

    model, params, x, y = _setup()
    extra = {"quant_params": quantize_unit_params(params)} \
        if backend == "pallas" else {}
    ev = InferenceAccuracyEvaluator(
        model.apply, params, x, y,
        FaultSpec(weight_fault_rate=0.2, act_fault_rate=0.2), SCALE,
        step_fn=model.step, eval_strategy="staged", fuse_chains=True,
        devices=1, fault_backend=backend, **extra, **kw)
    infos = model.layer_infos(num_classes=8, width=0.125, img=8)
    return ObjectiveFn(CostModel(infos, POD_TIERS_4[:2]), ev), ev, model


def _generations(n_units, gens=4, pop=8):
    """A converging population sequence: survivors plus point mutants."""
    rng = np.random.default_rng(3)
    P = rng.integers(0, 2, size=(pop, n_units))
    out = [P.copy()]
    for _ in range(gens - 1):
        P = P[rng.integers(0, pop, size=pop)].copy()
        where = rng.integers(0, n_units, size=pop)
        P[np.arange(pop), where] = rng.integers(0, 2, size=pop)
        out.append(P.copy())
    return out


# a store that holds about one batch: later waves recompute evicted parents
TIGHT = dict(eval_batch_size=2, max_store_bytes=4096)


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One tight-store search under the profiler, with the engine's chunk
    assemblies and unit runs tallied from outside (as plain wrappers)."""
    import jax
    from jax.profiler import ProfileData

    obj, ev, model = _objective(**TIGHT)
    eng = ev._ensure_prefix_engine()
    tally = {"assemblies": 0,
             "runs": np.zeros(model.n_units, np.int64)}
    plan, recompute, stack = (eng._plan_segments, eng._recompute,
                              eng._stack_chunk)

    def planned(rows):
        segs = plan(rows)
        for start, length, _, _ in segs:
            tally["runs"][start:start + length] += 1
        return segs

    def recomputed(prefix):
        tally["runs"][len(prefix) - 1] += 1
        return recompute(prefix)

    def stacked(parents, padded):
        tally["assemblies"] += 1
        return stack(parents, padded)

    eng._plan_segments, eng._recompute = planned, recomputed
    eng._stack_chunk = stacked
    d = tmp_path_factory.mktemp("trace")
    with jax.profiler.trace(str(d)):
        out = [obj(P) for P in _generations(model.n_units)]
    path, = glob.glob(str(d / "**" / "*.xplane.pb"), recursive=True)
    spans = [(ev_.name, ev_.start_ns, ev_.start_ns + ev_.duration_ns,
              dict(ev_.stats))
             for pl in ProfileData.from_file(path).planes
             for ln in pl.lines for ev_ in ln.events
             if ev_.name.startswith("repro.")]
    return {"spans": spans, "stats": eng.stats(), "tally": tally,
            "out": out}


def _within(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def test_profiled_run_records_every_span_nested(traced):
    spans = traced["spans"]
    assert {s[0] for s in spans} == {"repro." + n for n in SPANS}
    by = lambda n: [s for s in spans if s[0] == "repro." + n]
    objective, dispatch = by("objective"), by("engine.dispatch")
    for s in by("engine.stack") + by("engine.call"):
        assert any(_within(s, d) for d in dispatch), s
    for d in dispatch:
        assert any(_within(d, o) for o in objective), d
    calls = [s[3] for s in objective]
    assert [c["call"] for c in calls] == [1, 2, 3, 4]
    assert all(c["rows"] == 8 for c in calls)
    paths = {s[3]["path"] for s in by("engine.stack")}
    assert paths <= {"gather", "stack"}


def test_every_recompute_is_spanned_and_its_unit_run_counted(traced):
    st, spans = traced["stats"], traced["spans"]
    assert st["recomputes"] > 0 and st["evictions"] > 0
    assert st["unit_runs"] == traced["tally"]["runs"].sum()
    recomputes = [s for s in spans if s[0] == "repro.engine.recompute"]
    assert len(recomputes) == st["recomputes"]


def test_stack_spans_name_the_path_of_every_assembly(traced):
    paths = [s[3]["path"] for s in traced["spans"]
             if s[0] == "repro.engine.stack"]
    assert len(paths) == traced["tally"]["assemblies"]
    assert set(paths) == {"gather", "stack"}


def test_tracing_changes_no_delta_acc_bit(traced):
    obj, _, model = _objective(**TIGHT)
    for P, want in zip(_generations(model.n_units), traced["out"]):
        np.testing.assert_array_equal(obj(P), want)


@pytest.mark.parametrize("backend", ["generic", "pallas"])
def test_executables_carry_stable_names(backend):
    import jax
    import jax.numpy as jnp

    _, ev, model = _objective(backend)
    x = jnp.zeros((2, 8, 8, 3), jnp.float32)
    step = lambda i, a: model.step(i, ev._params[i], a)
    acts = jax.eval_shape(lambda a: step(1, step(0, a)), x)
    acts = jnp.zeros((2,) + acts.shape, acts.dtype)
    genes = jnp.zeros((2, 2), jnp.int32)
    seg = jax.jit(ev._segment_dispatch(2, 2)).lower(acts, genes)
    assert "engine_seg_2_2" in seg.as_text()
    unit = jax.jit(ev._segment_dispatch(2, 1)).lower(acts, genes)
    assert "engine_unit_2" in unit.as_text()
    first = jax.jit(ev._segment_dispatch(0, 2)).lower(None, genes)
    assert "engine_seg_0_2" in first.as_text()
