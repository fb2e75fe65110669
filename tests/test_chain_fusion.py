"""Chain-fused staged dispatch: bit-exactness + chain-detection rules.

The contracts under test (see core/eval_engine.PrefixEvalEngine "Chain
fusion" and DESIGN.md "Chain fusion"):

  * staged-fused ΔAcc == staged-unfused == full-forward, BIT for bit,
    across a CNN, a decoder-only LM (olmo-1b, deepened to 6 units so
    chains actually form) and the seamless enc-dec, for devices 1 and
    4 (the 4-device leg reuses the
    ``xla_force_host_platform_device_count=4`` subprocess harness);
  * fusion never crosses a branch node (a trie node with >= 2
    children), never crosses a shared-field keying depth, and the
    final unit always dispatches as its own segment;
  * chains split on the buddy-aligned power-of-two span ladder
    (``start % length == 0``), bounding the compile-cache keys;
  * dispatch outputs stay stacked (:class:`StackedView`) — parents are
    gathered per chunk, not sliced per row — and ``stats()`` counts
    the saved slice dispatches;
  * the ``fuse_chains`` knob threads through the evaluator,
    ``make_lm_accuracy_evaluator`` and ``ObjectiveFn``.
"""
import dataclasses
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core.eval_engine import PrefixEvalEngine, StackedView

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SRC = os.path.join(_ROOT, "src")

L, D, K = 8, 3, 4       # units, devices, activation width (synthetic)


# --------------------------------------------------------------------------
# synthetic exact-integer unit stack (the test_prefix_store_props idiom)
# --------------------------------------------------------------------------
def _unit_fns():
    import jax.numpy as jnp

    def depth0(acts, devs):
        return devs[:, None].astype(jnp.float32) \
            + jnp.arange(K, dtype=jnp.float32)

    fns = [depth0]
    for i in range(1, L - 1):
        fns.append(lambda acts, devs, i=i:
                   acts * (i + 2) + devs[:, None].astype(acts.dtype))
    fns.append(lambda acts, devs:
               (acts * (L + 1) + devs[:, None].astype(acts.dtype))
               .sum(axis=1))
    return fns


def _ref_row(row) -> float:
    act = row[0] + np.arange(K, dtype=np.float64)
    for i in range(1, L - 1):
        act = act * (i + 2) + row[i]
    return float((act * (L + 1) + row[-1]).sum())


def _segment_factory(fns, calls):
    """A ``segment_fn`` composing the synthetic units, recording every
    built (start, length) pair."""
    def segment_fn(start, length):
        calls.append((start, length))

        def run(acts, genes):
            x = acts
            for k in range(length):
                x = fns[start + k](x, genes[:, k])
            return x

        return run
    return segment_fn


def _engine(**kw):
    calls = []
    eng = PrefixEvalEngine(_unit_fns(), L,
                           segment_fn=_segment_factory(_unit_fns(), calls),
                           **kw)
    return eng, calls


def _trie(rows):
    kids = {(): set()}
    for r in rows:
        p = ()
        for g in r:
            kids.setdefault(p, set()).add(g)
            p += (g,)
            kids.setdefault(p, set())
    return kids


# --------------------------------------------------------------------------
# chain detection on hand-built prefix trees
# --------------------------------------------------------------------------
def test_chains_never_cross_branch_nodes():
    eng, _ = _engine()
    A = (0,) * L
    B = (0, 0, 0, 1, 1, 1, 1, 1)
    C = (0, 0, 0, 1, 1, 1, 1, 0)
    rows = [A, B, C]
    segments = eng._plan_segments(rows)
    kids = _trie(rows)

    for start, length, parent, genes in segments:
        assert length & (length - 1) == 0, "lengths are powers of two"
        if start > 0:
            assert start % length == 0, "buddy alignment"
        # interior nodes of a fused segment must be single-child:
        # branch nodes are never fused across
        for k in range(1, length):
            node = parent + genes[:k]
            assert len(kids[node]) == 1, (node, start, length)
    # the branch node (0,0,0) ends its chain exactly there
    assert any(s[2] + s[3] == (0, 0, 0) for s in segments)
    # the final unit is always its own segment (pre-logits checkpoint)
    finals = [s for s in segments if s[0] == L - 1]
    assert all(s[1] == 1 for s in finals)
    assert {s[2] + s[3] for s in finals} == set(rows)
    # coverage: every needed prefix is produced by exactly one segment
    produced = []
    for start, length, parent, genes in segments:
        produced += [parent + genes[:k] for k in range(1, length + 1)]
    want = {r[:d] for r in rows for d in range(1, L + 1)}
    assert len(produced) == len(set(produced)) == len(want)
    assert set(produced) == want


def test_chains_cut_at_shared_field_depths():
    eng, _ = _engine(shared_fields={"mem": 3})
    rows = [(0,) * L, (0, 0, 0, 0, 0, 1, 1, 1)]
    segments = eng._plan_segments(rows)
    # no segment spans the keying depth 3 -> 4 boundary, and one ends
    # exactly at it (the keyed activation must be stored for PrefixRef
    # resolution)
    assert all(s[0] + s[1] <= 4 for s in segments if s[0] <= 3)
    assert any(s[0] + s[1] == 4 for s in segments)


def test_plan_resumes_from_deepest_stored_prefix():
    eng, _ = _engine()
    A = (0,) * L
    eng.store.put(A[:4], np.zeros(K, np.float32))
    segments = eng._plan_segments([A])
    assert eng.prefix_hits == 1
    # nothing re-plans units 0..3; the chain starts at unit 4
    assert min(s[0] for s in segments) == 4
    covered = [s[2] + s[3][:k] for s in segments
               for k in range(1, s[1] + 1)]
    assert len(covered) == len(set(covered))
    assert set(covered) == {A[:d] for d in range(5, L + 1)}


def test_ladder_is_buddy_aligned_from_any_start():
    eng, _ = _engine()
    # resume mid-chain at depth 1: units 1..6 must decompose into
    # buddy blocks (1,1), (2,2), (4,2), (6,1) — never a block crossing
    # its own alignment
    A = (0,) * L
    eng.store.put(A[:1], np.zeros(K, np.float32))
    segments = eng._plan_segments([A])
    chain = sorted((s[0], s[1]) for s in segments if s[0] < L - 1)
    assert chain == [(1, 1), (2, 2), (4, 2), (6, 1)]


# --------------------------------------------------------------------------
# fused == unfused on the synthetic stack + dispatch economy
# --------------------------------------------------------------------------
def test_fused_matches_unfused_synthetic():
    rng = np.random.default_rng(7)
    eng_f, _ = _engine()
    eng_uf = PrefixEvalEngine(_unit_fns(), L)
    pool = rng.integers(0, D, size=(3, L))
    for _ in range(4):
        P = pool[rng.integers(0, 3, size=6)].copy()
        cuts = rng.integers(0, L + 1, size=6)
        for r in range(6):
            P[r, cuts[r]:] = rng.integers(0, D, size=L - cuts[r])
        want = np.array([_ref_row(r) for r in P])
        np.testing.assert_array_equal(eng_f.evaluate(P), want)
        np.testing.assert_array_equal(eng_uf.evaluate(P), want)
    assert eng_f.unit_runs <= eng_uf.unit_runs + eng_f.recomputes \
        or eng_f.unit_runs <= eng_f.rows_evaluated * L


def test_fused_collapses_converged_population_dispatches():
    """The target regime: a converged population (one long shared
    prefix run, branching only at the tail) must dispatch at least 2x
    fewer times fused than unfused."""
    eng_f, calls = _engine()
    eng_uf = PrefixEvalEngine(_unit_fns(), L)
    P = np.ones((6, L), np.int64)
    P[:, -1] = np.arange(6) % D          # branch only at the last gene
    want = [_ref_row(r) for r in P]
    np.testing.assert_array_equal(eng_f.evaluate(P), want)
    np.testing.assert_array_equal(eng_uf.evaluate(P), want)
    assert eng_f.unit_runs == eng_uf.unit_runs
    assert eng_f.dispatches * 2 <= eng_uf.dispatches
    # ladder bound on the fused dispatch count
    bound = eng_f.branch_nodes + eng_f.chains * max(
        1, (max(eng_f.max_chain, 1) - 1).bit_length())
    assert eng_f.dispatches <= bound
    # compile-key economy: (start, length) pairs, <= ~2L of them
    assert len(set(calls)) == len(calls) <= 2 * L


def test_fused_eviction_recomputes_bitwise():
    eng, _ = _engine(max_store_bytes=1)
    rng = np.random.default_rng(9)
    for _ in range(3):
        P = rng.integers(0, D, size=(5, L))
        np.testing.assert_array_equal(eng.evaluate(P),
                                      [_ref_row(r) for r in P])
    assert eng.store.evictions > 0


# --------------------------------------------------------------------------
# stacked views: no per-row unstack dispatches
# --------------------------------------------------------------------------
def test_store_holds_stacked_views_and_counts_saved_slices():
    eng, _ = _engine()
    P = np.ones((4, L), np.int64)
    P[:, -1] = np.arange(4) % D
    eng.evaluate(P)
    st = eng.stats()
    assert st["views_stored"] > 0
    # the shared chain's checkpoints are stored as views, consumed by
    # whole-chunk gathers — per-row slices only where chunks mix
    assert any(isinstance(v, StackedView) for v in eng.store._store.values())
    assert st["unstack_slices_saved"] >= 0
    assert st["unstack_slices_saved"] == \
        st["views_stored"] - st["slices_materialized"]
    # a view materialises correctly when sliced out
    key, view = next((k, v) for k, v in eng.store._store.items()
                     if isinstance(v, StackedView))
    act = eng._ensure_act(key)
    assert np.asarray(act).shape == (K,)


# --------------------------------------------------------------------------
# evaluator-level differential: CNN + olmo-1b + seamless, devices=1
# --------------------------------------------------------------------------
def _cnn_setup():
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


def _cnn_evaluator(staged, fused, **kw):
    from repro.core import FaultSpec, InferenceAccuracyEvaluator

    model, params, x, y = _cnn_setup()

    def apply_fn(p, xx, wr, ar, s):
        return model.apply(p, xx, w_rates=wr, a_rates=ar, seed=s)

    return InferenceAccuracyEvaluator(
        apply_fn, params, x, y,
        spec=FaultSpec(weight_fault_rate=0.2, act_fault_rate=0.2),
        device_fault_scale=np.array([1.0, 0.1]),
        step_fn=model.step if staged else None,
        eval_strategy="staged" if staged else "full",
        fuse_chains=fused, devices=1, **kw), model


def _generations(n_units, rng, gens=3, pop=6):
    """A converging population sequence: survivors plus point mutants."""
    P = rng.integers(0, 2, size=(pop, n_units))
    out = [P.copy()]
    for _ in range(gens - 1):
        P = P[rng.integers(0, pop, size=pop)].copy()
        where = rng.integers(0, n_units, size=pop)
        P[np.arange(pop), where] = rng.integers(0, 2, size=pop)
        out.append(P.copy())
    return out


def test_cnn_fused_matches_unfused_and_full_bitwise():
    rng = np.random.default_rng(3)
    ev_full, model = _cnn_evaluator(staged=False, fused=False)
    ev_uf, _ = _cnn_evaluator(staged=True, fused=False)
    ev_f, _ = _cnn_evaluator(staged=True, fused=True)
    ev_fc, _ = _cnn_evaluator(staged=True, fused=True, eval_batch_size=3)
    for P in _generations(model.n_units, rng):
        ref = ev_full.delta_acc(P)
        np.testing.assert_array_equal(ev_uf.delta_acc(P), ref)
        np.testing.assert_array_equal(ev_f.delta_acc(P), ref)
        np.testing.assert_array_equal(ev_fc.delta_acc(P), ref)
    st = ev_f.staged_stats()
    assert st["fused_segments"] > 0 and st["chains"] > 0
    assert 0 < st["unit_runs"] <= st["full_unit_runs"]


def test_segment_cache_bounded_and_reused():
    from repro.core import objectives

    rng = np.random.default_rng(4)
    ev, model = _cnn_evaluator(staged=True, fused=True)
    n = model.n_units
    for P in _generations(n, rng, gens=4):
        ev.delta_acc(P)
    cache = objectives._SEGMENT_CACHE[ev]
    # buddy-aligned (start, length) keys only, bounded by the ladder
    for start, length in cache:
        assert length & (length - 1) == 0
        assert start == 0 or start % length == 0
    assert len(cache) <= n * max(1, (n - 1).bit_length())
    # further generations reuse the compiled segments for the same
    # (start, length) shapes instead of growing the cache unboundedly
    size = len(cache)
    for P in _generations(n, rng, gens=3):
        ev.delta_acc(P)
    assert len(cache) <= max(size, 2 * n)
    # the fault-environment setter drops the fused executables (they
    # close over the old rates/tables)
    ev.device_fault_scale = np.array([1.5, 0.5])
    assert ev not in objectives._SEGMENT_CACHE


@pytest.mark.parametrize("arch,n_layers", [("olmo-1b", 6),
                                           ("seamless-m4t-medium", None)])
def test_lm_fused_matches_unfused_and_full_bitwise(arch, n_layers):
    from repro.configs import get_config
    from repro.core import FaultSpec
    from repro.core.objectives import make_lm_accuracy_evaluator
    from repro.testing.lm_harness import lm_calibration_setup

    cfg = get_config(arch).reduced()
    if n_layers:        # deepen so non-trivial chains actually form
        cfg = dataclasses.replace(cfg, n_layers=n_layers)
    params, batch, labels = lm_calibration_setup(cfg, B=1, S=4)
    spec = FaultSpec(weight_fault_rate=0.2, act_fault_rate=0.2, bits=8)
    scale = np.array([1.0, 0.25])
    n = (cfg.n_enc_layers + cfg.n_layers) if cfg.is_encdec else cfg.n_layers

    def ev(strategy, fused):
        return make_lm_accuracy_evaluator(
            cfg, params, batch, labels, spec, scale,
            eval_strategy=strategy, fuse_chains=fused, devices=1)

    e_full, e_uf, e_f = ev("full", False), ev("staged", False), \
        ev("staged", True)
    rng = np.random.default_rng(5)
    for P in _generations(n, rng):
        ref = e_full.delta_acc(P)
        np.testing.assert_array_equal(e_uf.delta_acc(P), ref)
        np.testing.assert_array_equal(e_f.delta_acc(P), ref)
    assert e_f.staged_stats()["fused_segments"] > 0


def test_lm_segment_composition_matches_apply():
    """The model-level segment contract: any split of the unit run
    composes to exactly ``apply`` (local rate indices, absolute-unit
    fault seeds)."""
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.models.transformer import LMStepModel
    from repro.testing.lm_harness import lm_calibration_setup

    cfg = dataclasses.replace(get_config("olmo-1b").reduced(), n_layers=4)
    params, batch, _ = lm_calibration_setup(cfg, B=1, S=4)
    sm = LMStepModel(cfg)
    units = sm.unit_params(params)
    row = np.array([1, 0, 1, 1])
    wr = jnp.asarray(0.2 * np.array([1.0, 0.25])[row], jnp.float32)
    ar = jnp.asarray(0.2 * np.array([1.0, 0.25])[row], jnp.float32)
    ref = sm.apply(units, batch, wr, ar, 3)
    for split in (1, 2, 3):
        x = sm.segment(0, units[:split], batch, wr[:split], ar[:split], 3)
        x = sm.segment(split, units[split:], x, wr[split:], ar[split:], 3)
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(x))


def test_cnn_segment_composition_matches_apply():
    import jax.numpy as jnp

    model, params, x, _ = _cnn_setup()
    n = model.n_units
    row = np.random.default_rng(1).integers(0, 2, size=n)
    wr = jnp.asarray(0.2 * np.array([1.0, 0.1])[row], jnp.float32)
    ar = jnp.asarray(0.2 * np.array([1.0, 0.1])[row], jnp.float32)
    ref = model.apply(params, x, w_rates=wr, a_rates=ar, seed=3)
    for split in (2, 5):
        h = model.segment(0, params[:split], x, wr[:split], ar[:split], 3)
        h = model.segment(split, params[split:], h, wr[split:],
                          ar[split:], 3)
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(h))


# --------------------------------------------------------------------------
# knob threading
# --------------------------------------------------------------------------
def test_fuse_chains_knob_threads():
    from repro.core.objectives import ObjectiveFn

    class FakeEvaluator:
        eval_strategy = "staged"
        eval_batch_size = None
        devices = 1
        fuse_chains = True

    class FakeCostModel:
        pass

    ev = FakeEvaluator()
    ObjectiveFn(FakeCostModel(), ev, fuse_chains=False)
    assert ev.fuse_chains is False
    ev2 = FakeEvaluator()
    ObjectiveFn(FakeCostModel(), ev2)              # None = leave alone
    assert ev2.fuse_chains is True


def test_fuse_chains_toggle_switches_engine():
    ev, _ = _cnn_evaluator(staged=True, fused=True)
    eng = ev._prefix_engine
    assert eng.segment_fn is not None
    ev.fuse_chains = False
    assert eng.segment_fn is None
    ev.fuse_chains = True
    assert eng.segment_fn is not None
    # both modes still agree after toggling mid-life
    P = np.random.default_rng(6).integers(0, 2, size=(4, ev._n_units))
    a = ev.delta_acc(P)
    ev.fuse_chains = False
    ev._prefix_engine.clear()
    np.testing.assert_array_equal(ev.delta_acc(P), a)


# --------------------------------------------------------------------------
# devices=4: fused == devices=1 full, bitwise (subprocess fake devices)
# --------------------------------------------------------------------------
_DIFF_SCRIPT = r"""
import dataclasses
import numpy as np, jax, jax.numpy as jnp
assert len(jax.local_devices()) == 4, jax.local_devices()
from repro.core import FaultSpec, InferenceAccuracyEvaluator
from repro.core.objectives import make_lm_accuracy_evaluator
from repro.models.cnn import CNN_MODELS
from repro.configs import get_config
from repro.testing.lm_harness import lm_calibration_setup

# ---- CNN: alexnet, fused staged devices=4 vs full devices=1 ----
model = CNN_MODELS["alexnet"]
scale = np.array([1.0, 0.1])
spec = FaultSpec(weight_fault_rate=0.2, act_fault_rate=0.2)
rng = np.random.default_rng(0)
params = model.init(jax.random.PRNGKey(2), num_classes=8, width=0.125, img=8)
x = jnp.asarray(rng.normal(size=(2, 8, 8, 3)), jnp.float32)
y = jnp.asarray(rng.integers(0, 8, size=(2,)))
apply_fn = lambda p, xx, wr, ar, s: model.apply(p, xx, w_rates=wr,
                                                a_rates=ar, seed=s)
P = rng.integers(0, 2, size=(6, model.n_units))
P[2:, :-2] = P[0, :-2]      # shared prefixes so chains actually fuse

def cnn_ev(staged, fused, devices):
    return InferenceAccuracyEvaluator(
        apply_fn, params, x, y, spec, scale,
        step_fn=model.step if staged else None,
        eval_strategy="staged" if staged else "full",
        fuse_chains=fused, devices=devices)

ref = cnn_ev(False, False, 1).delta_acc(P)
for fused in (False, True):
    got = cnn_ev(True, fused, 4).delta_acc(P)
    assert (got == ref).all(), ("cnn", fused)
ev4 = cnn_ev(True, True, 4)
ev4.delta_acc(P)
st = ev4.staged_stats()
assert st["fused_segments"] > 0
assert sum(st["device_dispatches"].values()) == st["dispatches"]
assert len(st["device_dispatches"]) >= 2, st["device_dispatches"]
print("CNN-OK")

# ---- LM: olmo-1b (6 units) + seamless enc-dec ----
SPEC = FaultSpec(weight_fault_rate=0.2, act_fault_rate=0.2, bits=8)
SCALE = np.array([1.0, 0.25])
for arch in ("olmo-1b", "seamless-m4t-medium"):
    cfg = get_config(arch).reduced()
    if not cfg.is_encdec:
        cfg = dataclasses.replace(cfg, n_layers=6)
    params, batch, labels = lm_calibration_setup(cfg, B=1, S=4)
    n = (cfg.n_enc_layers + cfg.n_layers) if cfg.is_encdec else cfg.n_layers
    P = np.random.default_rng(1).integers(0, 2, size=(5, n))
    P[2:, :-2] = P[0, :-2]
    ref = make_lm_accuracy_evaluator(cfg, params, batch, labels, SPEC,
                                     SCALE, eval_strategy="full",
                                     devices=1).delta_acc(P)
    for fused in (False, True):
        got = make_lm_accuracy_evaluator(cfg, params, batch, labels, SPEC,
                                         SCALE, eval_strategy="staged",
                                         fuse_chains=fused,
                                         devices=4).delta_acc(P)
        assert (got == ref).all(), (arch, fused)
    print(arch + "-OK")
print("ALL-OK")
"""


def test_fused_sharded_matches_single_device_bitwise_subprocess():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"          # fake host devices, never a chip
    env["PYTHONPATH"] = _SRC + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run([sys.executable, "-c", _DIFF_SCRIPT], env=env,
                       capture_output=True, text=True, timeout=1500)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    assert "ALL-OK" in r.stdout
