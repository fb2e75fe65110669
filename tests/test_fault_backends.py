"""The fault-backend pin: ``pallas == tables == generic``, BITWISE,
through the full evaluator stack (full and staged/fused strategies,
single- and multi-device pools), plus the pallas hot-swap contract —
changing ``device_fault_scale`` must not rebuild or recompile anything.

On CPU CI the pallas backend's ``ops.fault_matmul`` runs the exact
interpret-mode composition (see kernels/ops.py), which is what makes
the pin bitwise here; on a TPU the fused tile agrees within the
tolerance that ``chip_smoke.py`` checks on the chip.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.fault import FaultSpec
from repro.core.objectives import (InferenceAccuracyEvaluator, ObjectiveFn,
                                   make_lm_accuracy_evaluator)
from repro.models import cnn
from repro.models import transformer as T
from repro.models.cnn import CNN_MODELS

SCALE = np.array([0.0, 0.5, 1.0, 2.0], np.float32)
CNN_SPEC = FaultSpec(weight_fault_rate=0.3, act_fault_rate=0.05,
                     faulty_bits=cnn.FAULTY_BITS, bits=cnn.FAULT_BITS)
LM_SPEC = FaultSpec(weight_fault_rate=0.2, act_fault_rate=0.05,
                    faulty_bits=4, bits=8)


def _clean_argmax_labels(apply_fn, params, x, n_units):
    """Labels = the clean quantized model's own argmax, so clean
    accuracy is 1.0 and ΔAcc is a pure corruption measure that the
    max(0, ·) clamp cannot hide."""
    z = jnp.zeros((n_units,), jnp.float32)
    return jnp.argmax(apply_fn(params, x, z, z, 0), axis=-1)


def _spread_init(model, x):
    """Params and clean labels from the first init key whose model does
    NOT collapse to one class on the probe batch.  A collapsed head keeps
    its argmax under corruption, so ΔAcc would be identically zero and
    the bitwise pin vacuous.  Searching, rather than naming a key, keeps
    the setup valid when JAX changes its PRNG stream."""
    for key in range(16):
        params = model.init(jax.random.PRNGKey(key), num_classes=8,
                            width=0.25, img=16)
        labels = _clean_argmax_labels(model.apply, params, x, model.n_units)
        if np.unique(np.asarray(labels)).size > 1:
            return params, labels
    raise AssertionError("every init key collapses to one class")


@pytest.fixture(scope="module")
def cnn_setups():
    rng = np.random.default_rng(0)
    out = {}
    for name in CNN_MODELS:
        model = CNN_MODELS[name]
        x = jnp.asarray(rng.normal(size=(8, 16, 16, 3)).astype(np.float32))
        params, labels = _spread_init(model, x)
        P = rng.integers(0, len(SCALE), size=(10, model.n_units))
        out[name] = (model, params, x, labels, P)
    return out


def _cnn_evaluator(setup, backend, **kw):
    model, params, x, labels, _ = setup
    extra = {}
    if backend == "pallas":
        extra["quant_params"] = cnn.quantize_unit_params(params)
    elif backend == "tables":
        extra["weight_tables"] = cnn.build_weight_fault_tables(
            params, CNN_SPEC.weight_fault_rate * SCALE, base_seed=3)
    return InferenceAccuracyEvaluator(
        model.apply, params, x, labels, CNN_SPEC,
        device_fault_scale=SCALE, base_seed=3, step_fn=model.step,
        fault_backend=backend, **extra, **kw)


@pytest.mark.parametrize("name", list(CNN_MODELS))
@pytest.mark.parametrize("strategy,fuse", [("full", None), ("staged", True),
                                           ("staged", False)])
def test_cnn_backends_bitwise(cnn_setups, name, strategy, fuse):
    setup = cnn_setups[name]
    P = setup[4]
    res = {}
    for backend in ("generic", "tables", "pallas"):
        kw = {} if fuse is None else {"fuse_chains": fuse}
        ev = _cnn_evaluator(setup, backend, eval_strategy=strategy, **kw)
        res[backend] = ev.delta_acc(P)
        if backend == "pallas":
            assert ev.fault_table_bytes() == 0
            assert ev.fault_state_bytes() > 0
    assert res["generic"].max() > 0, "degenerate: no corruption measured"
    np.testing.assert_array_equal(res["generic"], res["tables"])
    np.testing.assert_array_equal(res["generic"], res["pallas"])


@pytest.fixture(scope="module")
def lm_setup():
    cfg = dataclasses.replace(get_config("olmo-1b").reduced(), n_layers=4)
    params = T.init_lm(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(1)
    batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab, size=(2, 16)))}
    sm = T.LMStepModel(cfg, bits=LM_SPEC.bits, faulty_bits=LM_SPEC.faulty_bits)
    labels = _clean_argmax_labels(sm.apply, sm.unit_params(params), batch,
                                  sm.n_units)
    P = rng.integers(0, len(SCALE), size=(10, sm.n_units))
    return cfg, params, batch, labels, P


@pytest.mark.parametrize("devices", [1, 4])
@pytest.mark.parametrize("strategy", ["full", "staged"])
def test_lm_backends_bitwise(lm_setup, devices, strategy):
    if devices > jax.local_device_count():
        pytest.skip(f"needs {devices} local devices")
    cfg, params, batch, labels, P = lm_setup
    res = {}
    for backend in ("generic", "tables", "pallas"):
        ev = make_lm_accuracy_evaluator(
            cfg, params, batch, labels, LM_SPEC, SCALE, base_seed=5,
            eval_strategy=strategy, devices=devices, fault_backend=backend)
        res[backend] = ev.delta_acc(P)
    assert res["generic"].max() > 0
    np.testing.assert_array_equal(res["generic"], res["tables"])
    np.testing.assert_array_equal(res["generic"], res["pallas"])


def test_pallas_hot_swap_no_rebuild(lm_setup):
    """The serving contract: changing the fault environment under the
    pallas backend keeps every compiled executable (rates/seed are
    traced arguments) and still produces the values a fresh evaluator
    at the new environment computes."""
    cfg, params, batch, labels, P = lm_setup
    ev = make_lm_accuracy_evaluator(cfg, params, batch, labels, LM_SPEC,
                                    SCALE, base_seed=5,
                                    fault_backend="pallas")
    d1 = ev.delta_acc(P)
    unit_fns = ev._built_unit_fns
    assert unit_fns is not None
    ev.device_fault_scale = SCALE * 0.5
    d2 = ev.delta_acc(P)
    assert ev._fault_env_rebuilds == 0
    assert ev._built_unit_fns is unit_fns
    assert (d1 != d2).any()
    fresh = make_lm_accuracy_evaluator(cfg, params, batch, labels, LM_SPEC,
                                       SCALE * 0.5, base_seed=5,
                                       fault_backend="pallas")
    np.testing.assert_array_equal(d2, fresh.delta_acc(P))


def test_tables_degrade_to_generic_on_env_change(lm_setup):
    """Legacy contract: a fault-environment change invalidates tables
    (they encode the old rates) and counts a rebuild."""
    cfg, params, batch, labels, P = lm_setup
    ev = make_lm_accuracy_evaluator(cfg, params, batch, labels, LM_SPEC,
                                    SCALE, base_seed=5,
                                    fault_backend="tables")
    ev.delta_acc(P)
    ev.device_fault_scale = SCALE * 0.5
    assert ev.fault_backend == "generic"
    assert ev._fault_env_rebuilds == 1
    fresh = make_lm_accuracy_evaluator(cfg, params, batch, labels, LM_SPEC,
                                       SCALE * 0.5, base_seed=5,
                                       fault_backend="generic")
    np.testing.assert_array_equal(ev.delta_acc(P), fresh.delta_acc(P))


def test_backend_validation_and_objectivefn_threading(lm_setup):
    cfg, params, batch, labels, P = lm_setup
    with pytest.raises(ValueError):
        make_lm_accuracy_evaluator(cfg, params, batch, labels, LM_SPEC,
                                   SCALE, fault_backend="warp")
    model = CNN_MODELS["alexnet"]
    p = model.init(jax.random.PRNGKey(0), num_classes=8, width=0.25, img=16)
    x = jnp.zeros((2, 16, 16, 3), jnp.float32)
    y = jnp.zeros((2,), jnp.int32)
    with pytest.raises(ValueError):            # pallas needs quant_params
        InferenceAccuracyEvaluator(model.apply, p, x, y, CNN_SPEC, SCALE,
                                   fault_backend="pallas")
    with pytest.raises(ValueError):            # tables needs weight_tables
        InferenceAccuracyEvaluator(model.apply, p, x, y, CNN_SPEC, SCALE,
                                   fault_backend="tables")
    # ObjectiveFn threads the backend to the evaluator it wraps
    ev = make_lm_accuracy_evaluator(cfg, params, batch, labels, LM_SPEC,
                                    SCALE, fault_backend="pallas")
    assert ev.fault_backend == "pallas"

    class _CM:                                  # minimal stand-in
        pass

    ObjectiveFn(_CM(), ev, fault_backend="generic")
    assert ev.fault_backend == "generic"
    ObjectiveFn(_CM(), ev, fault_backend="pallas")   # switch back works
    assert ev.fault_backend == "pallas"
