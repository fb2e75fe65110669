"""Sharded (multi-device) population evaluation: bit-exactness + placement.

The contracts under test (see core/eval_engine.DeviceScheduler and
DESIGN.md "Device scheduler"):

  * ``devices=1`` and ``devices=N`` produce BIT-IDENTICAL ΔAcc for a
    CNN and for LM configs, staged and full — the differential test
    runs in a subprocess with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=4`` (CPU-safe
    fake devices; the CI fast lane sets the same flag to run the
    in-process multi-device tests for real);
  * the full engine splits a whole-population dispatch into per-device
    chunks and gathers once per generation; the staged engine shards by
    prefix group (root gene -> device) so sibling prefixes and their
    parent activations stay device-local;
  * ``device_memory_budget``/``auto_eval_batch_size`` budget per
    device, not globally;
  * enc-dec static carries are stored once per ENCODER prefix, not once
    per (prefix × unit): the decoder input batch is closed over by the
    unit executables (never threaded through encoder carries) and the
    encoder memory is interned as a ``PrefixRef`` keyed by the encoder
    prefix (the ROADMAP open item this PR closes).
"""
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core.eval_engine import (ActivationStore, DeviceScheduler,
                                    DeviceStreams, PopulationEvalEngine,
                                    PrefixEvalEngine,
                                    PrefixRef, auto_eval_batch_size,
                                    device_memory_budget, parse_devices)

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SRC = os.path.join(_ROOT, "src")


def _n_local_devices():
    import jax
    return len(jax.local_devices())


# --------------------------------------------------------------------------
# knob grammar + scheduler resolution
# --------------------------------------------------------------------------
def test_parse_devices_grammar():
    assert parse_devices(None) is None          # leave-alone (ObjectiveFn)
    assert parse_devices("auto") == "auto"
    assert parse_devices("4") == 4
    assert parse_devices(2) == 2
    with pytest.raises(ValueError):
        parse_devices(0)
    with pytest.raises(ValueError):
        parse_devices("-1")


def test_device_scheduler_resolution():
    import jax
    n = _n_local_devices()
    sched = DeviceScheduler("auto")
    assert sched.n_devices == n
    assert sched.devices == list(sched.mesh.devices.flat)
    assert set(sched.mesh.axis_names) == {"data", "model"}
    assert DeviceScheduler(1).n_devices == 1
    with pytest.raises(ValueError):
        DeviceScheduler(n + 1)
    # round-robin chunk placement
    one = DeviceScheduler(1)
    assert one.device_for(0) is one.device_for(5) is jax.local_devices()[0]


# --------------------------------------------------------------------------
# per-device budgeting
# --------------------------------------------------------------------------
def test_device_memory_budget_per_device(monkeypatch):
    monkeypatch.delenv("REPRO_EVAL_MEM_BUDGET", raising=False)
    total = device_memory_budget()
    # CPU backend reports no bytes_limit, so the host-RAM (or default)
    # fallback is divided across the fake-device pool sharing that RAM
    assert device_memory_budget(n_devices=4) == total // 4
    # an explicit operator cap is already per-device: never rescaled
    monkeypatch.setenv("REPRO_EVAL_MEM_BUDGET", "123456")
    assert device_memory_budget(n_devices=1) == 123456
    assert device_memory_budget(n_devices=8) == 123456


def test_device_memory_budget_tpu_without_limit_raises(monkeypatch):
    """Host RAM is no stand-in for HBM: a TPU that reports no
    ``bytes_limit`` must fail loudly, not budget from the host."""
    import jax

    class _Tpu:
        platform, device_kind = "tpu", "TPU v5 lite"

        def memory_stats(self):
            return {}

    monkeypatch.delenv("REPRO_EVAL_MEM_BUDGET", raising=False)
    monkeypatch.setattr(jax, "local_devices", lambda: [_Tpu()])
    with pytest.raises(RuntimeError, match="bytes_limit"):
        device_memory_budget()


def test_auto_eval_batch_size_per_device(monkeypatch):
    probe = lambda n: 1000 + 100 * n            # fixed 1000 + 100/row
    # an explicit budget is the caller's per-device number: n_devices
    # must not rescale it
    assert auto_eval_batch_size(probe, budget=1000 + 100 * 64,
                                n_devices=4) == 64
    # default budget resolution goes through device_memory_budget(n)
    monkeypatch.setenv("REPRO_EVAL_MEM_BUDGET", str(1000 + 100 * 64))
    assert auto_eval_batch_size(probe, n_devices=4) == 64


# --------------------------------------------------------------------------
# engine-level placement plumbing (stub pool: one real device, 2 slots)
# --------------------------------------------------------------------------
class _StubScheduler:
    """Duck-typed 2-slot scheduler over the one real CPU device, so the
    placement plumbing (device= threading, per-device chunk splits,
    prefix-group assignment) runs everywhere without fake devices."""

    def __init__(self, n=2):
        import jax
        self.devices = [jax.local_devices()[0]] * n

    @property
    def n_devices(self):
        return len(self.devices)

    def device_for(self, i):
        return self.devices[i % len(self.devices)]


def test_device_streams_thread_per_group_in_order():
    """One group runs on the calling thread; with several, each group
    gets one worker thread of its own and keeps its submission order."""
    import threading

    main = threading.get_ident()
    with DeviceStreams(1) as one:
        assert one.submit(0, threading.get_ident).result() == main
    seen = {g: [] for g in range(3)}

    def call(g, k):
        seen[g].append(k)
        return threading.get_ident()

    with DeviceStreams(3) as streams:
        futs = {(g, k): streams.submit(g, call, g, k)
                for k in range(5) for g in range(3)}
    idents = {gk: f.result() for gk, f in futs.items()}
    assert all(seen[g] == list(range(5)) for g in range(3))
    assert main not in idents.values()
    per_group = [{idents[(g, k)] for k in range(5)} for g in range(3)]
    assert all(len(t) == 1 for t in per_group)
    assert len(set.union(*per_group)) == 3


def test_population_engine_splits_across_pool_bitwise():
    calls = []

    def batch_fn(rows, device=None):
        calls.append((len(rows), device))
        return rows.sum(axis=1).astype(np.float64)

    P = np.arange(14).reshape(7, 2)
    ref = PopulationEvalEngine(lambda rows: rows.sum(axis=1)).evaluate(P)
    eng = PopulationEvalEngine(batch_fn, scheduler=_StubScheduler(2))
    np.testing.assert_array_equal(eng.evaluate(P), ref)
    # eval_batch_size unset: the unique batch splits into n_devices
    # even chunks (ceil(7/2)=4 -> chunks of 4+3, padded to 4)
    assert eng.dispatches == 2
    assert [c[0] for c in calls] == [4, 4]
    assert all(c[1] is not None for c in calls)
    # cached re-evaluation: zero new dispatches
    np.testing.assert_array_equal(eng.evaluate(P[::-1]), ref[::-1])
    assert eng.dispatches == 2


def _synthetic_unit_fns(L, K=4):
    """Exact-integer float unit stack (from test_prefix_store_props)."""
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


def _synthetic_ref_row(row, L, K=4):
    act = row[0] + np.arange(K, dtype=np.float64)
    for i in range(1, L - 1):
        act = act * (i + 2) + row[i]
    return float((act * (L + 1) + row[-1]).sum())


def test_prefix_engine_shards_by_prefix_group_bitwise():
    L = 5
    rng = np.random.default_rng(3)
    P = rng.integers(0, 3, size=(8, L))
    want = [_synthetic_ref_row(r, L) for r in P]
    eng = PrefixEvalEngine(_synthetic_unit_fns(L), L,
                           scheduler=_StubScheduler(2))
    np.testing.assert_array_equal(eng.evaluate(P), want)
    st = eng.stats()
    assert sum(st["device_dispatches"].values()) == st["dispatches"]
    # every root gene got a slot, spread round-robin over the pool
    roots = {int(r[0]) for r in P}
    assert set(eng._root_device) == roots
    assert set(eng._root_device.values()) <= {0, 1}
    # all prefixes under one root inherit its slot (device-local chains)
    for p in eng.store._store:
        assert eng._device_index(p) == eng._root_device[int(p[0])]
    # second generation sharing prefixes: still bitwise, still grouped
    P2 = P.copy()
    P2[:, -1] = (P2[:, -1] + 1) % 3
    np.testing.assert_array_equal(eng.evaluate(P2),
                                  [_synthetic_ref_row(r, L) for r in P2])


def test_prefix_engine_sharded_eviction_recomputes():
    """LRU eviction under sharding still degrades to recompute, never to
    wrong results or cross-device mixing."""
    L = 5
    rng = np.random.default_rng(4)
    eng = PrefixEvalEngine(_synthetic_unit_fns(L), L, max_store_bytes=64,
                           scheduler=_StubScheduler(2))
    for _ in range(3):
        P = rng.integers(0, 3, size=(6, L))
        np.testing.assert_array_equal(eng.evaluate(P),
                                      [_synthetic_ref_row(r, L) for r in P])
    assert eng.store.evictions > 0


# --------------------------------------------------------------------------
# shared carries: PrefixRef accounting + the enc-dec store contract
# --------------------------------------------------------------------------
def test_prefix_ref_owns_no_store_bytes():
    store = ActivationStore()
    h = np.zeros(4, np.float32)
    store.put((0, 1), {"x": h, "mem": PrefixRef((0,))})
    assert store.nbytes == h.nbytes          # the ref is free
    assert isinstance(store.get((0, 1))["mem"], PrefixRef)


@pytest.mark.parametrize("devices", [1])
def test_encdec_static_carries_stored_once_per_enc_prefix(devices):
    """The ROADMAP open item, pinned: enc-dec staged evaluation stores
    the encoder memory once per ENCODER prefix (as the last encoder
    unit's activation) and every decoder activation holds a PrefixRef
    to it; the static decoder-input batch never enters the store at
    all (the encoder carries are plain arrays, the batch is closed over
    by the unit executables)."""
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.core import FaultSpec
    from repro.core.objectives import make_lm_accuracy_evaluator
    from repro.testing.lm_harness import lm_calibration_setup

    cfg = get_config("seamless-m4t-medium").reduced()
    ne, nd = cfg.n_enc_layers, cfg.n_layers
    n = ne + nd
    params, batch, labels = lm_calibration_setup(cfg, B=2, S=8)
    spec = FaultSpec(weight_fault_rate=0.2, act_fault_rate=0.2, bits=8)
    scale = np.array([1.0, 0.25])

    # two encoder-gene groups x several decoder branches
    rng = np.random.default_rng(5)
    P = rng.integers(0, 2, size=(6, n))
    P[:3, :ne] = 0
    P[3:, :ne] = 1
    ref = make_lm_accuracy_evaluator(cfg, params, batch, labels, spec,
                                     scale, eval_strategy="full",
                                     devices=devices).delta_acc(P)
    ev = make_lm_accuracy_evaluator(cfg, params, batch, labels, spec,
                                    scale, eval_strategy="staged",
                                    devices=devices)
    np.testing.assert_array_equal(ev.delta_acc(P), ref)

    eng = ev._prefix_engine
    assert eng.shared_fields == {"mem": ne - 1}
    store = eng.store._store
    enc_prefixes = {tuple(map(int, row[:ne])) for row in P}
    mem_payloads = 0
    for key, act in store.items():
        if len(key) < ne:                      # interior encoder carry
            assert hasattr(act, "dtype"), act  # plain array, no batch dict
        elif len(key) == ne:                   # the memory itself
            assert hasattr(act, "dtype"), act
            mem_payloads += 1
        else:                                  # decoder carry
            assert set(act) == {"x", "mem"}
            assert isinstance(act["mem"], PrefixRef)
            assert act["mem"].prefix == key[:ne]
    assert mem_payloads == len(enc_prefixes)
    # store accounting counts each decoder carry's hidden state only:
    # budget == sum of real leaves, no double-counted memory
    expect = sum(
        a.size * a.dtype.itemsize
        for act in store.values()
        for a in ([act] if hasattr(act, "dtype")
                  else [v for v in act.values() if hasattr(v, "dtype")]))
    assert eng.store.nbytes == expect
    # and shared-carry resolution survives eviction: shrink the budget,
    # force recompute chains, results unchanged
    ev2 = make_lm_accuracy_evaluator(cfg, params, batch, labels, spec,
                                     scale, eval_strategy="staged",
                                     devices=devices, max_store_bytes=1)
    np.testing.assert_array_equal(ev2.delta_acc(P), ref)
    assert ev2.staged_stats()["evictions"] > 0
    assert jnp.asarray(ref).size == len(P)


# --------------------------------------------------------------------------
# the differential test: devices=1 == devices=4, CNN + LM, staged + full
# (subprocess with 4 fake host devices, CPU-safe — the CI fast lane also
# sets XLA_FLAGS so the in-process multi-device test below runs there)
# --------------------------------------------------------------------------
_DIFF_SCRIPT = r"""
import numpy as np, jax, jax.numpy as jnp
assert len(jax.local_devices()) == 4, jax.local_devices()
from repro.core import FaultSpec, InferenceAccuracyEvaluator
from repro.core.objectives import make_lm_accuracy_evaluator
from repro.models.cnn import CNN_MODELS
from repro.configs import get_config
from repro.testing.lm_harness import lm_calibration_setup

# ---- CNN: alexnet, full + staged, devices 1 vs 4, chunked + not ----
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

def cnn_ev(staged, devices, ebs=None):
    return InferenceAccuracyEvaluator(
        apply_fn, params, x, y, spec, scale,
        step_fn=model.step if staged else None,
        eval_strategy="staged" if staged else "full",
        devices=devices, eval_batch_size=ebs)

ref = cnn_ev(False, 1).delta_acc(P)
for staged in (False, True):
    for ebs in (None, 3):
        got = cnn_ev(staged, 4, ebs).delta_acc(P)
        assert (got == ref).all(), ("cnn", staged, ebs)
ev4 = cnn_ev(False, 4)
ev4.delta_acc(P)
# U=6 over 4 devices: per-device chunk ceil(6/4)=2 -> ceil(6/2)=3 chunks
assert ev4._engine.dispatches == 3, ev4._engine.dispatches
st_ev = cnn_ev(True, 4)
st_ev.delta_acc(P)
dd = st_ev.staged_stats()["device_dispatches"]
assert dd and len(dd) >= 2, dd          # prefix groups actually sharded
print("CNN-OK")

# ---- LM: decoder-only (olmo) + enc-dec (seamless), staged + full ----
SPEC = FaultSpec(weight_fault_rate=0.2, act_fault_rate=0.2, bits=8)
SCALE = np.array([1.0, 0.25])
for arch in ("olmo-1b", "seamless-m4t-medium"):
    cfg = get_config(arch).reduced()
    params, batch, labels = lm_calibration_setup(cfg, B=1, S=4)
    n = (cfg.n_enc_layers + cfg.n_layers) if cfg.is_encdec else cfg.n_layers
    P = np.random.default_rng(1).integers(0, 2, size=(5, n))
    ref = make_lm_accuracy_evaluator(cfg, params, batch, labels, SPEC,
                                     SCALE, eval_strategy="full",
                                     devices=1).delta_acc(P)
    for strategy in ("full", "staged"):
        got = make_lm_accuracy_evaluator(cfg, params, batch, labels, SPEC,
                                         SCALE, eval_strategy=strategy,
                                         devices=4).delta_acc(P)
        assert (got == ref).all(), (arch, strategy)
    print(arch + "-OK")
print("ALL-OK")
"""


def test_sharded_matches_single_device_bitwise_subprocess():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"          # fake host devices, never a chip
    env["PYTHONPATH"] = _SRC + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run([sys.executable, "-c", _DIFF_SCRIPT], env=env,
                       capture_output=True, text=True, timeout=1500)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    assert "ALL-OK" in r.stdout


# --------------------------------------------------------------------------
# in-process multi-device coverage (runs when the ambient process has a
# pool — the CI fast lane sets xla_force_host_platform_device_count=4)
# --------------------------------------------------------------------------
@pytest.mark.skipif("_n_local_devices() < 2",
                    reason="needs >1 local device (CI fast lane sets "
                           "XLA_FLAGS=--xla_force_host_platform_"
                           "device_count=4)")
def test_real_pool_population_engine_bitwise():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def _metric(rows):
        return (rows * jnp.arange(1, rows.shape[1] + 1)).sum(axis=1) \
            .astype(jnp.float32)

    def batch_fn(rows, device=None):
        r = np.asarray(rows, np.int32)
        r = jnp.asarray(r) if device is None else jax.device_put(r, device)
        return _metric(r)

    P = np.arange(24).reshape(8, 3) % 5
    ref = PopulationEvalEngine(batch_fn).evaluate(P)
    eng = PopulationEvalEngine(batch_fn, scheduler=DeviceScheduler("auto"))
    np.testing.assert_array_equal(eng.evaluate(P), ref)
    U = len({tuple(r) for r in P.tolist()})
    per_dev = -(-U // _n_local_devices())
    assert eng.dispatches == -(-U // per_dev)


# --------------------------------------------------------------------------
# knob threading
# --------------------------------------------------------------------------
def test_objective_fn_threads_devices():
    class FakeEvaluator:
        eval_strategy = "staged"
        eval_batch_size = None
        devices = 1

    class FakeCostModel:
        pass

    from repro.core.objectives import ObjectiveFn
    ev = FakeEvaluator()
    ObjectiveFn(FakeCostModel(), ev, devices=3)
    assert ev.devices == 3
    ev2 = FakeEvaluator()
    ObjectiveFn(FakeCostModel(), ev2)              # None = leave alone
    assert ev2.devices == 1
