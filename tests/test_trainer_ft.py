"""Fault-tolerance behaviours of the training loop."""
import os
import shutil
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import latest_step, restore_latest, save_checkpoint
from repro.configs import get_config
from repro.data import TokenStream
from repro.train import AdamWConfig, Trainer, TrainerConfig


@pytest.fixture()
def tmpdir():
    d = tempfile.mkdtemp()
    yield d
    shutil.rmtree(d, ignore_errors=True)


def _mk_trainer(d, data=None, total=12, ckpt_every=4):
    cfg = get_config("olmo-1b").reduced()
    data = data or TokenStream(vocab=cfg.vocab, seq_len=16, batch=4, seed=0)
    return Trainer(cfg, AdamWConfig(lr=3e-3, warmup_steps=2, total_steps=100),
                   TrainerConfig(total_steps=total, ckpt_every=ckpt_every,
                                 ckpt_dir=d), data), data


def test_checkpoint_atomic_roundtrip(tmpdir):
    tree = {"a": jnp.arange(5, dtype=jnp.float32),
            "b": {"c": jnp.ones((2, 3), jnp.bfloat16)}}
    save_checkpoint(tmpdir, 7, tree, extra={"data": {"step": 7}})
    assert latest_step(tmpdir) == 7
    restored, meta = restore_latest(tmpdir, tree)
    assert meta["step"] == 7 and meta["extra"]["data"]["step"] == 7
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_checkpoint_gc_keeps_latest(tmpdir):
    tree = {"x": jnp.zeros(3)}
    for s in (1, 2, 3, 4, 5):
        save_checkpoint(tmpdir, s, tree, keep=2)
    names = sorted(d for d in os.listdir(tmpdir) if d.startswith("ckpt_"))
    assert names == ["ckpt_00000004", "ckpt_00000005"]


@pytest.mark.slow
def test_crash_restart_is_bit_identical(tmpdir):
    """Kill-and-relaunch == uninterrupted run (checkpoint + data state)."""
    t_full, _ = _mk_trainer(tmpdir + "/a", total=12, ckpt_every=4)
    t_full.run()

    # interrupted run: 2 sessions against the same ckpt dir
    d2 = tmpdir + "/b"
    t1, _ = _mk_trainer(d2, total=12, ckpt_every=4)
    t1.run(max_steps=8)           # "crash" after step 8 (ckpt at 8)
    t2, _ = _mk_trainer(d2, total=12, ckpt_every=4)
    assert t2.try_restore() and t2.step == 8
    t2.run()

    for a, b in zip(jax.tree.leaves(t_full.params),
                    jax.tree.leaves(t2.params)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_straggler_detection(tmpdir):
    import time
    t, _ = _mk_trainer(tmpdir, total=10, ckpt_every=100)
    fired = []
    t.on_straggler = lambda step: fired.append(step)
    t.tcfg.straggler_factor = 1e-9       # every step counts as slow
    t.tcfg.straggler_patience = 3
    t.run()
    assert len(t.straggler_events) >= 3
    assert fired, "straggler callback should fire after patience exceeded"


def test_elastic_reshard_helper():
    from repro.train.trainer import reshard_batch_spec
    assert reshard_batch_spec(256, 16) == 16
    assert reshard_batch_spec(256, 8) == 32     # device loss: bigger per-dev
    with pytest.raises(ValueError):
        reshard_batch_spec(256, 7)


def test_gradient_compression_error_feedback():
    """int8 EF compression: single-device psum == identity + bounded err,
    and error feedback carries the residual."""
    from repro.train.compression import compress_psum, init_error_feedback
    rng = np.random.default_rng(0)
    g = {"w": jnp.asarray(rng.normal(size=(64, 64)) * 1e-3, jnp.float32)}
    e = init_error_feedback(g)

    from jax.sharding import PartitionSpec as P

    def run(g, e):
        return jax.shard_map(
            lambda gg, ee: compress_psum(gg, ee, "x"),
            mesh=jax.make_mesh((1,), ("x",)),
            in_specs=(P(), P()), out_specs=P(), check_vma=False)(g, e)

    ghat, e2 = run(g, e)
    scale = float(jnp.max(jnp.abs(g["w"]))) / 127
    assert float(jnp.max(jnp.abs(ghat["w"] - g["w"]))) <= scale * 0.51
    # residual = exactly what was lost
    np.testing.assert_allclose(np.asarray(e2["w"]),
                               np.asarray(g["w"] - ghat["w"]), atol=1e-9)
    # next round re-injects the residual: two-step sum converges to truth
    ghat2, e3 = run(jax.tree.map(jnp.zeros_like, g), e2)
    total = ghat["w"] + ghat2["w"]
    assert float(jnp.max(jnp.abs(total - g["w"]))) <= \
        float(jnp.max(jnp.abs(ghat["w"] - g["w"])))
