"""Distribution-layer tests.

These need >1 XLA device, so they run in subprocesses with
``--xla_force_host_platform_device_count=8`` — the main pytest process
keeps the single real CPU device (per the dry-run isolation rule).
"""
import os
import subprocess
import sys
import textwrap

import pytest

_ENV = {**os.environ, "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
        "JAX_PLATFORMS": "cpu", "PYTHONPATH": "src"}


def _run(code: str):
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       env=_ENV, capture_output=True, text=True, timeout=540,
                       cwd=os.path.dirname(os.path.dirname(__file__)))
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-3000:]}"
    return r.stdout


@pytest.mark.slow
def test_pipeline_loss_matches_single_device():
    out = _run("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_config
    from repro.configs.base import ShapeSpec
    from repro.launch.mesh import make_test_mesh
    from repro.launch import steps as S
    from repro.models.transformer import init_lm
    from repro.train.train_step import make_loss_fn, init_train_state
    from repro.train.optimizer import AdamWConfig

    tshape = ShapeSpec("t", seq_len=16, global_batch=8, kind="train")
    cfg = get_config("olmo-1b").reduced()
    mesh2 = make_test_mesh((2,2,2), ("pod","data","model"))
    fn, _ = S.abstract_pp_train_step(cfg, mesh2, tshape, AdamWConfig(), n_micro=4)
    params = init_lm(cfg, jax.random.PRNGKey(0))
    from repro.launch.pipeline import stage_stack, group_cuts
    from repro.core.partitioner import contiguous_stages
    cuts = group_cuts(contiguous_stages(np.zeros(cfg.n_layers, np.int64), 2), cfg)
    stages, _ = stage_stack(params["groups"], cuts)
    ppp = {k: v for k, v in params.items() if k != "groups"}; ppp["stages"] = stages
    import repro.train.train_step as ts
    opt_state = ts.init_train_state(cfg, ppp)
    rng = np.random.default_rng(0)
    batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab, (8,16)), jnp.int32),
             "labels": jnp.asarray(rng.integers(0, cfg.vocab, (8,16)), jnp.int32)}
    with mesh2:
        _, _, metrics = fn(ppp, opt_state, batch)
    ref = float(make_loss_fn(cfg, remat=False)(params, batch))
    err = abs(float(metrics["loss"]) - ref)
    # 5e-3: microbatched pipeline accumulates the loss in a different
    # order than the single-device reference; CPU XLA's reduction order
    # also varies by backend version (seen up to ~2.5e-3)
    assert err < 5e-3, (float(metrics["loss"]), ref)
    print("PP-OK", err)
    """)
    assert "PP-OK" in out


@pytest.mark.slow
def test_pipeline_respects_afarepart_cut():
    """An uneven AFarePart partition produces a valid pipeline too."""
    out = _run("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_config
    from repro.configs.base import ShapeSpec
    from repro.launch.mesh import make_test_mesh
    from repro.launch import steps as S
    from repro.models.transformer import init_lm
    from repro.train.train_step import make_loss_fn
    import repro.train.train_step as ts
    from repro.launch.pipeline import stage_stack, group_cuts
    from repro.core.partitioner import contiguous_stages

    tshape = ShapeSpec("t", seq_len=8, global_batch=4, kind="train")
    cfg = get_config("olmo-1b").reduced()   # 2 groups
    import dataclasses
    cfg = dataclasses.replace(cfg, n_layers=6)   # 6 groups of 1
    # partition: first 2 layers tier0, rest tier1 -> uneven 2/4 cut
    part = np.array([0, 0, 1, 1, 1, 1])
    mesh2 = make_test_mesh((2,2,2), ("pod","data","model"))
    fn, _ = S.abstract_pp_train_step(cfg, mesh2, tshape, partition=part,
                                     n_micro=2)
    params = init_lm(cfg, jax.random.PRNGKey(1))
    cuts = group_cuts(contiguous_stages(part, 2), cfg)
    assert cuts == [0, 2, 6], cuts
    stages, lens = stage_stack(params["groups"], cuts)
    assert lens == [2, 4]
    ppp = {k: v for k, v in params.items() if k != "groups"}; ppp["stages"] = stages
    opt_state = ts.init_train_state(cfg, ppp)
    rng = np.random.default_rng(0)
    batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab, (4,8)), jnp.int32),
             "labels": jnp.asarray(rng.integers(0, cfg.vocab, (4,8)), jnp.int32)}
    with mesh2:
        _, _, metrics = fn(ppp, opt_state, batch)
    ref = float(make_loss_fn(cfg, remat=False)(params, batch))
    assert abs(float(metrics["loss"]) - ref) < 1e-3
    print("UNEVEN-OK")
    """)
    assert "UNEVEN-OK" in out


@pytest.mark.slow
def test_sharded_serve_matches_reference():
    out = _run("""
    import jax, jax.numpy as jnp, numpy as np, dataclasses
    from repro.configs import get_config
    from repro.configs.base import ShapeSpec
    from repro.launch.mesh import make_test_mesh
    from repro.launch import steps as S
    from repro.models.transformer import init_lm, forward

    mesh = make_test_mesh((4,2), ("data","model"))
    pshape = ShapeSpec("p", seq_len=32, global_batch=4, kind="prefill")
    dshape = ShapeSpec("d", seq_len=32, global_batch=4, kind="decode")
    for aid in ["mixtral-8x7b", "mamba2-2.7b", "gemma2-27b"]:
        cfg = get_config(aid).reduced()
        if cfg.is_moe:
            cfg = dataclasses.replace(cfg, moe_capacity_factor=0.0)
        params = init_lm(cfg, jax.random.PRNGKey(0))
        rng = np.random.default_rng(1)
        toks = jnp.asarray(rng.integers(0, cfg.vocab, (4, 32)), jnp.int32)
        with mesh:
            pfn, _ = S.abstract_serve_prefill(cfg, mesh, pshape)
            last, cache = pfn(params, {"tokens": toks[:, :31]})
            dfn, _ = S.abstract_serve_decode(cfg, mesh, dshape)
            dl, _ = dfn(params, cache, {"tokens": toks[:, 31],
                                        "positions": jnp.full((4,), 31, jnp.int32)})
        full = forward(params, cfg, {"tokens": toks})
        assert float(jnp.max(jnp.abs(dl - full[:, 31]))) < 3e-3, aid
        assert float(jnp.max(jnp.abs(last - full[:, 30]))) < 3e-3, aid
    print("SERVE-OK")
    """)
    assert "SERVE-OK" in out


@pytest.mark.slow
def test_dryrun_single_cell_end_to_end():
    """Full dry-run machinery on the production 512-device mesh (1 cell)."""
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch", "olmo-1b",
         "--shape", "decode_32k"],
        env={**os.environ, "PYTHONPATH": "src"}, capture_output=True,
        text=True, timeout=540,
        cwd=os.path.dirname(os.path.dirname(__file__)))
    assert r.returncode == 0, r.stdout + r.stderr[-2000:]
    assert "1 ok, 0 skipped, 0 failed" in r.stdout
