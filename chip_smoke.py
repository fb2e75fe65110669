#!/usr/bin/env python3
"""Bring-up check: the system's two main paths on a TPU, at full width.

    python chip_smoke.py             # one chip: kernels, search, serving
    python chip_smoke.py --chips 4   # four chips: the search evaluation at
                                     # devices=4 against devices=1, only

Phases (each raises on failure; nothing is caught and carried on):

* kernels -- ``ops.bitflip`` on an olmo-1b MLP weight (int8 2048x8192) is
  bit-exact against ``bitflip_ref`` under every fault model, and
  ``ops.fault_matmul`` agrees with ``fault_matmul_ref`` within a stated
  bound.  Both lower to a Mosaic ``tpu_custom_call``, so neither ran in
  interpret mode.
* search -- ``AFarePart(...).optimize()`` on ResNet18 at width 1.0,
  224x224, 1000 classes (ImageNet shapes), with the ``pallas`` fault
  backend, the staged evaluator, one device, population 16 and 3
  generations.  The images are seeded random; the labels are the clean
  model's argmax.  The final population is evaluated again with the
  ``generic`` backend (whole-model forward) and the two ΔAcc must agree.
* serving -- ``serve.Engine`` on full-width olmo-1b (bf16, seeded
  ``init_lm``) with ``max_batch`` 4 and ``max_len`` 256 serves 8 requests
  under a partition with nonzero fault rates, and a zero-rate decode
  matches the full-sequence forward of the same tokens.

Each phase prints one JSON line with its checks and seconds.  The last
line is ``{"ok": true, "device": {...}}`` as JAX reports the device.
Without a TPU (e.g. ``JAX_PLATFORMS=cpu``) or without the repo's ``src/``
beside it, the script exits non-zero before any phase.  Compiles go to
the persistent cache (``repro.launch.compile_cache``).
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

import numpy as np

_SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")

SEED = 0                             # weights, images, prompts, faults

# Kernel widths: olmo-1b's MLP up-projection (d_model 2048 -> d_ff 8192).
K, N = 2048, 8192
M = 256                              # tokens per contraction
# fault_matmul bound, elementwise, relative to |x| @ |w|.  The fused tile
# feeds f32 operands to the MXU and accumulates in f32 VMEM; if the MXU
# takes them as bf16 (8-bit mantissa), each product is off by at most
# about 2**-8 of |x_k w_k|, and f32 accumulation adds ~K * 2**-24.  The
# reference is the f64 product of the same corrupted weights.  2**-7 is
# twice the bf16 bound; a wrong index, scale or flip is off by O(1).
MATMUL_TOL = 2.0 ** -7

# Search: ImageNet shapes for the paper's ResNet18.
IMG, WIDTH, CLASSES = 224, 1.0, 1000
N_EVAL = 32                          # calibration images: ΔAcc step 1/32
POP, GENS = 16, 3
# pallas vs generic ΔAcc.  The two backends run the same conv and
# activation corruption; they differ in the fc layer only: its int8 weights
# flip inside the fused Pallas tile (f32 accumulation in VMEM) instead of
# in a dequantized copy fed to XLA's dot at TPU default precision.  That
# rounding difference moves an image's top-1 only where its top-2 logits
# lie within rounding distance of each other, a few percent of images.
# So: at most 3 images of 32 per row, and 1 image per row on average.
SEARCH_ROW_TOL = 3 / N_EVAL
SEARCH_MEAN_TOL = 1 / N_EVAL

# Serving: full-width olmo-1b.
MAX_BATCH, MAX_LEN = 4, 256
N_REQ, NEW_TOKENS = 8, 16
# zero-rate decode vs full forward, max |Δlogit| over max |logit|.  Both
# run bf16 weights and activations (unit roundoff 2**-8) but reduce in
# different orders (chunked prefill attention + one decode step against a
# single full-sequence pass); over 2 x 16 residual updates the relative
# drift is ~ sqrt(32) * 2**-8 = 2.2%.  5% leaves twice that; a wrong
# cache slot, position or mask is off by O(1).
SERVE_TOL = 0.05


def _line(**kw):
    print(json.dumps(kw), flush=True)


def _peak_bytes(dev) -> int | None:
    stats = dev.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


# --------------------------------------------------------------------------
# kernels
# --------------------------------------------------------------------------
def kernel_phase() -> dict:
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops
    from repro.kernels.faultmodel import FAULT_MODELS

    kq, kx = jax.random.split(jax.random.PRNGKey(SEED))
    q = jax.random.randint(kq, (K, N), -128, 128, dtype=jnp.int32
                           ).astype(jnp.int8)
    s, rate = jnp.int32(SEED + 1234), jnp.float32(0.1)
    out = {}
    for model in FAULT_MODELS:
        got = ops.bitflip(q, s, rate, 4, fault_model=model)
        want = ops.bitflip_ref(q, s, rate, 4, fault_model=model)
        if not bool(jnp.array_equal(got, want)):
            raise AssertionError(f"bitflip[{model}] differs from bitflip_ref")
        changed = int(jnp.sum(got != q))
        if changed == 0:
            raise AssertionError(f"bitflip[{model}] changed nothing")
        out[f"bitflip_{model}_changed"] = changed

    x = jax.random.normal(kx, (M, K), jnp.float32)
    scale = jnp.float32(0.01)
    got = np.asarray(ops.fault_matmul(x, q, scale, s, rate, 4), np.float64)
    qf = np.asarray(ops.bitflip_ref(q, s, rate, 4), np.float64)
    xh = np.asarray(x, np.float64)
    w = qf * float(scale)
    want = xh @ w
    bound = MATMUL_TOL * (np.abs(xh) @ np.abs(w)) + 1e-30
    ratio = float(np.max(np.abs(got - want) / bound))
    if not np.isfinite(got).all() or ratio > 1.0:
        raise AssertionError(f"fault_matmul off by {ratio:.3g} x the bound")
    clean = xh @ (np.asarray(q, np.float64) * float(scale))
    if np.allclose(want, clean):
        raise AssertionError("fault_matmul reference saw no fault")
    out["fault_matmul_max_err_over_bound"] = ratio
    out["fault_matmul_max_rel_err"] = float(
        np.max(np.abs(got - want)) / np.max(np.abs(want)))

    for name, fn, args in (
            ("bitflip", lambda a, b, c: ops.bitflip(a, b, c, 4),
             (q, s, rate)),
            ("fault_matmul",
             lambda a, b, c, d, e: ops.fault_matmul(a, b, c, d, e, 4),
             (x, q, scale, s, rate))):
        text = jax.jit(fn).lower(*args).as_text()
        if "tpu_custom_call" not in text:
            raise AssertionError(f"{name} did not lower to a Mosaic kernel")
        out[f"{name}_tpu_custom_call"] = True
    return out


# --------------------------------------------------------------------------
# search
# --------------------------------------------------------------------------
def search_setup():
    """ResNet18 at ImageNet shapes with seeded random images; labels are
    the clean quantized model's argmax, so clean accuracy is 1.0 and ΔAcc
    is a pure corruption measure.  No dataset or params cache needed."""
    import jax
    import jax.numpy as jnp

    from repro.models.cnn import ResNet18

    kp, kx = jax.random.split(jax.random.PRNGKey(SEED))
    params = jax.jit(lambda key: ResNet18.init(
        key, num_classes=CLASSES, width=WIDTH, img=IMG))(kp)
    x = jax.random.normal(kx, (N_EVAL, IMG, IMG, 3), jnp.float32)
    z = jnp.zeros((ResNet18.n_units,), jnp.float32)
    labels = jax.jit(lambda p, xx: jnp.argmax(
        ResNet18.apply(p, xx, z, z, SEED), axis=-1))(params, x)
    layers = ResNet18.layer_infos(num_classes=CLASSES, width=WIDTH, img=IMG)
    return params, x, labels, layers


def search_evaluator(setup, backend: str, devices: int,
                     strategy: str = "staged", eval_batch_size="auto"):
    """The ΔAcc evaluator over the four-tier v5e ladder (``POD_TIERS_4``):
    four fault tiers give the staged scheduler four depth-0 prefix
    groups, enough to spread over four chips.

    Rates are the paper's FR = 0.2 for weights and activations, on the
    CNNs' INT8 / 4-LSB regime.  The random-init ResNet18 predicts one
    class for every image at 224x224 (no normalisation layers, and the
    global pool averages 28x28 positions), with a wide top-2 margin;
    at a lower activation rate conv-layer faults stop moving that
    argmax, and only the fc layer's tier decides ΔAcc."""
    from repro.core import (POD_TIERS_4, FaultSpec,
                            InferenceAccuracyEvaluator)
    from repro.models.cnn import FAULT_BITS, FAULTY_BITS, ResNet18, \
        quantize_unit_params

    params, x, labels, _ = setup
    spec = FaultSpec(weight_fault_rate=0.2, act_fault_rate=0.2,
                     faulty_bits=FAULTY_BITS, bits=FAULT_BITS)
    scale = [d.fault_scale for d in POD_TIERS_4]
    qp = quantize_unit_params(params) if backend == "pallas" else None
    return spec, InferenceAccuracyEvaluator(
        ResNet18.apply, params, x, labels, spec, scale, base_seed=SEED,
        quant_params=qp, fault_backend=backend, step_fn=ResNet18.step,
        eval_strategy=strategy, devices=devices,
        eval_batch_size=eval_batch_size, max_store_bytes=4 << 30)


def search_phase() -> dict:
    from repro.core import POD_TIERS_4, AFarePart, NSGA2Config

    t0 = time.perf_counter()
    setup = search_setup()
    spec, ev = search_evaluator(setup, "pallas", devices=1)
    t_setup = time.perf_counter() - t0      # init, labels, batch probe
    part = AFarePart(setup[3], POD_TIERS_4, fault_spec=spec,
                     acc_evaluator=ev,
                     nsga2_config=NSGA2Config(population=POP,
                                              generations=GENS, seed=SEED),
                     eval_strategy="staged", eval_devices=1,
                     fault_backend="pallas")
    last = {}
    t0 = time.perf_counter()
    plan = part.optimize(callback=lambda g, pop, objs: last.update(
        pop=pop.copy(), objs=objs.copy()))
    t_search = time.perf_counter() - t0
    if ev.fault_backend != "pallas":
        raise AssertionError(f"evaluator ended on {ev.fault_backend!r}")
    rows, dacc = last["pop"], last["objs"][:, 2]
    classes = int(np.unique(np.asarray(setup[2])).size)
    if not (dacc > 0).any():
        raise AssertionError(
            f"final population's ΔAcc is all zero (labels span {classes} "
            f"classes; rows {rows.tolist()})")
    pal = ev.delta_acc(rows)                 # row-cache hits, no dispatch
    ebs, dispatches = ev.eval_batch_size, ev.dispatches
    del part, ev                             # free the activation store
    gc.collect()                             # (evaluator <-> engine cycle)

    # the reference takes the other path on every axis it can: generic
    # backend, whole-model forward (a handful of compiles, not a staged
    # ladder), at the chunk size the pallas probe chose
    t0 = time.perf_counter()
    _, gen = search_evaluator(setup, "generic", devices=1,
                              strategy="full",
                              eval_batch_size=ebs)
    ref = gen.delta_acc(rows)
    t_ref = time.perf_counter() - t0
    diff = np.abs(ref - pal)
    if diff.max() > SEARCH_ROW_TOL + 1e-9 or diff.mean() > SEARCH_MEAN_TOL:
        raise AssertionError(f"pallas vs generic ΔAcc: max {diff.max()}, "
                             f"mean {diff.mean()}; pallas {pal.tolist()}, "
                             f"generic {ref.tolist()}")
    return {"setup_s": t_setup, "search_s": t_search, "reference_s": t_ref,
            "label_classes": classes, "evaluations": plan.evaluations,
            "eval_batch_size": ebs, "dispatches": dispatches,
            "dacc_final_pop": dacc.tolist(),
            "dacc_generic": ref.tolist(),
            "pallas_vs_generic_max": float(diff.max()),
            "pallas_vs_generic_mean": float(diff.mean()),
            "plan_delta_acc": plan.delta_acc}


def sharded_search_phase(n_devices: int) -> dict:
    """The search's ΔAcc evaluation at ``devices=n_devices`` and at
    ``devices=1`` on the same rows: equal results, and every device of
    the pool dispatched work (prefix groups were spread, not piled on
    the first chip).  One row per dispatch: both pools then run the
    same executables on the same shapes, so equality tests placement
    alone.  Two rows under each tier's depth-0 gene: the staged
    scheduler places a prefix subtree by that gene, and each chip
    compiles every executable its subtree needs (the chips' compiles
    overlap, one host thread per chip).  The rest of each row sits on
    the faultiest tier or the next one: random rows read ΔAcc 0 on
    this model, which would make the equality vacuous."""
    from repro.core import POD_TIERS_4

    t0 = time.perf_counter()
    setup = search_setup()
    _line(phase="sharded_search setup", seconds=time.perf_counter() - t0)
    tiers = len(POD_TIERS_4)
    rows = np.zeros((2 * tiers, len(setup[3])), np.int64)
    rows[:, 0] = np.arange(2 * tiers) // 2       # depth-0 gene: the tier
    rows[1::2, 1:] = 1                           # lowvolt / mid below it
    out = {}
    for devices in (n_devices, 1):
        _, ev = search_evaluator(setup, "pallas", devices=devices,
                                 eval_batch_size=1)
        t0 = time.perf_counter()
        out[devices] = ev.delta_acc(rows)
        out[f"s_{devices}"] = time.perf_counter() - t0
        _line(phase=f"sharded_search devices={devices}",
              seconds=out[f"s_{devices}"], dacc=out[devices].tolist())
        if devices == n_devices:
            per_dev = ev.staged_stats()["device_dispatches"]
        del ev
        gc.collect()
    if not (out[1] > 0).any():
        raise AssertionError("every row's ΔAcc is 0: nothing to compare")
    if not np.array_equal(out[n_devices], out[1]):
        raise AssertionError(f"devices={n_devices} {out[n_devices]} != "
                             f"devices=1 {out[1]}")
    counts = [per_dev.get(d, 0) for d in range(n_devices)]
    if min(counts) <= 0:
        raise AssertionError(f"a device dispatched nothing: {counts}")
    return {"devices": n_devices, "device_dispatches": counts,
            "dacc": out[1].tolist(),
            f"eval_s_devices_{n_devices}": out[f"s_{n_devices}"],
            "eval_s_devices_1": out["s_1"]}


# --------------------------------------------------------------------------
# serving
# --------------------------------------------------------------------------
def serving_phase() -> dict:
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.core import POD_TIERS_4, FaultSpec
    from repro.models.transformer import (decode_step, forward, init_lm,
                                          prefill)
    from repro.serve import Engine, Request, ServeConfig

    cfg = get_config("olmo-1b")
    params = jax.jit(lambda key: init_lm(cfg, key))(jax.random.PRNGKey(SEED))
    rng = np.random.default_rng(SEED)
    spec = FaultSpec()
    scale = np.array([d.fault_scale for d in POD_TIERS_4], np.float32)
    partition = rng.integers(0, len(scale), cfg.n_layers)

    def partition_to_rates(p, scales):
        sc = scale if scales is None else np.asarray(scales, np.float32)
        return (spec.weight_fault_rate * sc[p], spec.act_fault_rate * sc[p])

    eng = Engine(cfg, params, ServeConfig(max_batch=MAX_BATCH,
                                          max_len=MAX_LEN),
                 partition_to_rates=partition_to_rates)
    eng.apply_partition(partition)
    reqs = [Request(uid=i, prompt=rng.integers(
                        0, cfg.vocab, int(rng.integers(16, 65))
                    ).astype(np.int32), max_new_tokens=NEW_TOKENS)
            for i in range(N_REQ)]
    t0 = time.perf_counter()
    eng.generate(reqs)
    t_serve = time.perf_counter() - t0
    st = eng.stats()
    if st["completed"] != N_REQ or st["dropped"] != 0:
        raise AssertionError(f"completed {st['completed']}, "
                             f"dropped {st['dropped']}")
    for r in reqs:
        if len(r.out) != NEW_TOKENS or not all(0 <= t < cfg.vocab
                                                for t in r.out):
            raise AssertionError(f"request {r.uid} produced {r.out}")

    # zero-rate decode (the faulted decode path at rate 0) against the
    # full-sequence forward of the same tokens
    S = 64
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (MAX_BATCH, S)), jnp.int32)
    zero = jnp.zeros((cfg.n_layers,), jnp.float32)
    full = jax.jit(lambda p, t: forward(p, cfg, {"tokens": t}))(params, toks)
    last, cache = jax.jit(lambda p, t: prefill(
        p, cfg, {"tokens": t}, max_len=MAX_LEN))(params, toks[:, :-1])
    dec, _ = jax.jit(lambda p, c, t, pos: decode_step(
        p, cfg, c, t, pos, fault=(zero, zero, jnp.int32(SEED))))(
        params, cache, toks[:, -1], jnp.full((MAX_BATCH,), S - 1, jnp.int32))
    errs = {}
    for name, got, want in (("decode", dec, full[:, -1]),
                            ("prefill", last[:, -1], full[:, -2])):
        got = np.asarray(got, np.float32)
        want = np.asarray(want, np.float32)
        if not np.isfinite(got).all():
            raise AssertionError(f"{name} logits are not finite")
        errs[name] = float(np.max(np.abs(got - want)) / np.max(np.abs(want)))
        if errs[name] > SERVE_TOL:
            raise AssertionError(f"zero-rate {name} vs forward: relative "
                                 f"error {errs[name]:.3g} > {SERVE_TOL}")
    return {"serve_s": t_serve, "completed": st["completed"],
            "dropped": st["dropped"], "decode_steps": st["decode_steps"],
            "decode_s": st["decode_s"], "ttft_s_mean": st["ttft_s_mean"],
            "tpot_s_mean": st["tpot_s_mean"],
            "zero_rate_decode_rel_err": errs["decode"],
            "zero_rate_prefill_rel_err": errs["prefill"]}


# --------------------------------------------------------------------------
def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the search evaluation at devices=4 "
                         "against devices=1")
    args = ap.parse_args()
    if not os.path.isdir(os.path.join(_SRC, "repro")):
        sys.exit(f"chip_smoke.py: no repro package under {_SRC}; run it "
                 "from a checkout of the repository")
    sys.path.insert(0, _SRC)

    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke.py: needs a TPU, JAX found "
                 f"{devs[0].platform!r} devices")
    if len(devs) < args.chips:
        sys.exit(f"chip_smoke.py: --chips {args.chips} but JAX sees "
                 f"{len(devs)} device(s)")
    from repro.launch.compile_cache import enable_compile_cache
    _line(phase="setup", compile_cache=enable_compile_cache(__file__),
          device_kind=devs[0].device_kind, devices=len(devs))

    if args.chips == 4:
        phases = [("sharded_search",
                   lambda: sharded_search_phase(args.chips))]
    else:
        phases = [("kernels", kernel_phase), ("search", search_phase),
                  ("serving", serving_phase)]
    for name, fn in phases:
        t0 = time.perf_counter()
        res = fn()
        _line(phase=name, ok=True, seconds=time.perf_counter() - t0,
              peak_bytes_in_use=_peak_bytes(devs[0]), **res)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)


if __name__ == "__main__":
    main()
