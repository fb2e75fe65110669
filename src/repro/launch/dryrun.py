import os
# 512 fake CPU devices; on a machine with a TPU this tool must not take it
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"

# --- everything below may import jax ---------------------------------------
import argparse
import dataclasses
import json
import time
import traceback

import jax
import numpy as np

from repro.configs.base import SHAPES
from repro.configs.registry import ARCH_IDS, get_config
from repro.core.eval_engine import peak_memory_bytes
from repro.launch.mesh import make_production_mesh
from repro.launch.roofline import (collective_bytes_from_hlo, model_flops,
                                   roofline_terms)
from repro.launch import steps as S

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "results", "dryrun")


def _build(cfg, shape, mesh, multi_pod, overrides, unroll):
    overrides = overrides or {}
    if shape.kind == "train":
        if multi_pod:
            return S.abstract_pp_train_step(
                cfg, mesh, shape, n_micro=overrides.get("n_micro", 4),
                partition=overrides.get("partition"), unroll=unroll)
        return S.abstract_train_step(
            cfg, mesh, shape, microbatches=overrides.get("microbatches"),
            remat=overrides.get("remat", True), unroll=unroll,
            seq_axis=overrides.get("seq_axis", "model"))
    if shape.kind == "prefill":
        return S.abstract_serve_prefill(
            cfg, mesh, shape, multi_pod=multi_pod, unroll=unroll,
            seq_axis=overrides.get("seq_axis", "model"))
    return S.abstract_serve_decode(cfg, mesh, shape, multi_pod=multi_pod,
                                   unroll=unroll)


def _shrink(cfg, n_groups: int):
    """Same-family config with exactly n_groups block-pattern groups
    (used by the cost probes; embeddings/head untouched = the intercept)."""
    pat = len(cfg.block_pattern)
    kw = {"n_layers": n_groups * pat}
    if cfg.is_encdec:
        kw["n_enc_layers"] = n_groups
    return dataclasses.replace(cfg, **kw)


def _compile_cell(cfg, shape, mesh, multi_pod, overrides, unroll):
    with mesh:
        fn, args = _build(cfg, shape, mesh, multi_pod, overrides, unroll)
        lowered = fn.lower(*args)
        compiled = lowered.compile()
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):   # older jaxlib: list of per-program dicts
        cost = cost[0] if cost else {}
    return compiled, cost


def run_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
             save: bool = True, hlo: bool = False,
             overrides: dict | None = None, tag_suffix: str = "") -> dict:
    """One (arch x shape x mesh) cell.

    Pass 1 (deliverable): the FULL model is lowered+compiled (rolled
    scans) on the production mesh — proves the sharding config and gives
    the real per-device memory analysis.

    Pass 2 (roofline): XLA's cost analysis does not multiply scan bodies
    by trip count, so per-step FLOPs/bytes/collective-bytes are measured
    on fully-unrolled 2-group and 4-group variants of the same config and
    extrapolated linearly in depth:  total(G) = fixed + G * per_group.
    The intercept captures embeddings/head/optimizer; the slope is the
    exact per-group cost.  (Full-depth unrolled compiles at 512-way SPMD
    exceed practical CPU compile budgets; extrapolation is exact for
    depth-homogeneous stacks, which all ten archs are.)
    """
    cfg = get_config(arch)
    if overrides and overrides.get("moe_capacity"):
        cfg = dataclasses.replace(
            cfg, moe_capacity_factor=float(overrides["moe_capacity"]))
    shape = SHAPES[shape_name]
    if not cfg.supports_shape(shape):
        return {"arch": arch, "shape": shape_name, "multi_pod": multi_pod,
                "status": "skipped",
                "reason": "long_500k requires sub-quadratic attention "
                          "(see DESIGN.md §5)"}
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = int(np.prod(list(mesh.shape.values())))
    from repro.models import layers as _L
    from repro.models import transformer as _T
    from jax.sharding import PartitionSpec as _P
    ov = overrides or {}
    _L.CAUSAL_SKIP = bool(ov.get("causal_skip", False))
    _L.ATTN_BF16_COMPUTE = bool(ov.get("attn_bf16", False))
    _T.LOGITS_SPEC = _P(None, None, "model") if ov.get("logit_shard") \
        else None
    _L.BLOCK_SEQ_AXIS = "model" if ov.get("block_seq") else None

    # ---- pass 1: full model, rolled, compile must SUCCEED ----------------
    t0 = time.time()
    compiled, _ = _compile_cell(cfg, shape, mesh, multi_pod, overrides,
                                unroll=False)
    t_full = time.time() - t0
    mem = compiled.memory_analysis()
    hlo_text = compiled.as_text() if hlo else None

    # ---- pass 2: unrolled cost probes at G=2 and G=4 ----------------------
    probes = {}
    for g in (2, 4):
        cfg_g = _shrink(cfg, g)
        t1 = time.time()
        comp_g, cost_g = _compile_cell(cfg_g, shape, mesh, multi_pod,
                                       overrides, unroll=True)
        probes[g] = {
            "flops": float(cost_g.get("flops", 0.0)),
            "bytes": float(cost_g.get("bytes accessed", 0.0)),
            "coll": collective_bytes_from_hlo(comp_g.as_text()),
            "compile_s": time.time() - t1,
        }
    G = cfg.n_groups

    def extrapolate(key):
        per_group = (probes[4][key] - probes[2][key]) / 2.0
        fixed = probes[2][key] - 2.0 * per_group
        return max(0.0, fixed + G * per_group)

    record = {
        "arch": arch, "shape": shape_name, "multi_pod": multi_pod,
        "status": "ok", "n_chips": n_chips, "n_groups": G,
        # per-device -> whole-step totals
        "flops": extrapolate("flops") * n_chips,
        "bytes_accessed": extrapolate("bytes") * n_chips,
        "collective_bytes": extrapolate("coll") * n_chips,
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", 0),
            "output_bytes": getattr(mem, "output_size_in_bytes", 0),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", 0),
            # shared with the evaluator's eval_batch_size="auto" probe
            "peak_bytes": peak_memory_bytes(compiled),
        },
        "compile_s": round(t_full, 1),
        "probe_compile_s": [round(probes[2]["compile_s"], 1),
                            round(probes[4]["compile_s"], 1)],
        "probes": {str(k): {kk: vv for kk, vv in v.items()}
                   for k, v in probes.items()},
    }
    record["roofline"] = roofline_terms(record)
    record["model_flops"] = model_flops(cfg, shape)
    record["useful_flop_ratio"] = (record["model_flops"] / record["flops"]
                                   if record["flops"] else 0.0)
    _L.CAUSAL_SKIP = False
    _L.ATTN_BF16_COMPUTE = False
    _T.LOGITS_SPEC = None
    _L.BLOCK_SEQ_AXIS = None
    record["overrides"] = {k: str(v) for k, v in (overrides or {}).items()}
    if save:
        os.makedirs(RESULTS_DIR, exist_ok=True)
        tag = f"{arch}_{shape_name}_{'mp' if multi_pod else 'sp'}{tag_suffix}"
        with open(os.path.join(RESULTS_DIR, tag + ".json"), "w") as f:
            json.dump(record, f, indent=1)
        if hlo_text is not None:
            with open(os.path.join(RESULTS_DIR, tag + ".hlo.txt"), "w") as f:
                f.write(hlo_text)
    return record


def main():
    ap = argparse.ArgumentParser(description="multi-pod dry run")
    ap.add_argument("--arch", default=None, choices=list(ARCH_IDS) + [None])
    ap.add_argument("--shape", default=None, choices=list(SHAPES) + [None])
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--hlo", action="store_true", help="save full HLO text")
    args = ap.parse_args()

    archs = [args.arch] if args.arch else list(ARCH_IDS)
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = [False, True] if (args.both_meshes or args.all) \
        else [args.multi_pod]

    n_ok = n_skip = n_fail = 0
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                tag = f"{arch} x {shape} x {'2x16x16' if mp else '16x16'}"
                try:
                    rec = run_cell(arch, shape, multi_pod=mp, hlo=args.hlo)
                    if rec["status"] == "skipped":
                        n_skip += 1
                        print(f"SKIP {tag}: {rec['reason']}", flush=True)
                        continue
                    n_ok += 1
                    r = rec["roofline"]
                    print(f"OK   {tag}: flops={rec['flops']:.3e} "
                          f"bytes={rec['bytes_accessed']:.3e} "
                          f"coll={rec['collective_bytes']:.3e} "
                          f"peak/dev={rec['memory']['peak_bytes']/2**30:.2f}GiB "
                          f"bottleneck={r['bottleneck']} "
                          f"(compile {rec['compile_s']}s"
                          f" probes {rec['probe_compile_s']})", flush=True)
                except Exception as e:
                    n_fail += 1
                    print(f"FAIL {tag}: {type(e).__name__}: {e}", flush=True)
                    traceback.print_exc()
    print(f"\ndry-run summary: {n_ok} ok, {n_skip} skipped, {n_fail} failed")
    raise SystemExit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
