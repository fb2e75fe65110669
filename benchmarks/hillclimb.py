"""§Perf hillclimb driver: hypothesis -> change -> re-lower -> re-analyse.

    PYTHONPATH=src python -m benchmarks.hillclimb --arch X --shape Y \
        --variant name [--multi-pod]
    PYTHONPATH=src python -m benchmarks.hillclimb --target eval-engine \
        [--model alexnet] [--pop 60] [--eval-batch-size N]

Two targets share the same iteration log:

  * ``roofline`` (default) — lower/compile one (arch x shape x mesh)
    cell with a named override bundle (see VARIANTS) and record the
    three roofline terms;
  * ``eval-engine`` — time the population-batched ΔAcc evaluation
    engine (benchmarks/eval_engine.py) at a given population /
    ``--eval-batch-size`` and record per-candidate latency + speedup,
    so engine optimisations hillclimb through the same
    results/perf_iterations.jsonl history as kernel/collective ones.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

LOG = os.path.join(os.path.dirname(__file__), "..", "results",
                   "perf_iterations.jsonl")

VARIANTS: dict[str, dict] = {
    "baseline": {},
    # compute: skip statically-masked causal score tiles (exact math)
    "causal_skip": {"causal_skip": True},
    # memory: no activation rematerialisation (trades HBM for FLOPs)
    "no_remat": {"remat": False},
    "no_remat_skip": {"remat": False, "causal_skip": True},
    # memory/compute balance: fewer/more grad-accum microbatches
    "mb1": {"microbatches": 1},
    "mb2": {"microbatches": 2},
    "mb8": {"microbatches": 8},
    # pipeline depth experiments (multi-pod train)
    "micro8": {"n_micro": 8},
    "micro2": {"n_micro": 2},
    # collective levers
    "head_parallel": {"seq_axis": ""},          # heads shard over model
    "attn_bf16": {"attn_bf16": True},           # bf16 KV gathers, fp32 acc
    "logit_shard": {"logit_shard": True},       # keep [B,S,V] vocab-sharded
    "combo_collective": {"seq_axis": "", "attn_bf16": True,
                         "logit_shard": True},
    "combo_all": {"seq_axis": "", "attn_bf16": True, "logit_shard": True,
                  "causal_skip": True},
    # full sequence-parallel residual stream (weights gathered, not acts)
    "block_seq": {"block_seq": True},
    "block_seq_full": {"block_seq": True, "logit_shard": True,
                       "attn_bf16": True, "causal_skip": True},
    "block_seq_noremat": {"block_seq": True, "logit_shard": True,
                          "attn_bf16": True, "causal_skip": True,
                          "remat": False},
    # refinements after attn_bf16 refutation (adds reshards on every cell)
    "block_seq_skip": {"block_seq": True, "causal_skip": True,
                       "logit_shard": True},
    "combo_noremat": {"seq_axis": "", "logit_shard": True,
                      "causal_skip": True, "remat": False},
    "moe_cap125": {"moe_capacity": 1.25},
    "block_seq_logit": {"block_seq": True, "logit_shard": True},
    "arctic_tuned": {"moe_capacity": 1.25, "causal_skip": True,
                     "logit_shard": True},
    "arctic_best": {"moe_capacity": 1.25, "remat": False},
    "deepseek_best": {"block_seq": True, "logit_shard": True,
                      "attn_bf16": False},
}


def run(arch: str, shape: str, variant: str, multi_pod: bool):
    from repro.launch.dryrun import run_cell
    ov = VARIANTS[variant]
    rec = run_cell(arch, shape, multi_pod=multi_pod, save=True,
                   overrides=ov, tag_suffix=f"__{variant}")
    rec["variant"] = variant
    os.makedirs(os.path.dirname(LOG), exist_ok=True)
    with open(LOG, "a") as f:
        f.write(json.dumps(rec, default=float) + "\n")
    r = rec["roofline"]
    print(f"{arch} x {shape} x {'mp' if multi_pod else 'sp'} "
          f"[{variant}]: compute={r['compute_s']:.4g}s "
          f"memory={r['memory_s']:.4g}s collective={r['collective_s']:.4g}s "
          f"bottleneck={r['bottleneck']} "
          f"useful={rec['useful_flop_ratio']:.3f} "
          f"peak={rec['memory']['peak_bytes']/2**30:.2f}GiB")
    return rec


def run_eval_engine(model: str, pop: int, eval_batch_size: int | None):
    from benchmarks.eval_engine import run_benchmark
    rec = run_benchmark(model_name=model, pop=pop,
                        eval_batch_size=eval_batch_size)
    rec["target"] = "eval-engine"
    os.makedirs(os.path.dirname(LOG), exist_ok=True)
    with open(LOG, "a") as f:
        f.write(json.dumps(rec, default=float) + "\n")
    ms = rec["per_candidate_ms"]
    print(f"eval-engine {model} pop={pop} ebs={eval_batch_size}: "
          f"loop={ms['loop']:.3f}ms/cand "
          f"batched={ms['batched']:.3f} tables={ms['batched_tables']:.3f} "
          f"staged={ms['staged']:.3f} "
          f"speedup={rec['speedup_vs_loop']['batched_tables']:.2f}x")
    return rec


def main():
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache(__file__)
    ap = argparse.ArgumentParser()
    ap.add_argument("--target", default="roofline",
                    choices=["roofline", "eval-engine"])
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--variant", default="baseline",
                    choices=sorted(VARIANTS))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--model", default="alexnet",
                    help="eval-engine target: CNN to evaluate")
    ap.add_argument("--pop", type=int, default=60,
                    help="eval-engine target: population size")
    from repro.core.eval_engine import parse_eval_batch_size
    ap.add_argument("--eval-batch-size", default=None,
                    type=parse_eval_batch_size,
                    help="eval-engine target: chromosomes per dispatch "
                         "(int, or 'auto' to probe the compiled footprint)")
    args = ap.parse_args()
    if args.target == "eval-engine":
        run_eval_engine(args.model, args.pop, args.eval_batch_size)
        return
    if not args.arch or not args.shape:
        ap.error("--arch and --shape are required for --target roofline")
    run(args.arch, args.shape, args.variant, args.multi_pod)


if __name__ == "__main__":
    main()
