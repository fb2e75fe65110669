"""Benchmark harness — one function per paper table/figure.

Outputs ``name,us_per_call,derived`` CSV lines (scaffold contract) plus
human-readable tables; everything is also dumped to results/bench/*.json
for EXPERIMENTS.md.

  bench_fig3    — Fig. 3: Top-1 @ 20 % weight faults, 3 CNNs x 3 tools
  bench_fig4    — Fig. 4: accuracy vs fault rate (ResNet18, 3 tools)
  bench_table2  — Table II: acc/lat/energy, 3 fault scenarios x 3 tools
  bench_kernels — fault-injection kernel path vs pure-jnp oracle
  bench_nsga2   — partitioner throughput (evaluations/sec, convergence)
  bench_surrogate — one-command surrogate pipeline: batched layer-wise
                  sensitivity profiling -> calibrated surrogate ->
                  full NSGA-II search + fidelity check
                  (``--surrogate [model]`` runs only this)
  bench_lm      — LM partitioning through the same pipeline as the
                  CNNs (``--lm [arch]`` runs only this): full-config
                  surrogate search over the analytic layer graph, plus
                  a reduced-config search with the TRUE staged
                  fault-injected evaluator in the NSGA-II loop when
                  ``lm_eval_strategy`` resolves the arch to "staged"

Flags: ``--paper`` (paper-scale pop/gens), ``--eval-batch-size N|auto``
(chromosomes per ΔAcc dispatch), ``--eval-strategy staged|full`` (ΔAcc
execution path; staged prefix-reuse is the CNN and small-LM default),
``--devices N|auto`` (shard ΔAcc dispatches over local devices —
bit-identical to one device, see core/eval_engine.DeviceScheduler).
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

RESULTS = os.path.join(os.path.dirname(__file__), "..", "results", "bench")

# quick mode (default) uses pop/gen 30/25; --paper uses the paper's 60/60
QUICK = "--paper" not in sys.argv
POP, GEN = (30, 25) if QUICK else (60, 60)
FAULT_RATE = 0.2


def _flag(name: str, default=None, cast=str):
    for i, arg in enumerate(sys.argv):
        if arg == name:
            if i + 1 >= len(sys.argv):
                sys.exit(f"{name} requires a value")
            return cast(sys.argv[i + 1])
        if arg.startswith(name + "="):
            return cast(arg.split("=", 1)[1])
    return default


def _int_flag(name: str, default=None):
    return _flag(name, default, cast=int)


def _ebs_flag(default=None):
    from repro.core.eval_engine import parse_eval_batch_size
    return parse_eval_batch_size(_flag("--eval-batch-size", default))


# cap chromosomes per ΔAcc device dispatch (memory knob, "auto" probes
# the compiled footprint; results unchanged — see core/eval_engine.py)
EVAL_BATCH = _ebs_flag()
# ΔAcc execution path: staged prefix-reuse (CNN default) or the full
# whole-forward batched path; bit-identical either way
EVAL_STRATEGY = _flag("--eval-strategy", "staged")


def _devices_flag(default="auto"):
    from repro.core.eval_engine import parse_devices
    return parse_devices(_flag("--devices", default))


# local devices the ΔAcc dispatches shard over ("auto" = all of them;
# single-device hosts degrade to the historical path, bit-identically)
EVAL_DEVICES = _devices_flag()


def _partitioners(name, params, fault_spec):
    from benchmarks._cnn_setup import make_evaluator
    from repro.core import (AFarePart, CNNPartedLike, FaultUnawareBaseline,
                            NSGA2Config, PAPER_DEVICES)
    from repro.models.cnn import CNN_MODELS

    layers = CNN_MODELS[name].layer_infos(num_classes=16, width=0.5, img=32)
    cfg = NSGA2Config(population=POP, generations=GEN, seed=0)
    ev = make_evaluator(name, params, fault_spec, eval_batch_size=EVAL_BATCH,
                        eval_strategy=EVAL_STRATEGY, devices=EVAL_DEVICES)
    # "auto" was already resolved (probe-compiled) inside make_evaluator;
    # hand the resolved value on so ObjectiveFn doesn't probe again
    ebs = ev.eval_batch_size if EVAL_BATCH == "auto" else EVAL_BATCH
    tools = {
        "CNNParted": CNNPartedLike(layers, PAPER_DEVICES, nsga2_config=cfg),
        "Flt-unaware": FaultUnawareBaseline(layers, PAPER_DEVICES,
                                            nsga2_config=cfg),
        "AFarePart": AFarePart(layers, PAPER_DEVICES, acc_evaluator=ev,
                               nsga2_config=cfg,
                               eval_batch_size=ebs),
    }
    return layers, {k: v.optimize() for k, v in tools.items()}, ev


_PLAN_CACHE: dict = {}


def _plans(name):
    from benchmarks._cnn_setup import get_trained
    from repro.core import FaultSpec
    if name not in _PLAN_CACHE:
        params = get_trained(name)
        spec = FaultSpec(weight_fault_rate=FAULT_RATE,
                         act_fault_rate=FAULT_RATE, bits=8)
        t0 = time.time()
        layers, plans, ev = _partitioners(name, params, spec)
        _PLAN_CACHE[name] = (params, layers, plans, ev, time.time() - t0)
    return _PLAN_CACHE[name]


def bench_fig3():
    """Fig. 3: Top-1 accuracy under 20 % weight faults."""
    from benchmarks._cnn_setup import accuracy_under_partition, clean_accuracy
    rows = {}
    for name in ("alexnet", "squeezenet", "resnet18"):
        params, layers, plans, ev, opt_s = _plans(name)
        clean = clean_accuracy(name, params)
        row = {"clean": clean}
        for tool, plan in plans.items():
            acc = accuracy_under_partition(name, params, plan.partition,
                                           weight_rate=FAULT_RATE,
                                           act_rate=0.0)
            row[tool] = acc
        rows[name] = row
        print(f"fig3.{name},{opt_s*1e6:.0f},clean={clean:.3f} " +
              " ".join(f"{t}={v:.3f}" for t, v in row.items() if t != "clean"))
    _dump("fig3", rows)
    return rows


def bench_fig4():
    """Fig. 4: accuracy vs weight-fault rate for ResNet18."""
    from benchmarks._cnn_setup import accuracy_under_partition
    params, layers, plans, ev, _ = _plans("resnet18")
    rows = {}
    for rate in (0.1, 0.2, 0.3, 0.4):
        t0 = time.time()
        row = {tool: accuracy_under_partition(
            name="resnet18", params=params, partition=plan.partition,
            weight_rate=rate, act_rate=0.0) for tool, plan in plans.items()}
        rows[f"{rate:.1f}"] = row
        print(f"fig4.fr{rate:.1f},{(time.time()-t0)*1e6:.0f}," +
              " ".join(f"{t}={v:.3f}" for t, v in row.items()))
    _dump("fig4", rows)
    return rows


def bench_table2():
    """Table II: acc/lat/energy under weight-only / input-only / both."""
    from benchmarks._cnn_setup import accuracy_under_partition
    scenarios = {"weight": (FAULT_RATE, 0.0), "input": (0.0, FAULT_RATE),
                 "both": (FAULT_RATE, FAULT_RATE)}
    out = {}
    for name in ("alexnet", "squeezenet", "resnet18"):
        params, layers, plans, ev, _ = _plans(name)
        out[name] = {}
        for tool, plan in plans.items():
            entry = {"latency_ms": plan.latency * 1e3,
                     "energy_mj": plan.energy * 1e3}
            for sc, (wr, ar) in scenarios.items():
                entry[f"acc_{sc}"] = accuracy_under_partition(
                    name, params, plan.partition, wr, ar)
            out[name][tool] = entry
            print(f"table2.{name}.{tool},{plan.latency*1e6:.1f},"
                  + " ".join(f"{k}={v:.4g}" for k, v in entry.items()))
    _dump("table2", out)
    return out


def bench_kernels():
    """Fused fault-injection kernel path vs oracle (CPU wall time; on TPU
    the same pallas_call lowers to Mosaic — see kernels/)."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops
    from repro.quant.fixedpoint import QuantSpec, quantize

    rng = np.random.default_rng(0)
    rows = {}
    x = jnp.asarray(rng.normal(size=(1024, 1024)), jnp.float32)

    def timeit(f, *a, n=20):
        f(*a)[0].block_until_ready() if isinstance(f(*a), tuple) else \
            f(*a).block_until_ready()
        t0 = time.perf_counter()
        for _ in range(n):
            r = f(*a)
        (r[0] if isinstance(r, tuple) else r).block_until_ready()
        return (time.perf_counter() - t0) / n * 1e6

    us = timeit(lambda: ops.quant_bitflip_ref(x, jnp.int32(1),
                                              jnp.float32(0.2), 4))
    rows["quant_bitflip_ref_1Mx4B"] = us
    print(f"kern.quant_bitflip_ref,{us:.0f},GBps={2*x.nbytes/us*1e6/1e9:.2f}")

    q = quantize(x)[0]
    us = timeit(lambda: ops.bitflip_ref(q, jnp.int32(1), jnp.float32(0.2), 4))
    rows["bitflip_ref_1Mx4B"] = us
    print(f"kern.bitflip_ref,{us:.0f},GBps={2*q.nbytes/us*1e6/1e9:.2f}")

    w = jnp.asarray(rng.normal(size=(1024, 1024)), jnp.float32)
    qw, scale = quantize(w, QuantSpec(16))
    xx = jnp.asarray(rng.normal(size=(256, 1024)), jnp.float32)
    us = timeit(lambda: ops.fault_matmul_ref(xx, qw, scale, jnp.int32(1),
                                             jnp.float32(0.2), 4))
    rows["fault_matmul_ref_256x1024x1024"] = us
    flops = 2 * 256 * 1024 * 1024
    print(f"kern.fault_matmul_ref,{us:.0f},GFLOPs={flops/us*1e6/1e9:.1f}")
    _dump("kernels", rows)
    return rows


def bench_nsga2():
    """Partitioner throughput and convergence."""
    from repro.core import CostModel, NSGA2Config, PAPER_DEVICES, nsga2
    from repro.core.objectives import ObjectiveFn, SurrogateAccuracyEvaluator
    from repro.models.cnn import ResNet18

    layers = ResNet18.layer_infos(num_classes=16, width=0.5, img=32)
    cm = CostModel(layers, PAPER_DEVICES)
    obj = ObjectiveFn(cm, SurrogateAccuracyEvaluator(cm))
    t0 = time.time()
    res = nsga2(obj, n_genes=len(layers), n_devices=2,
                config=NSGA2Config(population=60, generations=60, seed=0),
                violation_fn=obj.violation)
    dt = time.time() - t0
    evs = res.evaluations / dt
    print(f"nsga2.surrogate_60x60,{dt*1e6:.0f},evals_per_s={evs:.0f} "
          f"front={len(res.pareto_pop)}")
    _dump("nsga2", {"seconds": dt, "evals_per_s": evs,
                    "front_size": len(res.pareto_pop),
                    "history_first": list(map(float, res.history[0])),
                    "history_last": list(map(float, res.history[-1]))})
    return evs


def bench_surrogate(name: str = "resnet18"):
    """One-command surrogate pipeline (ROADMAP open item).

    Chains the pieces that previously required manual wiring:

      1. batched ``profile_layer_sensitivity`` (one vmapped sweep, the
         module-level compile cache makes repeat runs cheap);
      2. profiled sensitivities installed into the cost model's
         ``LayerInfo.sensitivity``;
      3. ``SurrogateAccuracyEvaluator.calibrate`` against a handful of
         true fault-injected evaluations (staged CNN evaluator);
      4. a full NSGA-II search on the calibrated surrogate;
      5. fidelity report: surrogate vs true ΔAcc on the found front.

    This is the exact recipe the transformer-scale archs use, exercised
    end to end on a CNN where the true evaluator exists to check it.
    """
    import dataclasses

    from benchmarks._cnn_setup import (eval_batch, get_trained,
                                       make_evaluator)
    from repro.core import (AFarePart, CostModel, FaultSpec, NSGA2Config,
                            PAPER_DEVICES, profile_layer_sensitivity)
    from repro.core.objectives import SurrogateAccuracyEvaluator
    from repro.models.cnn import CNN_MODELS

    model = CNN_MODELS[name]
    params = get_trained(name)
    spec = FaultSpec(weight_fault_rate=FAULT_RATE,
                     act_fault_rate=FAULT_RATE, bits=8)
    x, y = eval_batch(256)

    # pass the model's own (stable) apply so repeat pipeline runs hit
    # profile_layer_sensitivity's module-level compile cache — a fresh
    # closure per call would miss it every time
    t0 = time.time()
    sens = profile_layer_sensitivity(model.apply, params, x, y,
                                     model.n_units, spec)
    profile_s = time.time() - t0
    layers = [dataclasses.replace(li, sensitivity=float(s))
              for li, s in zip(model.layer_infos(num_classes=16, width=0.5,
                                                 img=32), sens)]

    true_ev = make_evaluator(name, params, spec, n_eval=256,
                             eval_batch_size=EVAL_BATCH,
                             eval_strategy=EVAL_STRATEGY,
                             devices=EVAL_DEVICES)
    cm = CostModel(layers, PAPER_DEVICES)
    sur = SurrogateAccuracyEvaluator(cm)
    t0 = time.time()
    calibration = sur.calibrate(true_ev.delta_acc, n_samples=8, seed=0)
    calibrate_s = time.time() - t0

    t0 = time.time()
    plan = AFarePart(layers, PAPER_DEVICES, acc_evaluator=sur,
                     nsga2_config=NSGA2Config(population=POP,
                                              generations=GEN,
                                              seed=0)).optimize()
    search_s = time.time() - t0

    true_front = true_ev.delta_acc(plan.front)
    sur_front = sur.delta_acc(plan.front)
    mae = float(np.abs(true_front - sur_front).mean())
    rec = {
        "model": name,
        "sensitivity": [float(s) for s in sens],
        "calibration": calibration,
        "front_size": len(plan.front),
        "front_mae": mae,
        "true_delta_acc_front": [float(v) for v in true_front],
        "surrogate_delta_acc_front": [float(v) for v in sur_front],
        "selected_partition": plan.partition.tolist(),
        "profile_s": profile_s, "calibrate_s": calibrate_s,
        "search_s": search_s, "evaluations": plan.evaluations,
    }
    print(f"surrogate.{name},{search_s*1e6:.0f},"
          f"cal={calibration:.4g} front={len(plan.front)} "
          f"front_mae={mae:.4f} profile_s={profile_s:.1f}")
    _dump("surrogate_pipeline", rec)
    return rec


def bench_lm(arch: str = "olmo-1b"):
    """LM partitioning end to end — no CNN/LM split (ISSUE 3).

    Two searches through ``core.partitioner.lm_partitioner``:

      1. the FULL config's analytic layer graph with the sensitivity
         surrogate — the only option at 27-480B scale, and what
         ``models.graph.lm_eval_strategy`` resolves for those configs;
      2. when the policy resolves the arch to "staged": a reduced-scale
         search with the TRUE staged fault-injected evaluator in the
         NSGA-II loop (``make_lm_accuracy_evaluator``; INT8-class fault
         regime; labels = clean model's own argmax), reporting the
         prefix-reuse accounting alongside the front.
    """
    from repro.configs import get_config
    from repro.core import FaultSpec, NSGA2Config, lm_partitioner
    from repro.core.costmodel import POD_TIERS_4
    from repro.core.objectives import make_lm_accuracy_evaluator
    from repro.models.graph import lm_eval_strategy
    from repro.testing.lm_harness import lm_calibration_setup

    cfg_full = get_config(arch)
    policy = lm_eval_strategy(cfg_full)
    nsga = NSGA2Config(population=POP, generations=GEN, seed=0)

    t0 = time.time()
    plan_sur = lm_partitioner(cfg_full, nsga2_config=nsga).optimize()
    sur_s = time.time() - t0
    print(f"lm.{arch}.surrogate,{sur_s*1e6:.0f},"
          f"policy={policy} front={len(plan_sur.front)} "
          f"lat_ms={plan_sur.latency*1e3:.3g} dacc={plan_sur.delta_acc:.4g}")

    rec = {"arch": arch, "policy": policy,
           "surrogate": {"front_size": len(plan_sur.front),
                         "latency_ms": plan_sur.latency * 1e3,
                         "energy_mj": plan_sur.energy * 1e3,
                         "delta_acc": plan_sur.delta_acc,
                         "partition": plan_sur.partition.tolist(),
                         "seconds": sur_s}}

    if policy == "staged":
        cfg = cfg_full.reduced()
        S = 16
        spec = FaultSpec(weight_fault_rate=0.2, act_fault_rate=0.2, bits=8)
        scale = np.array([d.fault_scale for d in POD_TIERS_4])
        params, batch, labels = lm_calibration_setup(cfg, S=S)
        ev = make_lm_accuracy_evaluator(
            cfg, params, batch, labels, spec, scale,
            eval_batch_size=EVAL_BATCH, eval_strategy=EVAL_STRATEGY,
            devices=EVAL_DEVICES)
        t0 = time.time()
        plan = lm_partitioner(cfg, ev, seq=S, nsga2_config=nsga).optimize()
        staged_s = time.time() - t0
        st = ev.staged_stats()
        rec["staged_reduced"] = {
            "n_units": ev._n_units, "front_size": len(plan.front),
            "delta_acc": plan.delta_acc,
            "partition": plan.partition.tolist(),
            "clean_accuracy": ev.clean_accuracy(),
            "seconds": staged_s, "staged_stats": st}
        print(f"lm.{arch}.staged_reduced,{staged_s*1e6:.0f},"
              f"front={len(plan.front)} dacc={plan.delta_acc:.4g} "
              f"unit_runs={st.get('unit_runs', 0)}/"
              f"{st.get('full_unit_runs', 0)}")
    _dump(f"lm_partition_{arch.replace('.', 'p')}", rec)
    return rec


def _dump(name, obj):
    os.makedirs(RESULTS, exist_ok=True)
    with open(os.path.join(RESULTS, f"{name}.json"), "w") as f:
        json.dump(obj, f, indent=1, default=float)


def _optional_value(flag: str) -> str | None:
    """Value of ``--flag [value]`` / ``--flag=value`` style arguments."""
    value = None
    for i, a in enumerate(sys.argv):
        if a.startswith(flag + "="):
            value = a.split("=", 1)[1]
        elif (a == flag and i + 1 < len(sys.argv)
              and not sys.argv[i + 1].startswith("-")):
            value = sys.argv[i + 1]
    return value


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache(__file__)
    print("# benchmark,us_per_call,derived")
    if any(a == "--surrogate" or a.startswith("--surrogate=")
           for a in sys.argv):
        bench_surrogate(_optional_value("--surrogate") or "resnet18")
        return
    if any(a == "--lm" or a.startswith("--lm=") for a in sys.argv):
        bench_lm(_optional_value("--lm") or "olmo-1b")
        return
    bench_kernels()
    bench_nsga2()
    bench_fig3()
    bench_fig4()
    bench_table2()


if __name__ == "__main__":
    main()
