"""Trace-driven serving benchmark: the paper's online phase end to end.

    PYTHONPATH=src python -m benchmarks.serve [--smoke] [--acc staged] ...

Replays a Poisson arrival trace through the continuous-batching engine
(``serve.Engine``) with a scheduled fault-injection environment behind
the telemetry monitor (``serve.monitor.FaultMonitor``): per-device
error counts are sampled from the *true* environment, the monitor
estimates fault scales by EWMA, the canary observes the deployed
partition's ΔAcc under the estimates, and the re-optimization runs one
NSGA-II generation per decode step off the critical path.  The
schedule contains two events:

  1. the reliable tier degrades hard (DEGRADED) — the canary trips θ
     and a hot swap moves layers off the glitching tier;
  2. the same tier fails outright (CRITICAL) — the engine reverts to
     the last-known-safe partition within one decode step, then
     re-optimizes again under the new estimates.

Reports goodput, p50/p99 request latency, TTFT/TPOT, queue depth,
swaps/reverts, and observed ΔAcc-under-fault before/after each swap to
results/bench/serving.json (EXPERIMENTS.md has the full schema).

With ``--smoke`` the run doubles as the CI guard and FAILS if:
  * any in-flight request is dropped (must be zero, always);
  * no hot swap happened, or any re-optimization swap did not strictly
    improve observed ΔAcc (post >= pre);
  * the worst swap stall exceeds max(one mean decode step, 5 ms);
  * monitor overhead reaches 5 % of decode wall-clock.

``--acc staged`` swaps the surrogate ΔAcc observer for the true
staged fault-injection evaluator (``make_lm_accuracy_evaluator``) on a
deepened reduced LM — slower, used by the nightly lane.

``--backend generic|tables|pallas`` picks the evaluator's fault
backend (implies ``--acc staged``).  Under ``pallas`` the fault rates
are traced arguments, so the canary's per-swap
``device_fault_scale = ...`` hot-swaps reuse every compiled
executable; with ``--smoke`` the run additionally FAILS unless at
least one fault-environment change actually happened during the trace
and the evaluator recorded zero rebuilds across all of them.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

RESULTS = os.path.join(os.path.dirname(__file__), "..", "results", "bench")


def build_system(args):
    import jax
    from repro.configs import get_config
    from repro.core import (CostModel, FaultSpec, NSGA2Config,
                            OnlineReconfigurator, POD_TIERS,
                            SurrogateAccuracyEvaluator, lm_partitioner,
                            make_lm_accuracy_evaluator)
    from repro.models.graph import lm_layer_infos
    from repro.models.transformer import init_lm
    from repro.testing.lm_harness import lm_calibration_setup

    cfg = dataclasses.replace(get_config(args.arch).reduced(),
                              n_layers=args.units)
    params = init_lm(cfg, jax.random.PRNGKey(0))
    base_scale = np.array([d.fault_scale for d in POD_TIERS])
    spec = FaultSpec(weight_fault_rate=0.2, act_fault_rate=0.2, bits=8)
    nsga2_cfg = NSGA2Config(population=16, generations=8, seed=args.seed)

    # counts true fault-environment changes the canary pushed into the
    # evaluator (successive distinct scale vectors) — the pallas smoke
    # guard checks these were absorbed without a rebuild
    env_swaps = {"n": 0, "last": None}

    if args.acc == "staged":
        cal_params, cal_batch, cal_labels = lm_calibration_setup(
            cfg, B=2, S=8, seed=7)
        ev = make_lm_accuracy_evaluator(
            cfg, cal_params, cal_batch, cal_labels, spec,
            device_fault_scale=base_scale.astype(np.float32),
            fault_backend=getattr(args, "backend", None) or "auto")
        part = lm_partitioner(cfg, ev, devices=POD_TIERS, seq=64,
                              fault_spec=spec, nsga2_config=nsga2_cfg)

        def observe(partition, scales):
            sc = np.asarray(scales, np.float32)
            if env_swaps["last"] is not None and \
                    not np.array_equal(sc, env_swaps["last"]):
                env_swaps["n"] += 1
            env_swaps["last"] = sc.copy()
            ev.device_fault_scale = sc
            return float(ev.delta_acc(np.asarray(partition)[None, :])[0])
    else:
        layers = lm_layer_infos(cfg, seq=64)
        cm = CostModel(layers, POD_TIERS)
        ev = SurrogateAccuracyEvaluator(cm)
        part = lm_partitioner(cfg, ev, devices=POD_TIERS, seq=64,
                              fault_spec=spec, nsga2_config=nsga2_cfg)

        def observe(partition, scales):
            old = cm.fault_scale.copy()
            cm.fault_scale = np.asarray(scales, float)
            v = float(cm.sensitivity_surrogate(
                np.asarray(partition)[None, :])[0])
            cm.fault_scale = old
            return v

    def partition_to_rates(partition, scales):
        sc = np.asarray(scales if scales is not None else base_scale)
        r = sc[np.asarray(partition)]
        return ((spec.weight_fault_rate * r).astype(np.float32),
                (spec.act_fault_rate * r).astype(np.float32))

    return (cfg, params, base_scale, part, observe, partition_to_rates,
            ev, env_swaps)


def run_trace(args):
    from repro.core import FaultEnvironment, OnlineReconfigurator
    from repro.serve import (Engine, FaultMonitor, MonitorConfig, Request,
                             ServeConfig)

    cfg, params, base_scale, part, observe, p2r, ev, env_swaps = \
        build_system(args)
    plan = part.optimize()

    # fault schedule: tier 1 (the reliable one the plan leans on)
    # degrades x64 at t1, then fails outright (another x8) at t2
    t1, t2 = args.steps // 4, (2 * args.steps) // 3
    env = FaultEnvironment(
        base_scale=base_scale,
        schedule={t1: base_scale * np.array([1.0, 64.0]),
                  t2: base_scale * np.array([1.0, 512.0])})

    # θ must sit above the best ΔAcc a re-opt can reach under the degraded
    # environment, or the canary re-triggers forever on equally-good
    # partitions (see docs/SERVING.md "Choosing θ")
    theta = observe(plan.partition, base_scale) * args.theta_mult + 1e-9
    rec = OnlineReconfigurator(part, plan, theta=theta, observe_fn=observe,
                               reopt_generations=args.reopt_generations)
    mcfg = MonitorConfig(base_error_rate=50.0, ewma_alpha=0.25,
                         scale_quantum=0.05, degraded_factor=4.0,
                         critical_factor=100.0, recovery_ticks=8,
                         watchdog_timeout_ticks=1000)
    mon = FaultMonitor(base_scale, mcfg)

    err_rng = np.random.default_rng(args.seed + 1)

    def error_source(tick):
        true = env.scales_at(tick)
        return err_rng.poisson(mcfg.base_error_rate * true)

    eng = Engine(cfg, params,
                 ServeConfig(max_batch=args.max_batch, max_len=64,
                             canary_every=args.canary_every,
                             pipeline_stages=2),
                 reconfigurator=rec, partition_to_rates=p2r,
                 monitor=mon, error_source=error_source)

    # Poisson arrival trace, precomputed (deterministic given --seed)
    trace_rng = np.random.default_rng(args.seed + 2)
    arrivals: list[tuple[int, Request]] = []
    uid = 0
    for t in range(args.steps):
        for _ in range(trace_rng.poisson(args.rate)):
            prompt = trace_rng.integers(
                0, cfg.vocab, int(trace_rng.integers(4, 13))
            ).astype(np.int32)
            arrivals.append((t, Request(
                uid=uid, prompt=prompt,
                max_new_tokens=int(trace_rng.integers(8, 17)))))
            uid += 1

    wall0 = time.perf_counter()
    ai = 0
    for t in range(args.steps):
        while ai < len(arrivals) and arrivals[ai][0] <= t:
            eng.submit(arrivals[ai][1])
            ai += 1
        eng.step()
    eng.run()                     # drain the tail under the final scales
    wall_s = time.perf_counter() - wall0

    stats = eng.stats()
    done = sorted(eng.completed, key=lambda r: r.uid)
    lat = np.array([r.finish_s - r.submit_s for r in done])
    ttft = np.array([r.ttft_s for r in done])
    tokens = sum(len(r.out) for r in done)
    reopts = [e for e in eng.swap_events if e["kind"] == "reopt"]

    rec_out = {
        "config": {"arch": args.arch, "units": args.units,
                   "acc": args.acc, "steps": args.steps,
                   "rate": args.rate, "max_batch": args.max_batch,
                   "canary_every": args.canary_every,
                   "reopt_generations": args.reopt_generations,
                   "seed": args.seed, "theta": theta,
                   "fault_schedule": {str(k): v.tolist()
                                      for k, v in env.schedule.items()}},
        "requests": len(done),
        "tokens": tokens,
        "wall_s": wall_s,
        "goodput_tok_s": tokens / wall_s,
        "latency_s": {"p50": float(np.percentile(lat, 50)),
                      "p99": float(np.percentile(lat, 99)),
                      "mean": float(lat.mean())},
        "ttft_s": {"p50": float(np.percentile(ttft, 50)),
                   "p99": float(np.percentile(ttft, 99))},
        "stats": stats,
        "monitor": mon.stats(),
        "swap_events": [
            {k: (v.tolist() if isinstance(v, np.ndarray) else v)
             for k, v in e.items() if k != "migration"}
            | ({"migrated_groups": e["migration"]["migrated_groups"]}
               if "migration" in e else {})
            for e in eng.swap_events],
        "observed_delta_acc": [
            {"step": s, "delta": d} for s, d in eng.observed_log],
        "fault_env": {
            "backend": getattr(ev, "fault_backend", None),
            "scale_changes": env_swaps["n"],
            "evaluator_rebuilds": getattr(ev, "_fault_env_rebuilds", None),
        },
    }
    return rec_out


def main():
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache(__file__)
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--smoke", action="store_true",
                    help="CI mode: guards fail the run")
    ap.add_argument("--acc", choices=["surrogate", "staged"],
                    default="surrogate")
    ap.add_argument("--backend", choices=["generic", "tables", "pallas"],
                    default=None,
                    help="fault backend for the staged ΔAcc evaluator "
                         "(implies --acc staged); with --smoke and "
                         "pallas, fail unless the canary's fault-scale "
                         "hot-swaps rebuilt nothing")
    ap.add_argument("--arch", default="olmo-1b")
    ap.add_argument("--units", type=int, default=6)
    ap.add_argument("--steps", type=int, default=120)
    ap.add_argument("--rate", type=float, default=0.25,
                    help="Poisson arrivals per engine step")
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--canary-every", type=int, default=8)
    ap.add_argument("--reopt-generations", type=int, default=6)
    ap.add_argument("--theta-mult", type=float, default=5.0,
                    help="theta = clean-baseline observed ΔAcc x this")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=os.path.join(RESULTS, "serving.json"))
    args = ap.parse_args()
    if args.backend:
        args.acc = "staged"

    rec = run_trace(args)
    s = rec["stats"]
    print("# benchmark,value,derived")
    print(f"serving.goodput_tok_s,{rec['goodput_tok_s']:.1f},"
          f"{rec['tokens']} tok / {rec['wall_s']:.2f} s")
    print(f"serving.latency_p50_s,{rec['latency_s']['p50']:.4f},"
          f"p99={rec['latency_s']['p99']:.4f}")
    print(f"serving.ttft_p50_s,{rec['ttft_s']['p50']:.4f},"
          f"p99={rec['ttft_s']['p99']:.4f}")
    print(f"serving.swaps,{s['swaps']},reverts={s['reverts']} "
          f"dropped={s['dropped']}")
    fe = rec["fault_env"]
    if fe["backend"] is not None:
        print(f"serving.fault_env,{fe['backend']},"
              f"scale_changes={fe['scale_changes']} "
              f"evaluator_rebuilds={fe['evaluator_rebuilds']}")
    for e in rec["swap_events"]:
        print(f"serving.swap@{e['step']},{e['kind']},"
              f"pre={e['pre_delta']} post={e['post_delta']} "
              f"stall_s={e['stall_s']:.2e}")

    os.makedirs(RESULTS, exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(rec, f, indent=1, default=float)
    print(f"wrote {args.out}")

    if args.smoke:
        ok = True
        if s["dropped"] != 0:
            print(f"FAIL: {s['dropped']} in-flight requests dropped "
                  "(must be zero)")
            ok = False
        reopts = [e for e in rec["swap_events"] if e["kind"] == "reopt"]
        if not reopts:
            print("FAIL: fault schedule completed without a hot swap")
            ok = False
        for e in reopts:
            if not (e["post_delta"] is not None and e["pre_delta"] is not None
                    and e["post_delta"] < e["pre_delta"]):
                print(f"FAIL: swap at step {e['step']} did not strictly "
                      f"improve ΔAcc (pre={e['pre_delta']} "
                      f"post={e['post_delta']})")
                ok = False
        step_s = s["decode_s"] / max(s["decode_steps"], 1)
        stall_bound = max(step_s, 5e-3)
        if s["swap_stall_s_max"] > stall_bound:
            print(f"FAIL: swap stall {s['swap_stall_s_max']:.2e} s exceeds "
                  f"bound {stall_bound:.2e} s (one decode step)")
            ok = False
        if s["monitor_s"] >= 0.05 * s["decode_s"]:
            print(f"FAIL: monitor overhead {s['monitor_s']:.3f} s is >= 5% "
                  f"of decode wall-clock {s['decode_s']:.3f} s")
            ok = False
        if args.backend == "pallas":
            if fe["scale_changes"] < 1:
                print("FAIL: trace completed without a single "
                      "fault-environment change — the hot-swap claim "
                      "was never exercised")
                ok = False
            if fe["evaluator_rebuilds"] != 0:
                print(f"FAIL: pallas evaluator rebuilt executables "
                      f"{fe['evaluator_rebuilds']} time(s) across "
                      f"{fe['scale_changes']} fault-scale changes "
                      "(rates are traced arguments — must be zero)")
                ok = False
        if not ok:
            sys.exit(1)
        print("smoke guards OK: zero drops, strict post-swap ΔAcc "
              "improvement, stall and monitor-overhead bounds hold")


if __name__ == "__main__":
    main()
