"""Microbenchmark: per-candidate ΔAcc evaluation latency, loop vs batched.

    PYTHONPATH=src python -m benchmarks.eval_engine [--smoke] [--paper] ...

Times three implementations of the NSGA-II inner loop (paper Alg. 1
lines 5-7) on one population of unique chromosomes:

  loop       — the historical path: one jitted dispatch + host sync per
               individual (what ``delta_acc`` did before the engine);
  batched    — one ``jit(vmap)`` dispatch over the whole population
               (generic per-layer rate vectors);
  batched+tables — the PR-1 full-forward path: weight corruption
               pre-computed per (layer, device) and gathered per
               candidate, so the per-candidate PRNG hashing is
               amortised away entirely (bit-identical; see
               models/cnn.build_weight_fault_tables);
  staged     — the prefix-reuse engine (PrefixEvalEngine): the model is
               walked unit by unit and each unique gene *prefix* is
               evaluated once, so per-generation cost scales with
               unique prefixes instead of unique_rows x L unit runs.

All paths produce bit-identical ΔAcc vectors (asserted here and locked
in by tests/test_eval_engine.py + tests/test_staged_eval.py); only the
latency differs.

A generational scenario (``run_generational``) replays the exact
population sequence of a converging NSGA-II search — where prefix
sharing emerges — through the PR-1 full-forward path and the staged
engine, reporting per-candidate latency, unit-runs-avoided and prefix
hit rate to results/bench/prefix_reuse.json.  With ``--smoke`` this
doubles as the CI regression guard: the run FAILS if the staged path
executes more unit runs than the full path would, or if the sharded
path dispatches more chunks than ``ceil(U / per_device_batch) x
devices``.

``--devices N|auto`` shards every evaluator's ΔAcc dispatches over N
local devices (``core.eval_engine.DeviceScheduler``; combine with
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` for fake host
devices) — bit-identical to one device, asserted here like every other
path equality.

``--lm [arch]`` runs the same generational replay on a transformer
config (reduced scale, per-unit step API via
``models.transformer.LMStepModel``, INT8-class fault regime, 4 pod
tiers) and writes results/bench/prefix_reuse_lm.json.  Its ``--smoke``
guard is stricter: the replay must avoid >= 30 % of the unit runs the
full-forward path would execute (ISSUE 3 acceptance criterion).

``--fused`` runs ONLY the chain-fusion comparison (``run_chain_fusion``):
the converged pop-60 replay — a deep reduced LM (24 units), converged
survivors plus point mutants per round, the online-reoptimisation
regime where the prefix trie is mostly non-branching chains — through
the staged path with ``fuse_chains=False`` vs ``True``, bit-identical
per round, writing results/bench/chain_fusion.json.  Its ``--smoke``
guards fail if the fused path issues more than HALF the unfused path's
engine dispatches (ISSUE 5 acceptance criterion) or exceeds the
span-ladder dispatch bound
``branch_nodes + chains x ceil(log2(max_chain))``.  Combine with
``--lm ARCH`` to pick a different architecture.

``--backend tables|pallas`` runs ONLY the fault-backend comparison
(``run_fault_backend``): the O(L×D) weight-table path vs the in-tile
pallas path at pop 60, bit-identical ΔAcc asserted, reporting
per-candidate wall-clock, compiled peak memory, resident fault-state
bytes and the cost of a fault-environment change, to
results/bench/fault_backend.json.  ``--smoke --backend pallas`` is the
CI guard: it FAILS if the pallas evaluator holds any resident
fault-table bytes, if its eval HBM footprint (dispatch I/O + resident
fault state) is not strictly below the tables path's, or if an
environment change rebuilt any executable.

The default configuration is the *dispatch-bound* regime — a small
calibration batch, the regime an edge-accelerator deployment sees where
a forward pass is microseconds and per-candidate dispatch overhead
dominates (the speedup headline tracked by CI).  ``--paper`` switches
to the paper-scale 512-sample calibration batch where the evaluation is
compute-bound on CPU and the win comes from dedup/caching instead.

A second scenario re-times the engine on a population with duplicate
chromosomes plus a warm cache (what NSGA-II populations actually look
like after a few generations) to report the dedup/cache effect.

Writes results/bench/eval_engine.json and prints the scaffold's
``name,us_per_call,derived`` CSV lines.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

RESULTS = os.path.join(os.path.dirname(__file__), "..", "results", "bench")


def run_benchmark(model_name: str = "alexnet", pop: int = 60, n_eval: int = 1,
                  width: float = 0.125, img: int = 16, reps: int = 3,
                  eval_batch_size: int | None = None, seed: int = 0,
                  devices: int | str = "auto") -> dict:
    import jax
    import jax.numpy as jnp
    from repro.core import FaultSpec, InferenceAccuracyEvaluator
    from repro.core.costmodel import PAPER_DEVICES
    from repro.models.cnn import CNN_MODELS, build_weight_fault_tables

    model = CNN_MODELS[model_name]
    L = model.n_units
    scale = np.array([d.fault_scale for d in PAPER_DEVICES])
    spec = FaultSpec(weight_fault_rate=0.2, act_fault_rate=0.2)
    rng = np.random.default_rng(seed)

    # untrained params: latency does not depend on the weights' values
    params = model.init(jax.random.PRNGKey(0), num_classes=16, width=width,
                        img=img)
    x = jnp.asarray(rng.normal(size=(n_eval, img, img, 3)), jnp.float32)
    labels = jnp.asarray(rng.integers(0, 16, size=(n_eval,)))

    def apply_fn(p, xx, wr, ar, s):
        return model.apply(p, xx, w_rates=wr, a_rates=ar, seed=s)

    def fresh(weight_tables=None, staged=False):
        return InferenceAccuracyEvaluator(
            apply_fn, params, x, labels, spec, scale,
            eval_batch_size=eval_batch_size, weight_tables=weight_tables,
            step_fn=model.step if staged else None,
            eval_strategy="staged" if staged else "full",
            devices=devices)

    # unique chromosomes only: no dedup/cache help for any path, so the
    # headline number isolates the engine itself
    seen, rows = set(), []
    while len(rows) < pop:
        r = tuple(rng.integers(0, len(scale), size=L).tolist())
        if r not in seen:
            seen.add(r)
            rows.append(r)
    P = np.array(rows)

    t0 = time.perf_counter()
    w_rates = np.asarray(spec.weight_fault_rate
                         * np.asarray(scale, np.float32), np.float32)
    tables = build_weight_fault_tables(params, w_rates, base_seed=0)
    table_build_s = time.perf_counter() - t0

    ev_loop = fresh()
    ev_vmap = fresh()
    ev_tab = fresh(weight_tables=tables)
    ev_st = fresh(weight_tables=tables, staged=True)

    from repro.testing.reference import loop_delta_acc as loop_path

    def timeit(fn, clear_caches):
        best = np.inf
        val = None
        for _ in range(reps):
            clear_caches()
            t0 = time.perf_counter()
            val = fn()
            best = min(best, time.perf_counter() - t0)
        return best, val

    # warm up every executable (compile outside the timed region)
    loop_path(ev_loop, P[:1])
    ev_vmap.delta_acc(P)
    ev_tab.delta_acc(P)
    ev_st.delta_acc(P)

    t_loop, v_loop = timeit(lambda: loop_path(ev_loop, P), lambda: None)
    d0 = ev_vmap.dispatches
    t_vmap, v_vmap = timeit(lambda: ev_vmap.delta_acc(P),
                            lambda: ev_vmap._cache.clear())
    vmap_dispatches = (ev_vmap.dispatches - d0) // reps
    d0 = ev_tab.dispatches
    t_tab, v_tab = timeit(lambda: ev_tab.delta_acc(P),
                          lambda: ev_tab._cache.clear())
    tab_dispatches = (ev_tab.dispatches - d0) // reps
    # clearing the staged engine drops BOTH the row cache and the
    # activation store, so each rep recomputes every prefix honestly
    t_st, v_st = timeit(lambda: ev_st.delta_acc(P),
                        lambda: ev_st._prefix_engine.clear())
    staged_stats = ev_st.staged_stats()

    assert (v_loop == v_vmap).all() and (v_loop == v_tab).all() \
        and (v_loop == v_st).all(), \
        "batched/staged paths must be bit-identical to the loop"

    # scenario 2: realistic converging population (duplicates + warm cache)
    P_dup = np.repeat(P[:max(1, pop // 6)], 6, axis=0)[:pop]
    ev_tab.delta_acc(P_dup)                      # warm the cache
    d0 = ev_tab.dispatches
    t0 = time.perf_counter()
    ev_tab.delta_acc(P_dup)
    t_cached = time.perf_counter() - t0
    cached_dispatches = ev_tab.dispatches - d0

    rec = {
        "config": {"model": model_name, "pop": pop, "n_eval": n_eval,
                   "width": width, "img": img, "reps": reps,
                   "eval_batch_size": eval_batch_size,
                   "n_devices": len(scale),
                   "eval_devices": ev_tab.devices},
        "per_candidate_ms": {
            "loop": t_loop / pop * 1e3,
            "batched": t_vmap / pop * 1e3,
            "batched_tables": t_tab / pop * 1e3,
            "staged": t_st / pop * 1e3,
            "cached_population": t_cached / pop * 1e3,
        },
        "speedup_vs_loop": {
            "batched": t_loop / t_vmap,
            "batched_tables": t_loop / t_tab,
            "staged": t_loop / t_st,
        },
        "dispatches": {"loop": pop, "batched": vmap_dispatches,
                       "batched_tables": tab_dispatches,
                       "cached_population": cached_dispatches},
        "staged": staged_stats,
        "table_build_s": table_build_s,
    }
    return rec


def run_fault_backend(model_name: str = "alexnet", pop: int = 60,
                      n_eval: int = 1, width: float = 0.125, img: int = 16,
                      reps: int = 3, seed: int = 0,
                      devices: int | str = "auto") -> dict:
    """``tables`` vs ``pallas`` fault backends on one pop-``pop``
    population (the ISSUE 7 tentpole comparison).

    The tables path pre-corrupts every (unit, device) weight variant —
    O(params × devices) resident float copies gathered per candidate.
    The pallas path keeps ONE resident int8 ``QTensor`` copy and flips
    bits inside the compute (``kernels.ops.fault_matmul``), so its
    resident fault state is O(params) and independent of the device
    ladder.  Both produce bit-identical ΔAcc (asserted here and pinned
    by tests/test_fault_backends.py); this scenario reports what
    differs: per-candidate wall-clock, compiled peak memory at the full
    population batch, resident fault-state bytes, and what a
    fault-environment change costs (pallas: nothing is rebuilt).

    Memory accounting: ``eval_hbm_bytes`` is the eval-time HBM
    footprint — dispatch argument + output buffers plus the resident
    fault state the evaluator keeps alive between dispatches (float
    weight-variant tables vs one int8 QTensor copy).  The raw
    ``compiled_peak_bytes`` (includes XLA temps) is reported alongside
    but NOT compared: on CPU CI the pallas path runs the exact
    interpret-mode composition, whose per-row corrupted-weight temps
    are an emulation artifact — the fused tile keeps that state in
    VMEM tiles and never writes it to HBM (kernels/ops.py).

    The ``--smoke --backend pallas`` CI guards:
      * the pallas evaluator must hold ZERO resident fault-table bytes;
      * its eval HBM footprint must be STRICTLY below the tables
        path's at the same population;
      * a fault-environment change must rebuild nothing.
    """
    import jax
    import jax.numpy as jnp
    from repro.core import FaultSpec, InferenceAccuracyEvaluator
    from repro.core.costmodel import PAPER_DEVICES
    from repro.core.eval_engine import peak_memory_bytes
    from repro.models.cnn import (CNN_MODELS, build_weight_fault_tables,
                                  quantize_unit_params)

    model = CNN_MODELS[model_name]
    L = model.n_units
    scale = np.array([d.fault_scale for d in PAPER_DEVICES], np.float32)
    spec = FaultSpec(weight_fault_rate=0.2, act_fault_rate=0.2)
    rng = np.random.default_rng(seed)

    params = model.init(jax.random.PRNGKey(0), num_classes=16, width=width,
                        img=img)
    x = jnp.asarray(rng.normal(size=(n_eval, img, img, 3)), jnp.float32)
    labels = jnp.asarray(rng.integers(0, 16, size=(n_eval,)))

    def apply_fn(p, xx, wr, ar, s):
        return model.apply(p, xx, w_rates=wr, a_rates=ar, seed=s)

    t0 = time.perf_counter()
    w_rates = np.asarray(spec.weight_fault_rate * scale, np.float32)
    tables = build_weight_fault_tables(params, w_rates, base_seed=0)
    table_build_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    qp = quantize_unit_params(params)
    quantize_s = time.perf_counter() - t0

    ev_tab = InferenceAccuracyEvaluator(
        apply_fn, params, x, labels, spec, scale, weight_tables=tables,
        fault_backend="tables", devices=devices)
    ev_pal = InferenceAccuracyEvaluator(
        apply_fn, params, x, labels, spec, scale, quant_params=qp,
        fault_backend="pallas", devices=devices)

    seen, rows = set(), []
    while len(rows) < pop:
        r = tuple(rng.integers(0, len(scale), size=L).tolist())
        if r not in seen:
            seen.add(r)
            rows.append(r)
    P = np.array(rows)

    v_tab = ev_tab.delta_acc(P)          # warm (compiles excluded below)
    v_pal = ev_pal.delta_acc(P)
    assert (v_tab == v_pal).all(), \
        "fault backends must be bit-identical (tables vs pallas)"

    def timeit(ev):
        best = np.inf
        for _ in range(reps):
            ev._cache.clear()
            t0 = time.perf_counter()
            ev.delta_acc(P)
            best = min(best, time.perf_counter() - t0)
        return best

    t_tab = timeit(ev_tab)
    t_pal = timeit(ev_pal)

    # memory at the full population batch: dispatch I/O + resident
    # fault state (the HBM footprint), with the raw compiled peak
    # alongside — see the docstring for why the peak is not compared
    def io_bytes(compiled):
        m = compiled.memory_analysis()    # a backend without one fails here
        return sum(int(getattr(m, f)) for f in
                   ("argument_size_in_bytes", "output_size_in_bytes"))

    seed32 = jnp.int32(0)
    tab_exec = ev_tab._acc_batch_tables.lower(
        jnp.zeros((pop, L), jnp.int32), seed32).compile()
    zd = jnp.zeros((len(scale),), jnp.float32)
    pal_exec = ev_pal._ensure_pallas_batch().lower(
        jnp.zeros((pop, L), jnp.int32), zd, zd, seed32).compile()

    mem = {
        "tables": {"fault_table_bytes": ev_tab.fault_table_bytes(),
                   "fault_state_bytes": ev_tab.fault_state_bytes(),
                   "compiled_peak_bytes": peak_memory_bytes(tab_exec),
                   "eval_hbm_bytes": (io_bytes(tab_exec)
                                      + ev_tab.fault_state_bytes())},
        "pallas": {"fault_table_bytes": ev_pal.fault_table_bytes(),
                   "fault_state_bytes": ev_pal.fault_state_bytes(),
                   "compiled_peak_bytes": peak_memory_bytes(pal_exec),
                   "eval_hbm_bytes": (io_bytes(pal_exec)
                                      + ev_pal.fault_state_bytes())},
    }

    # a fault-environment change: pallas rebuilds nothing, tables must
    # drop its variants (degrading to generic until rebuilt)
    ev_pal.device_fault_scale = scale * 0.5
    ev_tab.device_fault_scale = scale * 0.5
    env_change = {
        "pallas_rebuilds": ev_pal._fault_env_rebuilds,
        "tables_rebuilds": ev_tab._fault_env_rebuilds,
        "tables_backend_after": ev_tab.fault_backend,
        "table_build_s": table_build_s,
        "quantize_s": quantize_s,
    }

    return {
        "config": {"model": model_name, "pop": pop, "n_eval": n_eval,
                   "width": width, "img": img, "reps": reps, "seed": seed,
                   "n_devices": len(scale), "eval_devices": ev_pal.devices},
        "per_candidate_ms": {"tables": t_tab / pop * 1e3,
                             "pallas": t_pal / pop * 1e3},
        "pallas_speedup_vs_tables": t_tab / t_pal,
        "memory_bytes": mem,
        "env_change": env_change,
        "bitwise_equal": True,
    }


def _trace_nsga2(layers, devices, pop, gens, seed):
    """Record the exact population sequence a converging NSGA-II search
    evaluates (selection driven by the calibrated-surrogate objective:
    cheap, deterministic, converging like the real search)."""
    from repro.core import CostModel, NSGA2Config, nsga2
    from repro.core.objectives import ObjectiveFn, SurrogateAccuracyEvaluator

    cm = CostModel(layers, devices)
    obj = ObjectiveFn(cm, SurrogateAccuracyEvaluator(cm))
    trace: list[np.ndarray] = []

    def recording(P):
        trace.append(np.asarray(P).copy())
        return obj(P)

    nsga2(recording, n_genes=len(layers), n_devices=len(devices),
          config=NSGA2Config(population=pop, generations=gens, seed=seed),
          violation_fn=obj.violation)
    return trace


# lifetime gauges (running maxima), not cumulative counters: reported
# as-is by _replay instead of as warm-vs-timed deltas
_GAUGES = {"max_chain"}


def _replay(ev, trace, clear, stats_fn):
    """Warm every bucket shape, drop caches, then time a full replay of
    the traced population sequence; returns (seconds, values, counter
    deltas).  For staged evaluators the deltas get their own
    ``prefix_hit_rate`` (the timed pass's rate, not lifetime — same
    formula as PrefixEvalEngine.stats)."""
    for P in trace:
        ev.delta_acc(P)
    clear()
    before = dict(stats_fn())
    vals = []
    t0 = time.perf_counter()
    for P in trace:
        vals.append(ev.delta_acc(P))
    dt = time.perf_counter() - t0
    stats = {k: v - before[k]
             if isinstance(v, int) and k not in _GAUGES else v
             for k, v in stats_fn().items()}
    if "prefix_hits" in stats:
        needed = stats["unit_runs"] - stats["recomputes"] \
            + stats["prefix_hits"]
        stats["prefix_hit_rate"] = stats["prefix_hits"] / max(needed, 1)
    return dt, vals, stats


def _chunk_bound(trace, eval_batch_size, n_devices: int) -> int:
    """Dispatch-count ceiling for a full-engine replay of ``trace``.

    Per generation the engine owes at most ``ceil(U_g /
    per_device_batch)`` chunks, where ``U_g`` is that generation's new
    unique rows and the per-device batch is ``eval_batch_size`` (or an
    even split over the device pool when unset).  The sharded-path
    guard allows ``x n_devices`` slack on top (the ISSUE-4 contract: a
    scheduler may split chunks across the pool but must never explode
    the dispatch count beyond it)."""
    n_devices = max(1, n_devices)
    seen: set = set()
    bound = 0
    for P in trace:
        fresh = {tuple(map(int, row)) for row in np.asarray(P)} - seen
        seen |= fresh
        U = len(fresh)
        if not U:
            continue
        pdb = eval_batch_size or -(-U // n_devices)
        bound += -(-U // pdb) * n_devices
    return bound


def run_generational(model_name: str = "alexnet", pop: int = 60,
                     gens: int = 20, n_eval: int = 64, width: float = 0.125,
                     img: int = 16, seed: int = 0,
                     eval_batch_size: int | None = None,
                     devices: int | str = "auto") -> dict:
    """Staged vs full-forward over a real converging population sequence.

    Prefix reuse only pays off where gene prefixes actually repeat —
    i.e. in the NSGA-II populations of a running search, not in i.i.d.
    random chromosomes.  This scenario traces the exact evaluation
    requests of a ``pop x gens`` NSGA-II run (selection driven by the
    calibrated-surrogate objective: cheap, deterministic, and converging
    like the real search), then replays that request stream through

      * the PR-1 full-forward batched+tables path, and
      * the staged PrefixEvalEngine (same weight tables),

    asserting bit-identical ΔAcc per generation and timing only the
    replay.  Both evaluators are warmed first (compiles excluded), then
    their caches/stores are dropped so every activation is recomputed
    honestly inside the timed region.
    """
    import jax
    import jax.numpy as jnp
    from repro.core import FaultSpec, InferenceAccuracyEvaluator
    from repro.core.costmodel import PAPER_DEVICES
    from repro.models.cnn import CNN_MODELS, build_weight_fault_tables

    model = CNN_MODELS[model_name]
    L = model.n_units
    scale = np.array([d.fault_scale for d in PAPER_DEVICES])
    spec = FaultSpec(weight_fault_rate=0.2, act_fault_rate=0.2)
    rng = np.random.default_rng(seed)

    # ---- trace the population sequence a real search evaluates ----------
    layers = model.layer_infos(num_classes=16, width=width, img=img)
    trace = _trace_nsga2(layers, PAPER_DEVICES, pop, gens, seed)

    # ---- evaluators (both on the PR-1 weight-table fast path) ------------
    params = model.init(jax.random.PRNGKey(0), num_classes=16, width=width,
                        img=img)
    x = jnp.asarray(rng.normal(size=(n_eval, img, img, 3)), jnp.float32)
    labels = jnp.asarray(rng.integers(0, 16, size=(n_eval,)))
    w_rates = np.asarray(spec.weight_fault_rate
                         * np.asarray(scale, np.float32), np.float32)
    tables = build_weight_fault_tables(params, w_rates, base_seed=0)

    def apply_fn(p, xx, wr, ar, s):
        return model.apply(p, xx, w_rates=wr, a_rates=ar, seed=s)

    def fresh(staged):
        return InferenceAccuracyEvaluator(
            apply_fn, params, x, labels, spec, scale,
            eval_batch_size=eval_batch_size, weight_tables=tables,
            step_fn=model.step if staged else None,
            eval_strategy="staged" if staged else "full",
            devices=devices)

    ev_full = fresh(staged=False)
    t_full, v_full, full_stats = _replay(
        ev_full, trace, ev_full._cache.clear,
        lambda: {"rows_evaluated": ev_full._engine.rows_evaluated,
                 "dispatches": ev_full._engine.dispatches})
    full_rows = full_stats["rows_evaluated"]
    ev_st = fresh(staged=True)
    t_st, v_st, st = _replay(ev_st, trace, ev_st._prefix_engine.clear,
                             ev_st.staged_stats)
    for g, (a, b) in enumerate(zip(v_full, v_st)):
        assert (a == b).all(), f"staged != full at generation {g}"
    candidates = pop * (gens + 1)       # initial population + children/gen
    eval_devices = ev_full.devices
    rec = {
        "config": {"model": model_name, "pop": pop, "generations": gens,
                   "n_eval": n_eval, "width": width, "img": img,
                   "eval_batch_size": eval_batch_size, "seed": seed,
                   "n_devices": len(scale),
                   "eval_devices": eval_devices},
        "candidates": candidates,
        "unique_rows": full_rows,
        "full_dispatches": full_stats["dispatches"],
        # the bound uses the evaluator's RESOLVED chunk size ("auto"
        # becomes an int or None inside the evaluator)
        "chunk_bound": _chunk_bound(trace, ev_full.eval_batch_size,
                                    eval_devices),
        "per_candidate_ms": {
            "full": t_full / candidates * 1e3,
            "staged": t_st / candidates * 1e3,
        },
        "staged_speedup_vs_full": t_full / t_st,
        "unit_runs": {
            "full": full_rows * L,
            "staged": st["unit_runs"],
            "avoided": st["full_unit_runs"] - st["unit_runs"],
        },
        "prefix_hit_rate": st["prefix_hit_rate"],
        "staged_stats": st,
    }
    return rec


def run_chain_fusion(arch: str = "olmo-1b", n_units: int = 24,
                     pop: int = 60, rounds: int = 20, n_mut: int = 6,
                     B: int = 2, S: int = 8, seed: int = 0,
                     devices: int | str = "auto") -> dict:
    """Chain-fused vs unfused staged dispatch on the converged pop-60
    replay (ISSUE 5).

    The regime chain fusion targets: a DEEP model (the arch's reduced
    config deepened to ``n_units`` partitionable layers — reduced width
    keeps every unit CPU-cheap, so per-DISPATCH overhead dominates) and
    a CONVERGED population, whose prefix trie is mostly non-branching
    chains.  The scenario first converges a surrogate-driven NSGA-II
    search (``_trace_nsga2``) to obtain the converged pop-60, then
    replays the online-reoptimisation tail the paper's runtime phase
    produces: each round re-evaluates a population drawn from the
    converged survivors plus ``n_mut`` point mutants.  The unfused
    depth walk pays one dispatch per fresh depth per round (the whole
    mutated suffix, up to L); the fused walk pays the buddy-ladder
    pieces of the mutants' chains (~log L, shared across mutants).

    Both paths replay the identical trace, asserted bit-identical per
    round; dispatch counts, wall clock and the fused engine's chain
    accounting are reported.

    Guards (applied by ``--smoke --fused``):
      * the fused replay must issue <= HALF the unfused replay's
        engine dispatches (the ISSUE 5 acceptance criterion), and
      * fused dispatches must not exceed the span-ladder bound
        ``branch_nodes + chains × max(1, ceil(log2(max_chain)))``
        (valid for this scenario's unchunked dispatches: each chain
        compiles to at most ~2·ceil(log2(max_chain)) ladder pieces and
        ``(start, length)`` grouping only merges dispatches).
    """
    import dataclasses

    from repro.configs import get_config
    from repro.core import FaultSpec
    from repro.core.costmodel import POD_TIERS_4
    from repro.core.objectives import make_lm_accuracy_evaluator
    from repro.models.graph import lm_layer_infos
    from repro.testing.lm_harness import lm_calibration_setup

    cfg = dataclasses.replace(get_config(arch).reduced(), n_layers=n_units)
    scale = np.array([d.fault_scale for d in POD_TIERS_4])
    D = len(scale)
    spec = FaultSpec(weight_fault_rate=0.2, act_fault_rate=0.2, bits=8)

    # converge a surrogate-driven search, then build the mutation tail
    infos = lm_layer_infos(cfg, seq=S)
    search = _trace_nsga2(infos, POD_TIERS_4, pop, 12, seed)
    base = np.unique(np.asarray(search[-1]), axis=0)
    rng = np.random.default_rng(seed)
    trace = [base[rng.integers(0, len(base), size=pop)].copy()]
    for _ in range(rounds):
        P = base[rng.integers(0, len(base), size=pop)].copy()
        mut = rng.integers(0, pop, size=n_mut)
        P[mut, rng.integers(0, n_units, size=n_mut)] = \
            rng.integers(0, D, size=n_mut)
        trace.append(P)

    params, batch, labels = lm_calibration_setup(cfg, B=B, S=S, seed=seed)

    def fresh(fused):
        return make_lm_accuracy_evaluator(
            cfg, params, batch, labels, spec, scale,
            eval_strategy="staged", fuse_chains=fused, devices=devices)

    ev_uf = fresh(fused=False)
    t_uf, v_uf, st_uf = _replay(ev_uf, trace, ev_uf._prefix_engine.clear,
                                ev_uf.staged_stats)
    ev_f = fresh(fused=True)
    t_f, v_f, st_f = _replay(ev_f, trace, ev_f._prefix_engine.clear,
                             ev_f.staged_stats)
    for g, (a, b) in enumerate(zip(v_uf, v_f)):
        assert (a == b).all(), f"fused != unfused at round {g}"

    max_chain = max(st_f["max_chain"], 1)
    ladder_bound = st_f["branch_nodes"] + st_f["chains"] * max(
        1, (max_chain - 1).bit_length())
    candidates = pop * (rounds + 1)
    return {
        "config": {"arch": arch, "reduced": True, "n_units": n_units,
                   "pop": pop, "rounds": rounds, "n_mut": n_mut,
                   "B": B, "S": S, "seed": seed, "n_devices": D,
                   "fault_bits": 8, "eval_devices": ev_f.devices},
        "candidates": candidates,
        "base_rows": len(base),
        "dispatches": {"unfused": st_uf["dispatches"],
                       "fused": st_f["dispatches"]},
        "dispatch_ratio": st_uf["dispatches"] / max(st_f["dispatches"], 1),
        "ladder_bound": ladder_bound,
        "per_candidate_ms": {
            "unfused": t_uf / candidates * 1e3,
            "fused": t_f / candidates * 1e3,
        },
        "fused_speedup_vs_unfused": t_uf / t_f,
        "unit_runs": {"unfused": st_uf["unit_runs"],
                      "fused": st_f["unit_runs"]},
        "chains": st_f["chains"],
        "fused_segments": st_f["fused_segments"],
        "branch_nodes": st_f["branch_nodes"],
        "max_chain": st_f["max_chain"],
        "unstack_slices_saved": {
            "unfused": st_uf["unstack_slices_saved"],
            "fused": st_f["unstack_slices_saved"]},
        "unfused_stats": st_uf,
        "fused_stats": st_f,
    }


def run_lm_generational(arch: str = "olmo-1b", pop: int = 24,
                        gens: int = 8, B: int = 2, S: int = 16,
                        seed: int = 0,
                        eval_batch_size: int | None = None,
                        devices: int | str = "auto") -> dict:
    """Staged vs full-forward replay for a transformer arch (ISSUE 3).

    The LM twin of :func:`run_generational`: the same converging
    NSGA-II population trace, replayed through the full-forward and the
    staged prefix-reuse paths of the *transformer* step API
    (``models.transformer.LMStepModel`` via
    ``core.objectives.make_lm_accuracy_evaluator``), asserting
    bit-identical ΔAcc per generation.

    Runs the ``reduced()`` config (CPU smoke scale — the CI lane's
    "smallest config, 2 units deep") over the 4-level pod-tier ladder,
    in the paper's INT8-class fault regime via ``FaultSpec(bits=8)``
    (the default 16-bit/4-LSB one barely moves token-level top-1 at
    this scale).  Labels are the clean model's own argmax so ΔAcc
    measures pure corruption.
    """
    from repro.configs import get_config
    from repro.core import FaultSpec
    from repro.core.costmodel import POD_TIERS_4
    from repro.core.objectives import make_lm_accuracy_evaluator
    from repro.models.graph import lm_layer_infos
    from repro.testing.lm_harness import lm_calibration_setup

    cfg = get_config(arch).reduced()
    scale = np.array([d.fault_scale for d in POD_TIERS_4])
    spec = FaultSpec(weight_fault_rate=0.2, act_fault_rate=0.2, bits=8)

    infos = lm_layer_infos(cfg, seq=S)
    trace = _trace_nsga2(infos, POD_TIERS_4, pop, gens, seed)
    params, batch, labels = lm_calibration_setup(cfg, B=B, S=S, seed=seed)

    def fresh(staged):
        return make_lm_accuracy_evaluator(
            cfg, params, batch, labels, spec, scale,
            eval_batch_size=eval_batch_size,
            eval_strategy="staged" if staged else "full",
            devices=devices)

    ev_full = fresh(staged=False)
    t_full, v_full, full_stats = _replay(
        ev_full, trace, ev_full._cache.clear,
        lambda: {"rows_evaluated": ev_full._engine.rows_evaluated,
                 "dispatches": ev_full._engine.dispatches})
    ev_st = fresh(staged=True)
    t_st, v_st, st = _replay(ev_st, trace, ev_st._prefix_engine.clear,
                             ev_st.staged_stats)

    for g, (a, b) in enumerate(zip(v_full, v_st)):
        assert (a == b).all(), f"LM staged != full at generation {g}"
    L = ev_st._n_units
    full_rows = full_stats["rows_evaluated"]
    candidates = pop * (gens + 1)
    return {
        "config": {"arch": arch, "reduced": True, "n_units": L,
                   "pop": pop, "generations": gens, "B": B, "S": S,
                   "eval_batch_size": eval_batch_size, "seed": seed,
                   "n_devices": len(scale), "fault_bits": 8,
                   "eval_devices": ev_full.devices},
        "candidates": candidates,
        "unique_rows": full_rows,
        "full_dispatches": full_stats["dispatches"],
        "chunk_bound": _chunk_bound(trace, ev_full.eval_batch_size,
                                    ev_full.devices),
        "per_candidate_ms": {
            "full": t_full / candidates * 1e3,
            "staged": t_st / candidates * 1e3,
        },
        "staged_speedup_vs_full": t_full / t_st,
        "unit_runs": {
            "full": full_rows * L,
            "staged": st["unit_runs"],
            "avoided": st["full_unit_runs"] - st["unit_runs"],
        },
        "avoided_frac": (st["full_unit_runs"] - st["unit_runs"])
        / max(st["full_unit_runs"], 1),
        "prefix_hit_rate": st["prefix_hit_rate"],
        "staged_stats": st,
    }


def main():
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache(__file__)
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--model", default="alexnet",
                    choices=["alexnet", "squeezenet", "resnet18"])
    ap.add_argument("--pop", type=int, default=60,
                    help="population size (paper Sec. VI-A: 60)")
    ap.add_argument("--n-eval", type=int, default=1,
                    help="calibration batch size (dispatch-bound default)")
    ap.add_argument("--width", type=float, default=0.125)
    ap.add_argument("--img", type=int, default=16)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--eval-batch-size", default=None,
                    help="cap chromosomes per dispatch (int, or 'auto' to "
                         "probe the compiled memory footprint)")
    ap.add_argument("--devices", default=None,
                    help="shard ΔAcc dispatches over this many local "
                         "devices ('auto' = all; bit-identical to one "
                         "device — with --smoke the run also fails if "
                         "the sharded path dispatches more chunks than "
                         "ceil(U/per_device_batch) x devices)")
    ap.add_argument("--generations", type=int, default=20,
                    help="NSGA-II generations for the prefix-reuse replay")
    ap.add_argument("--gen-n-eval", type=int, default=64,
                    help="calibration batch for the generational scenario "
                         "(compute-bound regime where unit runs dominate)")
    ap.add_argument("--skip-generational", action="store_true",
                    help="only run the single-population microbenchmark")
    ap.add_argument("--fused", action="store_true",
                    help="run ONLY the chain-fusion comparison: the "
                         "converged pop-60 replay (24-unit reduced LM, "
                         "survivors + point mutants) through the "
                         "staged path unfused vs fused, reporting "
                         "dispatch counts and wall-clock (writes "
                         "chain_fusion.json; with --smoke, fails "
                         "unless fused dispatches are <= half the "
                         "unfused count and within the span-ladder "
                         "bound; --lm ARCH picks the architecture)")
    ap.add_argument("--backend", choices=["tables", "pallas"], default=None,
                    help="run ONLY the fault-backend comparison "
                         "(run_fault_backend): tables vs pallas at pop-60, "
                         "bit-identical ΔAcc asserted, per-candidate "
                         "wall-clock + peak/resident memory reported "
                         "(writes fault_backend.json; with --smoke, fails "
                         "if the pallas evaluator holds any resident "
                         "fault-table bytes or its eval HBM footprint is "
                         "not strictly below the tables path's)")
    ap.add_argument("--lm", metavar="ARCH", default=None,
                    help="run ONLY the transformer generational replay "
                         "on this arch's reduced config (writes "
                         "prefix_reuse_lm.json; with --smoke, fails "
                         "unless >=30%% of unit runs are avoided)")
    ap.add_argument("--lm-pop", type=int, default=24)
    ap.add_argument("--lm-gens", type=int, default=8)
    ap.add_argument("--paper", action="store_true",
                    help="paper-scale eval batch (512 samples, width .5, "
                         "img 32): compute-bound regime")
    ap.add_argument("--smoke", action="store_true",
                    help="two reps + regression guard (CI artifact run): "
                         "fails if the staged path runs more unit runs "
                         "than the full path")
    args = ap.parse_args()
    from repro.core.eval_engine import parse_devices, parse_eval_batch_size
    ebs = parse_eval_batch_size(args.eval_batch_size)
    dev = parse_devices(args.devices)
    dev = "auto" if dev is None else dev

    if args.backend:
        rec = run_fault_backend(model_name=args.model, pop=args.pop,
                                n_eval=args.n_eval, width=args.width,
                                img=args.img,
                                reps=2 if args.smoke else args.reps,
                                devices=dev)
        ms = rec["per_candidate_ms"]
        mem = rec["memory_bytes"]
        print("# benchmark,us_per_call,derived")
        print(f"eval_engine.fault_backend_tables,{ms['tables']*1e3:.0f},"
              f"table_bytes={mem['tables']['fault_table_bytes']} "
              f"eval_hbm={mem['tables']['eval_hbm_bytes']}")
        print(f"eval_engine.fault_backend_pallas,{ms['pallas']*1e3:.0f},"
              f"speedup={rec['pallas_speedup_vs_tables']:.2f}x "
              f"table_bytes={mem['pallas']['fault_table_bytes']} "
              f"state_bytes={mem['pallas']['fault_state_bytes']} "
              f"eval_hbm={mem['pallas']['eval_hbm_bytes']} "
              f"env_rebuilds={rec['env_change']['pallas_rebuilds']}")
        os.makedirs(RESULTS, exist_ok=True)
        out = os.path.join(RESULTS, "fault_backend.json")
        with open(out, "w") as f:
            json.dump(rec, f, indent=1, default=float)
        print(f"# wrote {out}")
        if args.smoke and args.backend == "pallas":
            pal, tab = mem["pallas"], mem["tables"]
            if pal["fault_table_bytes"] > 0:
                print(f"FAIL: pallas backend holds "
                      f"{pal['fault_table_bytes']} resident fault-table "
                      f"bytes (must be zero — corrupted weights must "
                      f"never materialise)")
                sys.exit(1)
            if pal["eval_hbm_bytes"] >= tab["eval_hbm_bytes"]:
                print(f"FAIL: pallas eval HBM footprint "
                      f"{pal['eval_hbm_bytes']} B is not strictly below "
                      f"the tables path's {tab['eval_hbm_bytes']} B at "
                      f"pop {args.pop}")
                sys.exit(1)
            if rec["env_change"]["pallas_rebuilds"] != 0:
                print("FAIL: pallas backend rebuilt executables on a "
                      "fault-environment change (rates must be traced)")
                sys.exit(1)
        return rec

    if args.fused:
        rec = run_chain_fusion(arch=args.lm or "olmo-1b", pop=args.pop,
                               devices=dev)
        d = rec["dispatches"]
        print("# benchmark,us_per_call,derived")
        print(f"eval_engine.chain_fusion_unfused,"
              f"{rec['per_candidate_ms']['unfused']*1e3:.0f},"
              f"dispatches={d['unfused']}")
        print(f"eval_engine.chain_fusion_fused,"
              f"{rec['per_candidate_ms']['fused']*1e3:.0f},"
              f"speedup={rec['fused_speedup_vs_unfused']:.2f}x "
              f"dispatches={d['fused']} "
              f"ratio={rec['dispatch_ratio']:.2f}x "
              f"ladder_bound={rec['ladder_bound']} "
              f"chains={rec['chains']} segments={rec['fused_segments']} "
              f"slices_saved={rec['unstack_slices_saved']['fused']}")
        os.makedirs(RESULTS, exist_ok=True)
        out = os.path.join(RESULTS, "chain_fusion.json")
        with open(out, "w") as f:
            json.dump(rec, f, indent=1, default=float)
        print(f"# wrote {out}")
        if args.smoke and d["fused"] * 2 > d["unfused"]:
            print(f"FAIL: fused staged replay issued {d['fused']} "
                  f"dispatches, more than half the unfused path's "
                  f"{d['unfused']} — chain fusion stopped collapsing "
                  f"the converged-pop prefix runs")
            sys.exit(1)
        if args.smoke and d["fused"] > rec["ladder_bound"]:
            print(f"FAIL: fused staged replay issued {d['fused']} "
                  f"dispatches, over the span-ladder bound "
                  f"branch_nodes + chains x ceil(log2(max_chain)) = "
                  f"{rec['ladder_bound']}")
            sys.exit(1)
        return rec

    if args.lm:
        rec = run_lm_generational(arch=args.lm, pop=args.lm_pop,
                                  gens=args.lm_gens, eval_batch_size=ebs,
                                  devices=dev)
        ur = rec["unit_runs"]
        print("# benchmark,us_per_call,derived")
        print(f"eval_engine.lm_generational_full,"
              f"{rec['per_candidate_ms']['full']*1e3:.0f},"
              f"unit_runs={ur['full']}")
        print(f"eval_engine.lm_generational_staged,"
              f"{rec['per_candidate_ms']['staged']*1e3:.0f},"
              f"speedup={rec['staged_speedup_vs_full']:.2f}x "
              f"unit_runs={ur['staged']} avoided={ur['avoided']} "
              f"avoided_frac={rec['avoided_frac']:.2f} "
              f"hit_rate={rec['prefix_hit_rate']:.2f}")
        os.makedirs(RESULTS, exist_ok=True)
        out = os.path.join(RESULTS, "prefix_reuse_lm.json")
        with open(out, "w") as f:
            json.dump(rec, f, indent=1, default=float)
        print(f"# wrote {out}")
        if args.smoke and (ur["staged"] > ur["full"]
                           or rec["avoided_frac"] < 0.30):
            print(f"FAIL: LM staged replay avoided only "
                  f"{rec['avoided_frac']:.0%} of the full path's "
                  f"{ur['full']} unit runs (< 30% guard) — prefix "
                  f"reuse regressed on the transformer step API")
            sys.exit(1)
        if args.smoke and rec["full_dispatches"] > rec["chunk_bound"]:
            print(f"FAIL: LM sharded path dispatched "
                  f"{rec['full_dispatches']} chunks, over the "
                  f"ceil(U/per_device_batch) x devices bound of "
                  f"{rec['chunk_bound']}")
            sys.exit(1)
        return rec

    kw = dict(model_name=args.model, pop=args.pop, n_eval=args.n_eval,
              width=args.width, img=args.img, reps=args.reps,
              eval_batch_size=ebs, devices=dev)
    if args.paper:
        # only fill in values the user left at their defaults
        paper = {"n_eval": 512, "width": 0.5, "img": 32}
        for k, v in paper.items():
            if getattr(args, k) == ap.get_default(k):
                kw[k] = v
    if args.smoke and args.reps == ap.get_default("reps"):
        kw["reps"] = 2

    rec = run_benchmark(**kw)
    ms = rec["per_candidate_ms"]
    sp = rec["speedup_vs_loop"]
    print("# benchmark,us_per_call,derived")
    print(f"eval_engine.loop,{ms['loop']*1e3:.0f},per-candidate")
    print(f"eval_engine.batched,{ms['batched']*1e3:.0f},"
          f"speedup={sp['batched']:.2f}x")
    print(f"eval_engine.batched_tables,{ms['batched_tables']*1e3:.0f},"
          f"speedup={sp['batched_tables']:.2f}x "
          f"dispatches={rec['dispatches']['batched_tables']}")
    print(f"eval_engine.staged,{ms['staged']*1e3:.0f},"
          f"speedup={sp['staged']:.2f}x "
          f"unit_runs={rec['staged']['unit_runs']}/"
          f"{rec['staged']['full_unit_runs']}")
    print(f"eval_engine.cached_population,{ms['cached_population']*1e3:.0f},"
          f"dispatches={rec['dispatches']['cached_population']}")
    os.makedirs(RESULTS, exist_ok=True)
    out = os.path.join(RESULTS, "eval_engine.json")
    with open(out, "w") as f:
        json.dump(rec, f, indent=1, default=float)
    print(f"# wrote {out}")

    if args.skip_generational:
        return rec

    gen = run_generational(model_name=args.model, pop=args.pop,
                           gens=args.generations, n_eval=args.gen_n_eval,
                           width=args.width, img=args.img,
                           eval_batch_size=ebs, devices=dev)
    ur = gen["unit_runs"]
    print(f"eval_engine.generational_full,"
          f"{gen['per_candidate_ms']['full']*1e3:.0f},"
          f"unit_runs={ur['full']}")
    print(f"eval_engine.generational_staged,"
          f"{gen['per_candidate_ms']['staged']*1e3:.0f},"
          f"speedup={gen['staged_speedup_vs_full']:.2f}x "
          f"unit_runs={ur['staged']} avoided={ur['avoided']} "
          f"hit_rate={gen['prefix_hit_rate']:.2f}")
    out = os.path.join(RESULTS, "prefix_reuse.json")
    with open(out, "w") as f:
        json.dump(gen, f, indent=1, default=float)
    print(f"# wrote {out}")

    if args.smoke and ur["staged"] > ur["full"]:
        print(f"FAIL: staged path ran {ur['staged']} unit runs, more than "
              f"the full path's {ur['full']} — prefix reuse regressed")
        sys.exit(1)
    if args.smoke and gen["full_dispatches"] > gen["chunk_bound"]:
        print(f"FAIL: sharded path dispatched {gen['full_dispatches']} "
              f"chunks, over the ceil(U/per_device_batch) x devices "
              f"bound of {gen['chunk_bound']}")
        sys.exit(1)
    return rec


if __name__ == "__main__":
    main()
