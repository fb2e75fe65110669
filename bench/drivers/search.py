"""Driver of the search cells: the ΔAcc objective of a recorded NSGA-II
search, evaluated by the staged, prefix-reusing engine.

Set-up makes the model and the calibration images on the device from the
configuration's ``weights_seed``, labels the images with the clean
quantized model, builds the evaluator the configuration states, compiles
every executable the engine can ask for (each fused segment of the
buddy-aligned span ladder at each chunk size), and evaluates the first
``warmup_generations`` populations of the trace, which fills the row
cache and the activation store as a live search would.  The window then
calls the objective on the following populations in order, and finishes
the generation in flight when the time is up.

The traffic's ``trace_seed`` fixes the search's trajectory, so every run
does the same work; ``--seed`` orders the rows of each population, draws
the fault hash's base seed and the rows that are checked.  The weights do
not depend on it: the evaluator bakes weights and images into every
executable as constants, so a seed-dependent model would compile every
executable afresh in every run.

While the window runs, the driver keeps a reference to every chunk the
engine stacks as input to the final unit (the fc), with the prefixes of
its rows: the features that units 0..L-2 produced for each row, as the
timed path computed them.  After the window two numbers are compared
with the configuration's plain reference:

- ``feature_gap_max``: over a sample of the rows first evaluated in the
  window, drawn from the seed, the largest relative L2 gap between the
  program's fc input and the reference's, which runs units 0..L-2 with
  the same faults.  It covers the staged engine (prefix resume, store,
  fused segments), the Pallas ``bitflip`` kernel, the activation faults
  and the convolutions.
- ``dacc_head_gap_mean``: over every row first evaluated in the window,
  the mean gap between the ΔAcc the objective returned and the ΔAcc of
  the reference's fc unit (its faults, its labels) applied to the
  program's own fc input.  It covers the fc through ``fault_matmul``,
  the labels and the ΔAcc the search is given.
"""
from __future__ import annotations

import gc
import sys
import time

import numpy as np

from bench import gen_search
from bench.harness import span

# device operations by their stable name in the trace (regex over an XLA
# op's text, which starts with its own name: ``%vmap_jit_bitflip_pallas__.2
# = s8[...] custom-call(...)``; ops that consume its output name it later)
KERNELS = {"bitflip": r"^%\S*bitflip\S* = "}


def seeds(seed: int) -> dict:
    """Independent 31-bit seeds from the run's ``--seed`` (any size)."""
    s = np.random.SeedSequence(int(seed)).generate_state(3)
    return {"traffic": int(s[0]), "fault": int(s[1]) % (1 << 30),
            "check": int(s[2])}


def ladder(n_units: int) -> list[tuple[int, int]]:
    """Every (start, length) segment the chain-fused engine can dispatch:
    power-of-two lengths at starts they divide (any at 0) that end before
    the final unit, and the final unit alone."""
    out = []
    for start in range(n_units - 1):
        ln = 1
        while start + ln <= n_units - 1:
            if start == 0 or start % ln == 0:
                out.append((start, ln))
            ln *= 2
    return out + [(n_units - 1, 1)]


def _log(t0: float, what: str):
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use", 0) / 2 ** 30
    print(f"search: {time.monotonic() - t0:8.1f} s  peak {peak:5.2f} GiB  "
          f"{what}", file=sys.stderr, flush=True)


class Run:
    KERNELS = KERNELS

    def __init__(self, config: dict, traffic: dict, seed: int, reference,
                 seconds: float):
        self.config, self.traffic = config, traffic
        self.seeds = seeds(seed)
        self.reference = reference
        self.rows: dict[tuple, float] = {}

    # -- set-up ---------------------------------------------------------
    def setup(self):
        import jax
        import jax.numpy as jnp

        from repro.core import (POD_TIERS_4, CostModel, FaultSpec,
                                InferenceAccuracyEvaluator, ObjectiveFn)
        from repro.models import cnn, layers
        from repro.models.cnn import (FAULT_BITS, FAULTY_BITS,
                                      quantize_unit_params)

        from bench.flops import arch

        t0 = time.monotonic()
        cfg, m, f, e = (self.config, self.config["model"],
                        self.config["fault"], self.config["evaluator"])
        # what the program fixes must be what the configuration states
        tiers = [(t["name"], t["fault_scale"]) for t in cfg["tiers"]]
        if tiers != [(d.name, d.fault_scale) for d in POD_TIERS_4]:
            raise ValueError(f"configured tiers {tiers} are not the "
                             "program's POD_TIERS_4")
        if (f["bits"], f["faulty_bits"], f["fault_model"]) != (
                FAULT_BITS, FAULTY_BITS, layers.FAULT_MODEL):
            raise ValueError("configured fault bits/model differ from the "
                             "program's CNN fault path")

        self.trace = gen_search.generate(cfg, self.traffic,
                                         self.seeds["traffic"])
        Model = getattr(cnn, arch(m["arch"]).PROGRAM_CLASS)
        n_eval, img = cfg["n_eval"], m["img"]

        def make(key):
            kp, kx = jax.random.split(key)
            return (Model.init(kp, num_classes=m["num_classes"],
                                  width=m["width"], img=img),
                    jax.random.normal(kx, (n_eval, img, img, 3),
                                      jnp.float32))

        params, x = jax.jit(make)(jax.random.PRNGKey(cfg["weights_seed"]))
        z = jnp.zeros((Model.n_units,), jnp.float32)
        labels = jax.jit(lambda p, xx: jnp.argmax(
            Model.apply(p, xx, z, z, 0), axis=-1))(params, x)
        spec = FaultSpec(weight_fault_rate=f["weight_fault_rate"],
                         act_fault_rate=f["act_fault_rate"],
                         faulty_bits=f["faulty_bits"], bits=f["bits"])
        scale = [t["fault_scale"] for t in cfg["tiers"]]
        ev = InferenceAccuracyEvaluator(
            Model.apply, params, x, labels, spec, scale,
            base_seed=self.seeds["fault"],
            quant_params=quantize_unit_params(params),
            fault_backend=e["fault_backend"], step_fn=Model.step,
            eval_strategy=e["eval_strategy"], devices=e["devices"],
            eval_batch_size=e["eval_batch_size"],
            max_store_bytes=e["max_store_bytes"],
            fuse_chains=e["fuse_chains"])
        layer_infos = Model.layer_infos(num_classes=m["num_classes"],
                                           width=m["width"], img=img)
        self.obj = ObjectiveFn(CostModel(layer_infos, POD_TIERS_4), ev)
        self.ev = ev
        self.eng = ev._ensure_prefix_engine()
        self._instrument(Model.n_units)
        _log(t0, "model, labels and evaluator")

        # compile every segment the engine can dispatch, at every chunk
        # size (eval_batch_size rows, and the smaller power-of-two tails)
        shapes, s = [], jax.ShapeDtypeStruct((n_eval, img, img, 3),
                                             jnp.float32)
        for i in range(Model.n_units - 1):
            s = jax.eval_shape(lambda a, i=i: Model.step(i, params[i], a),
                               s)
            shapes.append(s.shape)
        sizes, b = [], 1
        while b <= e["eval_batch_size"]:
            sizes.append(b)
            b *= 2
        for start, ln in ladder(Model.n_units):
            fn = ev._segment_dispatch(start, ln)
            for b in sizes:
                acts = None if start == 0 else \
                    jnp.zeros((b,) + shapes[start - 1], jnp.float32)
                jax.block_until_ready(fn(acts, jnp.asarray(
                    np.zeros((b, ln), np.int32))))
        _log(t0, "segment ladder compiled")
        self._warm_stacking(shapes, sizes)
        _log(t0, "chunk assembly compiled")
        for g, P in enumerate(self.trace[:self.traffic["warmup_generations"]]):
            self.obj(P)
            _log(t0, f"warm-up generation {g}")

    def _warm_stacking(self, shapes, sizes):
        """Compile the eager slicing, gathering and stacking by which the
        engine assembles a chunk's parent activations, at every
        activation shape: from one stored batch (a gather), from several
        (slice each, stack), and one row alone."""
        import jax
        import jax.numpy as jnp

        from repro.core.eval_engine import StackedView, _StackedBatch

        for shape in shapes:
            for n in sizes:
                batches = [_StackedBatch(jnp.zeros((n,) + shape, jnp.float32),
                                         n) for _ in range(2)]
                one, other = batches
                for parents, padded in (
                        ([StackedView(one, 0)], 1),
                        ([StackedView(one, 0)], 2),
                        ([StackedView(one, 0), StackedView(one, n - 1)], 2),
                        ([StackedView(one, 0), StackedView(other, 0)], 2)):
                    jax.block_until_ready(
                        self.eng._stack_chunk(parents, padded))

    def _instrument(self, n_units: int):
        """Count unit runs per unit (fused segments and eviction
        recomputes), mark the engine's host phases as spans, and, while
        ``self.capturing``, keep every chunk stacked as the final unit's
        input with the prefixes of its rows (references only: no device
        work is added)."""
        eng = self.eng
        self.runs = np.zeros(n_units, np.int64)
        self.capturing = False
        self.final_parents: dict[int, tuple] = {}   # id -> (entry, prefix)
        self.fc_inputs: list[tuple] = []            # (chunk, prefixes)
        plan, recompute = eng._plan_segments, eng._recompute
        stack, gather = eng._stack_chunk, eng._gather_final
        parent_for = eng._parent_for

        def planned(rows):
            with span("search.plan"):
                segs = plan(rows)
            for start, length, _, _ in segs:
                self.runs[start:start + length] += 1
            return segs

        def recomputed(prefix):
            self.runs[len(prefix) - 1] += 1
            return recompute(prefix)

        def parent(prefix):
            entry = parent_for(prefix)
            if self.capturing and len(prefix) == n_units - 1:
                # kept alive, so its id names it until the window ends
                self.final_parents[id(entry)] = (entry, prefix)
            return entry

        def stacked(parents, padded):
            with span("search.stack"):
                out = stack(parents, padded)
            if self.capturing:
                hits = [self.final_parents.get(id(p)) for p in parents]
                if all(h is not None and h[0] is p
                       for h, p in zip(hits, parents)):
                    self.fc_inputs.append((out, [h[1] for h in hits]))
            return out

        def gathered(pending):
            with span("search.gather"):
                return gather(pending)

        eng._plan_segments, eng._recompute = planned, recomputed
        eng._stack_chunk, eng._gather_final = stacked, gathered
        eng._parent_for = parent

    # -- window ---------------------------------------------------------
    def window(self, seconds: float) -> dict:
        st0, runs0 = self.eng.stats(), self.runs.copy()
        known = set(self.ev._cache)
        g = self.traffic["warmup_generations"]
        gens = cands = 0
        gen_s = []
        self.capturing = True
        t0 = time.perf_counter()
        while True:
            if g >= len(self.trace):
                raise RuntimeError(
                    f"the recorded search ran out after {gens} generations "
                    "in the window; record more generations")
            P = self.trace[g]
            t = time.perf_counter()
            with span("search.objective"):
                objs = self.obj(P)
            gen_s.append(time.perf_counter() - t)
            for row, d in zip(P, objs[:, 2]):
                key = tuple(int(v) for v in row)
                if key not in known:
                    self.rows[key] = float(d)
            known.update(self.rows)
            gens, cands, g = gens + 1, cands + len(P), g + 1
            if time.perf_counter() - t0 >= seconds:
                break
        elapsed = time.perf_counter() - t0
        self.capturing = False
        st = self.eng.stats()
        delta = {k: st[k] - st0[k] for k in (
            "unit_runs", "dispatches", "prefix_hits", "recomputes",
            "evictions", "rows_evaluated", "fused_segments")}
        return {"metrics": {"search_cands_per_s": cands / elapsed},
                "attempted": cands, "failed": 0,
                "layer": dict(delta, candidates=cands, generations=gens,
                              runs_per_unit=(self.runs - runs0).tolist(),
                              elapsed_s=elapsed),
                "notes": {"generations": gens, "window_s": elapsed,
                          "generation_s_max": max(gen_s),
                          "fresh_rows": len(self.rows),
                          "unit_runs": delta["unit_runs"],
                          "dispatches": delta["dispatches"]}}

    def drain(self):
        """Nothing is in flight once the window's last generation has
        returned."""

    def release(self):
        """Free the program's state; keep what the window produced (the
        fc inputs, on the host) for the check."""
        import jax

        self.features = {}
        for chunk, prefixes in self.fc_inputs:
            host = np.asarray(jax.device_get(chunk))
            for r, prefix in enumerate(prefixes):
                self.features.setdefault(prefix, host[r])
        del self.obj, self.ev, self.eng, self.fc_inputs, self.final_parents
        gc.collect()
        jax.clear_caches()

    # -- correctness ------------------------------------------------------
    def _window_rows(self):
        """Rows first evaluated in the window and their fc inputs
        (``None`` where no fc input was seen, which fails the check)."""
        keys = list(self.rows)
        feats = [self.features.get(k[:-1]) for k in keys]
        return keys, feats

    def _sample(self, keys) -> np.ndarray:
        rng = np.random.default_rng(self.seeds["check"])
        n = min(self.traffic["check_rows"], len(keys))
        return np.sort(rng.choice(len(keys), n, replace=False))

    def readings(self, ref, control=None) -> dict:
        """The two compared numbers, with ``ref`` the reference they are
        held to.  A ``control`` stands in for what the window produced:
        its own fc inputs for ``feature_gap_max``, its fc unit on the
        program's fc inputs for ``dacc_head_gap_mean``."""
        keys, feats = self._window_rows()
        if not keys or any(f is None for f in feats):
            return {"feature_gap_max": float("inf"),
                    "dacc_head_gap_mean": float("inf")}
        seed = self.seeds["fault"]
        rows, feats = np.array(keys, np.int64), np.stack(feats)
        pick = self._sample(keys)
        ref_f = ref.features(rows[pick, :-1], seed)
        if control is None:
            got_f = feats[pick]
            got_d = np.array([self.rows[k] for k in keys])
        else:
            got_f = control.features(rows[pick, :-1], seed)
            got_d = control.head_dacc(feats, rows[:, -1], seed)
        want_d = ref.head_dacc(feats, rows[:, -1], seed)
        flat = lambda a: a.reshape(len(pick), -1)
        gap = (np.linalg.norm(flat(got_f - ref_f), axis=1)
               / np.linalg.norm(flat(ref_f), axis=1))
        self.compared = {"rows": rows[pick].tolist(),
                         "feature_gap": gap.tolist(),
                         "dacc_rows": len(keys),
                         "dacc_program_mean": float(np.mean(got_d)),
                         "dacc_reference_mean": float(np.mean(want_d))}
        return {"feature_gap_max": float(np.max(gap)),
                "dacc_head_gap_mean": float(np.mean(np.abs(got_d - want_d)))}

    def check(self) -> list[dict]:
        """The window's fc inputs and ΔAcc against the reference."""
        ref = self.reference.Reference(self.config)
        limits = self.config["limits"]
        return [{"name": k, "value": v, "limit": limits[k]}
                for k, v in self.readings(ref).items()]

    def controls(self) -> dict:
        """The numbers with the reference, computed in a lower precision
        or with its faults off, in the program's place: its fc inputs for
        ``feature_gap_max``, its fc unit on the program's own fc inputs
        for ``dacc_head_gap_mean``.  Not part of a benchmark run."""
        Ref = self.reference.Reference
        ref = Ref(self.config)
        return {name: self.readings(ref, Ref(self.config, **kw))
                for name, kw in (("float8_e4m3fn", {"dtype": "float8_e4m3fn"}),
                                 ("int4", {"dtype": "int4"}),
                                 ("faults_off", {"faults": False}))}
