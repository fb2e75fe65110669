"""Objective evaluation for partition chromosomes.

Three objectives (paper Eq. 2), all minimised:
    [ Latency(P), Energy(P), ΔAcc(P) ]

Latency/Energy come from the analytical CostModel (vectorised over the
population).  ΔAcc comes from one of two evaluators:

  * ``InferenceAccuracyEvaluator`` — the paper's method: run the actual
    quantized model on a calibration batch with faults injected on the
    layers mapped to fault-prone devices (fused Pallas path), and
    measure Top-1 degradation.  Used for the CNN-scale models AND for
    LM configs small enough to instantiate
    (:func:`make_lm_accuracy_evaluator`;
    ``models.graph.lm_eval_strategy`` resolves which those are).
  * ``SurrogateAccuracyEvaluator`` — scalable path for the 27-480B
    archs: per-layer fault sensitivity is profiled once via the
    paper's layer-wise sweep, then ΔAcc(P) ≈ Σ_l sens_l · scale[P_l],
    calibrated against a handful of true evaluations.

Both are deterministic given (partition, seed) so NSGA-II results are
reproducible — the paper calls out non-reproducibility under transient
faults as a failure mode of existing tools.

Population batching
-------------------
``InferenceAccuracyEvaluator.delta_acc`` takes the whole ``[N, L]``
population and evaluates every unique uncached chromosome in ONE
``jit(vmap)`` dispatch (optionally chunked by ``eval_batch_size`` to cap
device memory).  Two batched paths exist:

  * generic — vmap over per-layer ``(weight_rates, act_rates)`` vectors;
    works for any ``apply_fn``;
  * weight-table — when ``weight_tables`` is given (see
    ``repro.models.cnn.build_weight_fault_tables``): corrupted weights
    depend only on (layer, device) because the seed is fixed and rates
    factor as ``base_rate * device_fault_scale[P_l]``, so they are
    precomputed once per search and *gathered* per candidate instead of
    re-hashed.  This removes the O(params · faulty_bits) per-candidate
    PRNG work and is bit-identical to the inline path;
  * pallas — when ``quant_params`` is given
    (``fault_backend="pallas"``): the model's corruptible weights live
    as ONE resident int8 ``QTensor`` copy and the flips happen inside
    the compute itself (``kernels.ops.fault_matmul`` — fused into the
    matmul tile on TPU, the exact bitflip→dequant→matmul composition
    in interpret mode), so no corrupted weight variant is ever
    materialised: resident fault state is O(params) instead of the
    tables' O(params × devices), and the per-device rate arrays + seed
    are traced arguments, so fault-environment hot-swaps reuse every
    compiled executable.  Bit-identical to both other paths on
    CPU/interpret (tests/test_fault_backends.py).

Both batched paths produce results bit-identical to the per-individual
loop (the per-row computation is unchanged; vmap only adds the
population axis), which tests/test_eval_engine.py locks in.

Staged (prefix-reuse) evaluation
--------------------------------
When the model exposes the per-unit ``step`` API (the CNNs in
``repro.models.cnn``; every LM arch via
``models.transformer.LMStepModel``), pass ``step_fn`` and the evaluator
defaults to
``eval_strategy="staged"``: instead of re-running all L units for every
unique chromosome, a :class:`~repro.core.eval_engine.PrefixEvalEngine`
walks the model depth by depth and evaluates each unique *gene prefix*
once, reusing stored activations across chromosomes and generations.
Per-generation cost then scales with unique prefixes, not
``unique_rows x L`` — converged NSGA-II populations share long
prefixes, so most unit runs disappear.  ``eval_strategy="full"``
selects the PR-1 whole-forward batched path; both are bit-identical
(tests/test_staged_eval.py) and share one row-level result cache.

Chain-fused staged dispatch
---------------------------
``fuse_chains=True`` (the default) additionally collapses every
NON-BRANCHING run of the gene-prefix tree into one fused executable: a
segment function composing the unit step fns ``start..start+length-1``
inside a single ``jit(vmap)`` (:meth:`InferenceAccuracyEvaluator.
_build_segment_fn` — heterogeneous layer shapes rule out ``lax.scan``,
so composition happens at trace time and XLA fuses the bodies).  Per-
device fault rates, weight tables and per-unit params are closed over
or gathered exactly as the per-unit executables do, so fused results
stay bitwise identical (tests/test_chain_fusion.py).  Segment
executables are cached per ``(start, length)`` on the buddy-aligned
power-of-two span ladder — at most ``~2·L`` entries, shared across
generations and (via the module-level ``_SEGMENT_CACHE`` keyed on
evaluator identity) across partitioner runs that reuse one evaluator.
"""
from __future__ import annotations

import dataclasses
import functools
import warnings
import weakref
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.costmodel import CostModel
from repro.core.eval_engine import (DeviceScheduler, PopulationEvalEngine,
                                    PrefixEvalEngine, auto_eval_batch_size,
                                    chunked_rows, pad_rows,
                                    peak_memory_bytes)
from repro.core.fault import FaultSpec

__all__ = [
    "InferenceAccuracyEvaluator", "SurrogateAccuracyEvaluator",
    "ObjectiveFn", "profile_layer_sensitivity",
    "make_lm_accuracy_evaluator",
]


# Module-level compiled-segment cache, keyed on evaluator identity (weak:
# dropping the evaluator drops its executables).  Living here rather than
# on the instance is deliberate: ObjectiveFn/partitioner rebuilds that
# reuse one evaluator keep hitting the same compiled segments across
# partitioner runs, and the fault-environment setter can invalidate the
# whole entry in one pop.
_SEGMENT_CACHE: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()


def _pallas_env_args(ref):
    """Fetch the evaluator's CURRENT fault environment as the traced
    trailing arguments every pallas-backend executable takes:
    ``(w_rates_by_device, a_rates_by_device, base_seed)``.

    ``ref`` is a ``weakref.ref`` to the evaluator — the wrappers that
    call this live in the weak-keyed ``_SEGMENT_CACHE`` (and on the
    evaluator itself), so a strong capture would leak the evaluator,
    its params and every compiled executable.  Reading at call time is
    what makes ``device_fault_scale`` hot-swaps free: the executables
    are environment-agnostic, only these arguments change.
    """
    ev = ref()
    return (jnp.asarray(ev.w_rates_by_device),
            jnp.asarray(ev.a_rates_by_device),
            jnp.int32(ev.base_seed))


class InferenceAccuracyEvaluator:
    """ΔAcc via true fault-injected inference (paper Alg. 1 lines 5-7).

    ``apply_fn(params, x, weight_rates, act_rates, seed)`` must run the
    model with per-layer fault rates (traced vectors of length L) and
    return logits.  One jitted executable serves the whole search; the
    population axis is added with ``vmap`` so each ``delta_acc`` call
    costs one dispatch per unique-uncached chunk, not one per candidate.

    Args:
      eval_batch_size: max chromosomes per dispatch (None = whole
        unique batch in one dispatch; ``"auto"`` = probe the compiled
        executable's memory footprint and pick the largest power-of-two
        chunk fitting the device budget, see
        ``eval_engine.auto_eval_batch_size``).  Caps device memory;
        chunking never changes results.
      weight_tables: optional per-(unit, device) pre-corrupted weight
        tables (``repro.models.cnn.build_weight_fault_tables``).  When
        given, ``apply_fn`` must accept ``weight_rates=None`` and skip
        weight corruption (the gathered weights are already corrupted).
      quant_params: optional quantized parameter set (``layers.QTensor``
        leaves at the float leaves' flatten positions — CNN:
        ``models.cnn.quantize_unit_params``, LM:
        ``LMStepModel.quant_unit_params``).  Required by the
        ``"pallas"`` fault backend: corruption then happens on the
        resident int8 copy inside the compute (matmul-tile fused on
        TPU), so no corrupted weight variant ever materialises.
      fault_backend: which ΔAcc fault-injection path dispatches —
        ``"generic"`` (inline quantize→corrupt→dequantize at traced
        per-layer rates), ``"tables"`` (gather pre-corrupted
        ``weight_tables`` per gene), ``"pallas"`` (in-tile corruption
        of ``quant_params``; per-device rate arrays and seed are
        *traced* arguments, so fault-environment hot-swaps never
        rebuild an executable and resident fault state is O(params),
        not O(params × devices)).  ``"auto"`` = ``tables`` iff
        ``weight_tables`` is given, else ``generic``.  All three are
        bitwise-identical on CPU/interpret
        (tests/test_fault_backends.py); the TPU pallas tile holds
        under tolerance.
      step_fn: optional per-unit forward ``step(i, params_i, x, wr, ar,
        seed)`` (the CNN models' ``step``).  Enables the staged
        prefix-reuse engine; ``params`` must then be the per-unit list
        the model's ``init`` returns.
      eval_strategy: ``"staged"`` (prefix-reuse layer walk, requires
        ``step_fn``), ``"full"`` (whole-forward batched path), or
        ``"auto"`` (staged iff ``step_fn`` is given).  Both strategies
        are bit-identical; only cost differs.
      max_store_bytes: LRU cap on the staged engine's activation store
        (None = unbounded).  Eviction falls back to recompute — a
        performance knob, never a correctness one.
      devices: how many local devices the evaluation may shard over —
        ``"auto"`` (every ``jax.local_devices()`` entry, the default)
        or a positive count.  Chunks are placed round-robin (full path)
        or by prefix group (staged path) via
        ``eval_engine.DeviceScheduler``; one device is exactly the
        historical single-device path, and sharding never changes
        values (tests/test_sharded_eval.py pins devices=1 == devices=N
        bitwise).
      shared_carry_fields: staged-engine interning spec — maps a
        top-level carry-dict field to the unit depth whose gene prefix
        fully determines (and whose stored activation equals) it, e.g.
        ``{"mem": n_enc_layers - 1}`` for enc-dec encoder memory.  The
        store then keeps one payload per keying prefix instead of one
        per (prefix × unit).
      fuse_chains: staged-path chain fusion (default on).  Maximal
        non-branching runs of the gene-prefix tree dispatch as single
        fused segment executables (one ``jit(vmap)`` composing units
        ``start..start+length-1`` on the buddy-aligned power-of-two
        span ladder) instead of one dispatch per unit per depth —
        bitwise identical, cost only (tests/test_chain_fusion.py).
        ``False`` restores the PR-2 depth-by-depth walk.
    """

    def __init__(self, apply_fn, params, x: jax.Array, labels: jax.Array,
                 spec: FaultSpec, device_fault_scale: np.ndarray,
                 base_seed: int = 0,
                 eval_batch_size: int | str | None = None,
                 weight_tables: list | None = None,
                 quant_params: list | None = None,
                 fault_backend: str | None = "auto",
                 step_fn: Callable | None = None,
                 eval_strategy: str = "auto",
                 n_units: int | None = None,
                 max_store_bytes: int | None = 256 << 20,
                 devices: int | str | None = "auto",
                 shared_carry_fields: dict | None = None,
                 fuse_chains: bool = True):
        self.spec = spec
        self.base_seed = base_seed
        self.labels = labels
        self.weight_tables = weight_tables
        self._acc_batch_tables = None
        self._qparams = quant_params
        self._acc_batch_pallas = None
        self._fault_env_rebuilds = 0
        if fault_backend in (None, "auto"):
            fault_backend = "tables" if weight_tables is not None \
                else "generic"
        if fault_backend not in ("generic", "tables", "pallas"):
            raise ValueError(f"unknown fault_backend {fault_backend!r}")
        if fault_backend == "pallas" and quant_params is None:
            raise ValueError("fault_backend='pallas' needs quant_params "
                             "(QTensor-quantized model parameters)")
        if fault_backend == "pallas" and weight_tables is not None:
            raise ValueError("fault_backend='pallas' takes quant_params, "
                             "not weight_tables — pass one or the other")
        if fault_backend == "tables" and weight_tables is None:
            raise ValueError("fault_backend='tables' needs weight_tables")
        self._fault_backend = fault_backend
        self._apply_fn = apply_fn
        self._params = params
        self._x = x
        self._step_fn = step_fn
        self._built_unit_fns = None
        self._prefix_engine = None
        self.max_store_bytes = max_store_bytes
        self._scheduler = DeviceScheduler(devices)
        self.shared_carry_fields = dict(shared_carry_fields or {})
        self._fuse_chains = bool(fuse_chains)
        if n_units is None and isinstance(params, (list, tuple)):
            # per-unit param lists carry their own unit count; anything
            # else (e.g. a raw param dict) must pass n_units explicitly
            n_units = len(params)
        self._n_units = n_units
        if eval_strategy == "auto":
            eval_strategy = "staged" if step_fn is not None else "full"
        if eval_strategy not in ("staged", "full"):
            raise ValueError(f"unknown eval_strategy {eval_strategy!r}")
        if eval_strategy == "staged" and (step_fn is None or not n_units):
            raise ValueError("eval_strategy='staged' needs step_fn and "
                             "per-unit params (n_units)")
        self._strategy = eval_strategy
        # property setter: derives the per-device rate arrays
        self.device_fault_scale = device_fault_scale

        def _acc_row(weight_rates, act_rates, seed):
            logits = apply_fn(params, x, weight_rates, act_rates, seed)
            pred = jnp.argmax(logits, axis=-1)
            return jnp.mean((pred == labels).astype(jnp.float32))

        self._acc = jax.jit(_acc_row)          # single-row (clean + loop ref)

        @jax.jit
        def _acc_batch(WR, AR, seed):
            return jax.vmap(lambda wr, ar: _acc_row(wr, ar, seed))(WR, AR)

        self._acc_batch = _acc_batch

        if weight_tables is not None:
            n_units = len(weight_tables)
            a_dev = jnp.asarray(self.a_rates_by_device)

            def _acc_row_tables(p_row, seed):
                gathered = [jax.tree.map(lambda t: t[p_row[i]],
                                         weight_tables[i])
                            for i in range(n_units)]
                logits = apply_fn(gathered, x, None, a_dev[p_row], seed)
                pred = jnp.argmax(logits, axis=-1)
                return jnp.mean((pred == labels).astype(jnp.float32))

            @jax.jit
            def _acc_batch_tables(P_dev, seed):
                return jax.vmap(lambda p: _acc_row_tables(p, seed))(P_dev)

            self._acc_batch_tables = _acc_batch_tables

        self._engine = PopulationEvalEngine(self._dispatch, None,
                                            scheduler=self._scheduler)
        if self._strategy == "staged":
            self._ensure_prefix_engine()
        self._cache = self._engine._cache      # chromosome -> faulty accuracy
        self.eval_batch_size = eval_batch_size  # resolves "auto" via probe
        self._clean: float | None = None       # computed lazily

    # -- staged (prefix-reuse) machinery ------------------------------------
    def _ensure_prefix_engine(self) -> PrefixEvalEngine:
        """Build the staged engine once; it shares the full path's
        row-level result cache so strategies interoperate."""
        if self._prefix_engine is None:
            L = self._n_units
            self._prefix_engine = PrefixEvalEngine(
                [functools.partial(self._unit_dispatch, i) for i in range(L)],
                L, eval_batch_size=self._engine.eval_batch_size,
                max_store_bytes=self.max_store_bytes,
                scheduler=self._scheduler,
                shared_fields=self.shared_carry_fields,
                segment_fn=self._segment_dispatch if self._fuse_chains
                else None)
            self._prefix_engine._cache = self._engine._cache
        return self._prefix_engine

    def _unit_dispatch(self, i: int, acts, devs):
        """PrefixEvalEngine unit callable: one jit(vmap) dispatch of
        unit ``i`` over the fresh prefixes' (parent act, device) rows."""
        if self._built_unit_fns is None:
            self._built_unit_fns = self._build_unit_fns()
        return self._built_unit_fns[i](acts, devs)

    @property
    def fuse_chains(self) -> bool:
        """Whether the staged path fuses non-branching prefix chains
        into single segment executables (see the constructor)."""
        return self._fuse_chains

    @fuse_chains.setter
    def fuse_chains(self, value: bool):
        self._fuse_chains = bool(value)
        if self._prefix_engine is not None:
            self._prefix_engine.segment_fn = \
                self._segment_dispatch if self._fuse_chains else None

    def _segment_dispatch(self, start: int, length: int) -> Callable:
        """PrefixEvalEngine ``segment_fn``: the fused executable for
        units ``start..start+length-1``, built once per (start, length)
        and cached at module level (``_SEGMENT_CACHE``) so the
        compiled segments survive ObjectiveFn/partitioner rebuilds."""
        cache = _SEGMENT_CACHE.get(self)
        if cache is None:
            cache = _SEGMENT_CACHE[self] = {}
        fn = cache.get((start, length))
        if fn is None:
            fn = cache[(start, length)] = \
                self._build_segment_fn(start, length)
        return fn

    def _build_segment_fn(self, start: int, length: int) -> Callable:
        """One jitted vmapped executable composing units
        ``start..start+length-1`` — the chain-fusion tentpole.

        Exactly the per-unit executables' math, composed at trace time
        so XLA fuses the bodies into one dispatch: the same per-unit
        seed derivation (``base_seed + 7919·i``), the same
        weight-table gather (wr=None, pre-corrupted weights indexed by
        the row's gene) or inline corruption at the per-device scalar
        rates, depth 0 closing over the calibration batch, and the
        final depth folding the Top-1 accuracy reduction at the
        segment tail so logits never hit the activation store.
        Length-1 segments reuse the per-unit executables (shared with
        the unfused walk and the eviction-recompute fallback) instead
        of compiling twins.

        The returned callable must NOT capture ``self``: it is cached
        in the weak-keyed ``_SEGMENT_CACHE``, and a value referencing
        its key would make the entry (evaluator, params, calibration
        batch and all compiled executables) immortal.
        """
        if length == 1:
            if self._built_unit_fns is None:
                self._built_unit_fns = self._build_unit_fns()
            unit = self._built_unit_fns[start]
            return lambda acts, genes, f=unit: f(acts, genes[:, 0])
        if self._fault_backend == "pallas":
            return self._build_segment_fn_pallas(start, length)
        step, x0, labels = self._step_fn, self._x, self.labels
        L = self._n_units
        a_dev = jnp.asarray(self.a_rates_by_device)
        w_dev = jnp.asarray(self.w_rates_by_device)
        tables = self.weight_tables if self._fault_backend == "tables" \
            else None
        params = self._params
        final = start + length == L
        base = int(self.base_seed)

        def seg(x, genes):
            for k in range(length):
                i = start + k
                d = genes[k]
                s_i = base + 7919 * i
                if tables is not None:
                    p = jax.tree.map(lambda t: t[d], tables[i])
                    x = step(i, p, x, None, a_dev[d], s_i)
                else:
                    x = step(i, params[i], x, w_dev[d], a_dev[d], s_i)
            if final:
                pred = jnp.argmax(x, axis=-1)
                return jnp.mean((pred == labels).astype(jnp.float32))
            return x

        name = f"engine_seg_{start}_{length}"
        if start == 0:
            batched = jax.jit(jax.vmap(obs.named(lambda g: seg(x0, g),
                                                 name)))
            return lambda acts, genes, b=batched: b(genes)
        batched = jax.jit(jax.vmap(obs.named(seg, name)))
        return lambda acts, genes, b=batched: b(acts, genes)

    def _build_segment_fn_pallas(self, start: int, length: int) -> Callable:
        """Fused segment executable for the ``pallas`` backend.

        Same composition as :meth:`_build_segment_fn`, but the per-unit
        params are the resident ``QTensor`` set (corruption happens
        inside the unit's contractions via ``layers.fault_dense``) and
        the per-device rate arrays + base seed enter as TRACED
        broadcast arguments instead of baked-in constants — one
        compiled segment serves every fault environment, so
        ``device_fault_scale`` hot-swaps keep the whole executable
        ladder.  The returned wrapper re-reads the evaluator's current
        environment per call through a weakref (no strong ``self``
        capture — see ``_pallas_env_args``).
        """
        step, x0, labels = self._step_fn, self._x, self.labels
        L = self._n_units
        qp = self._qparams
        final = start + length == L
        ref = weakref.ref(self)

        def seg(x, genes, w_dev, a_dev, sd):
            for k in range(length):
                i = start + k
                d = genes[k]
                x = step(i, qp[i], x, w_dev[d], a_dev[d], sd + 7919 * i)
            if final:
                pred = jnp.argmax(x, axis=-1)
                return jnp.mean((pred == labels).astype(jnp.float32))
            return x

        name = f"engine_seg_{start}_{length}"
        if start == 0:
            batched = jax.jit(jax.vmap(
                obs.named(lambda g, w, a, s: seg(x0, g, w, a, s), name),
                in_axes=(0, None, None, None)))
            return lambda acts, genes, b=batched, r=ref: \
                b(genes, *_pallas_env_args(r))
        batched = jax.jit(jax.vmap(obs.named(seg, name),
                                   in_axes=(0, 0, None, None, None)))
        return lambda acts, genes, b=batched, r=ref: \
            b(acts, genes, *_pallas_env_args(r))

    def _build_unit_fns(self) -> list:
        """One jitted vmapped executable per unit depth.

        Mirrors the full path exactly: per-unit seed ``base_seed +
        7919*i`` (what ``models.cnn._rates`` derives), weight-table
        gather when tables exist (wr=None, acts corrupted at
        ``a_rates_by_device[d]``), inline corruption at the per-device
        scalar rates otherwise.  Depth 0 closes over the calibration
        batch; the final depth folds in the Top-1 accuracy reduction so
        logits never hit the activation store.
        """
        if self._fault_backend == "pallas":
            return self._build_unit_fns_pallas()
        step, x, labels = self._step_fn, self._x, self.labels
        L = self._n_units
        a_dev = jnp.asarray(self.a_rates_by_device)
        w_dev = jnp.asarray(self.w_rates_by_device)
        tables = self.weight_tables if self._fault_backend == "tables" \
            else None
        fns = []
        for i in range(L):
            s_i = int(self.base_seed) + 7919 * i
            if tables is not None:
                t_i = tables[i]
                def one(act, d, i=i, t_i=t_i, s_i=s_i):
                    p = jax.tree.map(lambda t: t[d], t_i)
                    return step(i, p, act, None, a_dev[d], s_i)
            else:
                p_i = self._params[i]
                def one(act, d, i=i, p_i=p_i, s_i=s_i):
                    return step(i, p_i, act, w_dev[d], a_dev[d], s_i)
            if i == L - 1:
                def one(act, d, unit=one):
                    logits = unit(act, d)
                    pred = jnp.argmax(logits, axis=-1)
                    return jnp.mean((pred == labels).astype(jnp.float32))
            name = f"engine_unit_{i}"
            if i == 0:
                batched = jax.jit(jax.vmap(obs.named(
                    lambda d, f=one: f(x, d), name)))
                fns.append(lambda acts, devs, b=batched: b(devs))
            else:
                batched = jax.jit(jax.vmap(obs.named(one, name)))
                fns.append(lambda acts, devs, b=batched: b(acts, devs))
        return fns

    def _build_unit_fns_pallas(self) -> list:
        """Per-unit executables for the ``pallas`` backend.

        The unit step runs on the resident ``QTensor`` params (flips
        happen inside the unit's contractions), and the per-device rate
        arrays + base seed are TRACED broadcast arguments — one
        compiled executable per unit depth serves every fault
        environment.  Wrappers fetch the evaluator's current arrays at
        call time through a weakref (``_pallas_env_args``), so a
        ``device_fault_scale`` assignment changes the next call's
        arguments without touching any compiled state.
        """
        step, x, labels = self._step_fn, self._x, self.labels
        L = self._n_units
        qp = self._qparams
        ref = weakref.ref(self)
        fns = []
        for i in range(L):
            p_i = qp[i]

            def one(act, d, w_dev, a_dev, sd, i=i, p_i=p_i):
                return step(i, p_i, act, w_dev[d], a_dev[d], sd + 7919 * i)
            if i == L - 1:
                def one(act, d, w_dev, a_dev, sd, unit=one):
                    logits = unit(act, d, w_dev, a_dev, sd)
                    pred = jnp.argmax(logits, axis=-1)
                    return jnp.mean((pred == labels).astype(jnp.float32))
            name = f"engine_unit_{i}"
            if i == 0:
                batched = jax.jit(jax.vmap(
                    obs.named(lambda d, w, a, s, f=one: f(x, d, w, a, s),
                              name),
                    in_axes=(0, None, None, None)))
                fns.append(lambda acts, devs, b=batched, r=ref:
                           b(devs, *_pallas_env_args(r)))
            else:
                batched = jax.jit(jax.vmap(
                    obs.named(one, name), in_axes=(0, 0, None, None, None)))
                fns.append(lambda acts, devs, b=batched, r=ref:
                           b(acts, devs, *_pallas_env_args(r)))
        return fns

    def staged_stats(self) -> dict:
        """Prefix-reuse accounting (unit runs, hits, evictions, ...)."""
        if self._prefix_engine is None:
            return {}
        return self._prefix_engine.stats()

    @property
    def fault_backend(self) -> str:
        """Which ΔAcc fault-injection path dispatches: ``"generic"``,
        ``"tables"`` or ``"pallas"`` (see the constructor)."""
        return self._fault_backend

    @fault_backend.setter
    def fault_backend(self, value: str | None):
        """Switch the injection path.  Backends are value-identical
        (bitwise on CPU/interpret), so this is a cost decision; the
        path-specific executables and cached activations are dropped
        and rebuilt lazily under the new backend."""
        if value in (None, "auto"):
            value = "tables" if self.weight_tables is not None \
                else "generic"
        if value not in ("generic", "tables", "pallas"):
            raise ValueError(f"unknown fault_backend {value!r}")
        if value == self._fault_backend:
            return
        if value == "pallas" and self._qparams is None:
            raise ValueError("fault_backend='pallas' needs quant_params "
                             "(QTensor-quantized model parameters) at "
                             "construction")
        if value == "tables" and self.weight_tables is None:
            raise ValueError("fault_backend='tables' needs weight_tables "
                             "(they were dropped or never built)")
        self._fault_backend = value
        self._built_unit_fns = None
        _SEGMENT_CACHE.pop(self, None)
        self._engine._cache.clear()
        if self._prefix_engine is not None:
            self._prefix_engine.store.clear()
        if getattr(self, "_ebs_auto", False):
            # the probed chunk size was fitted to the OLD backend's
            # per-row footprint; re-resolve against the new path
            self.eval_batch_size = "auto"

    def _ensure_pallas_batch(self) -> Callable:
        """Build the full-forward pallas batch executable once: rows of
        device ids -> accuracies, with the per-device rate arrays and
        seed traced (same hot-swap contract as the staged pallas fns).
        Gathering ``w_dev[p]`` inside the trace is bitwise-identical to
        the generic path's host-side ``w_rates_by_device[rows]``."""
        if self._acc_batch_pallas is None:
            apply_fn, qp = self._apply_fn, self._qparams
            x, labels = self._x, self.labels

            @jax.jit
            def _batch(P_dev, w_dev, a_dev, seed):
                def row(p):
                    logits = apply_fn(qp, x, w_dev[p], a_dev[p], seed)
                    pred = jnp.argmax(logits, axis=-1)
                    return jnp.mean((pred == labels).astype(jnp.float32))
                return jax.vmap(row)(P_dev)

            self._acc_batch_pallas = _batch
        return self._acc_batch_pallas

    def fault_table_bytes(self) -> int:
        """Resident bytes of pre-corrupted weight-table variants — the
        O(L × D) state the ``pallas`` backend eliminates (its value
        there is 0, which benchmarks/eval_engine.py guards)."""
        if self.weight_tables is None:
            return 0
        return int(sum(int(leaf.nbytes)
                       for t in self.weight_tables
                       for leaf in jax.tree.leaves(t)
                       if hasattr(leaf, "nbytes")))

    def fault_state_bytes(self) -> int:
        """Resident bytes of backend-specific fault state: the weight
        tables (``tables``), the quantized int8 parameter copy
        (``pallas`` — O(params), device-count independent), or 0
        (``generic``)."""
        if self._fault_backend == "pallas":
            from repro.models.layers import QTensor
            return int(sum(int(leaf.qw.nbytes) + int(leaf.scale.nbytes)
                           for leaf in jax.tree.leaves(self._qparams)
                           if isinstance(leaf, QTensor)))
        return self.fault_table_bytes()

    @property
    def devices(self) -> int:
        """Local devices the evaluation shards over (see the
        constructor's ``devices``)."""
        return self._scheduler.n_devices

    @devices.setter
    def devices(self, value: int | str | None):
        sched = DeviceScheduler("auto" if value is None else value)
        if sched.n_devices == self._scheduler.n_devices:
            return                              # same pool, keep state
        self._scheduler = sched
        self._engine.scheduler = sched
        if self._prefix_engine is not None:
            self._prefix_engine.scheduler = sched
            # stored activations are committed to the OLD pool; jax
            # raises on cross-device stacking, so drop placement+store
            # (row-level results are host floats and stay valid)
            self._prefix_engine.reset_placement()
        if getattr(self, "_ebs_auto", False):
            # an "auto"-probed chunk size was fitted to the OLD pool's
            # per-device budget; re-resolve against the new one
            self.eval_batch_size = "auto"

    @property
    def eval_strategy(self) -> str:
        return self._strategy

    @eval_strategy.setter
    def eval_strategy(self, value: str):
        if value == "auto":
            value = "staged" if self._step_fn is not None else "full"
        if value not in ("staged", "full"):
            raise ValueError(f"unknown eval_strategy {value!r}")
        if value == "staged" and (self._step_fn is None
                                  or not self._n_units):
            raise ValueError("eval_strategy='staged' needs step_fn and "
                             "per-unit params (n_units)")
        self._strategy = value
        if value == "staged":
            self._ensure_prefix_engine()

    @property
    def device_fault_scale(self) -> np.ndarray:
        return self._device_fault_scale

    @device_fault_scale.setter
    def device_fault_scale(self, value):
        """Refresh the evaluator's view of the fault environment.

        The online reconfigurator (runtime.py) assigns this when the
        observed environment shifts: the per-device rate arrays are
        re-derived (indexing after the multiply stays bitwise-identical
        to the historical ``rate * scale[P]``) and the chromosome cache
        is invalidated.  What ELSE it costs depends on the backend:

        * ``pallas`` — nothing.  Every pallas executable takes the rate
          arrays and seed as traced arguments, so the compiled unit,
          segment and batch executables all survive; only cached
          RESULTS (row cache, staged activation store) encode the old
          rates and are dropped.  ``_fault_env_rebuilds`` stays 0 —
          benchmarks/serve.py's hot-swap guard pins this.
        * ``tables`` / ``generic`` — the pre-corrupted weight tables
          (which encode the OLD rates) are dropped, degrading
          ``tables`` to ``generic`` until tables are rebuilt, and the
          staged executables (which close over the rate arrays as
          constants) are invalidated; ``_fault_env_rebuilds`` counts
          these teardowns.
        """
        value = np.asarray(value, np.float32)
        changed = (getattr(self, "_device_fault_scale", None) is not None
                   and not np.array_equal(self._device_fault_scale, value))
        self._device_fault_scale = value
        self.w_rates_by_device = np.asarray(
            self.spec.weight_fault_rate * value, np.float32)
        self.a_rates_by_device = np.asarray(
            self.spec.act_fault_rate * value, np.float32)
        if changed:
            if getattr(self, "_engine", None) is not None:
                self._engine._cache.clear()
            if self._fault_backend == "pallas":
                if getattr(self, "_prefix_engine", None) is not None:
                    self._prefix_engine.store.clear()
                return
            self._fault_env_rebuilds += 1
            self.weight_tables = None
            self._acc_batch_tables = None
            if self._fault_backend == "tables":
                self._fault_backend = "generic"
            # staged state encodes the old rates too: drop the unit
            # executables, the fused-segment executables and the
            # activation store (row cache is shared with _engine and
            # already cleared above)
            self._built_unit_fns = None
            _SEGMENT_CACHE.pop(self, None)
            if getattr(self, "_prefix_engine", None) is not None:
                self._prefix_engine.store.clear()

    @property
    def eval_batch_size(self) -> int | None:
        return self._engine.eval_batch_size

    @eval_batch_size.setter
    def eval_batch_size(self, value: int | str | None):
        # remember "auto" so a later pool change (the devices setter)
        # can re-fit the chunk size to the new per-device budget
        self._ebs_auto = value == "auto"
        if value == "auto":
            value = self._auto_eval_batch_size()
        self._engine.eval_batch_size = value
        if self._prefix_engine is not None:
            self._prefix_engine.eval_batch_size = value

    def _auto_eval_batch_size(self) -> int | None:
        """Resolve ``eval_batch_size="auto"`` by probing the batched
        executable's compiled memory footprint at 1 and 2 rows (the
        launch/dryrun.py two-point analysis) and fitting the largest
        power-of-two chunk into the device budget, with the staged
        activation-store cap carved out up front.

        The probe targets the executable that will actually dispatch:
        the pallas path under ``fault_backend="pallas"`` (whose budget
        excludes the O(params × devices) table variants entirely — the
        reclaimed memory shows up here as larger auto chunks), the
        weight-table path when tables exist (its per-row footprint
        includes the gathered per-unit weights, which the generic path
        shares as vmap constants), else the generic path.  The staged
        engine's per-unit dispatches touch strictly less than one full
        forward per row, so the full-forward probe is a safe upper
        bound for it.

        Budgeting is PER DEVICE: a chunk is a single-device dispatch
        even when the scheduler spreads chunks over a pool, so the
        chunk must fit one device's share
        (``device_memory_budget(n_devices=...)``).  The staged
        activation-store cap is still reserved in full on every device
        — prefix-group sharding balances resident activations across
        the pool only as well as the depth-0 genes spread, so the full
        cap is the safe bound.
        """
        L = self._n_units
        if not L:
            return None

        def probe(n: int) -> int:
            # a compile error here is the chip's compiler refusing the
            # executable that would dispatch: let it propagate
            if self._fault_backend == "pallas":
                D = len(self.w_rates_by_device)
                zd = jnp.zeros((D,), jnp.float32)
                compiled = self._ensure_pallas_batch().lower(
                    jnp.zeros((n, L), jnp.int32), zd, zd,
                    jnp.int32(self.base_seed)).compile()
            elif self._fault_backend == "tables" \
                    and self._acc_batch_tables is not None:
                compiled = self._acc_batch_tables.lower(
                    jnp.zeros((n, L), jnp.int32),
                    jnp.int32(self.base_seed)).compile()
            else:
                z = jnp.zeros((n, L), jnp.float32)
                compiled = self._acc_batch.lower(
                    z, z, jnp.int32(self.base_seed)).compile()
            return peak_memory_bytes(compiled)

        reserved = self.max_store_bytes or 0 \
            if self._strategy == "staged" else 0
        return auto_eval_batch_size(probe, reserved=reserved,
                                    n_devices=self._scheduler.n_devices)

    @property
    def dispatches(self) -> int:
        """Jitted batch dispatches issued so far (cache hits cost zero)."""
        n = self._engine.dispatches
        if self._prefix_engine is not None:
            n += self._prefix_engine.dispatches
        return n

    def _dispatch(self, rows: np.ndarray, device=None):
        """One jitted dispatch: [U, L] device rows -> [U] faulty
        accuracies, returned as the UN-SYNCED device array (the engine
        gathers once per generation).  ``device`` commits the chunk's
        inputs — and with them the computation — to one scheduler
        device."""
        seed = jnp.int32(self.base_seed)
        put = DeviceScheduler.put
        if self._fault_backend == "pallas":
            return self._ensure_pallas_batch()(
                put(np.asarray(rows, np.int32), device),
                put(np.asarray(self.w_rates_by_device, np.float32), device),
                put(np.asarray(self.a_rates_by_device, np.float32), device),
                seed)
        if self._fault_backend == "tables" \
                and self._acc_batch_tables is not None:
            return self._acc_batch_tables(
                put(np.asarray(rows, np.int32), device), seed)
        WR = put(np.asarray(self.w_rates_by_device[rows], np.float32), device)
        AR = put(np.asarray(self.a_rates_by_device[rows], np.float32), device)
        return self._acc_batch(WR, AR, seed)

    def _clean_for(self, n: int) -> float:
        if self._clean is None:
            z = jnp.zeros((n,), jnp.float32)
            self._clean = float(self._acc(z, z, jnp.int32(self.base_seed)))
        return self._clean

    def clean_accuracy(self, n_layers: int | None = None) -> float:
        """Accuracy of the quantized-but-unflipped model (zero rates).

        The layer count is derived from the model's own unit count.
        The ``n_layers`` parameter is DEPRECATED: it used to be the
        caller's job, and a mismatched count silently mis-shaped the
        clean-rate rows.  Passing it now warns, and a value that
        disagrees with the model's ``n_units`` raises.
        """
        if n_layers is not None:
            warnings.warn(
                "clean_accuracy(n_layers) is deprecated; the layer count "
                "is derived from the model's n_units", DeprecationWarning,
                stacklevel=2)
            if self._n_units is not None and n_layers != self._n_units:
                raise ValueError(
                    f"n_layers={n_layers} does not match the model's "
                    f"n_units={self._n_units}")
        n = self._n_units or n_layers
        if not n:
            raise ValueError(
                "unit count unknown: construct the evaluator with "
                "n_units= (or per-unit list params)")
        return self._clean_for(n)

    def delta_acc(self, P: np.ndarray) -> np.ndarray:
        """P: [N, L] device ids -> ΔAcc per candidate.

        Deduplicates the population, evaluates only unique uncached
        chromosomes, and scatters results back through the shared row
        cache.  ``eval_strategy="full"`` pushes unique rows through one
        whole-forward vmapped dispatch per ``eval_batch_size`` chunk;
        ``"staged"`` walks the model layer by layer, evaluating each
        unique gene prefix once (see PrefixEvalEngine).  Bit-identical
        either way.
        """
        P = np.asarray(P)
        if self._n_units is not None and P.shape[1] != self._n_units:
            raise ValueError(f"population rows have {P.shape[1]} genes "
                             f"but the model has {self._n_units} units")
        clean = self._clean_for(self._n_units or P.shape[1])
        if self._strategy == "staged":
            faulty = self._ensure_prefix_engine().evaluate(P)
        else:
            faulty = self._engine.evaluate(P)
        return np.maximum(0.0, clean - faulty)


def make_lm_accuracy_evaluator(cfg, params, batch, labels,
                               spec: FaultSpec, device_fault_scale,
                               *, base_seed: int = 0,
                               eval_batch_size: int | str | None = None,
                               eval_strategy: str = "auto",
                               max_store_bytes: int | None = 256 << 20,
                               devices: int | str | None = "auto",
                               fuse_chains: bool = True,
                               fault_backend: str | None = "auto",
                               ) -> InferenceAccuracyEvaluator:
    """Staged-capable ΔAcc evaluator for any ``configs.ArchConfig`` LM.

    Bridges the unified transformer stack into the same
    :class:`InferenceAccuracyEvaluator` the CNNs use — there is no
    CNN/LM split in the evaluation engine.  The model is wrapped in
    ``models.transformer.LMStepModel`` (per-unit step contract, one
    unit per partitionable layer in ``models.graph.lm_layer_infos``
    order: encoder layers first for enc-dec), its stacked params are
    sliced into the per-unit list the staged engine walks, and
    ``apply`` — derived from the step composition — serves the
    full-forward path and the clean-accuracy row.

    Args:
      cfg: the architecture (use ``cfg.reduced()`` for smoke scale;
        ``models.graph.lm_eval_strategy`` says whether the full config
        is small enough to instantiate at all).
      params: ``transformer.init_lm`` output for ``cfg``.
      batch: calibration batch dict — ``{"tokens": [B,S]}`` or
        ``{"embeds": [B,S,D]}``, plus ``{"enc_embeds"}`` for enc-dec.
      labels: ``[B, S]`` target tokens; ΔAcc is token-level top-1
        degradation.  Using the clean model's own argmax makes
        clean_accuracy 1.0 and ΔAcc a pure corruption measure.
      eval_strategy: "auto" resolves to "staged" (the step API is
        always available here); "full" selects the whole-forward path
        — bit-identical, cost only (tests/test_transformer_staged.py).
      fault_backend: ``"generic"`` (the historical LM path — "auto"
        resolves here), ``"pallas"`` (builds
        ``LMStepModel.quant_unit_params``: one resident int8 copy,
        flips inside the contraction, hot-swap-free rate changes) or
        ``"tables"`` (builds ``LMStepModel.build_weight_fault_tables``:
        O(L × D) pre-corrupted variants gathered per gene).  All
        value-identical; see InferenceAccuracyEvaluator.

    ``spec.bits``/``spec.faulty_bits`` pin the fixed-point fault width
    of the corruption (the paper's INT8-class ``bits=8`` regime is
    what visibly moves token-level top-1 at smoke scale) — no separate
    ``layers.set_fault_bits`` call needed.

    Enc-dec configs get the lean staged carries: the static decoder
    input is bound into the step model (closed over by the first
    decoder unit's executable, never threaded through the encoder
    carries) and the encoder memory is interned by encoder prefix
    (``shared_carry_fields={"mem": n_enc_layers - 1}``), so the
    activation store pays for it once per encoder prefix instead of
    once per (prefix × unit) — the ROADMAP enc-dec open item,
    pinned by tests/test_sharded_eval.py.
    """
    from repro.models.transformer import LMStepModel
    sm = LMStepModel(cfg, bits=spec.bits, faulty_bits=spec.faulty_bits,
                     batch=batch if cfg.is_encdec else None,
                     fault_model=spec.fault_model, mbu_width=spec.mbu_width)
    shared = {"mem": cfg.n_enc_layers - 1} if cfg.is_encdec else None
    units = sm.unit_params(params)
    if fault_backend in (None, "auto"):
        fault_backend = "generic"    # no LM tables unless asked for
    quant_params = tables = None
    if fault_backend == "pallas":
        quant_params = sm.quant_unit_params(params)
    elif fault_backend == "tables":
        tables = sm.build_weight_fault_tables(
            units, spec.weight_fault_rate * np.asarray(device_fault_scale,
                                                       np.float32),
            base_seed=base_seed)
    return InferenceAccuracyEvaluator(
        sm.apply, units, batch, labels, spec,
        device_fault_scale, base_seed=base_seed,
        eval_batch_size=eval_batch_size, weight_tables=tables,
        quant_params=quant_params, fault_backend=fault_backend,
        step_fn=sm.step,
        eval_strategy=eval_strategy, n_units=sm.n_units,
        max_store_bytes=max_store_bytes, devices=devices,
        shared_carry_fields=shared, fuse_chains=fuse_chains)


class SurrogateAccuracyEvaluator:
    """ΔAcc ≈ Σ_l sensitivity_l · fault_scale[P_l], calibrated.

    ``calibrate(true_fn, samples)`` fits a single multiplicative factor
    against true fault-injected evaluations so the surrogate is in
    ΔAcc units rather than arbitrary sensitivity units.
    """

    def __init__(self, cost_model: CostModel):
        self.cm = cost_model
        self.calibration = 1.0

    def calibrate(self, true_delta_acc_fn: Callable[[np.ndarray], np.ndarray],
                  n_samples: int = 8, seed: int = 0):
        rng = np.random.default_rng(seed)
        L, D = len(self.cm.layers), len(self.cm.devices)
        P = rng.integers(0, D, size=(n_samples, L))
        true = np.asarray(true_delta_acc_fn(P))
        sur = self.cm.sensitivity_surrogate(P)
        denom = float((sur * sur).sum())
        if denom > 0:
            self.calibration = float((true * sur).sum()) / denom
        return self.calibration

    def delta_acc(self, P: np.ndarray) -> np.ndarray:
        return self.cm.sensitivity_surrogate(P) * self.calibration


@dataclasses.dataclass
class ObjectiveFn:
    """Assembles the [N,3] (or [N,2] for fault-unaware) objective matrix.

    This is the ``eval_fn`` handed to :func:`repro.core.nsga2.nsga2`:
    it receives the full ``[N, L]`` population once per generation and
    returns ``[N, M]`` in a single call, so the ΔAcc evaluator can batch
    every unique chromosome into one device dispatch.  Set
    ``eval_batch_size`` to cap chromosomes per dispatch; dispatch count
    stays O(generations), never O(generations × population).

    ``eval_batch_size`` semantics: a non-None value OVERRIDES the
    evaluator's own chunk size at construction time (the evaluator is
    mutated — don't share one evaluator between ObjectiveFns that want
    different chunking); None means "leave the evaluator's setting
    alone", not "force full-batch".  ``"auto"`` asks the evaluator to
    probe its compiled memory footprint and size the chunk itself.
    ``eval_strategy`` follows the same override-or-leave-alone rule:
    ``"staged"`` / ``"full"`` select the ΔAcc execution path on
    evaluators that support it (see InferenceAccuracyEvaluator),
    ``fuse_chains`` (True/False) toggles the staged path's chain-fused
    dispatch, ``fault_backend`` (``"generic"`` / ``"tables"`` /
    ``"pallas"`` / ``"auto"``) selects the fault-injection path, and
    ``devices`` (``"auto"`` or a count) selects how many local devices
    the ΔAcc dispatches shard over — placement, fusion and backend
    never change results.
    """

    cost_model: CostModel
    acc_evaluator: object | None          # None => fault-unaware baseline
    latency_weight: float = 1.0
    energy_weight: float = 1.0
    eval_batch_size: int | str | None = None
    eval_strategy: str | None = None
    devices: int | str | None = None
    fuse_chains: bool | None = None
    fault_backend: str | None = None

    def __post_init__(self):
        # devices first (eval_batch_size="auto" budgets per device),
        # then strategy (staged reserves the activation store) and the
        # injection path, then the chunk size that depends on all three
        if (self.devices is not None
                and hasattr(self.acc_evaluator, "devices")):
            self.acc_evaluator.devices = self.devices
        if (self.eval_strategy is not None
                and hasattr(self.acc_evaluator, "eval_strategy")):
            self.acc_evaluator.eval_strategy = self.eval_strategy
        if (self.fuse_chains is not None
                and hasattr(self.acc_evaluator, "fuse_chains")):
            self.acc_evaluator.fuse_chains = self.fuse_chains
        if (self.fault_backend is not None
                and hasattr(self.acc_evaluator, "fault_backend")):
            self.acc_evaluator.fault_backend = self.fault_backend
        if (self.eval_batch_size is not None
                and hasattr(self.acc_evaluator, "eval_batch_size")):
            self.acc_evaluator.eval_batch_size = self.eval_batch_size
        self._calls = 0

    @property
    def n_objectives(self) -> int:
        return 2 if self.acc_evaluator is None else 3

    def __call__(self, P: np.ndarray) -> np.ndarray:
        self._calls += 1
        with obs.span("objective", call=self._calls, rows=len(P)):
            lat = self.cost_model.latency(P) * self.latency_weight
            en = self.cost_model.energy_of(P) * self.energy_weight
            if self.acc_evaluator is None:
                return np.stack([lat, en], axis=1)
            dacc = self.acc_evaluator.delta_acc(P)
            return np.stack([lat, en, dacc], axis=1)

    def violation(self, P: np.ndarray) -> np.ndarray:
        return self.cost_model.violation(P)


@functools.lru_cache(maxsize=32)
def _profile_acc_batch(apply_fn):
    """Module-level compile cache for the layer-sweep batch.

    The jitted executable used to live inside
    :func:`profile_layer_sensitivity`, so every call re-traced and
    re-compiled from scratch.  Hoisting it here — keyed by ``apply_fn``,
    with params/data as traced arguments — makes repeated profiling
    calls (surrogate pipelines sweep many rates/seeds) hit jit's own
    cache instead.

    The cache key is ``apply_fn``'s identity: pass a *stable* function
    (e.g. ``model.apply`` itself) rather than a fresh per-call closure,
    or every call misses and re-compiles anyway.
    """

    @jax.jit
    def _acc_batch(params, x, labels, WR, AR, seed):
        def row(wr, ar):
            logits = apply_fn(params, x, wr, ar, seed)
            pred = jnp.argmax(logits, axis=-1)
            return jnp.mean((pred == labels).astype(jnp.float32))
        return jax.vmap(row)(WR, AR)

    return _acc_batch


def profile_layer_sensitivity(apply_fn, params, x, labels, n_layers: int,
                              spec: FaultSpec, base_seed: int = 0,
                              eval_batch_size: int | None = None,
                              ) -> np.ndarray:
    """Paper Sec. V-C strategy 1: layer-wise fault sweeping.

    Injects faults into ONE layer at a time (weights+activations at the
    spec's base rates) and records the Top-1 drop.  The resulting vector
    seeds ``LayerInfo.sensitivity`` for the surrogate evaluator and is
    itself a deliverable (which layers are fragile).

    The clean row plus the L one-hot rows form one ``[L+1, L]`` batch
    evaluated in a single vmapped dispatch (chunked by
    ``eval_batch_size`` if set) instead of an L-iteration loop.  The
    jitted executable is cached at module level (``_profile_acc_batch``)
    so repeated calls with the same ``apply_fn`` never re-trace.
    """
    _acc_batch = _profile_acc_batch(apply_fn)

    # row 0 = clean; row 1+l = faults on layer l only
    WR = np.zeros((n_layers + 1, n_layers), np.float32)
    AR = np.zeros((n_layers + 1, n_layers), np.float32)
    WR[1:][np.diag_indices(n_layers)] = np.float32(spec.weight_fault_rate)
    AR[1:][np.diag_indices(n_layers)] = np.float32(spec.act_fault_rate)

    accs = np.empty(n_layers + 1)
    seed = jnp.int32(base_seed)
    for start, stop, padded in chunked_rows(n_layers + 1, eval_batch_size):
        wr = pad_rows(WR[start:stop], padded)
        ar = pad_rows(AR[start:stop], padded)
        vals = np.asarray(_acc_batch(params, x, labels,
                                     jnp.asarray(wr), jnp.asarray(ar), seed))
        accs[start:stop] = vals[:stop - start]
    return np.maximum(0.0, accs[0] - accs[1:])
