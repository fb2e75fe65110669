"""Population-level evaluation engine: three layers, one contract.

The NSGA-II inner loop evaluates fault-injected ΔAcc for a whole
population every generation (paper Alg. 1 lines 5-7).  This module owns
every population-side concern of that loop, stacked in three layers:

1. **Population engine** (:class:`PopulationEvalEngine`, PR 1) — the
   whole-forward path.  Deduplicates rows inside a population, caches
   rows across generations (chromosomes are hashable integer tuples and
   evaluation is deterministic given the seed, so caching is exact),
   and pushes the unique uncached rows through chunked, shape-bucketed
   ``jit(vmap)`` dispatches: ``eval_batch_size`` caps rows per dispatch,
   chunks are padded (by repeating the last row) to a small set of
   static shapes so XLA compiles O(log N) variants.

2. **Prefix engine** (:class:`PrefixEvalEngine`, PRs 2-3, 5) — the
   staged path.  A chromosome's corrupted activation after unit *i*
   depends only on genes ``P[0..i]``, so the engine evaluates each
   unique gene *prefix* once, with an LRU-bounded
   :class:`ActivationStore` (eviction falls back to recompute, never
   to wrong results).  Per-generation cost scales with unique
   prefixes, not ``unique_rows × L``.  With a ``segment_fn`` (PR 5,
   the default through ``InferenceAccuracyEvaluator``) the walk is
   *chain-fused*: maximal non-branching runs of the prefix trie
   dispatch as single fused segment executables instead of one
   dispatch per unit per depth, and dispatch outputs stay stacked in
   the store as :class:`StackedView` entries instead of being
   unstacked row by row.

3. **Device scheduler** (:class:`DeviceScheduler`, PR 4) — the sharded
   path.  Both engines accept a scheduler that places their dispatch
   chunks across ``jax.local_devices()`` (mesh enumeration via
   ``launch/mesh.make_eval_mesh``) and gathers results once per
   generation instead of syncing per chunk.  The full engine
   round-robins chunks; the prefix engine shards by *prefix group* —
   every prefix under one depth-0 gene lands on one device, so parent
   activations, their children, and any shared carries
   (:class:`PrefixRef`) stay device-local and no dispatch ever mixes
   devices.  With one device (or no scheduler) both engines degrade to
   the exact single-device behaviour.

Per-row results must be independent of the other rows in the batch
(true for vmapped per-candidate metrics), so padding, chunk boundaries,
and device placement never change values — tests/test_eval_engine.py,
tests/test_staged_eval.py and tests/test_sharded_eval.py assert
bit-for-bit equality against the per-individual loop, across engines,
and across device counts.  The ``batch_fn`` contract of the population
engine is

    batch_fn(rows: np.ndarray [U, L]) -> [U] per-row metrics

evaluated in a SINGLE device dispatch (typically ``jit(vmap(...))``);
when a multi-device scheduler is attached the engine also passes
``device=`` and the callable must commit its inputs there
(``jax.device_put``) and return the un-synced device array.
"""
from __future__ import annotations

import os
from collections import OrderedDict
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Callable, Sequence

import numpy as np

from repro import obs

__all__ = ["PopulationEvalEngine", "PrefixEvalEngine", "ActivationStore",
           "DeviceScheduler", "DeviceStreams", "PrefixRef", "StackedView",
           "chunked_rows", "bucket_size", "pad_rows",
           "auto_eval_batch_size", "device_memory_budget",
           "peak_memory_bytes", "parse_eval_batch_size", "parse_devices"]


def parse_eval_batch_size(value) -> int | str | None:
    """The one CLI/config grammar for ``eval_batch_size``: ``None`` and
    ``"auto"`` pass through, anything else must be a positive int.
    Shared by every benchmark CLI so the grammar cannot drift."""
    if value in (None, "auto"):
        return value
    n = int(value)
    if n < 1:
        raise ValueError(f"eval_batch_size must be >= 1, got {n}")
    return n


def parse_devices(value) -> int | str | None:
    """The one CLI/config grammar for the ``devices`` knob: ``None``
    (leave the evaluator's setting alone) and ``"auto"`` (use every
    local device) pass through, anything else must be a positive device
    count.  Shared by every benchmark CLI, like
    :func:`parse_eval_batch_size`."""
    if value is None or value == "auto":
        return value
    n = int(value)
    if n < 1:
        raise ValueError(f"devices must be >= 1, got {n}")
    return n


class DeviceScheduler:
    """Placement of evaluation dispatches across local devices.

    Owns the device pool both engines shard over: ``devices="auto"``
    takes every ``jax.local_devices()`` entry, an int takes the first
    ``n`` of them (raising when the host has fewer).  The pool is
    enumerated through a mesh built by ``launch/mesh.make_eval_mesh``
    so the evaluation engines and the launch stack agree on device
    order, and ``self.mesh`` is available to callers that want
    collective-based evaluation on top of it.

    Placement is *committed-input* scheduling: callers
    ``jax.device_put`` a chunk's inputs onto ``device_for(i)`` (or a
    device the caller picked) and jit runs the chunk there — no
    collectives, no resharding, and chunks on different devices execute
    concurrently because jax dispatch is asynchronous.  Per-row results
    are device-independent, so placement never changes values (the
    differential test in tests/test_sharded_eval.py pins
    ``devices=1 == devices=N`` bitwise).
    """

    def __init__(self, devices: int | str | None = "auto"):
        import jax

        local = jax.local_devices()
        spec = parse_devices(devices)
        n = len(local) if spec in (None, "auto") else spec
        if n > len(local):
            raise ValueError(
                f"devices={n} requested but only {len(local)} local "
                f"devices exist (set XLA_FLAGS="
                f"--xla_force_host_platform_device_count={n} for fake "
                f"host devices)")
        from repro.launch.mesh import make_eval_mesh
        self.mesh = make_eval_mesh(n)
        self.devices = list(self.mesh.devices.flat)

    @property
    def n_devices(self) -> int:
        return len(self.devices)

    def device_for(self, i: int):
        """Round-robin device for the ``i``-th chunk of a batch."""
        return self.devices[i % len(self.devices)]

    @staticmethod
    def put(array, device):
        """THE placement idiom: commit a host array to ``device``, or
        convert in place when ``device`` is None (the single-device
        degradation path).  Both engines and every ``batch_fn``
        implementation route through this so the convention lives in
        one place."""
        import jax
        import jax.numpy as jnp

        if device is None:
            return jnp.asarray(array)
        return jax.device_put(array, device)


class DeviceStreams:
    """One host thread per device group of a dispatch wave.

    jit compiles an executable once per committed device, so the first
    dispatch of each executable on each device compiles, and a host
    thread that dispatches to the pool's devices one after another pays
    N compiles in a row.  XLA compiles for different devices proceed in
    parallel; with one worker thread per group, N devices pay about one
    compile's wall time.  Each group's calls keep their order (one
    worker per group).  Once compiled, a call is an asynchronous
    dispatch either way.  With one group, calls run on the calling
    thread, exactly as without streams.
    """

    def __init__(self, n_groups: int):
        self._workers = [ThreadPoolExecutor(1) for _ in range(n_groups)] \
            if n_groups > 1 else None

    def submit(self, group: int, fn: Callable, *args, **kwargs) -> Future:
        if self._workers is not None:
            return self._workers[group].submit(fn, *args, **kwargs)
        done: Future = Future()
        done.set_result(fn(*args, **kwargs))
        return done

    def __enter__(self) -> "DeviceStreams":
        return self

    def __exit__(self, *exc):
        for w in self._workers or ():
            w.shutdown()


def _call(fn: Callable, *args):
    """One jitted dispatch, spanned where it runs (a stream's worker
    or the calling thread)."""
    with obs.span("engine.call"):
        return fn(*args)


def bucket_size(n: int) -> int:
    """Smallest power of two >= n (compile-shape bucketing)."""
    b = 1
    while b < n:
        b *= 2
    return b


def chunked_rows(n_rows: int, eval_batch_size: int | None
                 ) -> list[tuple[int, int, int]]:
    """Chunk plan: (start, stop, padded_size) per dispatch.

    With ``eval_batch_size`` set, full chunks are padded to exactly that
    size and a trailing partial chunk to its own power-of-two bucket —
    at most 1 + log2(bs) static shapes total, and a small population
    never pays for a huge configured chunk (an ``"auto"``-resolved cap
    can be 1024 rows while a deduped population is 6).  Without it the
    whole batch goes out in one dispatch padded to the next power of
    two.
    """
    if n_rows <= 0:
        return []
    if eval_batch_size is None:
        return [(0, n_rows, bucket_size(n_rows))]
    bs = max(1, int(eval_batch_size))
    return [(s, min(s + bs, n_rows),
             min(bs, bucket_size(min(s + bs, n_rows) - s)))
            for s in range(0, n_rows, bs)]


def pad_rows(rows: np.ndarray, padded: int) -> np.ndarray:
    """Pad a chunk to its static dispatch shape by repeating the last
    row (results for padding rows are sliced off; per-row independence
    makes them free)."""
    if padded <= len(rows):
        return rows
    pad = np.repeat(rows[-1:], padded - len(rows), axis=0)
    return np.concatenate([rows, pad], axis=0)


class PrefixRef:
    """Marker leaf inside a stored activation: "this carry field equals
    the activation stored at ``prefix``".

    The staged enc-dec walk used to store the encoder memory inside
    EVERY decoder prefix's activation — one ``[B, Se, D]`` buffer per
    (prefix × unit) even though the memory depends only on the encoder
    genes.  The engine now *interns* such fields
    (``shared_fields``): before storing, the field's value is replaced
    by a :class:`PrefixRef` to the keying prefix, and resolution fetches
    (or, after LRU eviction, recomputes) the real activation through the
    normal ``_ensure_act`` machinery.  A ref owns no buffer, so the
    store budget counts the shared payload once — per encoder prefix,
    not per (prefix × unit) — which tests/test_sharded_eval.py pins.
    """

    __slots__ = ("prefix",)

    def __init__(self, prefix: tuple):
        self.prefix = prefix

    def __repr__(self):
        return f"PrefixRef({self.prefix!r})"


class _StackedBatch:
    """One dispatch's stacked ``[U, ...]`` output pytree, kept whole.

    The staged engine used to unstack every dispatch output row by row
    (``jax.tree.map(lambda a: a[j])`` per surviving prefix — one device
    dispatch per row per leaf).  Now the batch stays intact and the
    :class:`ActivationStore` holds per-row :class:`StackedView` entries
    into it; slicing is deferred to first materialisation, and
    consumers that read a whole chunk from one batch *gather*
    (``a[idx]``, one dispatch) instead of slicing per row.
    """

    __slots__ = ("tree", "n", "row_nbytes")

    def __init__(self, tree, n: int):
        self.tree = tree
        self.n = n
        total = 0
        import jax
        for a in jax.tree.leaves(tree):
            if hasattr(a, "dtype"):
                total += (int(np.prod(a.shape[1:])) * a.dtype.itemsize
                          if a.ndim > 1 else a.dtype.itemsize)
        self.row_nbytes = total

    @property
    def total_nbytes(self) -> int:
        return self.row_nbytes * self.n


class StackedView:
    """Store entry: row ``index`` of a :class:`_StackedBatch`.

    Owns no buffer of its own; the store charges the WHOLE batch when
    its first view enters and releases it when its last view leaves
    (:meth:`ActivationStore._entry_bytes_add`) — the batch buffer is
    retained as long as any sibling view survives, so batch-level
    accounting is the real residency and the LRU budget stays honest
    under partial eviction.  The first materialisation memoises its
    slice, so a parent consumed repeatedly across dispatch groups pays
    one slice dispatch total, like the eager store did (the memoised
    copy is small — one row — and dies with the view).
    """

    __slots__ = ("batch", "index", "_sliced")

    def __init__(self, batch: _StackedBatch, index: int):
        self.batch = batch
        self.index = index
        self._sliced = None

    def materialize(self):
        import jax

        if self._sliced is None:
            self._sliced = jax.tree.map(lambda a: a[self.index],
                                        self.batch.tree)
        return self._sliced

    def __repr__(self):
        return f"StackedView(row {self.index} of [{self.batch.n}, ...])"


def _nbytes(act) -> int:
    """Buffer bytes of an activation (array or pytree — the LM units
    thread dicts of hidden state + shared-carry refs) without forcing a
    transfer.  :class:`StackedView` entries are accounted at the batch
    level by the store (``_entry_bytes_add``), not here."""
    import jax

    total = 0
    for a in jax.tree.leaves(act):
        if not hasattr(a, "dtype"):
            continue                 # PrefixRef markers own no buffer
        total += int(np.prod(a.shape)) * a.dtype.itemsize if a.ndim \
            else a.dtype.itemsize
    return total


class ActivationStore:
    """LRU-bounded ``prefix key -> activation`` store.

    The staged evaluator keys an activation by the gene prefix that
    produced it (the calibration batch, fault seed and per-device rates
    are fixed for a search, so the prefix tuple IS the activation's full
    provenance).  ``max_bytes`` caps resident bytes; eviction is
    least-recently-used, skipping keys the caller has pinned for the
    current depth.  Eviction is a *performance* event, never a
    correctness one — the engine recomputes evicted prefixes on demand.
    """

    def __init__(self, max_bytes: int | None = None):
        self.max_bytes = max_bytes
        self._store: OrderedDict[tuple, object] = OrderedDict()
        self.nbytes = 0
        self.evictions = 0
        # stacked-batch residency: id(batch) -> (live view count, bytes).
        # A batch is charged once when its first view enters and
        # released when its last view leaves — evicting one view of a
        # still-referenced batch frees nothing real, and the accounting
        # says so (ids stay valid because a counted batch is kept alive
        # by its remaining views)
        self._batch_views: dict[int, list] = {}

    def __len__(self) -> int:
        return len(self._store)

    def __contains__(self, key: tuple) -> bool:
        return key in self._store

    def get(self, key: tuple):
        act = self._store.get(key)
        if act is not None:
            self._store.move_to_end(key)
        return act

    def put(self, key: tuple, act, pinned: frozenset | set = frozenset()):
        if key in self._store:
            self._store.move_to_end(key)
            return
        self._store[key] = act
        self.nbytes += self._entry_bytes_add(act)
        if self.max_bytes is not None:
            self._evict(pinned)

    def _entry_bytes_add(self, act) -> int:
        """Bytes newly resident because of this entry: eager entries
        own their leaves, a :class:`StackedView` charges its whole
        batch iff it is the batch's first stored view."""
        if isinstance(act, StackedView):
            rec = self._batch_views.get(id(act.batch))
            if rec is None:
                self._batch_views[id(act.batch)] = \
                    [1, act.batch.total_nbytes]
                return act.batch.total_nbytes
            rec[0] += 1
            return 0
        return _nbytes(act)

    def _entry_bytes_drop(self, act) -> int:
        """Bytes actually freed by dropping this entry (a batch is
        freed only with its LAST stored view)."""
        if isinstance(act, StackedView):
            rec = self._batch_views.get(id(act.batch))
            if rec is None:
                return 0
            rec[0] -= 1
            if rec[0] <= 0:
                del self._batch_views[id(act.batch)]
                return rec[1]
            return 0
        return _nbytes(act)

    def _evict(self, pinned):
        for key in list(self._store):
            if self.nbytes <= self.max_bytes:
                return
            if key in pinned:
                continue
            self.nbytes -= self._entry_bytes_drop(self._store.pop(key))
            self.evictions += 1
        # everything left is pinned: allow a transient overshoot rather
        # than evict activations the current depth is about to read

    def clear(self):
        self._store.clear()
        self._batch_views.clear()
        self.nbytes = 0


class PrefixEvalEngine:
    """Layer-wise population evaluation with gene-prefix deduplication.

    The full-forward engine (:class:`PopulationEvalEngine`) evaluates
    every unique chromosome end to end: ``unique_rows x L`` unit runs
    per generation.  But a chromosome's corrupted activation after unit
    *i* depends only on genes ``P[0..i]`` — and evolving populations
    share long gene prefixes (converged NSGA-II populations especially),
    so most of those unit runs recompute activations another chromosome
    already produced.  This engine walks depth ``i = 0..L-1`` and at
    each depth:

      1. collects the unique prefixes ``P[:, :i+1]`` of the uncached
         rows (population-level prefix dedup);
      2. skips prefixes whose activation is already in the
         :class:`ActivationStore` (cross-row and cross-generation
         reuse);
      3. runs unit *i* over only the *fresh* prefixes in chunked,
         shape-bucketed ``jit(vmap)`` dispatches (one per
         ``eval_batch_size`` chunk, padded like the full engine);
      4. stores the new activations, LRU-evicting under ``max_bytes``.

    The per-depth callable contract is

        unit_fns[i](parent_acts, device_ids) -> child_acts | accs

    where ``parent_acts`` is the stacked depth ``i-1`` activations
    (ignored at depth 0 — the callable closes over the calibration
    batch) and ``device_ids`` is ``[U]`` (the prefixes' last gene).
    Activations may be single ``[U, ...]`` arrays (the CNNs' image
    batches) or arbitrary pytrees stacked leaf-wise — the LM units
    carry ``[U,B,S,D]`` hidden states plus static entries (token
    batches, encoder memory) threaded through as dict fields.  Depths
    ``< L-1`` return activations; the final depth returns the ``[U]``
    per-row scalar metric, which is cached exactly like the full
    engine caches rows.  Per-row results must be independent of
    batch-mates (vmap semantics), so chunking and padding never change
    values.

    Cost accounting: ``unit_runs`` counts unit executions actually
    performed (including recompute fallbacks after eviction);
    ``rows_evaluated * n_units`` is what the full-forward path would
    have run, so ``unit_runs_avoided`` is the engine's win.

    Sharding (``scheduler``): with a multi-device
    :class:`DeviceScheduler` the engine shards by *prefix group* —
    every prefix under one depth-0 gene is assigned to one device
    (depth-0 genes round-robin over the pool), so siblings land
    together, a chunk's parent activations are already resident on its
    device (jax raises on cross-device mixing, so this grouping is
    load-bearing, not a preference), and the :class:`ActivationStore`
    stays device-local.  Final-depth results are gathered once per
    ``evaluate`` call after every chunk has been dispatched, so devices
    run concurrently.  One device (or no scheduler) is the exact
    single-device path.

    Shared carries (``shared_fields``): maps a top-level carry-dict
    field name to the depth whose prefix fully determines it (the
    field's value must EQUAL the activation stored at that prefix —
    true for the enc-dec encoder memory, which IS the last encoder
    unit's output).  Stored activations deeper than that depth carry a
    :class:`PrefixRef` instead of the payload.

    Chain fusion (``segment_fn``, PR 5): a converging population's
    prefix trie degenerates to long NON-BRANCHING runs — with the
    depth-by-depth walk each run costs one tiny dispatch per unit plus
    per-row unstacking between depths, which is exactly the
    dispatch-bound regime on deep models.  When ``segment_fn(start,
    length)`` is provided, :meth:`_run_rows` plans maximal
    single-child chains over the fresh rows' trie and dispatches each
    as ONE fused ``jit(vmap)`` executable composing units
    ``start..start+length-1`` (callable contract:
    ``fn(parent_acts, genes[U, length]) -> child_acts | accs``).
    Fusion never crosses a *branch node* (a trie node with two or more
    children — its activation is a shared parent and must
    materialise), never crosses a ``shared_fields`` keying depth (the
    keyed activation must be stored for :class:`PrefixRef` resolution),
    and the final unit always dispatches as its own segment so the
    pre-logits activation remains a stored checkpoint for
    last-gene-mutant reuse.  Chains are cut on a buddy-aligned
    power-of-two span ladder (``start % length == 0``), so the
    compile-cache keys ``(start, length)`` number at most ``~2·L``
    (< L·log2 L) and repeat across generations.  Fused and unfused
    walks are bitwise identical — the segment executables compose the
    exact per-unit math (tests/test_chain_fusion.py pins the
    differential and the chain-detection rules).
    """

    def __init__(self, unit_fns: Sequence[Callable], n_units: int,
                 eval_batch_size: int | None = None,
                 max_store_bytes: int | None = None,
                 scheduler: DeviceScheduler | None = None,
                 shared_fields: dict[str, int] | None = None,
                 segment_fn: Callable[[int, int], Callable] | None = None):
        assert len(unit_fns) == n_units, (len(unit_fns), n_units)
        self.unit_fns = unit_fns
        self.n_units = n_units
        self.eval_batch_size = eval_batch_size
        self.store = ActivationStore(max_store_bytes)
        self.scheduler = scheduler
        self.shared_fields = dict(shared_fields or {})
        self.segment_fn = segment_fn       # None => unfused depth walk
        self._root_device: dict[int, int] = {}  # depth-0 gene -> device idx
        self._cache: dict[tuple, float] = {}   # full row -> final metric
        self.dispatches = 0        # unit_fn invocations (jit dispatches)
        self.device_dispatches: dict[int, int] = {}  # device idx -> count
        self.rows_evaluated = 0    # unique uncached rows walked
        self.unit_runs = 0         # unit executions actually performed
        self.prefix_hits = 0       # needed prefixes found in the store
        self.recomputes = 0        # unit runs redone after LRU eviction
        self.views_stored = 0      # activations stored as StackedViews
        self.slices_materialized = 0  # views actually sliced out later
        self.chains = 0            # fused chains planned (incl. finals)
        self.fused_segments = 0    # ladder segments dispatched
        self.branch_nodes = 0      # trie nodes with >= 2 children seen
        self.max_chain = 0         # longest chain planned (pre-ladder)

    # -- derived stats -------------------------------------------------------
    @property
    def full_unit_runs(self) -> int:
        """Unit runs the full-forward batched path would have performed."""
        return self.rows_evaluated * self.n_units

    @property
    def unit_runs_avoided(self) -> int:
        return self.full_unit_runs - self.unit_runs

    def stats(self) -> dict:
        # prefix_hits and (unit_runs - recomputes) both count UNIQUE
        # prefixes per depth, so their sum is the unique-prefix lookups
        # and the hit rate is the store's cross-round reuse fraction;
        # in-round sharing shows up in unit_runs_avoided instead
        needed = self.unit_runs - self.recomputes + self.prefix_hits
        return {
            "rows_evaluated": self.rows_evaluated,
            "unit_runs": self.unit_runs,
            "full_unit_runs": self.full_unit_runs,
            "unit_runs_avoided": self.unit_runs_avoided,
            "prefix_hits": self.prefix_hits,
            "prefix_hit_rate": self.prefix_hits / max(needed, 1),
            "recomputes": self.recomputes,
            "evictions": self.store.evictions,
            "dispatches": self.dispatches,
            "device_dispatches": dict(self.device_dispatches),
            "store_entries": len(self.store),
            "store_bytes": self.store.nbytes,
            # chain fusion + stacked-view accounting (PR 5)
            "chains": self.chains,
            "fused_segments": self.fused_segments,
            "branch_nodes": self.branch_nodes,
            "max_chain": self.max_chain,
            "views_stored": self.views_stored,
            "slices_materialized": self.slices_materialized,
            "unstack_slices_saved":
                self.views_stored - self.slices_materialized,
        }

    def clear(self):
        """Drop cached accuracies and activations (fault env changed)."""
        self._cache.clear()
        self.store.clear()

    def reset_placement(self):
        """Forget prefix-group device assignments, per-device dispatch
        accounting, AND the stored activations (they are committed to
        the old device pool; mixing them with a new pool would raise
        at stack time)."""
        self._root_device.clear()
        self.device_dispatches.clear()
        self.store.clear()

    # -- evaluation ----------------------------------------------------------
    @staticmethod
    def key(row: Sequence) -> tuple:
        return tuple(int(v) for v in row)

    def evaluate(self, P: np.ndarray) -> np.ndarray:
        """P: [N, L] int device rows -> [N] cached final-depth values."""
        P = np.asarray(P)
        assert P.ndim == 2 and P.shape[1] == self.n_units, P.shape
        keys = [self.key(row) for row in P]
        fresh: dict[tuple, None] = {}
        for k in keys:
            if k not in self._cache and k not in fresh:
                fresh[k] = None
        if fresh:
            self._run_rows(np.array(list(fresh), dtype=P.dtype))
        return np.array([self._cache[k] for k in keys])

    def _multi(self) -> DeviceScheduler | None:
        """The scheduler iff it actually shards (> 1 device)."""
        s = self.scheduler
        return s if s is not None and s.n_devices > 1 else None

    def _device_index(self, prefix: tuple) -> int:
        """Device slot for a prefix: its depth-0 gene's slot (depth-0
        genes round-robin over the pool in first-seen order, which is
        deterministic because prefixes are walked in population order).
        Children inherit transitively, so a whole prefix subtree — and
        every activation a dispatch stacks — lives on one device."""
        root = int(prefix[0])
        if root not in self._root_device:
            self._root_device[root] = \
                len(self._root_device) % self.scheduler.n_devices
        return self._root_device[root]

    def _run_rows(self, R: np.ndarray):
        """Evaluate unique uncached rows: the chain-fused walk when a
        ``segment_fn`` is attached, the depth-by-depth walk otherwise.
        Both gather final-depth chunk results AFTER every dispatch has
        been issued (jax dispatch is async, so with a multi-device
        scheduler the per-device chunk streams execute concurrently)."""
        self.rows_evaluated += len(R)
        if self.segment_fn is not None:
            self._run_rows_fused(R)
        else:
            self._run_rows_staged(R)

    def _run_rows_staged(self, R: np.ndarray):
        """The PR-2 depth walk: one dispatch group per (depth, device)."""
        L = self.n_units
        sched = self._multi()
        pending: list[tuple[list, list]] = []   # (prefixes, result chunks)
        for i in range(L):
            last = i == L - 1
            todo: dict[tuple, None] = {}
            seen: set[tuple] = set()
            for row in R:
                p = self.key(row[:i + 1])
                if p in seen:               # in-round sharing: counted via
                    continue                # unit_runs_avoided, not as a hit
                seen.add(p)
                if not last and p in self.store:
                    self.prefix_hits += 1   # one hit per unique prefix
                else:
                    todo[p] = None          # last-depth rows pre-filtered
                                            # vs the row cache
            if not todo:
                continue
            prefixes = list(todo)
            if sched is None:
                groups = [(None, prefixes)]
            else:
                by_dev: dict[int, list] = {}
                for p in prefixes:
                    by_dev.setdefault(self._device_index(p), []).append(p)
                groups = [(d, by_dev[d]) for d in sorted(by_dev)]
            pin = set(prefixes)
            jobs = [(None if i == 0 else
                     [self._parent_for(p[:-1]) for p in group],
                     np.array([[p[-1]] for p in group], np.int64), dev_idx)
                    for dev_idx, group in groups]
            for (_, group), outs in zip(groups, self._dispatch_groups(
                    self.unit_fns[i], jobs, final=last, unit_axis=False)):
                if last:
                    pending.append((group, outs))
                else:
                    self._store_group(group, outs, pin)
                self.unit_runs += len(group)
        self._gather_final(pending)

    # -- chain-fused walk (PR 5) --------------------------------------------
    def _run_rows_fused(self, R: np.ndarray):
        """Plan non-branching chains over the fresh rows' prefix trie
        and dispatch each buddy-aligned ``(start, length)`` segment
        group as one fused executable (see the class docstring)."""
        L = self.n_units
        sched = self._multi()
        segments = self._plan_segments([self.key(row) for row in R])
        groups: dict[tuple, list] = {}
        for seg in segments:
            start, length, parent, genes = seg
            dev_idx = None if sched is None \
                else self._device_index(parent + genes)
            groups.setdefault((start, length, dev_idx), []).append(seg)
        pending: list[tuple[list, list]] = []
        # ascending start: every parent-producing segment (ending at
        # start-1) has start' < start, so dependencies are satisfied
        order = sorted(groups, key=lambda t: (
            t[0], t[1], -1 if t[2] is None else t[2]))
        waves: dict[tuple, list] = {}      # (start, length) -> groups
        for start, length, dev_idx in order:
            waves.setdefault((start, length), []).append(
                (dev_idx, groups[(start, length, dev_idx)]))
        for (start, length), wave in waves.items():
            final = start + length == L
            jobs = [(None if start == 0 else
                     [self._parent_for(s[2]) for s in segs],
                     np.array([s[3] for s in segs], np.int64),  # [U, length]
                     dev_idx)
                    for dev_idx, segs in wave]
            for (_, segs), outs in zip(wave, self._dispatch_groups(
                    self.segment_fn(start, length), jobs, final=final)):
                keys = [s[2] + s[3] for s in segs]  # segment end prefixes
                if final:
                    pending.append((keys, outs))
                else:
                    # pin only the keys being stored (the depth walk's
                    # semantics): an evicted parent re-enters through the
                    # recompute fallback, so a tight budget stays tight
                    # instead of pinning every pending parent
                    self._store_group(keys, outs, set(keys))
                self.unit_runs += len(segs) * length
                self.fused_segments += len(segs)
        self._gather_final(pending)

    def _plan_segments(self, rows: list) -> list:
        """Plan the fused walk: ``[(start, length, parent_prefix,
        genes)]`` covering every unit run the fresh ``rows`` need.

        1. Build the rows' prefix trie (insertion order = population
           order, so device assignment stays deterministic).
        2. Per row, resume from the DEEPEST stored prefix (one
           ``prefix_hits`` count per unique resume point); everything
           below it down to depth L-2 is *needed*.
        3. Extract maximal chains: a chain extends through nodes with
           exactly one needed child and stops at branch nodes (>= 2
           children — never fused across), at ``shared_fields`` keying
           depths (the keyed activation must be stored), and before the
           final unit (each row's final unit is its own segment so the
           pre-logits checkpoint stays stored).
        4. Split each chain on the buddy-aligned power-of-two span
           ladder: each piece takes the largest power-of-two length
           that divides its start (any length at start 0) and fits the
           remainder.  At most ``2·ceil(log2(m))`` pieces per chain,
           and the piece boundaries are CANONICAL depths — mutants in
           later generations resume at the same aligned checkpoints
           and their pieces merge into the same ``(start, length)``
           dispatch groups.  Compile keys number at most ``~2·L``
           (< the L·log2 L ladder bound).
        """
        with obs.span("engine.plan"):
            L = self.n_units
            kids: dict[tuple, dict] = {(): {}}
            for r in rows:
                p = ()
                for g in r:
                    kids.setdefault(p, {}).setdefault(g, None)
                    p += (g,)
                kids.setdefault(p, {})
            self.branch_nodes += sum(1 for c in kids.values() if len(c) >= 2)

            need: dict[tuple, None] = {}       # ordered set, parents first
            hits: set = set()
            for r in rows:
                d = L - 1                      # deepest proper prefix to probe
                while d > 0 and r[:d] not in self.store:
                    d -= 1
                if d > 0 and r[:d] not in hits:
                    hits.add(r[:d])
                    self.prefix_hits += 1
                for dd in range(d + 1, L):
                    need.setdefault(r[:dd])
            need_children: dict[tuple, list] = {}
            for p in need:
                need_children.setdefault(p[:-1], []).append(p[-1])

            cut = set(self.shared_fields.values())
            chains: list[tuple[tuple, list]] = []   # (parent_prefix, genes)
            for p in need:                     # parents precede children
                par = p[:-1]
                if (par in need and len(need_children.get(par, ())) == 1
                        and (len(par) - 1) not in cut):
                    continue                   # p extends its parent's chain
                genes = [p[-1]]
                cur = p
                while True:
                    nc = need_children.get(cur, ())
                    if len(nc) != 1 or (len(cur) - 1) in cut:
                        break
                    cur += (nc[0],)
                    genes.append(nc[0])
                chains.append((par, genes))
                self.max_chain = max(self.max_chain, len(genes))
            # every row's final unit: its own length-1 chain/segment
            finals = [(r[:L - 1], [r[L - 1]]) for r in rows]
            self.chains += len(chains) + len(finals)

            segments: list[tuple[int, int, tuple, tuple]] = []
            for par, genes in chains + finals:
                s, m, off = len(par), len(genes), 0
                while m:
                    ln = 1 << (m.bit_length() - 1)
                    at = s + off
                    if at:
                        ln = min(ln, at & -at)     # buddy alignment
                    segments.append((at, ln, par + tuple(genes[:off]),
                                     tuple(genes[off:off + ln])))
                    off += ln
                    m -= ln
            return segments

    # -- storage / materialisation -------------------------------------------
    def _use_views(self) -> bool:
        """Stacked views are incompatible with per-row shared-field
        interning (a view cannot rewrite one row's carry field), so
        engines with ``shared_fields`` (enc-dec) keep the eager store
        layout the PrefixRef contract tests pin."""
        return not self.shared_fields

    def _store_group(self, keys: list, chunks: list, pin: set):
        """Store one dispatch group's outputs: per-row
        :class:`StackedView` entries into the intact batch (no unstack
        dispatches), or eager per-row slices when shared-field
        interning must rewrite fields."""
        import jax

        with obs.span("engine.store"):
            j = 0
            for batch, n in chunks:
                rows = keys[j:j + n]
                if self._use_views():
                    for r, key in enumerate(rows):
                        self.store.put(key, StackedView(batch, r),
                                       pinned=pin)
                    self.views_stored += n
                else:
                    for r, key in enumerate(rows):
                        act = jax.tree.map(lambda a, r=r: a[r], batch.tree)
                        self.store.put(key, self._intern(key, act),
                                       pinned=pin)
                j += n

    def _gather_final(self, pending: list):
        """The once-per-call gather: one host transfer per chunk."""
        with obs.span("engine.gather"):
            for keys, chunks in pending:
                j = 0
                for out, n in chunks:
                    for p, v in zip(keys[j:j + n], np.asarray(out)[:n]):
                        self._cache[p] = float(v)
                    j += n

    def _intern(self, prefix: tuple, act):
        """Replace shared carry fields (deeper than their keying depth)
        with :class:`PrefixRef` markers before storing."""
        if not self.shared_fields or not isinstance(act, dict):
            return act
        out = act
        for field, depth in self.shared_fields.items():
            if (len(prefix) > depth + 1 and field in out
                    and not isinstance(out[field], PrefixRef)):
                if out is act:
                    out = dict(act)
                out[field] = PrefixRef(prefix[:depth + 1])
        return out

    def _resolve(self, act):
        """Materialise :class:`PrefixRef` fields of a stored activation
        (recomputing the referenced prefix if it was LRU-evicted)."""
        if not self.shared_fields or not isinstance(act, dict) \
                or not any(isinstance(v, PrefixRef) for v in act.values()):
            return act
        return {k: self._ensure_act(v.prefix) if isinstance(v, PrefixRef)
                else v for k, v in act.items()}

    def _materialize(self, entry):
        """A stored entry as a standalone activation: slice views out
        of their batch (counted — these are the dispatches the stacked
        store exists to avoid; memoised, so each view pays at most
        once), resolve shared-field refs."""
        if isinstance(entry, StackedView):
            if entry._sliced is None:
                self.slices_materialized += 1
            return entry.materialize()
        return self._resolve(entry)

    def _parent_for(self, prefix: tuple):
        """Stored entry for a parent prefix — a :class:`StackedView` is
        returned AS-IS so chunk assembly can gather instead of slicing
        — or the recompute fallback when LRU eviction dropped it."""
        act = self.store.get(prefix)
        if act is not None:
            return act
        return self._recompute(prefix)

    def _ensure_act(self, prefix: tuple):
        """Resolved standalone activation for ``prefix``, recomputing
        the chain from the nearest resident ancestor if LRU eviction
        dropped it (slower, never wrong)."""
        return self._materialize(self._parent_for(prefix))

    def _recompute(self, prefix: tuple):
        """The eviction fallback: re-run unit ``len(prefix)-1`` for one
        prefix (recursing up the chain as needed) and re-store it."""
        import jax

        with obs.span("engine.recompute"):
            i = len(prefix) - 1
            parents = None if i == 0 else [self._parent_for(prefix[:-1])]
            devs = np.array([[prefix[-1]]], np.int64)
            dev_idx = None if self._multi() is None else \
                self._device_index(prefix)
            outs, = self._dispatch_groups(self.unit_fns[i],
                                          [(parents, devs, dev_idx)],
                                          final=False, unit_axis=False)
            batch, _ = outs[0]
            act = jax.tree.map(lambda a: a[0], batch.tree)
            self.unit_runs += 1
            self.recomputes += 1
            self.store.put(prefix, self._intern(prefix, act),
                           pinned={prefix})
            return act

    def _stack_chunk(self, parents: list, padded: int):
        """Assemble one dispatch chunk's stacked parent activations.
        When every parent is a view into ONE batch this is a single
        gather (``a[idx]``) instead of per-row slice+stack dispatches —
        identical values, O(1) dispatches instead of O(rows)."""
        import jax
        import jax.numpy as jnp

        chunk = list(parents) + [parents[-1]] * (padded - len(parents))
        gather = (len(chunk) > 1
                  and all(isinstance(p, StackedView) for p in chunk)
                  and all(p.batch is chunk[0].batch for p in chunk))
        with obs.span("engine.stack", path="gather" if gather else "stack"):
            if gather:
                idx = np.array([p.index for p in chunk], np.int32)
                return jax.tree.map(lambda a: a[idx], chunk[0].batch.tree)
            mats = [self._materialize(p) for p in chunk]
            return jax.tree.map(lambda *xs: jnp.stack(xs), *mats)

    def _dispatch_groups(self, fn: Callable, jobs: list, final: bool,
                         unit_axis: bool = True) -> list:
        """Chunked shape-bucketed dispatches of one unit or fused
        segment, for each device group ``(parents, genes, dev_idx)`` of
        ``jobs``; returns one list of chunk outputs per group.
        Non-final dispatches give ``(_StackedBatch, n)`` per chunk
        (callers store per-row views — no per-row unstack dispatches);
        the final depth gives the un-synced ``(chunk_result, n)`` pairs
        the caller converts after every dispatch has been issued.
        ``dev_idx`` commits the chunk inputs to that scheduler device;
        parents are resident there already (prefix-group invariant).
        ``unit_axis=False`` strips the per-unit gene axis for the
        single-unit ``unit_fns`` contract (``devs: [U]``).

        Chunk inputs are assembled on this thread (the store is not
        thread-safe) round-robin over the groups; each group's calls go
        to its own :class:`DeviceStreams` worker, so first-call compiles
        on different devices overlap.  A group keeps at most one call
        in flight, so at most one assembled chunk per device waits
        beyond what the single-device path holds."""
        import jax

        plans = [list(chunked_rows(len(genes), self.eval_batch_size))
                 for _, genes, _ in jobs]
        outs: list[list] = [[] for _ in jobs]
        flight: list = [None] * len(jobs)    # (future, n, padded) per group

        def land(j: int):
            fut, n, padded = flight[j]
            out = fut.result()
            flight[j] = None
            if final:
                outs[j].append((out, n))
            else:
                if n < padded:      # drop padding rows: one slice per
                                    # chunk, keeps view accounting exact
                    out = jax.tree.map(lambda a: a[:n], out)
                outs[j].append((_StackedBatch(out, n), n))

        with DeviceStreams(len(jobs)) as streams:
            for k in range(max(map(len, plans), default=0)):
                for j, (parents, genes, dev_idx) in enumerate(jobs):
                    if k >= len(plans[j]):
                        continue
                    with obs.span("engine.dispatch"):
                        start, stop, padded = plans[j][k]
                        device = None if dev_idx is None \
                            else self.scheduler.devices[dev_idx]
                        g = np.asarray(pad_rows(genes[start:stop], padded),
                                       np.int32)
                        g_c = DeviceScheduler.put(
                            g if unit_axis else g[:, 0], device)
                        acts = None if parents is None else \
                            self._stack_chunk(parents[start:stop], padded)
                        if flight[j] is not None:
                            land(j)
                        flight[j] = (streams.submit(j, _call, fn, acts, g_c),
                                     stop - start, padded)
                        self.dispatches += 1
                        if dev_idx is not None:
                            self.device_dispatches[dev_idx] = \
                                self.device_dispatches.get(dev_idx, 0) + 1
            with obs.span("engine.dispatch"):
                for j in range(len(jobs)):
                    if flight[j] is not None:
                        land(j)
        return outs


class PopulationEvalEngine:
    """Dedup + cache + chunked single-dispatch evaluation of int rows.

    With a multi-device :class:`DeviceScheduler`, chunks round-robin
    over the pool (``batch_fn`` is then called with ``device=`` and
    must commit its inputs there) and results are converted to host
    values only after EVERY chunk has been dispatched — jax dispatch is
    async, so the devices execute their chunk streams concurrently and
    the host pays one gather per generation instead of one sync per
    chunk.  When ``eval_batch_size`` is unset the unique batch is split
    into ``n_devices`` even chunks so a whole-population dispatch still
    parallelises; one device (or no scheduler) degrades to the exact
    single-device path.  Placement never changes values (per-row
    independence), which tests/test_sharded_eval.py pins bitwise.
    """

    def __init__(self, batch_fn: Callable[[np.ndarray], np.ndarray],
                 eval_batch_size: int | None = None,
                 scheduler: DeviceScheduler | None = None):
        self.batch_fn = batch_fn
        self.eval_batch_size = eval_batch_size
        self.scheduler = scheduler
        self._cache: dict[tuple, float] = {}
        self.dispatches = 0          # batch_fn invocations (== jit dispatches)
        self.rows_evaluated = 0      # unique rows actually computed

    @staticmethod
    def key(row: Sequence) -> tuple:
        return tuple(int(v) for v in row)

    def evaluate(self, P: np.ndarray) -> np.ndarray:
        """P: [N, L] int rows -> [N] cached batch_fn values."""
        P = np.asarray(P)
        keys = [self.key(row) for row in P]
        fresh: dict[tuple, int] = {}
        for i, k in enumerate(keys):
            if k not in self._cache and k not in fresh:
                fresh[k] = i
        if fresh:
            rows = P[list(fresh.values())]
            fresh_keys = list(fresh)
            sched = self.scheduler
            if sched is not None and sched.n_devices <= 1:
                sched = None
            ebs = self.eval_batch_size
            if ebs is None and sched is not None:
                # per-device chunks: a whole-population dispatch would
                # serialise on one device, so split the unique batch
                # evenly over the pool
                ebs = -(-len(rows) // sched.n_devices)
            pending = []
            n_dev = 1 if sched is None else sched.n_devices
            with DeviceStreams(n_dev) as streams:   # a stream per device
                for ci, (start, stop, padded) in enumerate(
                        chunked_rows(len(rows), ebs)):
                    chunk = pad_rows(rows[start:stop], padded)
                    if sched is not None:
                        val = streams.submit(ci % n_dev, self.batch_fn,
                                             chunk,
                                             device=sched.device_for(ci))
                    else:
                        val = streams.submit(0, self.batch_fn, chunk)
                    self.dispatches += 1
                    self.rows_evaluated += stop - start
                    pending.append((fresh_keys[start:stop], val,
                                    stop - start))
            for chunk_keys, val, n in pending:   # once-per-call gather
                vals = np.asarray(val.result())
                for k, v in zip(chunk_keys, vals[:n]):
                    self._cache[k] = float(v)
        return np.array([self._cache[k] for k in keys])


# --------------------------------------------------------------------------
# eval_batch_size auto-tuning (the device-memory analysis launch/dryrun.py
# applies to the LM archs, turned on the evaluator's own executables)
# --------------------------------------------------------------------------
def peak_memory_bytes(compiled) -> int:
    """Peak device bytes of an AOT-compiled executable, falling back to
    argument+output+temp when the backend does not report a peak (the
    same fields launch/dryrun.py records per arch x shape cell).  0 when
    the backend reports no memory analysis at all."""
    mem = compiled.memory_analysis()
    if mem is None:
        return 0
    peak = int(getattr(mem, "peak_memory_in_bytes", 0) or 0)
    if peak:
        return peak
    return sum(int(getattr(mem, f, 0) or 0) for f in
               ("argument_size_in_bytes", "output_size_in_bytes",
                "temp_size_in_bytes"))


def device_memory_budget(default: int = 2 << 30, n_devices: int = 1) -> int:
    """Bytes of device memory the evaluator may plan against, PER
    DEVICE.

    Order: ``REPRO_EVAL_MEM_BUDGET`` env var (bytes per device — an
    explicit operator cap is never rescaled) -> the backend's reported
    ``bytes_limit`` (already per device) -> a quarter of host RAM (CPU
    backend) divided by ``n_devices``, because fake host devices
    (``--xla_force_host_platform_device_count``) share the one RAM pool
    -> ``default / n_devices``.  With the default ``n_devices=1`` this
    is exactly the historical global budget.  A TPU that reports no
    ``bytes_limit`` is an error: host RAM says nothing about HBM.
    """
    import jax

    n_devices = max(1, int(n_devices))
    env = os.environ.get("REPRO_EVAL_MEM_BUDGET")
    if env:
        return int(env)
    dev = jax.local_devices()[0]
    limit = int((dev.memory_stats() or {}).get("bytes_limit", 0))
    if limit > 0:
        return limit
    if dev.platform == "tpu":
        raise RuntimeError(f"{dev.device_kind} reports no bytes_limit in "
                           "memory_stats(); set REPRO_EVAL_MEM_BUDGET")
    try:
        pages = os.sysconf("SC_PHYS_PAGES")
        page = os.sysconf("SC_PAGE_SIZE")
        if pages > 0 and page > 0:
            return pages * page // 4 // n_devices
    except (ValueError, OSError, AttributeError):
        pass
    return default // n_devices


def auto_eval_batch_size(probe: Callable[[int], int],
                         budget: int | None = None,
                         reserved: int = 0,
                         max_rows: int = 1024,
                         n_devices: int = 1) -> int | None:
    """Pick the largest power-of-two chunk whose memory footprint fits
    ONE device.

    ``probe(n_rows)`` returns the peak device bytes of the evaluator's
    batched executable compiled for ``n_rows`` (see
    :func:`peak_memory_bytes`).  Two probes (1 and 2 rows) give the
    per-row slope and the fixed intercept — the same two-point
    extrapolation ``launch/dryrun.py`` uses for its depth cost probes;
    footprints are linear in the vmapped row axis for the same reason
    they are linear in depth there.  ``reserved`` carves out bytes the
    caller keeps resident across dispatches (e.g. the staged engine's
    activation store cap).  A chunk is a single-device dispatch even
    when a :class:`DeviceScheduler` spreads chunks over a pool, so the
    budget this fits against is per-device: an explicit ``budget`` is
    taken as the caller's per-device number, otherwise
    :func:`device_memory_budget` resolves it for ``n_devices``.
    Returns None when the backend reports no usable numbers OR no
    measurable per-row slope (meaning: the probe carries no sizing
    information, so don't pretend to cap).  When even one row exceeds
    the budget the floor is still 1 — a dispatch has to happen — which
    is the best a chunk-size knob can do.
    """
    p1, p2 = probe(1), probe(2)
    if p1 <= 0 or p2 <= 0 or p2 <= p1:
        return None
    per_row = p2 - p1
    fixed = max(p1 - per_row, 0)
    avail = (budget if budget is not None
             else device_memory_budget(n_devices=n_devices))
    avail -= reserved + fixed
    n = 1
    while n * 2 <= max_rows and (n * 2) * per_row <= avail:
        n *= 2
    return n
