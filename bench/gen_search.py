"""Search traffic: the exact population sequence of a converging NSGA-II
search, recorded from a seed.

Selection is driven by the analytic cost model and the calibrated
sensitivity surrogate, so the sequence is cheap, host-only and
deterministic, and converges like the real search: later generations
share long gene prefixes.  This is a copy of the partitioner's NSGA-II
(``repro.core.nsga2``), of the cost model it scores with
(``repro.core.costmodel``) and of the recorder
(``benchmarks/eval_engine.py::_trace_nsga2``), kept here so that a
change to the program cannot change the traffic it is measured on.
``tests/bench/test_bench_traffic.py`` pins the copy to the program's
recorder.
"""
from __future__ import annotations

import numpy as np

from bench.flops import arch


# --------------------------------------------------------------------------
# cost model
# --------------------------------------------------------------------------
class CostModel:
    """Latency, energy, memory violation and surrogate ΔAcc of a
    population of layer -> tier rows (no link costs, batch 1)."""

    def __init__(self, layers: list[dict], tiers: list[dict]):
        L, D = len(layers), len(tiers)
        lat = np.zeros((L, D))
        en = np.zeros((L, D))
        for li, layer in enumerate(layers):
            moved = float(layer["weight_bytes"] + layer["act_in_bytes"]
                          + layer["act_out_bytes"])
            for di, dev in enumerate(tiers):
                t_compute = layer["macs"] / dev["peak_macs"]
                t_mem = moved / dev["dram_bw"]
                lat[li, di] = max(t_compute, t_mem) + dev["dispatch_s"]
                en[li, di] = (layer["macs"] * dev["pj_per_mac"]
                              + moved * dev["pj_per_byte"]) * 1e-12
        self.lat, self.energy = lat, en
        self.weight_bytes = np.array([li["weight_bytes"] for li in layers])
        self.sens = np.array([li["sensitivity"] for li in layers])
        self.fault_scale = np.array([d["fault_scale"] for d in tiers])
        self.mem_capacity = np.array([d["mem_capacity"] for d in tiers])

    def objectives(self, P: np.ndarray) -> np.ndarray:
        L = self.lat.shape[0]
        lat = self.lat[np.arange(L)[None, :], P].sum(axis=1)
        en = self.energy[np.arange(L)[None, :], P].sum(axis=1)
        dacc = (self.fault_scale[P] * self.sens[None, :]).sum(axis=1)
        return np.stack([lat, en, dacc], axis=1)

    def violation(self, P: np.ndarray) -> np.ndarray:
        N = P.shape[0]
        v = np.zeros(N)
        for d in range(len(self.fault_scale)):
            load = ((P == d) * self.weight_bytes[None, :]).sum(axis=1)
            over = np.maximum(0.0, load - self.mem_capacity[d])
            v += over / max(self.weight_bytes.sum(), 1.0)
        return v


# --------------------------------------------------------------------------
# NSGA-II (Deb et al. 2002), constrained dominance
# --------------------------------------------------------------------------
def _dominance(F, viol):
    le = (F[:, None, :] <= F[None, :, :]).all(axis=2)
    lt = (F[:, None, :] < F[None, :, :]).any(axis=2)
    dom = le & lt
    feas = viol <= 0.0
    both = ~feas[:, None] & ~feas[None, :]
    dom = np.where(feas[:, None] & ~feas[None, :], True, dom)
    dom = np.where(~feas[:, None] & feas[None, :], False, dom)
    dom = np.where(both, viol[:, None] < viol[None, :], dom)
    np.fill_diagonal(dom, False)
    return dom


def _ranks(F, viol):
    dom = _dominance(F, viol)
    ranks = np.full(F.shape[0], -1, dtype=np.int64)
    remaining = dom.sum(axis=0).astype(np.int64).copy()
    current = np.where(remaining == 0)[0]
    r = 0
    while current.size:
        ranks[current] = r
        remaining = remaining - dom[current].sum(axis=0)
        remaining[current] = -1
        current = np.where(remaining == 0)[0]
        r += 1
    return ranks


def _crowding(F, ranks):
    n, m = F.shape
    dist = np.zeros(n)
    if n == 0:
        return dist
    o1 = np.argsort(F, axis=0, kind="stable")
    o2 = np.argsort(ranks[o1], axis=0, kind="stable")
    order = np.take_along_axis(o1, o2, axis=0)
    fs = np.take_along_axis(F, order, axis=0)
    rsorted = ranks[order[:, 0]]
    first = np.empty(n, bool)
    first[0] = True
    first[1:] = rsorted[1:] != rsorted[:-1]
    last = np.empty(n, bool)
    last[-1] = True
    last[:-1] = first[1:]
    starts = np.flatnonzero(first)
    sizes = np.diff(np.append(starts, n))
    fid = np.cumsum(first) - 1
    span = fs[np.flatnonzero(last)][fid] - fs[starts][fid]
    small = (sizes <= 2)[fid]
    contrib = np.zeros((n, m))
    contrib[1:-1] = fs[2:] - fs[:-2]
    interior = (~(first | last | small))[:, None] & (span > 0)
    np.add.at(dist, order.T[interior.T],
              (contrib / np.where(span > 0, span, 1.0)).T[interior.T])
    dist[order[first | last | small].ravel()] = np.inf
    return dist


def _tournament(rng, ranks, crowd, k, n_pick):
    cand = rng.integers(0, ranks.shape[0], size=(n_pick, k))
    order = np.lexsort((-crowd[cand], ranks[cand]), axis=-1)
    return cand[np.arange(n_pick), order[..., 0]]


def nsga2_trace(cm: CostModel, n_genes: int, n_tiers: int, population: int,
                generations: int, seed: int, crossover_rate: float = 0.9,
                mutation_rate: float = 0.08, tournament_k: int = 2
                ) -> list[np.ndarray]:
    """Every population the search hands to its objective, in order: the
    initial population, then the children of each generation."""
    rng = np.random.default_rng(seed)
    N = population
    pop = rng.integers(0, n_tiers, size=(N, n_genes))
    trace = [pop.copy()]
    objs, viol = cm.objectives(pop), cm.violation(pop)
    for _ in range(generations):
        ranks = _ranks(objs, viol)
        crowd = _crowding(objs, ranks)
        pa = _tournament(rng, ranks, crowd, tournament_k, N)
        pb = _tournament(rng, ranks, crowd, tournament_k, N)
        a, b = pop[pa], pop[pb]
        do = rng.random(N) < crossover_rate
        mask = rng.random((N, n_genes)) < 0.5
        children = np.where(do[:, None], np.where(mask, a, b), a)
        mut = rng.random((N, n_genes)) < mutation_rate
        rand = rng.integers(0, n_tiers, size=(N, n_genes))
        children = np.where(mut, rand, children)
        trace.append(children.copy())
        allpop = np.concatenate([pop, children])
        allobjs = np.concatenate([objs, cm.objectives(children)])
        allviol = np.concatenate([viol, cm.violation(children)])
        aranks = _ranks(allobjs, allviol)
        keep = np.lexsort((-_crowding(allobjs, aranks), aranks))[:N]
        pop, objs, viol = allpop[keep], allobjs[keep], allviol[keep]
    return trace


def generate(config: dict, traffic: dict, seed: int) -> list[np.ndarray]:
    """The traffic of a search cell: ``traffic`` gives the population, the
    generations to record and the search's ``trace_seed``; ``config`` the
    model and the tier ladder.  Every run's seed gets the same populations
    (the same work), each with its rows in an order drawn from ``seed``."""
    m = config["model"]
    layers = arch(m["arch"]).cost_layers(m["width"], m["img"],
                                         m["num_classes"])
    cm = CostModel(layers, config["tiers"])
    trace = nsga2_trace(cm, len(layers), len(config["tiers"]),
                        traffic["population"], traffic["generations"],
                        traffic["trace_seed"])
    rng = np.random.default_rng(seed)
    return [P[rng.permutation(len(P))] for P in trace]
