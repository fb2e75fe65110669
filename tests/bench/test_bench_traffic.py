"""The traffic generators: determinism, parameters, and the search trace
against the recorder it was copied from."""
import importlib.util
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import gen_search  # noqa: E402


def _config(name):
    with open(os.path.join(ROOT, "bench", "configs", name + ".json")) as f:
        return json.load(f)


def _traffic(name):
    with open(os.path.join(ROOT, "bench", "traffic", name + ".json")) as f:
        return json.load(f)


SEARCH = dict(_traffic("search_converge"), generations=12)


def _sorted(P):
    return P[np.lexsort(P.T[::-1])]


def test_search_trace_same_seed_same_stream():
    cfg = _config("resnet18-224")
    a = gen_search.generate(cfg, SEARCH, 2 ** 31 + 7)
    b = gen_search.generate(cfg, SEARCH, 2 ** 31 + 7)
    c = gen_search.generate(cfg, SEARCH, 11)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not all(np.array_equal(x, y) for x, y in zip(a, c))


def test_every_seed_gets_the_same_work_in_another_order():
    cfg = _config("resnet18-224")
    a = gen_search.generate(cfg, SEARCH, 1)
    b = gen_search.generate(cfg, SEARCH, 2 ** 31 + 3)
    for x, y in zip(a, b):
        assert np.array_equal(_sorted(x), _sorted(y))


def test_search_trace_shape_follows_the_mix():
    cfg = _config("resnet18-224")
    tr = gen_search.generate(cfg, SEARCH, 5)
    assert len(tr) == SEARCH["generations"] + 1
    for P in tr:
        assert P.shape == (SEARCH["population"], 10)
        assert P.min() >= 0 and P.max() < len(cfg["tiers"])


def test_search_trace_converges():
    """Later generations bring fewer new rows than the first: the
    prefix-sharing regime the cell exists to measure."""
    cfg = _config("resnet18-224")
    tr = gen_search.generate(cfg, dict(SEARCH, generations=40), 3)
    seen, fresh = set(), []
    for P in tr:
        new = {tuple(r) for r in P} - seen
        seen |= new
        fresh.append(len(new))
    assert fresh[0] == SEARCH["population"]
    assert np.mean(fresh[-10:]) < fresh[0]


@pytest.mark.parametrize("trace_seed", [0, 1234, 2 ** 31 + 99])
def test_search_trace_is_the_recorders(trace_seed):
    """Row for row the population sequence that the partitioner's
    NSGA-II hands its objective, as ``benchmarks/eval_engine.py`` records
    it with the program's own cost model and tier ladder; the run's seed
    only orders the rows of each population."""
    spec = importlib.util.spec_from_file_location(
        "bench_test_eval_engine",
        os.path.join(ROOT, "benchmarks", "eval_engine.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    from repro.core import POD_TIERS_4
    from repro.models.cnn import ResNet18

    cfg = _config("resnet18-224")
    m = cfg["model"]
    layers = ResNet18.layer_infos(num_classes=m["num_classes"],
                                  width=m["width"], img=m["img"])
    want = mod._trace_nsga2(layers, POD_TIERS_4, SEARCH["population"],
                            SEARCH["generations"], trace_seed)
    got = gen_search.generate(cfg, dict(SEARCH, trace_seed=trace_seed), 9)
    assert len(got) == len(want)
    for g, (a, b) in enumerate(zip(got, want)):
        assert np.array_equal(_sorted(a), _sorted(b)), f"generation {g}"
