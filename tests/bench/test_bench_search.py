"""The search cell end to end on the CPU at a tiny size: a sound run is
correct; a run whose answers are altered where they are produced, or
whose fc inputs are, is not; and neither is each control put in the
program's place.  Only the look for a chip is skipped."""
import copy
import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import harness  # noqa: E402

CELL = "resnet18-224.search_converge"


def _tiny_cell():
    cell = harness.load_cell(harness.load_spec(), CELL)
    cell = copy.deepcopy(cell)
    cell.config["model"].update(width=0.125, img=8, num_classes=10)
    cell.config["n_eval"] = 4
    cell.config["evaluator"].update(eval_batch_size=1,
                                    max_store_bytes=1 << 20)
    # every row first evaluated in the window is compared
    cell.traffic.update(warmup_generations=2, check_rows=10_000)
    return cell


def _run(tmp_path, seed, controls=False):
    return harness.run_cell(_tiny_cell(), seed=seed, seconds=1.0,
                            trace=False, t0=time.monotonic(),
                            trace_dir=str(tmp_path / "trace"),
                            controls=controls)


@pytest.fixture(scope="module")
def sound(tmp_path_factory):
    """One sound run, with the controls read after its check."""
    return _run(tmp_path_factory.mktemp("sound"), 2 ** 31 + 17,
                controls=True)


def test_sound_run_is_correct(sound):
    out = sound
    assert out["correct"] is True
    assert out["checks"]["dacc_head_gap_mean"]["value"] == 0.0
    assert out["checks"]["feature_gap_max"]["value"] < 1e-4
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"search_cands_per_s", "setup_s"}
    assert out["metrics"]["search_cands_per_s"]["value"] > 0
    assert out["notes"]["compiles_in_window"] == 0
    assert list(out)[-2:] == ["controls", "checks"]


def test_altered_answer_is_not_correct(tmp_path, monkeypatch):
    from repro.core.eval_engine import PrefixEvalEngine

    gather = PrefixEvalEngine._gather_final

    def altered(self, pending):
        gather(self, pending)
        for keys, _ in pending:
            for k in keys:
                v = self._cache[k]
                self._cache[k] = v - 0.5 if v >= 0.5 else v + 0.5

    monkeypatch.setattr(PrefixEvalEngine, "_gather_final", altered)
    out = _run(tmp_path, 5)
    assert out["correct"] is False
    assert out["checks"]["dacc_head_gap_mean"]["value"] == pytest.approx(0.5)


def test_altered_fc_input_is_not_correct(tmp_path, monkeypatch):
    """The features a fused segment hands to the final unit, scaled by
    1.1 where they are produced."""
    from repro.core.eval_engine import PrefixEvalEngine

    stack = PrefixEvalEngine._stack_chunk

    def altered(self, parents, padded):
        out = stack(self, parents, padded)
        return out * 1.1 if out.ndim == 3 else out

    monkeypatch.setattr(PrefixEvalEngine, "_stack_chunk", altered)
    out = _run(tmp_path, 7)
    assert out["correct"] is False
    assert out["checks"]["feature_gap_max"]["value"] == pytest.approx(
        0.1, rel=1e-3)


def test_controls_in_the_programs_place_are_not_correct(sound):
    """Each control (fp8 operands, int4 quantization, faults off) in the
    program's place fails one of the numbers, at this size too."""
    limits = _tiny_cell().config["limits"]
    assert set(sound["controls"]) == {"float8_e4m3fn", "int4", "faults_off"}
    for name, readings in sound["controls"].items():
        assert any(readings[k] > limits[k] for k in limits), (name,
                                                               readings)
