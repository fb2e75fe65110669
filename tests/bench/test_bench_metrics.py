"""Each per-layer reader on a hand-made context."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import flops, harness  # noqa: E402
from bench.peaks import peaks_for  # noqa: E402

CONFIG = {"n_eval": 4,
          "model": {"arch": "resnet18", "width": 0.125, "img": 8,
                    "num_classes": 10},
          "fault": {"faulty_bits": 4}}
RUNS = [1, 2, 0, 0, 0, 0, 0, 0, 3, 5]


def _ctx(kernel_s=1e-3):
    return {"window": {"layer": {"unit_runs": 11, "candidates": 22,
                                 "dispatches": 12, "generations": 3,
                                 "runs_per_unit": RUNS}},
            "trace": {"window_s": 2.0, "busy_s": 0.5,
                      "kernel_s": {"bitflip": kernel_s}},
            "compiles": 0, "peaks": peaks_for("TPU v5 lite"),
            "config": CONFIG}


def _read(name, ctx):
    return harness.load_reader(name)(ctx)


def test_engine_counts():
    assert _read("unit_runs_per_cand.search", _ctx()) == 0.5
    assert _read("dispatches_per_gen.search", _ctx()) == 4.0
    assert _read("compiles_in_window.search", _ctx()) == 0


def test_idle_share():
    assert _read("device_idle_share.search", _ctx()) == pytest.approx(75.0)


def test_mfu_counts_performed_unit_runs():
    per_image = flops.arch("resnet18").unit_flops(0.125, 8, 10)
    want = sum(r * f for r, f in zip(RUNS, per_image)) * 4
    got = _read("mfu.search", _ctx())
    assert got == pytest.approx(100 * want / (2.0 * 197e12))


def test_bitflip_roofline_is_bytes_bound_and_silent_without_kernel():
    n = sum(r * w for r, w in zip(RUNS, flops.arch(
        "resnet18").unit_conv_weights(0.125)))
    ops, nbytes = flops.bitflip_cost(n, 4)
    assert nbytes / 819e9 > ops / 393e12
    got = _read("bitflip_roofline.search", _ctx(kernel_s=1e-3))
    assert got == pytest.approx(100 * (nbytes / 819e9) / 1e-3)
    assert _read("bitflip_roofline.search", _ctx(kernel_s=0.0)) is None
