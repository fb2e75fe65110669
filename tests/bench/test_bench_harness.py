"""The harness: pieces found by name from files beside it, the contract
of BENCHMARK.json, and refusal without a TPU."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import harness, peaks  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def _copy_benchmark(dst):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), dst)
    shutil.copytree(os.path.join(ROOT, "bench"), os.path.join(dst, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__"))


def test_pieces_added_beside_are_found_without_an_edit(tmp_path):
    _copy_benchmark(tmp_path)
    b = tmp_path / "bench"
    cfg = json.loads((b / "configs" / "resnet18-224.json").read_text())
    cfg["name"] = "resnet18-64"
    cfg["model"]["img"] = 64
    (b / "configs" / "resnet18-64.json").write_text(json.dumps(cfg))
    (b / "traffic" / "search_wide.json").write_text(json.dumps(
        {"driver": "search", "population": 30, "generations": 5,
         "warmup_generations": 1, "check_rows": 2}))
    (b / "metrics" / "rows_per_gen.search.py").write_text(
        "def read(ctx):\n"
        "    w = ctx['window']['layer']\n"
        "    return w['rows_evaluated'] / w['generations']\n")
    spec = json.loads((tmp_path / "BENCHMARK.json").read_text())
    spec["configs"].append(dict(spec["configs"][0], name="resnet18-64",
                                file="bench/configs/resnet18-64.json"))
    spec["workloads"].append({"name": "resnet18-64.search_wide",
                              "config": "resnet18-64",
                              "traffic": "search_wide", "chips": 1,
                              "why": "test"})
    spec["per_layer"].append({"name": "rows_per_gen.search", "unit": "rows",
                              "better": "lower", "source": "program_counter",
                              "layer": "staged engine",
                              "moves": "search_cands_per_s",
                              "workloads": ["resnet18-64.search_wide"]})
    cell = harness.load_cell(spec, "resnet18-64.search_wide", str(tmp_path))
    assert cell.config["model"]["img"] == 64
    assert cell.traffic["population"] == 30
    assert [m["name"] for m in cell.per_layer] == ["rows_per_gen.search"]
    read = harness.load_reader("rows_per_gen.search", str(tmp_path))
    assert read({"window": {"layer": {"rows_evaluated": 90,
                                      "generations": 3}}}) == 30
    ref = harness.load_reference(cell.config, str(tmp_path))
    assert hasattr(ref, "Reference")
    assert harness.load_driver(cell.traffic["driver"]).Run


def test_missing_pieces_are_errors(tmp_path):
    _copy_benchmark(tmp_path)
    spec = harness.load_spec(str(tmp_path))
    with pytest.raises(harness.BenchError):
        harness.load_cell(spec, "no.such_cell", str(tmp_path))
    with pytest.raises(harness.BenchError):
        harness.load_reader("no_such_metric", str(tmp_path))
    with pytest.raises(harness.BenchError):
        harness.load_driver("no_such_driver")


def test_benchmark_json_keeps_the_contract():
    spec = harness.load_spec()
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= spec["run_seconds"] <= 51
    for p in spec["paths"]:
        assert os.path.isdir(os.path.join(ROOT, p))
    cfgs = {c["name"]: c for c in spec["configs"]}
    used = set()
    for c in spec["configs"]:
        assert NAME.match(c["name"])
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
        assert c["file"].startswith("bench/")
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    assert "setup_s" in e2e
    for m in spec["end_to_end"]:
        assert m["bound"] <= 0.25 and m["bound"] >= 0.01
    for w in spec["workloads"]:
        assert NAME.match(w["name"]) and w["config"] in cfgs
        assert w["chips"] in (1, 4) and 0 < len(w["why"]) <= 200
        used.add(w["config"])
        cell = harness.load_cell(spec, w["name"])
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer
    assert used == set(cfgs)
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in spec["per_layer"]:
        assert m["moves"] in e2e
        assert harness.load_reader(m["name"])
        assert m["unit"] == "%" or not m["name"].endswith("_roofline")


def test_unknown_device_kind_is_an_error():
    assert peaks.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks_for("TPU v99")


def test_no_tpu_is_an_error(capsys):
    import jax

    assert jax.devices()[0].platform != "tpu"
    with pytest.raises(harness.BenchError, match="needs a TPU"):
        harness.require_devices(1)
    from bench import run

    rc = run.main(["--workload", "resnet18-224.search_converge",
                   "--seed", "1", "--seconds", "1"])
    assert rc != 0
    assert capsys.readouterr().out == ""


def test_bare_benchmark_directory_exits_nonzero(tmp_path):
    _copy_benchmark(tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "resnet18-224.search_converge", "--seed", "1", "--seconds", "1"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout == ""
