"""BENCHMARK.json, and every configuration, traffic mix and metric file it
names, parse and hang together."""

import json
import pathlib
import re

import pytest

from bench import harness

ROOT = pathlib.Path(__file__).resolve().parents[2]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.fixture(scope="module")
def spec():
    return harness.load_spec(ROOT)


def test_benchmark_json_shape(spec):
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert spec["command"] == ["python3", "bench/run.py"]
    assert spec["paths"] == ["bench"]
    assert 1 <= spec["run_seconds"] <= 51
    metrics = spec["end_to_end"] + spec["per_layer"]
    names = [x["name"] for x in spec["configs"] + spec["workloads"] + metrics]
    assert all(NAME.match(n) for n in names)
    assert len({m["name"] for m in metrics}) == len(metrics)
    assert len({c["name"] for c in spec["configs"]}) == len(spec["configs"])
    e2e = {m["name"] for m in spec["end_to_end"]}
    assert "setup_s" in e2e
    for m in spec["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in spec["per_layer"]:
        assert m["moves"] in e2e
    cells = {w["name"] for w in spec["workloads"]}
    for m in metrics:
        assert set(m.get("workloads", cells)) <= cells


@pytest.mark.parametrize("kind", ["configs", "traffic"])
def test_every_data_file_parses(kind):
    files = sorted((ROOT / "bench" / kind).glob("*.json"))
    assert files
    for f in files:
        data = json.loads(f.read_text())
        assert data["name"] == f.stem


def test_every_cell_finds_its_files_and_metrics(spec):
    for w in spec["workloads"]:
        cell, config, traffic = harness.load_cell(spec, w["name"], ROOT)
        assert cell["chips"] in (1, 4)
        assert config["check"]["residual_limit"] > 0
        assert traffic["rhs_pool"] >= 1 and traffic["check_sample"] >= 1
        e2e = [m["name"] for m in harness.metrics_for(spec, w["name"], False)]
        layer = harness.metrics_for(spec, w["name"], True)
        assert "setup_s" in e2e and len(e2e) >= 2 and layer
        for m in harness.metrics_for(spec, w["name"], False) + layer:
            assert callable(harness.load_reader(m["name"], ROOT))
    for c in spec["configs"]:
        config = json.loads((ROOT / c["file"]).read_text())
        assert config["name"] == c["name"]
        assert config["reduced"] == c["reduced"]


def test_cg_bytes_at_512_against_a_hand_sum():
    config = json.loads(
        (ROOT / "bench/configs/hpcg27-cg-f32-b512.json").read_text())
    # q = A p: read p, write q (2); x += a p (3); r -= a q (3); r.r (1);
    # p = r + b p (3): 12 vectors of 4-byte elements per point
    per_point = (2 + 3 + 3 + 1 + 3) * 4
    assert config["bytes_per_point_per_iter"] == per_point
    points = 512 ** 3
    assert per_point * points == 6_442_450_944
    assert harness.global_grid(config, 1) == (512, 512, 512)
    assert harness.global_grid(config, 4) == (512, 512, 2048)


def test_only_the_z_split_is_known():
    config = {"block_per_chip": [8, 8, 8], "split": "xy"}
    with pytest.raises(ValueError):
        harness.global_grid(config, 4)
