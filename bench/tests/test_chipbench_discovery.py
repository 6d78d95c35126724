"""The harness finds configs, traffic, drivers and metric readers by name,
and BENCHMARK.json keeps to the shape the check expects."""
import copy
import json
import re
import shutil

import pytest

from bench import harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.fixture(scope="module")
def spec():
    return harness.load_spec()


def test_every_entry_resolves_by_name(spec):
    for cfg in spec["configs"]:
        assert harness.load_config(cfg["name"])["name"] == cfg["name"]
    for cell in spec["workloads"]:
        traffic = harness.load_traffic(cell["traffic"])
        assert callable(harness.load_driver(traffic["driver"]))
        harness.load_config(cell["config"])
    for m in spec["per_layer"]:
        assert callable(harness.load_reader(m["name"]))


def test_spec_shape(spec):
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    names = [c["name"] for c in spec["configs"]]
    names += [w["name"] for w in spec["workloads"]]
    names += [m["name"] for m in spec["end_to_end"] + spec["per_layer"]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    e2e = {m["name"] for m in spec["end_to_end"]}
    assert "setup_s" in e2e
    for m in spec["per_layer"]:
        assert m["moves"] in e2e
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    for cell in spec["workloads"]:
        assert cell["chips"] in (1, 4) and len(cell["why"]) <= 200
        reported = harness.metrics_of(spec, cell, "end_to_end")
        assert len(reported) >= 2
        assert harness.metrics_of(spec, cell, "per_layer")
    for m in spec["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25


def test_unknown_names_are_refused(spec, tmp_path):
    with pytest.raises(harness.BenchError):
        harness.find_cell(spec, "no-such-cell")
    with pytest.raises(harness.BenchError):
        harness.load_config("no_such_config")
    with pytest.raises(harness.BenchError):
        harness.load_limits("no-such-cell")
    with pytest.raises(harness.BenchError):
        harness.load_reader("no_such.metric")


def test_every_cell_has_its_limits(spec):
    for cell in spec["workloads"]:
        limits = harness.load_limits(cell["name"])
        assert limits and all(isinstance(v, (int, float)) and v >= 0
                              for v in limits.values())


def test_compare_holds_each_limited_reading():
    seen = []
    checks = harness.compare({"a": 1.0, "b": 5.0, "c": 0.5},
                             {"a": 2.0, "b": 4.0}, seen.append)
    assert checks == [{"name": "a", "value": 1.0, "limit": 2.0},
                      {"name": "b", "value": 5.0, "limit": 4.0}]
    assert seen == ["reading c=0.5 (not compared)"]
    with pytest.raises(harness.BenchError, match="no reading"):
        harness.compare({"a": 1.0}, {"a": 2.0, "d": 1.0})


def _snapshot(root):
    return {p: p.read_bytes() for p in sorted(root.rglob("*"))
            if p.is_file() and "__pycache__" not in p.parts}


def test_a_new_cell_needs_only_data_files(spec, tmp_path):
    """A throwaway configuration, traffic mix and limits file, added as
    files in a directory of their own, load and run through the existing
    driver, and no file the benchmark already has changes."""
    before = _snapshot(harness.BENCH)
    for kind in ("configs", "traffic", "limits"):
        (tmp_path / kind).mkdir()
    cfg = harness.load_config("protein")
    cfg["name"] = "protein_small"
    (tmp_path / "configs" / "protein_small.json").write_text(json.dumps(cfg))
    traffic = dict(harness.load_traffic("train_reset"), reset_every=2)
    (tmp_path / "traffic" / "tiny_reset.json").write_text(json.dumps(traffic))
    (tmp_path / "limits" / "tiny-train.json").write_text(
        json.dumps(harness.load_limits("protein-train")))
    shutil.copytree(harness.BENCH / "metrics", tmp_path / "metrics")
    cell = {"name": "tiny-train", "config": "protein_small",
            "traffic": "tiny_reset", "chips": 1, "why": "test"}
    spec = copy.deepcopy(spec)
    spec["workloads"].append(cell)
    for m in spec["end_to_end"]:
        if "epoch_s" == m["name"]:
            m["workloads"] = m["workloads"] + ["tiny-train"]
    assert harness.load_config("protein_small", tmp_path)["dataset"] == "protein"
    import jax
    import time
    out = harness.run_cell(spec, cell, seed=2**31 + 5, seconds=0.5,
                           trace=False, devices=jax.devices(),
                           peak=harness.load_peaks()["devices"]["TPU v5 lite"],
                           t_start=time.perf_counter(), bench=tmp_path,
                           scale=0.01)
    assert set(out["metrics"]) == {"setup_s", "epoch_s"}
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert list(out)[-1] == "checks"
    assert set(out["checks"]) == set(harness.load_limits("protein-train"))
    assert _snapshot(harness.BENCH) == before
