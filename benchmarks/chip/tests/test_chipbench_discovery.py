"""The harness finds a cell, its configuration, its traffic mix and a
per-layer metric by name, from files alone; and refuses to run off the
chip."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))

from chipbench import common  # noqa: E402


def test_files_placed_in_a_directory_are_found(tmp_path):
    bench = {
        "workloads": [{"name": "toy.cell", "config": "toy-config",
                       "traffic": "toy-mix", "chips": 1, "why": "test"}],
        "end_to_end": [{"name": "setup_s", "unit": "s"},
                       {"name": "toy_rate", "unit": "ops/s",
                        "workloads": ["toy.cell"]},
                       {"name": "other_rate", "unit": "ops/s",
                        "workloads": ["other.cell"]}],
        "per_layer": [{"name": "toy.share", "unit": "%",
                       "workloads": ["toy.cell"]}],
    }
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    for kind, name, body in (("configs", "toy-config", {"peers": 3}),
                             ("traffic", "toy-mix", {"driver": "ring"})):
        (tmp_path / kind).mkdir()
        (tmp_path / kind / f"{name}.json").write_text(json.dumps(body))
    (tmp_path / "metrics").mkdir()
    (tmp_path / "metrics" / "toy.share.py").write_text(
        "def read(run):\n    return run.layer.get('x')\n")
    (tmp_path / "references").mkdir()
    (tmp_path / "references" / "toy.py").write_text("ANSWER = 42\n")

    b = common.load_benchmark(tmp_path)
    cell = common.find_cell(b, "toy.cell")
    assert common.load_named("configs", cell["config"], tmp_path) == {"peers": 3}
    traffic = common.load_named("traffic", cell["traffic"], tmp_path)
    assert common.load_driver(traffic).__name__ == "chipbench.ring_driver"
    assert [m["name"] for m in common.metrics_for(b, "toy.cell", "end_to_end")] \
        == ["setup_s", "toy_rate"]
    [m] = common.metrics_for(b, "toy.cell", "per_layer")
    read = common.load_reader(m["name"], tmp_path)
    out = common.Outcome()
    assert read(out) is None               # nothing to read: no number
    out.layer["x"] = 7.0
    assert read(out) == 7.0
    assert common.load_reference("toy", tmp_path).ANSWER == 42
    with pytest.raises(common.BenchError):
        common.find_cell(b, "missing.cell")
    with pytest.raises(common.BenchError):
        common.load_named("configs", "missing", tmp_path)


def test_every_cell_of_the_benchmark_resolves():
    b = common.load_benchmark()
    for cell in b["workloads"]:
        common.load_named("configs", cell["config"])
        common.load_driver(common.load_named("traffic", cell["traffic"]))
        for m in common.metrics_for(b, cell["name"], "per_layer"):
            common.load_reader(m["name"])
    names = [m["name"] for m in b["per_layer"]]
    assert len(names) == len(set(names))


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "ring.lookup.bulk", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_means_an_error_and_no_result():
    p = _run(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_a_checkout_of_only_the_benchmark_fails(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(HERE, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_benchmark_json_keeps_its_shape():
    import re

    b = common.load_benchmark()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
    unit = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
    assert 1 <= b["run_seconds"] <= 51
    configs = {c["name"]: c for c in b["configs"]}
    for c in b["configs"]:
        assert name.match(c["name"]) and (ROOT / c["file"]).is_file()
        assert any(c["file"].startswith(p + "/") for p in b["paths"])
        assert 1 <= len(c["source"]) <= 200 and 1 <= len(c["why"]) <= 200
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert "setup_s" in e2e
    pairs = set()
    for w in b["workloads"]:
        assert name.match(w["name"]) and w["config"] in configs
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        reports = [m for m in b["end_to_end"]
                   if "workloads" not in m or w["name"] in m["workloads"]]
        assert len(reports) >= 2
        assert common.metrics_for(b, w["name"], "per_layer")
    for m in b["end_to_end"]:
        assert name.match(m["name"]) and unit.match(m["unit"])
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in b["per_layer"]:
        assert name.match(m["name"]) and unit.match(m["unit"])
        assert m["moves"] in e2e and "bound" not in m
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        for w in m["workloads"]:
            reported = e2e[m["moves"]].get("workloads")
            assert reported is None or w in reported


def test_each_ring_mix_reports_a_rate_its_cell_is_held_to():
    b = common.load_benchmark()
    for w in b["workloads"]:
        traffic = common.load_named("traffic", w["traffic"])
        if traffic["driver"] != "ring":
            continue
        e2e = common.metrics_for(b, w["name"], "end_to_end")
        assert traffic["rate_metric"] in [m["name"] for m in e2e]


def test_a_clients_reader_reads_as_its_bulk_twin():
    out = common.Outcome()
    out.layer.update(apply_s=0.5, events=100, upload_bytes=4096)
    for name in ("ring.maint_ms_per_event", "ring.upload_bytes_per_event"):
        assert common.load_reader(name + ".clients")(out) \
            == common.load_reader(name)(out) is not None
    assert common.load_reader("ring.idle_share.clients")(out) is None
