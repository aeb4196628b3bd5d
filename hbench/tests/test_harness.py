"""The command refuses to measure without a TPU, and the harness finds a
new cell made only of new files."""
import json
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

from hbench import bench
from hbench.tests import tiny

ROOT = tiny.ROOT


def _run_cmd(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "hbench/run.py", *args], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300)


def test_no_tpu_exits_before_any_window(tmp_path):
    root = tiny.make_root(tmp_path)
    shutil.copytree(ROOT / "src", tmp_path / "src")
    p = _run_cmd(tmp_path, "--workload", "tiny.stream", "--seed", "1",
                 "--seconds", "1", "--trace", "0")
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "not a TPU" in p.stderr
    assert not (tmp_path / ".jax_cache").exists()


def test_only_benchmark_files_exit_nonzero(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "hbench", tmp_path / "hbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_cmd(tmp_path, "--workload", "paper_t2.mcf_stream", "--seed",
                 "1", "--seconds", "1", "--trace", "0")
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_unknown_device_kind_is_an_error(tmp_path):
    peaks = tmp_path / "peaks.json"
    peaks.write_text(json.dumps({"TPU v5 lite": {"hbm_bytes_per_s": 1}}))
    with pytest.raises(KeyError, match="not in peaks.json"):
        bench.check_device(1, peaks, require_tpu=False)


def test_a_new_cell_of_new_files_is_found(tmp_path):
    root = tiny.make_root(tmp_path)
    hb = root / "hbench"
    before = {p: p.read_bytes() for p in hb.rglob("*") if p.is_file()}
    conf = json.loads((hb / "configs" / "paper_t2.json").read_text())
    conf["name"] = "new_platform"
    conf["platform"]["n_fast_pages"] = 65536
    conf["platform"]["n_slow_pages"] = 229376
    (hb / "configs" / "new_platform.json").write_text(json.dumps(conf))
    (hb / "traffic" / "new_mix.json").write_text(json.dumps(
        {"kind": "stream", "pool_segments": 2,
         "stream": {"pattern": "sequential", "requests": 1024,
                    "footprint_bytes": 4096 * 500, "write_frac": 0.5}}))
    (hb / "metrics" / "new_metric.py").write_text(
        "def read(ctx):\n    return 2 * ctx['work']['calls']\n")
    bench_json = json.loads((root / "BENCHMARK.json").read_text())
    bench_json["configs"].append(
        {"name": "new_platform", "source": "https://example.org/x",
         "file": "hbench/configs/new_platform.json", "reduced": [],
         "why": "test"})
    bench_json["workloads"].append(
        {"name": "new_platform.new_mix", "config": "new_platform",
         "traffic": "new_mix", "chips": 1, "why": "test"})
    bench_json["per_layer"].append(
        {"name": "new_metric", "unit": "count", "better": "lower",
         "source": "program_counter", "layer": "repro.Engine host path",
         "moves": "point_req_per_s", "workloads": ["new_platform.new_mix"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench_json))
    after = {p: p.read_bytes() for p in hb.rglob("*") if p.is_file()}
    assert all(after[p] == b for p, b in before.items())

    _, cell, conf2, traffic = bench.load_cell(root, "new_platform.new_mix")
    assert conf2["platform"]["n_fast_pages"] == 65536
    assert bench.driver_class(traffic["kind"]).__module__ == \
        "hbench.kinds.stream"
    names = [m["name"] for m in
             bench.metrics_for(bench_json, "new_platform.new_mix", True)]
    assert "new_metric" in names
    assert bench.reader(root, "new_metric")({"work": {"calls": 3}}) == 6
    e2e = [m["name"] for m in
           bench.metrics_for(bench_json, "new_platform.new_mix", False)]
    assert e2e == ["setup_s"]
