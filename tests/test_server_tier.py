"""The benchmark's server-socket cell ``server_tier.ycsb_a``: its files
resolve, its capacities agree with one another, and its platform (HeMem's
socket: bus-attached tiers, the write-weighted hot rule) runs through the
harness's ``stream`` kind bitwise against ``hbench/reference.py``, scaled
down to 2,048 + 8,192 pages so that a CPU runs it in seconds."""
import json
import pathlib
import shutil
import sys
import time

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from hbench import bench  # noqa: E402

CELL = "server_tier.ycsb_a"
GIB = 2 ** 30


def _cell():
    return bench.load_cell(ROOT, CELL)


def test_load_cell_resolves_the_socket_cell():
    _, cell, conf, traffic = _cell()
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "server_tier", "ycsb_a", 1)
    assert conf["name"] == "server_tier"
    assert traffic["kind"] == "stream"
    p = conf["platform"]
    assert {p["fast"], p["slow"]} <= set(conf["technologies"])


def test_pages_are_one_to_four_and_match_the_capacities():
    _, _, conf, _ = _cell()
    p, cap = conf["platform"], conf["capacity_bytes"]
    assert p["n_slow_pages"] == 4 * p["n_fast_pages"]
    assert p["n_fast_pages"] * p["page_size"] == cap["dram"] == 32 * GIB
    assert p["n_slow_pages"] * p["page_size"] == cap["nvm"] == 128 * GIB
    # A sixth of the socket, in both tiers.
    assert cap["socket_dram"] == 6 * cap["dram"]
    assert cap["socket_nvm"] == 6 * cap["nvm"]
    # The flat table index row * 8 + lane stays inside int32.
    assert (p["n_fast_pages"] + p["n_slow_pages"]) * 8 < 2 ** 31


def test_footprint_is_at_most_ninety_percent_of_the_memory():
    _, _, conf, traffic = _cell()
    p, s = conf["platform"], traffic["stream"]
    total = (p["n_fast_pages"] + p["n_slow_pages"]) * p["page_size"]
    assert s["footprint_bytes"] % p["page_size"] == 0
    assert s["footprint_bytes"] <= 0.9 * total
    assert s["footprint_bytes"] == 144 * GIB


SCALED_PAGES = {"n_fast_pages": 2048, "n_slow_pages": 8192}


@pytest.fixture(scope="module")
def scaled_root(tmp_path_factory):
    """A checkout-shaped root holding the benchmark's files, with the
    socket's configuration and traffic cut to 2,048 + 8,192 pages and
    a footprint of 90% of them; every other setting is the cell's."""
    tmp = tmp_path_factory.mktemp("root")
    shutil.copy(ROOT / "BENCHMARK.json", tmp / "BENCHMARK.json")
    shutil.copytree(ROOT / "hbench", tmp / "hbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    conf_path = tmp / "hbench" / "configs" / "server_tier.json"
    conf = json.loads(conf_path.read_text())
    conf["platform"].update(SCALED_PAGES)
    conf_path.write_text(json.dumps(conf))
    traffic_path = tmp / "hbench" / "traffic" / "ycsb_a.json"
    traffic = json.loads(traffic_path.read_text())
    pages = sum(SCALED_PAGES.values()) * 9 // 10
    traffic["stream"].update(requests=4096,
                             footprint_bytes=pages * conf["platform"][
                                 "page_size"])
    traffic_path.write_text(json.dumps(traffic))
    peaks_path = tmp / "hbench" / "peaks.json"
    peaks = json.loads(peaks_path.read_text())
    peaks["cpu"] = dict(peaks["TPU v5 lite"], source="test")
    peaks_path.write_text(json.dumps(peaks))
    return tmp


def _altered_run(orig):
    def run(self, trace, **kw):
        r = orig(self, trace, **kw)
        outs = dict(r.outs)
        outs["latency"] = outs["latency"].at[0].add(1)
        return r._replace(outs=outs)
    return run


@pytest.mark.parametrize("fault", [None, "altered"])
def test_scaled_socket_runs_bitwise_through_the_continuation(
        scaled_root, fault, monkeypatch):
    from repro import Engine

    if fault == "altered":
        monkeypatch.setattr(Engine, "run", _altered_run(Engine.run))
    r = bench.run_cell(scaled_root, CELL, 2 ** 31 + 12345, 1.0, False,
                       time.perf_counter(), require_tpu=False)
    assert r["attempted"] > 0 and r["failed"] == 0
    checks = {k: c["value"] for k, c in r["checks"].items()}
    if fault is None:
        assert r["correct"], r["checks"]
        assert checks["outs_mismatch"] == 0
        assert checks["state_mismatch"] == 0
        assert checks["float_counter_gap"] == 0.0
        assert checks["window_compiles"] == 0
    else:
        assert not r["correct"], r["checks"]
        assert checks["outs_mismatch"] > 0
