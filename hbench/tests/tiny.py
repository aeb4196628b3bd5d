"""Tiny cells for the benchmark's own tests: the repository's BENCHMARK.json
and hbench data files, copied under a temporary root, plus small
configurations and traffic files that a CPU runs in seconds.

The tiny serving cell holds no pin contracts: the scheduler compiles a
padding program for each new contract batch size, which would compile
inside the window (PERF.md, section 7). ``test_reference.py`` checks the
reference's pin contracts against the program's directly."""
from __future__ import annotations

import copy
import json
import pathlib
import shutil

HBENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = HBENCH.parent

TINY_PLATFORM = {"n_fast_pages": 64, "n_slow_pages": 448, "chunk": 16,
                 "hot_threshold": 3, "decay_every": 8}
STREAM = {"pattern": "zipfian", "requests": 256,
          "footprint_bytes": 400 * 4096, "zipf_alpha": 0.9,
          "write_frac": 0.5}
TRAFFIC = {
    "tiny_stream": {"kind": "stream", "stream": STREAM, "pool_segments": 3},
    "tiny_grid": {"kind": "sweep", "stream": dict(STREAM, requests=128),
                  "grid": {"technologies": ["3dxpoint", "stt-ram"],
                           "fast_fractions": [0.125, 0.25],
                           "policies": ["hotness", "static"]},
                  "pool_segments": 3, "checked_points": 6},
    "tiny_serve": {"kind": "serve_closed", "population": 60,
                   "mix": {"prompt_pages": [1, 2, 3], "prompt_p": [0.5, 0.3,
                                                                   0.2],
                           "decode_lo": 2, "decode_hi": 12},
                   "pool_sequences": 20000},
}
TINY_SERVE = {"sorted_batch_sizes": [32, 64, 128], "max_live_seqs": 60,
              "max_live_batches": 2, "max_admit_per_step": 16,
              "pin_pages_per_seq": 0, "max_pages_per_seq": 6,
              "positions_per_page": 8, "window_pages": 2,
              "prefill_writes_per_page": 2, "free_low_frac": 0.02,
              "free_high_frac": 0.04, "slo_latency_us": 100000.0,
              "pinned_slo": 0.9}
CELLS = {"tiny.stream": ("tiny_t2", "tiny_stream"),
         "tiny.grid": ("tiny_t2", "tiny_grid"),
         "tiny.serve": ("tiny_kv", "tiny_serve")}


def make_root(tmp: pathlib.Path) -> pathlib.Path:
    """A checkout-shaped root holding the repository's benchmark files and
    the tiny cells, with a peaks entry for the CPU."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp / "BENCHMARK.json")
    shutil.copytree(HBENCH, tmp / "hbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench = json.loads((tmp / "BENCHMARK.json").read_text())
    t2 = json.loads((HBENCH / "configs" / "paper_t2.json").read_text())
    kv = json.loads((HBENCH / "configs" / "serve_kv.json").read_text())
    confs = {"tiny_t2": copy.deepcopy(t2), "tiny_kv": copy.deepcopy(kv)}
    confs["tiny_t2"]["platform"].update(TINY_PLATFORM)
    confs["tiny_kv"]["platform"].update(TINY_PLATFORM,
                                        n_fast_pages=128,
                                        n_slow_pages=384)
    confs["tiny_kv"]["serve"] = TINY_SERVE
    for name, c in confs.items():
        c["name"] = name
        (tmp / "hbench" / "configs" / f"{name}.json").write_text(
            json.dumps(c))
        bench["configs"].append({"name": name, "source": c["source"],
                                 "file": f"hbench/configs/{name}.json",
                                 "reduced": [], "why": "test"})
    for name, t in TRAFFIC.items():
        (tmp / "hbench" / "traffic" / f"{name}.json").write_text(
            json.dumps(t))
    for cell, (conf, traffic) in CELLS.items():
        bench["workloads"].append({"name": cell, "config": conf,
                                   "traffic": traffic, "chips": 1,
                                   "why": "test"})
        for m in bench["end_to_end"] + bench["per_layer"]:
            ws = m.get("workloads")
            if ws is not None and any(
                    w.startswith(("paper_t2.", "serve_kv.")) and
                    _kind(w) == TRAFFIC[traffic]["kind"] for w in ws):
                ws.append(cell)
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    peaks = json.loads((tmp / "hbench" / "peaks.json").read_text())
    peaks["cpu"] = dict(peaks["TPU v5 lite"], source="test")
    (tmp / "hbench" / "peaks.json").write_text(json.dumps(peaks))
    return tmp


def _kind(cell: str) -> str:
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    traffic = {w["name"]: w["traffic"] for w in bench["workloads"]}[cell]
    return json.loads((HBENCH / "traffic" / f"{traffic}.json")
                      .read_text())["kind"]
