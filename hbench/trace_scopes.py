#!/usr/bin/env python3
"""Device time of a cell's chunk step by phase and stage, from one traced
run.

    python3 hbench/trace_scopes.py --workload <cell> --seed <n> --seconds <s> [--keep <dir>]

Runs the cell once through the whole harness with the profiler on, as
``hbench/run.py --trace 1`` does (``bench.run_cell``), keeps its profiler
trace, and reduces the trace by the program's named scopes
(``hbench/scopes.py``). Standard error gets one line per phase and stage:
device microseconds per chunk step and share of busy time. The last line
of standard output is one JSON object: the run's result line, its
``notes`` (the work of the window and the harness's timings), and
``scopes``, which holds microseconds per chunk step of each of
``scopes.GROUPS`` (they add up to busy time per chunk step),
``busy_us_per_chunk`` from the same trace, and ``scope_read_s``, the time
the scope reduction itself took.

A workload ``tiny.<name>`` is a tiny cell of ``hbench/tests/tiny.py``.
``--keep <dir>`` writes the gzipped trace there as ``<cell>.xplane.pb.gz``
and the JSON object as ``<cell>.json``, with ``.`` in the cell's name
turned to ``_``. ``hbench/tests/data/tiny_stream.*`` were recorded so::

    python3 hbench/trace_scopes.py --workload tiny.stream --seed 2147483655 --seconds 0.01 --keep hbench/tests/data

Exits non-zero without a TPU.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from hbench import bench, profile, scopes  # noqa: E402
from hbench.tests import tiny  # noqa: E402


def reduce_trace(path, devices, steps: int) -> tuple[scopes.ScopeTimes,
                                                     dict]:
    """Scope times of the trace at ``path`` on ``devices`` (ids), and the
    summary the JSON object carries."""
    ops, spans = profile.read_events(profile.load(path))
    ops = {k: v for k, v in ops.items() if k in devices}
    busy = profile.reduce(ops, spans).busy_s
    t0 = time.perf_counter()
    window = next((a, b) for n, a, b in spans if n == profile.WINDOW)
    times = scopes.reduce(ops, scopes.load_op_names(path), *window)
    summary = {f"{g}_us_per_chunk": v
               for g, v in times.groups_us(steps).items()}
    summary["busy_us_per_chunk"] = 1e6 * sum(busy) / len(busy) / steps
    summary["scope_read_s"] = time.perf_counter() - t0
    return times, summary


def main(argv) -> int:
    ap = argparse.ArgumentParser(prog="hbench/trace_scopes.py",
                                 description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--keep", default=None,
                    help="directory to keep the trace and the result in")
    args = ap.parse_args(argv)
    stem = args.workload.replace(".", "_")
    with tempfile.TemporaryDirectory(prefix="hbench-scopes-") as tmp:
        tmp = pathlib.Path(tmp)
        root = ROOT
        if args.workload.startswith("tiny."):
            root = tiny.make_root(tmp)
        out = pathlib.Path(args.keep) if args.keep else tmp
        out.mkdir(parents=True, exist_ok=True)
        trace = out / f"{stem}.xplane.pb.gz"
        try:
            r = bench.run_cell(root, args.workload, args.seed, args.seconds,
                               True, T_START, keep_trace=trace)
        except bench.NoChip as e:
            print(f"trace_scopes: {e}", file=sys.stderr)
            return 2
        import jax

        _, cell, _, _ = bench.load_cell(root, args.workload)
        devices = {d.id for d in jax.devices()[:cell["chips"]]}
        r["notes"] = r.pop("_notes")
        steps = r["notes"]["chunks"] * r["notes"]["points"]
        times, r["scopes"] = reduce_trace(trace, devices, steps)
    r.pop("_where")
    print(f"run: {json.dumps(r['notes'])}", file=sys.stderr)
    for row in times.table(steps):
        print(row, file=sys.stderr)
    if args.keep:
        (out / f"{stem}.json").write_text(json.dumps(r, indent=1))
    print(json.dumps(r), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
