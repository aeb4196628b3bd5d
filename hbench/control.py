#!/usr/bin/env python3
"""Readings that set the limits of the correctness check.

    python3 hbench/control.py --workload <cell> --seconds <s> --seeds 1 2 3

For each seed, in one process: the cell's set-up and a short window at
the cell's own size and load, then the numbers the check compares, twice:
the program against the reference (the lower reading), and the control
against the reference (the upper reading). The control is the reference
with the swap-progress redirect guarantee broken (``reference.run(...,
redirect=False)``). One JSON line per seed. The benchmark's own runs do
not run the control.
"""
import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from hbench import bench, compare  # noqa: E402


def readings(root, name, seed, seconds) -> dict:
    _, _, conf, traffic = bench.load_cell(root, name)
    drv = bench.driver_class(traffic["kind"])(conf, traffic, seed)
    drv.setup()
    work = drv.window(seconds)
    out = {"seed": seed, "attempted": work["attempted"]}
    for side, control in (("program", False), ("control", True)):
        t = compare.Tally()
        drv.check(t, control=control)
        if not control:
            t.n["requests_uncounted"] = work["attempted"] - work["counted"]
        out[side] = t.n
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args()
    root = ROOT
    _, cell, _, _ = bench.load_cell(root, args.workload)
    try:
        bench.check_device(cell["chips"], root / "hbench" / "peaks.json",
                           True)
    except bench.NoChip as e:
        print(f"control: {e}", file=sys.stderr)
        return 2
    bench.enable_compile_cache(root)
    for seed in args.seeds:
        print(json.dumps(readings(root, args.workload, seed, args.seconds)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
