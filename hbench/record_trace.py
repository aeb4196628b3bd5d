#!/usr/bin/env python3
"""Records the small chip trace that ``hbench/tests/test_chip_trace.py``
reduces by hand.

    python3 hbench/record_trace.py <out dir>

Runs the tiny stream cell of ``hbench/tests/tiny.py`` on the chip through
the whole harness, with a short traced window, and writes
``<out>/tiny_stream.xplane.pb.gz`` (the profiler trace) and
``<out>/tiny_stream.json`` (the run's result line and the work of its
window). Exits non-zero without a TPU.
"""
import json
import pathlib
import sys
import tempfile
import time

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from hbench import bench  # noqa: E402
from hbench.tests import tiny  # noqa: E402


def main() -> int:
    out = pathlib.Path(sys.argv[1])
    out.mkdir(parents=True, exist_ok=True)
    with tempfile.TemporaryDirectory() as tmp:
        root = tiny.make_root(pathlib.Path(tmp))
        try:
            r = bench.run_cell(root, "tiny.stream", 2 ** 31 + 7, 0.05, True,
                               time.perf_counter(),
                               keep_trace=out / "tiny_stream.xplane.pb.gz")
        except bench.NoChip as e:
            print(f"record_trace: {e}", file=sys.stderr)
            return 2
    r.pop("_where")
    (out / "tiny_stream.json").write_text(json.dumps(r, indent=1))
    print(json.dumps(r))
    return 0


if __name__ == "__main__":
    sys.exit(main())
