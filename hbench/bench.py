"""The harness: finds a cell and everything it names by name, runs it, and
prints the result line.

Everything specific to a configuration, a traffic mix or a metric sits in
a file of its own, found by the name ``BENCHMARK.json`` gives it:

* ``<config file>`` (``BENCHMARK.json`` ``configs[].file``): the platform,
  its technology table, its guarantees, and its serving settings;
* ``hbench/traffic/<traffic>.json``: the traffic's parameters, with its
  ``kind``, which names the driver ``hbench/kinds/<kind>.py``;
* ``hbench/metrics/<metric>.py``: a ``read(ctx)`` that returns the
  metric, or None where the run has nothing to read it from;
* ``hbench/peaks.json``: the chip's peaks by ``device_kind``.

A run: check the chip, make the traffic and warm every shape (set-up),
measure for ``seconds`` (profiled when ``trace``), read the memory peak,
replay what the window produced through the reference, and print one
JSON line whose last key holds each compared number beside its limit.
"""
from __future__ import annotations

import gzip
import importlib
import importlib.util
import json
import pathlib
import sys
import tempfile
import time

HBENCH = pathlib.Path(__file__).resolve().parent


class NoChip(RuntimeError):
    """The run found no accelerator it can measure."""


def load_cell(root: pathlib.Path, name: str):
    """(cell, config, traffic) of the workload called ``name``."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; have {sorted(cells)}")
    cell = cells[name]
    confs = {c["name"]: c for c in bench["configs"]}
    conf = json.loads((root / confs[cell["config"]]["file"]).read_text())
    traffic = json.loads((root / "hbench" / "traffic"
                          / f"{cell['traffic']}.json").read_text())
    return bench, cell, conf, traffic


def metrics_for(bench: dict, cell: str, trace: bool) -> list[dict]:
    """The metrics a run of ``cell`` reports: the end-to-end ones, or with
    ``trace`` the per-layer ones; those with a ``workloads`` list only in
    the cells it names."""
    group = bench["per_layer" if trace else "end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def reader(root: pathlib.Path, name: str):
    path = root / "hbench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"hbench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def driver_class(kind: str):
    return importlib.import_module(f"hbench.kinds.{kind}").Kind


def check_device(chips: int, peaks_path: pathlib.Path, require_tpu: bool):
    """The first device, the devices the cell uses, and the peaks of its
    kind. Raises :class:`NoChip` before any work when there is no TPU or
    too few of them, and ``KeyError`` for a kind the peaks table lacks."""
    import jax

    devs = jax.devices()
    dev = devs[0]
    if require_tpu and dev.platform != "tpu":
        raise NoChip(f"JAX's first device is {dev.platform} "
                     f"({dev.device_kind}), not a TPU")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    peaks = json.loads(peaks_path.read_text())
    if dev.device_kind not in peaks:
        raise KeyError(f"device kind {dev.device_kind!r} is not in "
                       f"{peaks_path.name}; have {sorted(peaks)}")
    return dev, devs[:chips], peaks[dev.device_kind]


class CompileCount:
    """Programs compiled or loaded from the persistent compilation cache,
    through ``jax.monitoring``: JAX times each with the backend-compile
    event, which spans the look into the cache. So a program first needed
    inside the window counts also where an earlier run left it in the
    cache (``hbench/tests/test_compile_count.py``)."""
    COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **_):
        if event == self.COMPILE:
            self.n += 1


def run_cell(root: pathlib.Path, name: str, seed: int, seconds: float,
             trace: bool, t_start: float, *, require_tpu: bool = True,
             keep_trace: pathlib.Path | None = None) -> dict:
    """Run one cell; returns the result line as a dict. ``require_tpu``
    False (the benchmark's own tests) skips the look for a chip and the
    compile cache. ``keep_trace`` names a file that a traced run's
    profiler trace is copied to, gzipped."""
    import jax

    from hbench import compare, profile

    bench, cell, conf, traffic = load_cell(root, name)
    dev, used, peaks = check_device(
        cell["chips"], root / "hbench" / "peaks.json", require_tpu)
    if require_tpu:
        enable_compile_cache(root)
    compiles = CompileCount()

    drv = driver_class(traffic["kind"])(conf, traffic, seed)
    drv.setup()
    setup_s = time.perf_counter() - t_start

    reduced, tdir = None, None
    n0 = compiles.n
    if trace:
        tdir = tempfile.TemporaryDirectory(prefix="hbench-trace-")
        jax.profiler.start_trace(tdir.name)
    try:
        with jax.profiler.TraceAnnotation(profile.WINDOW):
            work = drv.window(seconds)
    finally:
        if trace:
            jax.profiler.stop_trace()
    window_compiles = compiles.n - n0
    read_s = 0.0
    if trace:
        t_read = time.perf_counter()
        xplane = profile.find_xplane(tdir.name)
        if keep_trace is not None:
            with open(xplane, "rb") as f:
                keep_trace.write_bytes(gzip.compress(f.read()))
        ops, spans = profile.read_events(profile.load(xplane))
        ops = {k: v for k, v in ops.items() if k in {d.id for d in used}}
        reduced = profile.reduce(ops, spans)
        tdir.cleanup()
        read_s = time.perf_counter() - t_read
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in used)

    tally = compare.Tally()
    t_check = time.perf_counter()
    drv.check(tally)
    check_s = time.perf_counter() - t_check
    tally.n["requests_uncounted"] = work["attempted"] - work["counted"]
    tally.n["window_compiles"] = window_compiles

    ctx = {"kind": traffic["kind"], "work": work, "setup_s": setup_s,
           "profile": reduced, "peaks": peaks, "chunk": drv.chunk,
           "n_devices": len(used)}
    metrics = {}
    for m in metrics_for(bench, name, trace):
        v = reader(root, m["name"])(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    result = {"correct": tally.correct(), "attempted": work["attempted"],
              "failed": work["attempted"] - work["counted"],
              "metrics": metrics, "device": device}
    if reduced is not None:
        device["busy_s"] = sum(reduced.busy_s) / len(reduced.busy_s)
        device["window_s"] = reduced.window_s
        result["breakdown"] = {"device_ops": reduced.top_ops,
                               "idle_gaps": reduced.idle_gaps}
    result["checks"] = tally.checks()
    result["_where"] = tally.where[:20]
    result["_notes"] = dict(work, setup_s=setup_s, check_s=check_s,
                            trace_read_s=read_s)
    return result


def enable_compile_cache(root: pathlib.Path) -> None:
    """JAX's persistent compilation cache at a fixed path in the checkout,
    for every program, however quick to compile."""
    import jax

    jax.config.update("jax_compilation_cache_dir", str(root / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def main(argv, t_start: float) -> int:
    import argparse

    ap = argparse.ArgumentParser(prog="hbench/run.py",
                                 description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    root = HBENCH.parent
    try:
        import jax

        jax.devices()
    except RuntimeError as e:
        print(f"hbench: JAX found no usable device: {e}", file=sys.stderr)
        return 2
    try:
        result = run_cell(root, args.workload, args.seed, args.seconds,
                          bool(args.trace), t_start)
    except NoChip as e:
        print(f"hbench: {e}; the benchmark measures only on a TPU",
              file=sys.stderr)
        return 2
    where = result.pop("_where")
    print(f"run: {json.dumps(result.pop('_notes'))}", file=sys.stderr)
    for w in where:
        print(f"differs: {w}", file=sys.stderr)
    for k, c in result["checks"].items():
        print(f"check {k}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0
