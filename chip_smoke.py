#!/usr/bin/env python3
"""Bring-up smoke: the emulator's main path on a TPU, at the paper's
Table II geometry (128 MB DRAM + 1 GB NVM = 294,912 pages).

    python3 chip_smoke.py              # one chip: phases (a)-(e)
    python3 chip_smoke.py --chips 4    # the sharded 64-point sweep only

One-chip phases, all through the public entry points, run in the order
(a), (d), (b), (c), (e) (the bitwise checks follow the run they check):

  (a) run    ``Engine.run`` on a 2^20-request SPEC CPU2017 recipe, then
             one continuation with the donated state;
  (b) sweep  the 16-point ``bench_sweep`` grid in ONE compile, plus one
             ``continue_sweep``;
  (c) serve  the ``bench_serve`` quick and degraded profiles through
             ``ContinuousBatchingScheduler``, their emulated metrics
             equal to those in ``BENCH_serve.json``;
  (d) check  ``Engine.run`` at ``chunk=1`` bitwise against
             ``trace_sim.simulate``, and (a) bitwise against the same
             program on the host's CPU device;
  (e) kernels an explicit request for a Pallas kernel the TPU compiler
             refuses raises instead of running another path.

``--chips 4`` runs a 64-point sweep plus a continuation sharded over a
4-device mesh and compares it bitwise with the same sweep unsharded on
one of those chips.

Exits non-zero, before any phase, when JAX's first device is not a TPU;
any failed phase exits non-zero. The last line of standard output is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

# The CPU reference run of phase (d) needs the host's CPU backend next to
# the TPU one in this same process.
_platforms = os.environ.get("JAX_PLATFORMS", "")
if _platforms and "cpu" not in _platforms.split(","):
    os.environ["JAX_PLATFORMS"] = _platforms + ",cpu"

# The imports below follow the JAX_PLATFORMS and sys.path set-up.
# ruff: noqa: E402
import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import bench_serve
from benchmarks.bench_sweep import make_spec
from benchmarks.compile_cache import enable_compile_cache
from repro import Engine
from repro.analysis import assert_compile_flat
from repro.core import Trace, paper_platform
from repro.core.table import ROW_W
from repro.kernels import chunk_step, ops
from repro.sims import trace_sim
from repro.sweep import build_points
from repro.trace import workload_trace

N_RUN = 1 << 20        # phase (a) requests per call
N_SWEEP = 1 << 17      # sweep requests per segment
N_ORACLE = 4096        # phase (d) chunk=1 anchor
WORKLOAD = "505.mcf"   # SPEC CPU2017 recipe (602 MB footprint, zipfian)
# bench_serve's emulated metrics (everything but host timings and the
# float mean), compared exactly with the committed BENCH_serve.json.
SERVE_EMULATED = (
    "n_sequences", "n_mem_requests", "n_dispatches", "live_seqs_high_water",
    "inflight_high_water", "p50_latency_us", "p99_latency_us",
    "slo_attainment", "pinned_accesses", "pinned_fast_hit_rate",
    "evictions", "refetches", "frames_retired", "fault_refetches",
    "renegotiations")


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok, msg: str) -> None:
    """A check of the smoke's results (kept under ``python -O``)."""
    if not ok:
        raise AssertionError(msg)


class CompileLog:
    """Backend compilations and persistent-cache hits, via jax.monitoring."""
    def __init__(self):
        self.n = 0
        self.secs = 0.0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.secs += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def mark(self):
        return (self.n, self.secs, self.hits)

    def since(self, mark) -> str:
        n, s, h = mark
        return (f"{self.n - n} backend compile(s) in {self.secs - s:.2f} s, "
                f"{self.hits - h} persistent-cache hit(s)")


def peak_bytes() -> str:
    stats = jax.devices()[0].memory_stats() or {}
    return f"peak_bytes_in_use={stats.get('peak_bytes_in_use', 'n/a')}"


def host(tree):
    return jax.tree.map(np.asarray, tree)


def assert_trees_equal(a, b, what: str) -> None:
    la, ta = jax.tree_util.tree_flatten_with_path(a)
    lb, tb = jax.tree_util.tree_flatten_with_path(b)
    check(ta == tb, f"{what}: tree structures differ")
    for (path, x), (_, y) in zip(la, lb):
        if not np.array_equal(np.asarray(x), np.asarray(y)):
            raise AssertionError(
                f"{what}: {jax.tree_util.keystr(path)} differs")


def int_leaves(state):
    """The integer leaves of an EmulatorState (everything but the float
    counter accumulators)."""
    return {jax.tree_util.keystr(p): np.asarray(x)
            for p, x in jax.tree_util.tree_flatten_with_path(state)[0]
            if np.issubdtype(np.asarray(x).dtype, np.integer)}


def check_request_count(counters, n: int, what: str) -> None:
    total = sum(np.asarray(getattr(counters, f)).astype(np.int64)
                for f in ("reads_fast", "writes_fast", "reads_slow",
                          "writes_slow"))
    check(np.all(total == n), f"{what}: counted {total} requests, sent {n}")


def paths(cfg) -> str:
    step = ("pallas kernel" if chunk_step.use_chunk_step_kernel(cfg)
            else "scan path")
    gather = ("pallas hmmu_lookup" if ops.use_pallas("hmmu_lookup")
              else "xla native gather")
    return f"chunk step: {step}; row gather: {gather}"


# --------------------------------------------------------------------- #
# one-chip phases
# --------------------------------------------------------------------- #

def phase_run(clog, cfg, trace):
    """(a): fresh run, one donated continuation, one more timed call."""
    log(f"(a) run: Engine(paper_platform().with_(chunk={cfg.chunk})).run, "
        f"{WORKLOAD} recipe, {N_RUN} requests/call, "
        f"{cfg.n_pages} pages; {paths(cfg)}")
    engine = Engine(cfg)
    mark = clog.mark()
    t0 = time.perf_counter()
    with assert_compile_flat(engine, allow=1, msg="fresh run"):
        r1 = engine.run(trace)
        jax.block_until_ready(r1)
    first_s = time.perf_counter() - t0
    log(f"(a) first call (trace + compile + run): {first_s:.3f} s; "
        f"{clog.since(mark)}")
    outs1 = host(r1.outs)

    t0 = time.perf_counter()
    with assert_compile_flat(engine, allow=1, msg="donated continuation"):
        r2 = engine.run(trace, state=r1.state)       # donated
        jax.block_until_ready(r2)
    log(f"(a) donated continuation (incl. its compile): "
        f"{time.perf_counter() - t0:.3f} s")
    outs2, state2 = host(r2.outs), host(r2.state)
    check_request_count(state2.counters, 2 * N_RUN, "(a)")

    t0 = time.perf_counter()
    with assert_compile_flat(engine, msg="warm continuation"):
        r3 = engine.run(trace, state=r2.state)
        jax.block_until_ready(r3)
    warm_s = time.perf_counter() - t0
    summ = r3.summary()
    log(f"(a) warm continuation: {warm_s:.3f} s, {N_RUN / warm_s:.0f} "
        f"requests/s (host clock, block_until_ready); compile count "
        f"{engine.compile_count}; swaps {int(r3.state.dma.swaps_done)}")
    log("(a) counters after 3 calls: " + json.dumps(summ))
    log(f"(a) ok; {peak_bytes()}")
    return outs1, outs2, state2


def phase_sweep(clog, base, trace, trace2):
    """(b): the 16-point bench_sweep grid, one compile, one continuation."""
    points = build_points(make_spec(base))
    engine = Engine(points[0].cfg)
    log(f"(b) sweep: {len(points)} design points at {base.n_pages} pages, "
        f"{N_SWEEP} requests/segment")
    mark = clog.mark()
    t0 = time.perf_counter()
    with assert_compile_flat(engine, allow=1, msg="design-space sweep") as cc:
        res = engine.sweep(points, trace)
        jax.block_until_ready(res.states.clock)
    check(cc.count == 1, f"sweep compiled {cc.count} programs, not 1")
    log(f"(b) sweep first call: {time.perf_counter() - t0:.3f} s, "
        f"{cc.count} compile; {clog.since(mark)}")
    t0 = time.perf_counter()
    cont = engine.continue_sweep(res, trace2)
    jax.block_until_ready(cont.states.clock)
    log(f"(b) continue_sweep (incl. its compile): "
        f"{time.perf_counter() - t0:.3f} s")
    check_request_count(cont.states.counters, 2 * N_SWEEP, "(b)")
    best = cont.best()
    log(f"(b) best AMAT after 2 segments: {best['label']} "
        f"({best['amat_cyc']:.1f} cyc)")
    log(f"(b) ok; {peak_bytes()}")


def phase_serve(clog):
    """(c): bench_serve quick + degraded through the scheduler, held to
    zero recompiles after warmup, to bench_serve's SLO and pinned
    fast-hit floors, and to the emulated metrics committed in
    BENCH_serve.json (exactly: they do not depend on the host)."""
    floors = bench_serve.PROFILES["degraded"]["floors"]
    committed = json.loads((ROOT / "BENCH_serve.json").read_text())
    for name in ("quick", "degraded"):
        mark = clog.mark()
        m, _ = bench_serve.run_profile(name)   # raises on a recompile
        check(m["recompiles_after_warmup"] == 0, f"(c) {name}: recompiled")
        for k, floor in floors.items():
            check(m[k] >= floor, f"(c) {name}: {k} {m[k]} below {floor}")
        want = committed[f"{name}_metrics"]
        diff = {k: (m[k], want[k]) for k in SERVE_EMULATED if m[k] != want[k]}
        check(not diff, f"(c) {name}: differs from BENCH_serve.json {diff}")
        log(f"(c) {name}: warmup {m['warmup_s']:.3f} s, "
            f"{m['req_per_s']:.0f} requests/s, 0 recompiles after warmup; "
            f"slo_attainment {m['slo_attainment']}, pinned_fast_hit_rate "
            f"{m['pinned_fast_hit_rate']} (floors {floors}); p50/p99 "
            f"{m['p50_latency_us']}/{m['p99_latency_us']} us; emulated "
            f"metrics == BENCH_serve.json; {clog.since(mark)}")
    log(f"(c) ok; {peak_bytes()}")


def phase_check(clog, cfg, trace, outs1, outs2, state2):
    """(d): the chunk=1 trace_sim anchor, and (a) against the CPU device."""
    # --- chunk=1 against the sequential oracle
    ocfg = cfg.with_(chunk=1, hot_threshold=2, decay_every=64)
    t, _, n = workload_trace(WORKLOAD, scale=1.0, max_requests=N_ORACLE)
    state, outs = Engine(ocfg).run(t)
    ref = trace_sim.simulate(ocfg, *host(tuple(t)))
    for k in ("returns", "latency", "device"):
        np.testing.assert_array_equal(np.asarray(outs[k]), getattr(ref, k),
                                      err_msg=f"(d) trace_sim {k}")
    c = host(state.counters)
    for k in ("reads_fast", "writes_fast", "reads_slow", "writes_slow",
              "reorder_held"):
        check(int(getattr(c, k)) == ref.counters[k], f"(d) counter {k}")
    check(float(c.bytes_read_fast) + float(c.bytes_read_slow)
          == ref.counters["bytes_read"], "(d) counter bytes_read")
    check(int(state.clock) == ref.clock, "(d) clock")
    check(int(state.dma.swaps_done) == ref.swaps > 0, "(d) swaps")
    log(f"(d) chunk=1 Engine.run == trace_sim.simulate bitwise: {n} "
        f"requests, returns/latency/device/counters/clock, "
        f"{ref.swaps} swaps")

    # --- (a) again on the host's CPU device. The kernel dispatch keys on
    # the process's default backend (TPU), so it must select no Pallas
    # kernel, or the CPU run would compile a different program.
    check(not ops.use_pallas("hmmu_lookup"), "(d) Pallas gather selected")
    check(not chunk_step.use_chunk_step_kernel(cfg),
          "(d) Pallas chunk step selected")
    cpu = jax.devices("cpu")[0]
    mark = clog.mark()
    t0 = time.perf_counter()
    with jax.default_device(cpu):
        engine = Engine(cfg)
        t_cpu = jax.device_put(trace, cpu)
        c1 = engine.run(t_cpu)
        c2 = engine.run(t_cpu, state=c1.state)
        check(c2.state.table.devices() == {cpu}, "(d) replay left the CPU")
        cpu_outs1, cpu_outs2 = host(c1.outs), host(c2.outs)
        cpu_state2 = host(c2.state)
    log(f"(d) CPU-device replay of (a): {time.perf_counter() - t0:.3f} s; "
        f"{clog.since(mark)}")
    assert_trees_equal(outs1, cpu_outs1, "(d) first-call outputs vs CPU")
    assert_trees_equal(outs2, cpu_outs2, "(d) continuation outputs vs CPU")
    tpu_ints, cpu_ints = int_leaves(state2), int_leaves(cpu_state2)
    assert_trees_equal(tpu_ints, cpu_ints, "(d) integer state vs CPU")
    worst = 0.0
    for f in ("bytes_read_fast", "bytes_write_fast", "bytes_read_slow",
              "bytes_write_slow", "sum_read_latency", "energy_pj"):
        a = float(getattr(state2.counters, f))
        b = float(getattr(cpu_state2.counters, f))
        worst = max(worst, abs(a - b) / max(abs(b), 1.0))
    check(worst <= 1e-6, f"(d) float counters differ by {worst:.3g}")
    log(f"(d) (a) on TPU == (a) on CPU bitwise: outputs of both calls, "
        f"{len(tpu_ints)} integer state leaves (table, counters, "
        f"registers); float counters worst relative difference {worst:.3g}")
    log(f"(d) ok; {peak_bytes()}")


def phase_kernels():
    """(e): explicit requests for a refused kernel raise on the chip."""

    def raises(fn) -> str:
        try:
            jax.block_until_ready(fn())
        except Exception as e:   # the expected outcome, checked below
            return f"{type(e).__name__}: {str(e).splitlines()[0][:160]}"
        raise AssertionError("(e) a refused kernel ran instead of raising")

    cfg = paper_platform().with_(chunk=512, chunk_step_kernel="on")
    n = 4 * cfg.chunk
    t = Trace(jnp.arange(n, dtype=jnp.int32) % cfg.n_pages,
              jnp.zeros(n, jnp.int32), jnp.zeros(n, bool),
              jnp.full(n, 64, jnp.int32))
    msg = raises(lambda: Engine(cfg).run(t).state.clock)
    log(f'(e) chunk_step_kernel="on" raised: {msg}')
    table = jnp.zeros((cfg.n_pages, ROW_W), jnp.int32)
    pages = jnp.arange(cfg.chunk, dtype=jnp.int32)
    os.environ["REPRO_FORCE_PALLAS"] = "1"
    try:
        msg = raises(lambda: ops.hmmu_lookup(table, pages))
    finally:
        del os.environ["REPRO_FORCE_PALLAS"]
    log(f"(e) REPRO_FORCE_PALLAS=1 hmmu_lookup raised: {msg}")
    log("(e) ok")


def one_chip(clog):
    cfg = paper_platform().with_(chunk=512)
    trace, _, n = workload_trace(WORKLOAD, scale=1.0, max_requests=N_RUN)
    check(n == N_RUN, f"trace has {n} requests")
    outs1, outs2, state2 = phase_run(clog, cfg, trace)
    phase_check(clog, cfg, trace, outs1, outs2, state2)

    base = cfg.with_(hot_threshold=4, decay_every=32, write_weight=4)
    s1, _, _ = workload_trace(WORKLOAD, scale=1.0, max_requests=N_SWEEP)
    s2, _, _ = workload_trace(WORKLOAD, scale=1.0, max_requests=N_SWEEP,
                              seed=1)
    phase_sweep(clog, base, s1, s2)
    phase_serve(clog)
    phase_kernels()


# --------------------------------------------------------------------- #
# four chips
# --------------------------------------------------------------------- #

def four_chips(clog):
    """64 points sharded over a 4-device mesh vs unsharded on device 0."""
    check(len(jax.devices()) == 4, f"need 4 chips, have {jax.devices()}")
    base = paper_platform().with_(chunk=512, hot_threshold=4,
                                  decay_every=32, write_weight=4)
    spec = dataclasses.replace(
        make_spec(base),
        technologies=("3dxpoint", "stt-ram", "mram", "flash"),
        extra_axes=(("hot_threshold", (4, 8)),))
    points = build_points(spec)
    check(len(points) == 64, f"{len(points)} points")
    t1, _, _ = workload_trace(WORKLOAD, scale=1.0, max_requests=N_SWEEP)
    t2, _, _ = workload_trace(WORKLOAD, scale=1.0, max_requests=N_SWEEP,
                              seed=1)
    engine = Engine(points[0].cfg)
    log(f"[4 chips] 64-point sweep at {base.n_pages} pages, {N_SWEEP} "
        f"requests/segment; {paths(base)}")

    results = {}
    for mesh in ("auto", None):
        tag = "sharded over 4" if mesh else "unsharded on device 0"
        mark = clog.mark()
        t0 = time.perf_counter()
        with assert_compile_flat(engine, allow=1, msg=f"sweep {tag}"):
            res = engine.sweep(points, t1, mesh=mesh)
            jax.block_until_ready(res.states.clock)
        sweep_s = time.perf_counter() - t0
        shards = sorted((s.device.id, s.data.shape[0])
                        for s in res.states.table.addressable_shards)
        want = ([(d.id, 16) for d in jax.devices()] if mesh
                else [(jax.devices()[0].id, 64)])
        check(shards == sorted(want), f"{tag}: point placement {shards}")
        first = host(res.outs)
        t0 = time.perf_counter()
        cont = engine.continue_sweep(res, t2, mesh=mesh)
        jax.block_until_ready(cont.states.clock)
        cont_s = time.perf_counter() - t0
        check_request_count(cont.states.counters, 2 * N_SWEEP, tag)
        results[tag] = (first, host(cont.outs), host(cont.states))
        log(f"[4 chips] {tag}: sweep {sweep_s:.3f} s, continue_sweep "
            f"{cont_s:.3f} s (each incl. compile); points per device "
            f"{[c for _, c in shards]}; {clog.since(mark)}; "
            f"{peak_bytes()}")
        del res, cont
    (a1, a2, a3), (b1, b2, b3) = results.values()
    assert_trees_equal(a1, b1, "[4 chips] sweep outputs")
    assert_trees_equal(a2, b2, "[4 chips] continue_sweep outputs")
    assert_trees_equal(a3, b3, "[4 chips] continue_sweep states")
    log("[4 chips] sharded == unsharded bitwise: sweep outputs, "
        "continue_sweep outputs and states (all 64 points)")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded sweep and its reference")
    args = ap.parse_args()

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU chip found (JAX's first device is "
                 f"{dev.platform}: {dev.device_kind}); this smoke runs "
                 "only on a TPU")

    log(f"device kind {dev.device_kind}, {len(jax.devices())} device(s), "
        f"jax {jax.__version__}; compile cache {enable_compile_cache()}")
    clog = CompileLog()
    t0 = time.perf_counter()
    if args.chips == 4:
        four_chips(clog)
    else:
        one_chip(clog)
    log(f"all phases ok in {time.perf_counter() - t0:.1f} s; "
        f"{clog.n} backend compile(s) totalling {clog.secs:.2f} s, "
        f"{clog.hits} persistent-cache hit(s); {peak_bytes()}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()
