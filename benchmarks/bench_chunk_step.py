"""Chunk-step microbenchmark: the everything-path of the platform.

Every request of every design point of every sweep flows through
``_chunk_step``; this bench isolates the three perf levers this repo
tunes on it, on the default paper geometry (n_banks=16, chunk=512):

* ``resolver=dense`` vs ``resolver=segmented`` — O(n_banks*chunk) one-hot
  bank-queue resolution vs the O(chunk log chunk) sort-based segmented
  max-plus scan (bitwise identical; see core.latency);
* ``gather=unfused`` vs ``gather=fused`` — separate dynamic-slice reads
  of the DMA swap pair's table rows vs appending them to the chunk's
  lookup-kernel launch (chunk + 2 rows, one gather);
* ``donate=off`` vs ``donate=on`` — continued emulation with the carried
  state's buffers copied vs donated (the packed table updates in place);
* ``kernel=off`` vs ``kernel=on`` — the restructured scan path vs the
  one-kernel Pallas chunk step (``chunk_step_kernel``; interpret mode
  off-TPU, so its absolute number is only meaningful on real hardware —
  benched at a reduced request count).

Device time per phase and stage of the chunk step comes from the named
scopes in the program, read from a traced run of a benchmark cell on the
chip (``python3 hbench/trace_scopes.py``, reduced by
``hbench/scopes.py``).

Runnable standalone::

    PYTHONPATH=src python -m benchmarks.bench_chunk_step --quick \
        --out BENCH_chunk_step.json [--check-against BENCH_chunk_step.json]

``--check-against`` is the tiered CI perf-regression gate shared by
every bench (``benchmarks.schema.check_against``): a GitHub
``::warning::`` past the warn tolerance, a failing ``::error::`` past
the fail tolerance — CI runners are noisy, so the smoke job passes a
wide fail tolerance for this wall-clock metric.
"""
from __future__ import annotations

import argparse
import time

import jax

from benchmarks.bench_throughput import _bench  # shared warm-then-average
from benchmarks.schema import (add_check_args, bench_payload, run_check,
                               write_bench_json)
from repro import Engine
from repro.core import paper_platform
from repro.trace import TraceSpec, generate

# The default hot path: what plain paper_platform() users get.
_DEFAULT_CASE = "resolver=auto/gather=fused"


def run(verbose=True, n=32_768, reps=5, out=None):
    base = paper_platform().with_(chunk=512)
    trace = generate(TraceSpec(n_requests=n, footprint_pages=60_000,
                               write_frac=0.4, pattern="zipfian",
                               zipf_alpha=1.05))
    rows = []

    def case(name, cfg, state=None, donate=False):
        engine = Engine(cfg)
        if state is None:
            fn = lambda: jax.block_until_ready(  # noqa: E731
                engine.run(trace).state.clock)
            sec = _bench(fn, reps)
        else:
            # Continued emulation: each call consumes the previous call's
            # state — exactly the serving/incremental-sweep access pattern
            # donation exists for. Warm with the same donate flag (the
            # donated entry point is its own compilation).
            s = engine.run(trace, state=state, donate=donate).state
            jax.block_until_ready(s.clock)
            t0 = time.time()
            for _ in range(reps):
                s = engine.run(trace, state=s, donate=donate).state
            jax.block_until_ready(s.clock)
            sec = (time.time() - t0) / reps
        rows.append({"case": name, "s_per_call": sec,
                     "us_per_req": sec / n * 1e6})
        if verbose:
            print(f"  {name:38s} {sec * 1e3:9.1f} ms/call "
                  f"{rows[-1]['us_per_req']:8.3f} us/req")
        return sec

    sec_pre = case("resolver=dense/gather=unfused (pre-PR path)",
                   base.with_(bank_resolver="dense", fuse_swap_gather=False))
    sec_dense = case("resolver=dense/gather=fused",
                     base.with_(bank_resolver="dense"))
    sec_seg = case("resolver=segmented/gather=fused",
                   base.with_(bank_resolver="segmented"))
    sec_unfused = case("resolver=auto/gather=unfused",
                       base.with_(fuse_swap_gather=False))
    sec_default = case(_DEFAULT_CASE, base)

    state0 = Engine(base).run(trace).state
    sec_nodon = case("continued/donate=off", base, state=state0)
    state0 = Engine(base).run(trace).state
    sec_don = case("continued/donate=on", base, state=state0, donate=True)

    # One-kernel chunk step. Off-TPU the kernel runs in interpret mode —
    # orders of magnitude slower than compiled — so bench it on a reduced
    # trace: the case exists to pin the path end-to-end and to carry a
    # trajectory for TPU runs, not to win on CPU.
    n_kernel = min(n, 2_048)
    ktrace = jax.tree.map(lambda x: x[:n_kernel], trace)
    kcfg = base.with_(chunk_step_kernel="on")
    engine_k = Engine(kcfg)
    fn_k = lambda: jax.block_until_ready(  # noqa: E731
        engine_k.run(ktrace).state.clock)
    sec_kernel = _bench(fn_k, max(2, reps // 2))
    rows.append({"case": "kernel=on (interpret off-TPU)",
                 "s_per_call": sec_kernel,
                 "us_per_req": sec_kernel / n_kernel * 1e6,
                 "n_requests": n_kernel})
    if verbose:
        print(f"  {'kernel=on (interpret off-TPU)':38s} "
              f"{sec_kernel * 1e3:9.1f} ms/call "
              f"{rows[-1]['us_per_req']:8.3f} us/req  (n={n_kernel})")

    metrics = {
        "n_requests": n,
        "us_per_req_default": sec_default / n * 1e6,
        "us_per_req_pre_pr_path": sec_pre / n * 1e6,
        "us_per_req_dense": sec_dense / n * 1e6,
        "us_per_req_segmented": sec_seg / n * 1e6,
        "speedup_vs_pre_pr": sec_pre / sec_default,
        "speedup_segmented_vs_dense": sec_dense / sec_seg,
        "speedup_fused_vs_unfused": sec_unfused / sec_default,
        "speedup_donate": sec_nodon / sec_don,
        "us_per_req_kernel_interpret": sec_kernel / n_kernel * 1e6,
    }
    if verbose:
        print(f"  vs pre-PR path: {metrics['speedup_vs_pre_pr']:.2f}x, "
              f"segmented vs dense: {metrics['speedup_segmented_vs_dense']:.2f}x, "
              f"fused vs unfused: {metrics['speedup_fused_vs_unfused']:.2f}x, "
              f"donated continuation: {metrics['speedup_donate']:.2f}x")
    summary = bench_payload(
        "chunk_step", metrics,
        config={"chunk": base.chunk, "n_banks": base.n_banks,
                "n_pages": base.n_pages, "reps": reps,
                "n_kernel": n_kernel},
        cases=rows)
    if out:
        path = write_bench_json(out, summary)
        if verbose:
            print(f"  written to {path}")
    return summary


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--quick", action="store_true",
                    help="8k requests, 2 reps (CI smoke)")
    ap.add_argument("--requests", type=int, default=None)
    ap.add_argument("--out", default=None,
                    help="write the standardized BENCH_chunk_step.json")
    add_check_args(ap)
    args = ap.parse_args()
    n = args.requests or (8_192 if args.quick else 32_768)
    summary = run(n=n, reps=2 if args.quick else 5, out=args.out)
    run_check(summary, args,
              ["us_per_req_default", "us_per_req_kernel_interpret"])


if __name__ == "__main__":
    main()
