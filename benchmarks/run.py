"""Benchmark harness — one bench per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--quick]

Prints one CSV line per bench: ``name,us_per_call,derived``.
"""
from __future__ import annotations

import argparse


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    args = ap.parse_args()

    from benchmarks.compile_cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}")

    from benchmarks import (bench_chunk_step, bench_engine,
                            bench_latency_fidelity, bench_policies,
                            bench_request_volume, bench_serve, bench_speedup,
                            bench_sweep, bench_throughput)

    csv = []

    print("== Fig 7: simulation time vs native (slowdowns & speedups) ==")
    rows, summary = bench_speedup.run(
        scale=3e-9 if args.quick else 6e-9,
        workloads=["505.mcf", "538.imagick"] if args.quick else None)
    emu_us = 1e6 * sum(r["native_s"] * r["emu_slowdown"] for r in rows) / \
        sum(r["requests"] for r in rows)
    csv.append(("fig7_speedup", f"{emu_us:.3f}",
                f"geomean_speedup_vs_gem5class={summary['speedup_vs_cyclesim']:.1f}x;"
                f"vs_champsimclass={summary['speedup_vs_tracesim']:.1f}x;"
                f"emu_slowdown={summary['emu_slowdown']:.1f}x"))

    print("== Fig 8: memory request volumes ==")
    vol = bench_request_volume.run(scale=2e-9 if args.quick else 4e-9)
    mx = max(vol, key=lambda r: r["paper_scale_TB_read"])
    csv.append(("fig8_request_volume", "0",
                f"max_workload={mx['workload']};"
                f"max_TB={mx['paper_scale_TB_read']+mx['paper_scale_TB_written']:.2f}"))

    print("== Table I: arbitrary-latency emulation fidelity ==")
    fid = bench_latency_fidelity.run()
    worst = max(r["rel_err"] for r in fid)
    csv.append(("tableI_latency_fidelity", "0", f"worst_rel_err={worst:.4f}"))

    print("== Policy design-space exploration (platform use case) ==")
    pol = bench_policies.run(n_requests=30_000 if args.quick else 120_000)
    best = min(pol, key=lambda r: r["mean_read_latency"])
    static = [r for r in pol if r["policy"] == "static"][0]
    csv.append(("policy_exploration", "0",
                f"best={best['policy']};"
                f"latency_gain={static['mean_read_latency']/best['mean_read_latency']:.2f}x"))

    print("== Design-space sweep (one compiled vmapped emulation) ==")
    sw = bench_sweep.run(n_requests=20_000 if args.quick else 100_000)
    csv.append(("design_space_sweep", f"{sw['us_per_point_req']:.3f}",
                f"points={sw['n_points']};compiles={sw['compiles']};"
                f"best={sw['best_label']};best_amat={sw['best_amat']:.1f}"))

    print("== Chunk-step hot path (resolver / gather fusion / donation) ==")
    cs = bench_chunk_step.run(n=8_192 if args.quick else 32_768,
                              reps=2 if args.quick else 5)
    m = cs["metrics"]
    csv.append(("chunk_step", f"{m['us_per_req_default']:.3f}",
                f"seg_vs_dense={m['speedup_segmented_vs_dense']:.2f}x;"
                f"fused_vs_unfused={m['speedup_fused_vs_unfused']:.2f}x;"
                f"donate={m['speedup_donate']:.2f}x"))

    print("== Session API dispatch overhead (Engine vs raw jit) ==")
    ev = bench_engine.run(reps=10 if args.quick else 50)
    em = ev["metrics"]
    csv.append(("engine_dispatch", f"{em['us_per_call_engine']:.1f}",
                f"overhead={em['dispatch_overhead_us']:+.1f}us;"
                f"warm_recompiles={em['warm_construct_recompiles']}"))

    print("== Serving SLO (continuous batching over the tiered KV) ==")
    sv, _ = bench_serve.run_profile("quick" if args.quick else "full")
    csv.append(("serve_slo", f"{sv['p99_latency_us']:.0f}",
                f"slo_attainment={sv['slo_attainment']:.3f};"
                f"pinned_fast_hit={sv['pinned_fast_hit_rate']:.3f};"
                f"live_peak={sv['live_seqs_high_water']};"
                f"recompiles={sv['recompiles_after_warmup']}"))

    print("== Emulator throughput (chunk width / channels) ==")
    thr = bench_throughput.run(n=16_384 if args.quick else 65_536)
    best_thr = min(thr, key=lambda r: r["us_per_req"])
    csv.append(("emulator_throughput", f"{best_thr['us_per_req']:.3f}",
                f"best_mode={best_thr['mode']};req_per_s={best_thr['req_per_s']:.0f}"))

    print("\nname,us_per_call,derived")
    for name, us, derived in csv:
        print(f"{name},{us},{derived}")


if __name__ == "__main__":
    main()
