"""Benchmark aggregator: one reduced run per paper table/figure.

Emits ``name,us_per_call,derived`` CSV on stdout (progress on stderr).
Full-size variants: ``python -m benchmarks.bench_<x> --full``.

``--emit-json [DIR]`` runs the machine-readable perf suites (batched
dispatch + time-vs-n + matrix-free scaling + RMAE-vs-eps + sustained
serving throughput + certificate tightness + robust serving under chaos)
and writes standardized ``BENCH_batch.json`` / ``BENCH_time.json`` /
``BENCH_scale.json`` / ``BENCH_eps.json`` / ``BENCH_serve.json`` /
``BENCH_certify.json`` / ``BENCH_robust.json``
(schema ``repro-bench-v1``: method, n, B, wall-time, RMAE per row) so the
perf trajectory stays comparable across PRs — and gate-able by
``tools/bench_gate.py``.
"""
from __future__ import annotations

import argparse
import os
import sys
import time


def _emit_json(out_dir: str) -> None:
    from benchmarks import (
        bench_batch,
        bench_certify,
        bench_rmae_vs_eps,
        bench_robust,
        bench_scale,
        bench_serve,
        bench_time,
        common,
    )

    os.makedirs(out_dir, exist_ok=True)
    print(f"--- batch (JSON -> {out_dir}) ---", file=sys.stderr)
    bench_batch.run()
    common.write_json(os.path.join(out_dir, "BENCH_batch.json"), "batch")
    print("--- time vs n (JSON) ---", file=sys.stderr)
    bench_time.run()
    common.write_json(os.path.join(out_dir, "BENCH_time.json"), "time")
    print("--- matrix-free scale sweep (JSON) ---", file=sys.stderr)
    bench_scale.run()
    common.write_json(os.path.join(out_dir, "BENCH_scale.json"), "scale")
    print("--- RMAE vs eps sweep (JSON) ---", file=sys.stderr)
    bench_rmae_vs_eps.run(n=256, n_rep=4)
    bench_rmae_vs_eps.run(n=256, n_rep=4, lam=0.5)
    common.write_json(os.path.join(out_dir, "BENCH_eps.json"), "eps")
    print("--- sustained serving throughput (JSON) ---", file=sys.stderr)
    bench_serve.run()
    common.write_json(os.path.join(out_dir, "BENCH_serve.json"), "serve")
    print("--- certificate tightness sweep (JSON) ---", file=sys.stderr)
    bench_certify.run(n_rep=2)
    bench_certify.run(n_rep=2, lam=1.0)
    common.write_json(os.path.join(out_dir, "BENCH_certify.json"), "certify")
    print("--- robust serving under chaos (JSON) ---", file=sys.stderr)
    bench_robust.run()
    common.write_json(os.path.join(out_dir, "BENCH_robust.json"), "robust")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--emit-json",
        nargs="?",
        const=".",
        default=None,
        metavar="DIR",
        help="run the perf suites and write BENCH_batch.json / BENCH_time.json",
    )
    args = ap.parse_args()
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    if args.emit_json is not None:
        _emit_json(args.emit_json)
        return

    from benchmarks import (
        bench_barycenter,
        bench_batch,
        bench_echo,
        bench_rmae_ot,
        bench_rmae_uot,
        bench_rmae_vs_eps,
        bench_rmae_vs_n,
        bench_roofline,
        bench_router,
        bench_scale,
        bench_time,
    )

    print("name,us_per_call,derived")
    suites = [
        ("fig2 (RMAE OT vs s)", lambda: bench_rmae_ot.run(
            n=500, d=5, mults=(2, 8), n_rep=5, eps_grid=(1e-1, 1e-2), patterns=("C1",))),
        ("fig3 (RMAE UOT vs s)", lambda: bench_rmae_uot.run(
            patterns=("C1",), regimes=("R2",), n=500, mults=(2, 8), n_rep=4)),
        ("fig4 (RMAE vs n)", lambda: bench_rmae_vs_n.run(ns=(400, 800), n_rep=4)),
        ("rmae vs eps (log-domain sparse)", lambda: bench_rmae_vs_eps.run(
            eps_grid=(1e-1, 1e-3), n=192, n_rep=3, max_iter=2000)),
        ("fig5 (time vs n)", lambda: bench_time.run(ns=(800, 1600, 3200))),
        ("scale (matrix-free vs dense sketch)", lambda: bench_scale.run(
            ns=(2 ** 10, 2 ** 11, 2 ** 12), n_rep=2)),
        ("fig11 (barycenters)", lambda: bench_barycenter.run(
            n=400, eps_grid=(0.05,), mults=(5, 20), n_rep=4)),
        ("table1 (echo ED prediction)", lambda: bench_echo.run(
            n_videos=3, size=48, stride=3, methods=("sinkhorn", "spar_sink"),
            s_mult=16)),
        ("router (MoE spar-sink)", lambda: bench_router.run(n_tokens=1024)),
        ("batch (executor vs loop)", lambda: bench_batch.run()),
        ("roofline (dry-run artifacts)", lambda: bench_roofline.summarize(
            bench_roofline.best_artifact(), "1pod")),
    ]
    t0 = time.time()
    for name, fn in suites:
        print(f"--- {name} ---", file=sys.stderr)
        try:
            fn()
        except Exception as e:  # noqa: BLE001 — a suite failure must not hide others
            print(f"SUITE FAILED {name}: {e!r}", file=sys.stderr)
            print(f"suite_error/{name.split()[0]},0.0,{e!r}")
    print(f"total bench time: {time.time() - t0:.0f}s", file=sys.stderr)


if __name__ == "__main__":
    main()
