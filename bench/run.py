"""Run one benchmark cell once on the chip it is started on.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Prints set-up parts, generator lateness and the compared numbers on
stderr, and as the last line of stdout one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown`` with
``--trace 1``), then ``checks``, each compared number with its limit.
Exits 2, printing no result, where JAX finds no TPU, fewer chips than the
cell asks for, or float64 switched on.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

REPO = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(REPO), str(REPO / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness

    def log(msg):
        print(msg, file=sys.stderr, flush=True)

    try:
        benchmark = harness.read_json(REPO / "BENCHMARK.json")
        device = harness.check_device(harness.chips_of(benchmark, args.workload))
    except harness.NoDevice as e:
        log(f"bench: {e}; nothing run")
        return 2
    import jax

    from repro.launch.compile_cache import use_compile_cache

    cache = use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    log(f"device platform={device['platform']} kind={device['kind']!r} "
        f"count={device['count']} jax={jax.__version__} compile_cache={cache}")
    result = harness.run_cell(args.workload, args.seed, args.seconds, bool(args.trace),
                              t_start=T_START, device=device, log=log)
    harness.report(result, log)
    return 0


if __name__ == "__main__":
    sys.exit(main())
