"""Benchmark harness: one module per paper table/figure (+ substrate
benches). Prints ``name,us_per_call,derived`` CSV."""
from __future__ import annotations

import os
import sys
import traceback

# make `python benchmarks/run.py` equivalent to `python -m benchmarks.run`:
# the bench modules import each other as the `benchmarks` namespace package,
# which needs the repo root on sys.path
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

MODULES = [
    "benchmarks.bench_motivation",       # Table I / Figs 1-4
    "benchmarks.bench_resource_model",   # Figs 6-7
    "benchmarks.bench_predictors",       # Table II / Figs 8-12
    "benchmarks.bench_schedulers",       # Figs 13-15
    "benchmarks.bench_control",          # runtime mitigation on/off
    "benchmarks.bench_scheduler_latency",
    "benchmarks.bench_rollout_scale",    # vmap vs shard_map engine rows
    "benchmarks.bench_metric_pipeline",
    "benchmarks.bench_kernels",
    "benchmarks.bench_roofline",         # EXPERIMENTS.md §Roofline source
]


def selftest() -> int:
    """Seconds-scale smoke: import every bench module and check it exposes
    the ``run(fast=...)`` contract, without executing any benchmark."""
    failures = 0
    for modname in MODULES:
        try:
            mod = __import__(modname, fromlist=["run"])
            if not callable(getattr(mod, "run", None)):
                raise TypeError("module has no callable run(fast=...)")
            print(f"{modname}: ok")
        except Exception as e:
            failures += 1
            print(f"{modname}: FAIL ({e})")
            traceback.print_exc(file=sys.stderr)
    print(f"selftest: {len(MODULES) - failures}/{len(MODULES)} modules ok")
    return 1 if failures else 0


def main() -> None:
    if "--selftest" in sys.argv:
        sys.exit(selftest())
    from repro.launch.cache import enable_persistent_cache

    enable_persistent_cache()
    fast = "--full" not in sys.argv
    print("name,us_per_call,derived")
    failed = []
    for modname in MODULES:
        try:
            mod = __import__(modname, fromlist=["run"])
            for name, us, derived in mod.run(fast=fast):
                print(f"{name},{us:.1f},{derived}")
            sys.stdout.flush()
        except Exception:
            # keep going so one broken bench does not hide the others'
            # rows, but the run as a whole fails
            failed.append(modname)
            print(f"{modname},0,ERROR")
            traceback.print_exc(file=sys.stderr)
    if failed:
        print(f"failed: {', '.join(failed)}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
