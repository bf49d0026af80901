"""Readings that the limits of ``correct`` are set from.

    python3 chipbench/calibrate.py --workload <cell> --seeds <n> \\
        --control <m> [--first-seed <s>]

For each of ``--seeds`` seeds the cell's inputs are drawn from that seed
exactly as a benchmark run draws them, one timed unit of work runs (one
replay call) and the numbers compared are read against
the float32 reference: the program's readings.  For the first
``--control`` of those seeds the reference computed in bfloat16 is put in
the program's place and read the same way: the control's readings.  A
limit lies above every program reading and below every control reading
that fails it.  One JSON line per reading, then a summary line.

Like a benchmark run it needs the chips the cell asks for.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def readings(name: str, seeds: list[int], control: int):
    import jax.numpy as jnp

    from chipbench import harness

    for i, seed in enumerate(seeds):
        cell = harness.load_cell(name, seed)
        driver = harness.load_module("drivers", cell.workload["driver"])
        ctx = driver.setup(cell, warm=False)
        records = [driver.call(ctx, 0)]
        driver.release(ctx)
        yield {"seed": seed, "side": "program",
               **driver.check(ctx, records)}
        if i < control:
            yield {"seed": seed, "side": "control",
                   **driver.check(ctx, driver.as_reference(
                       ctx, records, dtype=jnp.bfloat16))}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2**31 + 17)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    # the TPU runtime logs to /tmp/tpu_logs unless told otherwise
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    from chipbench import harness
    from repro.launch.cache import enable_persistent_cache

    harness.require_chips(harness.load_cell(args.workload, 0).chips)
    enable_persistent_cache()
    seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]
    lo: dict = {}
    hi: dict = {}
    for r in readings(args.workload, seeds, args.control):
        print(json.dumps(r), flush=True)
        side = lo if r["side"] == "program" else hi
        for k, v in r.items():
            if k in ("seed", "side"):
                continue
            side[k] = (max if side is lo else min)(side.get(k, v), v)
    print(json.dumps({"workload": args.workload, "program_max": lo,
                      "control_min": hi}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
