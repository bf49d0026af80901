"""Run one benchmark cell on the chips this machine holds.

    python3 chipbench/run_cell.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

Set-up (inputs from the seed, the program's compile cache under
``<checkout>/.jax_cache``, one warm call of every shape the window uses)
counts as ``setup_s``.  The window then runs the cell's driver for
``--seconds``; ``--trace 1`` runs it under the profiler and reports the
per-layer metrics instead of the end-to-end ones.  Afterwards what the
window produced is compared with the plain reference under
``chipbench/reference``; each number compared is printed beside its limit
on standard error and under ``checks`` in the result.  The last line of
standard output is the result as one JSON object.

Without an accelerator, or with fewer chips than the cell asks for, it
exits non-zero and prints no result.
"""
from __future__ import annotations

import time

START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CACHE_DIR = os.path.join(ROOT, ".jax_cache")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    # the program keeps its compile cache where this variable points; the
    # benchmark fixes it inside the checkout so runs of a cell share it
    # the TPU runtime logs to /tmp/tpu_logs unless told otherwise
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    from chipbench import harness

    cell = harness.load_cell(args.workload, args.seed)
    devices = harness.require_chips(cell.chips)
    from repro.launch.cache import enable_persistent_cache

    enable_persistent_cache()
    result = harness.run_cell(cell, args.seconds, bool(args.trace), devices,
                              START)
    sys.stdout.write(json.dumps(result) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
