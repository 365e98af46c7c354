#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

run from the root of a checkout, on the machine that holds the chip.  It
finds the cell's files by the names in `BENCHMARK.json` (`harness/loader.py`),
refuses to run without an accelerator (exit 2, no result line, never a CPU
fallback), and prints as the last line of standard output one JSON object:
`correct`, `attempted`, `failed`, `metrics`, `device`, with `--trace 1`
`breakdown`, and last `checks` (every number compared beside its limit,
also the last lines of standard error).  `--trace 0`: the cell's
end-to-end metrics; `--trace 1`: its per-layer metrics, from a run with the
profiler and the loop sampler on.  Everything else worth reading is on
earlier `[bench]` lines.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()  # set-up runs from here to the window's first request

import argparse  # noqa: E402
import asyncio  # noqa: E402
import faulthandler  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
sys.path.insert(0, BENCH_DIR)
sys.path.insert(0, ROOT)


def find_device(chips: int, allow_host: bool = False) -> dict | None:
    """The device as JAX reports it, or None where there is no accelerator
    or fewer chips than the cell asks for."""
    import jax

    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}
    if (dev["platform"] == "cpu" and not allow_host) or len(devs) < chips:
        print(f"[bench] no accelerator for {chips} chip(s): jax {jax.__version__} found "
              f"{len(devs)} x {dev['platform']} ({dev['kind']})", file=sys.stderr, flush=True)
        return None
    return dev


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    if not os.path.isdir(os.path.join(ROOT, "garage_tpu")):
        print(f"[bench] no program to measure: {ROOT}/garage_tpu is not there", file=sys.stderr)
        return 3
    # a run that hangs says where, and ends: every thread's stack on standard
    # error, exit 1, no result line (a cold first run compiles for minutes)
    faulthandler.dump_traceback_later(1100, exit=True)

    from harness import loader
    from harness.cell import run_cell
    from harness.cluster import say

    cell = loader.cell(args.workload)
    device = find_device(cell["workload"]["chips"])
    if device is None:
        return 2
    # the program's own switch: JAX_COMPILATION_CACHE_DIR if set, else the
    # fixed <checkout>/.xla_cache
    from garage_tpu.utils.compile_cache import enable_persistent_cache

    cache_dir = enable_persistent_cache()
    scratch = os.path.join(ROOT, ".bench_scratch")
    os.makedirs(scratch, exist_ok=True)
    say("run", workload=args.workload, seed=args.seed, seconds=args.seconds, trace=args.trace,
        device=device, compile_cache_dir=cache_dir)
    result = asyncio.run(run_cell(
        cell, abs(args.seed), args.seconds, bool(args.trace), T_PROCESS, device, scratch))
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    # every node lived in this process: end it here, without the interpreter's
    # own wait for whatever thread a stopped node may have left
    os._exit(0)


if __name__ == "__main__":
    sys.exit(main())
