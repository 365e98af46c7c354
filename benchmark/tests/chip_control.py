#!/usr/bin/env python3
"""The control and the faults at a cell's own size, on the chip.

    chiprun --chips 1 -- python benchmark/tests/chip_control.py \\
        --workload ec83-put-8m --fault control_parity_shard_dropped --seeds 11,12,13 --seconds 12

Runs the cell as `run.py` does (same harness, sizes, generator and checks;
a short window, long enough to finish the mix's longest requests) with the
timed path broken underneath (`faults.py`), once per seed in one process,
and exits 0 only if EVERY run came out as not correct.  The benchmark's own
runs never run it.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
import time

TESTS = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(TESTS)
ROOT = os.path.dirname(BENCH_DIR)
for p in (TESTS, BENCH_DIR, ROOT):
    sys.path.insert(0, p)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--fault", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=12.0)
    args = ap.parse_args()

    import faults
    import run
    from harness import loader
    from harness.cell import run_cell

    from garage_tpu.utils.compile_cache import enable_persistent_cache

    cell = loader.cell(args.workload)
    device = run.find_device(cell["workload"]["chips"])
    if device is None:
        return 2
    enable_persistent_cache()
    scratch = os.path.join(ROOT, ".bench_scratch")
    os.makedirs(scratch, exist_ok=True)
    caught = []
    for seed in [int(x) for x in args.seeds.split(",")]:
        try:
            r = asyncio.run(run_cell(cell, seed, args.seconds, False, time.perf_counter(), device,
                                     scratch, sabotage=faults.BY_NAME[args.fault]))
        finally:
            faults.undo()
        failed = {k: v["value"] for k, v in r["checks"].items() if not v["ok"]}
        print(f"[control] {args.workload} {args.fault} seed {seed}: correct={r['correct']} "
              f"numbers over their limit: {json.dumps(failed)}", flush=True)
        caught.append(r["correct"] is False)
    print(f"[control] {args.workload} {args.fault}: not correct in {sum(caught)} of {len(caught)} runs", flush=True)
    return 0 if all(caught) else 1


if __name__ == "__main__":
    sys.exit(main())
