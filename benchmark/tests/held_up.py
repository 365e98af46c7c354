#!/usr/bin/env python3
"""A run whose host stands still: what the program answers, and what `correct` says.

    chiprun --chips 1 -- python3 benchmark/tests/held_up.py --workload ec42-small-mixed \\
        --seed 77 --seconds 45 --at set_up+20,pieces_landed+18 --for 5

Starts `run.py` as a run is started, reads its `[bench]` lines as they come,
and `--at <line>+<seconds>` after each named line stops that process (the
cluster and the chip; the load generator, its child, goes on) with SIGSTOP for
`--for` seconds, as a machine that stands still does.  The
program answers the requests in flight across such a stop with 500 "could not
reach quorum" (its RPC timeouts fire when it wakes): refused, so failed, not
wrong (PERF.md, Findings).  Prints the run's `window`, `preload` and `verify`
lines and its result; exits 0 only if the run was `correct`.  This process
never imports JAX.  The benchmark's own runs never run it.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=45.0)
    ap.add_argument("--at", required=True, help="<line>+<seconds>[,...]: set_up+20,pieces_landed+18")
    ap.add_argument("--for", dest="stop_s", type=float, default=5.0)
    args = ap.parse_args()
    stops = {}
    for spec in args.at.split(","):
        line, _, after = spec.partition("+")
        stops[line] = float(after or 0)

    proc = subprocess.Popen(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"), "--workload", args.workload,
         "--seed", str(args.seed), "--seconds", str(args.seconds), "--trace", "0"],
        cwd=ROOT, stdout=subprocess.PIPE, text=True)

    def hold(after: float, line: str) -> None:
        time.sleep(after)
        if proc.poll() is None:
            proc.send_signal(signal.SIGSTOP)
            print(f"[held_up] stopped {args.stop_s} s, {after} s after {line!r}", flush=True)
            time.sleep(args.stop_s)
            proc.send_signal(signal.SIGCONT)

    last = ""
    for out in proc.stdout:
        out = out.rstrip("\n")
        if out:
            last = out
        tag = out.split(" ", 2)[1] if out.startswith("[bench] ") else ""
        if tag in stops:
            threading.Thread(target=hold, args=(stops.pop(tag), tag), daemon=True).start()
        if tag in ("preload", "window", "verify"):
            print(out[:6000], flush=True)
    rc = proc.wait()
    print(last, flush=True)
    try:
        correct = json.loads(last)["correct"] is True
    except (ValueError, KeyError):
        correct = False
    print(f"[held_up] exit {rc}, correct={correct}, stops not reached: {sorted(stops)}", flush=True)
    return 0 if rc == 0 and correct else 1


if __name__ == "__main__":
    sys.exit(main())
