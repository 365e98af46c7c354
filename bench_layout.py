#!/usr/bin/env python3
"""Layout-transition benchmark: grow a live EC cluster and bank what the
rebalance observatory (rpc/transition.py, doc/monitoring.md §"Rebalance
observatory") measured about it.

Boots an in-process EC cluster with the first `--base` nodes in the
layout, seeds objects through the real S3 API, then stages the remaining
`--grow` nodes and applies — opening a genuine layout transition that
the per-node `TransitionTracker`s narrate while background workers sync
and retire the old version.  The banked artifact is the observatory's
own output: transition duration, bytes attributed to (src → dst) pairs,
the final sync fraction, and the structured transition-report — so
`script/bench_diff.py` floors catch the observatory (or the migration
plane under it) silently breaking.

Prints ONE JSON line and (with --artifact) commits it:

    {"metric": "layout_transition_s", "value": T, "unit": "s",
     "bytes_moved": B, "pairs": P, "sync_fraction_final": 1.0, ...}

Usage: python bench_layout.py [--base 5 --grow 2] [--artifact F]
"""

import argparse
import asyncio
import json
import os
import pathlib
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "tests"))

os.environ.setdefault("JAX_PLATFORMS", "cpu")


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--base", type=int, default=7,
                    help="nodes in the initial layout")
    ap.add_argument("--grow", type=int, default=2,
                    help="nodes added by the transition")
    ap.add_argument("--mode", default="ec:4:2")
    ap.add_argument("--objects", type=int, default=48)
    ap.add_argument("--object-bytes", type=int, default=20_000)
    ap.add_argument("--timeout", type=float, default=360.0,
                    help="seconds to wait for the transition to close")
    ap.add_argument("--artifact", help="also write the JSON result here")
    ap.add_argument("--verbose", action="store_true")
    return ap.parse_args(argv)


def vlog(args, msg):
    if args.verbose:
        print(f"# {msg}", file=sys.stderr)


async def run_bench(args, tmp):
    from test_ec_cluster import make_ec_cluster, stop_cluster

    from garage_tpu.api.s3.api_server import S3ApiServer
    from garage_tpu.api.s3.client import S3Client
    from garage_tpu.rpc.layout.types import NodeRole
    from garage_tpu.rpc.transition import cluster_events_response

    n = args.base + args.grow
    garages = await make_ec_cluster(
        tmp, n=n, mode=args.mode, assign=set(range(args.base))
    )
    s3 = S3ApiServer(garages[0])
    await s3.start("127.0.0.1", 0)
    ep = f"http://127.0.0.1:{s3.runner.addresses[0][1]}"
    key = await garages[0].helper.create_key("bench-layout")
    key.params().allow_create_bucket.update(True)
    await garages[0].key_table.insert(key)
    client = S3Client(ep, key.key_id, key.secret())
    try:
        await client.create_bucket("bench")
        bodies = {}
        for i in range(args.objects):
            k = f"obj-{i:04d}"
            bodies[k] = f"{i}:".encode() + os.urandom(args.object_bytes)
            await client.put_object("bench", k, bodies[k])
        vlog(args, f"seeded {args.objects} objects on {args.base} nodes")

        lm = garages[0].layout_manager
        for i in range(args.base, n):
            lm.stage_role(
                garages[i].node_id, NodeRole(zone=f"dc{i}", capacity=10**12)
            )
        t0 = time.perf_counter()
        lm.apply_staged()

        deadline = t0 + args.timeout
        closed_s = None
        while time.perf_counter() < deadline:
            await asyncio.sleep(0.25)
            if all(
                not g.transition_tracker.active
                and g.transition_tracker.sync_fraction() == 1.0
                and g.transition_tracker.reports > 0
                for g in garages
            ):
                closed_s = time.perf_counter() - t0
                break
        if closed_s is None:
            frac = [g.transition_tracker.sync_fraction() for g in garages]
            raise RuntimeError(
                f"transition did not close within {args.timeout}s "
                f"(sync fractions: {frac})"
            )
        vlog(args, f"transition closed in {closed_s:.1f}s")

        # read-back after the move: every object survives the grow
        for k, body in bodies.items():
            got = await client.get_object("bench", k)
            if got != body:
                raise RuntimeError(f"{k}: corrupted after the transition")

        # aggregate the per-node reports (each report's bytesMoved must
        # equal its own pair counters — the acceptance invariant)
        reports = [
            g.transition_tracker.last_report
            for g in garages
            if g.transition_tracker.last_report is not None
        ]
        for rep in reports:
            pair_sum = sum(p["bytes"] for p in rep["pairs"])
            if rep["bytesMoved"] != pair_sum:
                raise RuntimeError(
                    f"report bytesMoved {rep['bytesMoved']} != "
                    f"pair sum {pair_sum}"
                )
        bytes_moved = sum(r["bytesMoved"] for r in reports)
        pairs = sum(len(r["pairs"]) for r in reports)
        duration_max = max(r["durationSecs"] for r in reports)

        ev = await cluster_events_response(garages[0], since=0.0)
        frac_final = min(
            g.transition_tracker.sync_fraction() for g in garages
        )
        return {
            "metric": "layout_transition_s",
            "value": round(closed_s, 2),
            "unit": "s",
            "layout_transition_s": round(closed_s, 2),
            "transition_s": round(closed_s, 2),
            "report_duration_max_s": round(duration_max, 2),
            "bytes_moved": int(bytes_moved),
            "pairs": pairs,
            "reports": len(reports),
            "sync_fraction_final": frac_final,
            "events_nodes_responding": len(ev["nodesResponding"]),
            "events_nodes_failed": len(ev["nodesFailed"]),
            "timeline_events": len(ev["events"]),
            "objects": args.objects,
            "object_bytes": args.object_bytes,
            "mode": args.mode,
            "nodes_before": args.base,
            "nodes_after": n,
            "utc": time.strftime("%Y-%m-%d %H:%M:%S", time.gmtime()),
        }
    finally:
        await stop_cluster(garages, [s3], [client])


def main(argv=None):
    args = parse_args(argv)
    with tempfile.TemporaryDirectory(prefix="bench_layout_") as tmp:
        result = asyncio.run(run_bench(args, pathlib.Path(tmp)))
    print(json.dumps(result))
    if args.artifact:
        with open(args.artifact, "w") as f:
            json.dump(result, f, indent=1)
            f.write("\n")


if __name__ == "__main__":
    main()
