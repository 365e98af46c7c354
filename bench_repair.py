#!/usr/bin/env python3
"""Repair-plane benchmark: one-node-kill batched reconstruction.

The BASELINE scrub/repair config (row 4: "EC(8,3), kill one node,
batched resync of 10k blocks") measured end-to-end through the REAL
repair plane: an in-process cluster of k+m BlockManager nodes (full
netapp RPC between them), a 10k-block EC(8,3) population, one node's
data dir wiped (the node is alive, its disk is gone), and the
`RepairPlanner` (block/repair_plan.py) on the degraded node scanning,
coalescing and driving `bulk_reconstruct` until every stripe is healed.

Prints ONE JSON line and (with --artifact) commits it:

    {"metric": "repair_blocks_per_s", "value": N, "unit": "blocks/s",
     "blocks": B, "repaired": R, "dispatches": D, "mesh_engaged": M,
     "platform": "cpu"|"tpu", ...}

`dispatches` counts actual ec_reconstruct device dispatches — the
acceptance bar is dispatches << blocks (batched repair, not per-block);
`mesh_engaged` counts dispatches served by the multi-device shard_map
mesh (ops/ec_tpu.py 2x-devices threshold).  On a CPU-only box the mesh
is 8 virtual host devices (same topology the test suite uses).

The measured time covers the WHOLE plane — inventory survey RPCs, k
surviving-piece gathers per stripe over loopback netapp, grouped device
dispatches, and piece writes — so the number moves when any stage of
repair regresses, not just the kernel.
"""

import argparse
import asyncio
import json
import os
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

# virtual multi-device mesh on hosts without real chips (same flag the
# test conftest uses) — must be set before the first jax import
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--blocks", type=int, default=10_000)
    ap.add_argument("--block-bytes", type=int, default=8192)
    ap.add_argument("--k", type=int, default=8)
    ap.add_argument("--m", type=int, default=3)
    ap.add_argument("--batch", type=int, default=1024,
                    help="planner blocks per coalesced round")
    ap.add_argument("--bytes-in-flight", type=int, default=256 * 1024 * 1024)
    ap.add_argument("--victim", type=int, default=1,
                    help="node index whose data dir is lost")
    ap.add_argument("--artifact", help="also write the JSON result here")
    ap.add_argument("--verbose", action="store_true")
    return ap.parse_args(argv)


def vlog(args, msg):
    if args.verbose:
        print(f"# {msg}", file=sys.stderr)


def counter_sum(name, **want):
    from garage_tpu.utils.metrics import registry

    total = 0.0
    for (n, labels), v in registry.counters.items():
        if n != name:
            continue
        d = dict(labels)
        if all(d.get(k) == v2 for k, v2 in want.items()):
            total += v
    return total


async def make_cluster(tmp, n, rf, codec):
    """In-process BlockManager cluster over real netapp loopback (the
    shape tests/test_block.py uses, sized for EC(k,m))."""
    from garage_tpu.block.manager import BlockManager
    from garage_tpu.db import open_db
    from garage_tpu.net import NetApp
    from garage_tpu.net.handshake import gen_node_key
    from garage_tpu.rpc.layout.manager import LayoutManager
    from garage_tpu.rpc.layout.types import NodeRole
    from garage_tpu.rpc.replication_mode import ReplicationMode
    from garage_tpu.rpc.rpc_helper import RpcHelper
    from garage_tpu.rpc.system import System
    from garage_tpu.utils.config import DataDir

    apps, systems, managers = [], [], []
    netkey = b"R" * 32
    for i in range(n):
        app = NetApp(netkey, gen_node_key())
        await app.listen("127.0.0.1", 0)
        apps.append(app)
    for app in apps:
        peers = [(a.id, a.bind_addr) for a in apps if a is not app]
        lm = LayoutManager(app.id, rf)
        sysd = System(app, lm, ReplicationMode(rf), bootstrap=peers)
        await sysd.start()
        systems.append(sysd)
    for _ in range(200):
        await asyncio.sleep(0.05)
        if all(len(s.peering.connected_peers()) == n - 1 for s in systems):
            break
    lm0 = systems[0].layout_manager
    for app in apps:
        lm0.stage_role(app.id, NodeRole(zone="dc1", capacity=10**12))
    lm0.apply_staged()
    for _ in range(200):
        await asyncio.sleep(0.05)
        if all(s.layout_manager.digest() == lm0.digest() for s in systems):
            break
    for i, (app, sysd) in enumerate(zip(apps, systems)):
        meta = os.path.join(tmp, f"meta{i}")
        os.makedirs(meta, exist_ok=True)
        db = open_db(meta, engine="memory")
        managers.append(
            BlockManager(
                sysd,
                RpcHelper(app.id, sysd.peering),
                db,
                [DataDir(os.path.join(tmp, f"data{i}"))],
                meta,
                codec=codec,
            )
        )
    return apps, systems, managers


async def populate(args, managers, victim_idx):
    """Encode the population in batched dispatches and lay pieces
    directly into each SURVIVING node's store (the victim's disk is the
    one that died); reference every block on every node's rc."""
    import numpy as np

    from garage_tpu.block.manager import wrap_piece
    from garage_tpu.utils.data import blake2sum

    codec = managers[0].codec
    by_id = {m.system.id: m for m in managers}
    victim_id = managers[victim_idx].system.id
    layout = managers[0].system.layout_manager.history.current()
    rng = np.random.default_rng(0)

    hashes = []
    written = 0
    t0 = time.perf_counter()
    for start in range(0, args.blocks, 2048):
        count = min(2048, args.blocks - start)
        datas = [
            rng.integers(0, 256, args.block_bytes, dtype=np.uint8).tobytes()
            for _ in range(count)
        ]
        encoded = codec.encode_batch(datas)
        for data, pieces in zip(datas, encoded):
            h = blake2sum(data)
            hashes.append(h)
            nodes = layout.nodes_of(h)[: codec.n_pieces]
            for rank, nid in enumerate(nodes):
                if nid == victim_id:
                    continue  # this node's disk is the one that died
                await by_id[nid].write_block_local(
                    h, wrap_piece(len(data), pieces[rank]), False, piece=rank
                )
                written += 1
    for mgr in managers:
        hs = hashes
        for i in range(0, len(hs), 1000):
            chunk = hs[i : i + 1000]
            mgr.db.transaction(
                lambda tx, c=chunk, m=mgr: [m.rc.incr(tx, h) for h in c]
                and None
            )
    vlog(args, f"populated {len(hashes)} blocks / {written} pieces "
               f"in {time.perf_counter() - t0:.1f}s")
    return hashes


async def run_bench(args, tmp):
    from garage_tpu.block.codec.ec import EcCodec
    from garage_tpu.block.durability import DurabilityScanner, ScanParams
    from garage_tpu.block.repair_plan import (
        PlanParams,
        RepairPlanner,
        _mesh_width,
    )
    from garage_tpu.ops.telemetry import resolved_platform
    from garage_tpu.utils.background import WorkerState

    k, m = args.k, args.m
    codec = EcCodec(k, m)
    if codec._tpu is None:
        raise RuntimeError("jax EC codec unavailable on this backend")
    apps, systems, managers = await make_cluster(tmp, k + m, k + m, codec)
    try:
        hashes = await populate(args, managers, args.victim)
        victim = managers[args.victim]
        assert not any(victim.local_pieces(h) for h in hashes[:32])

        disp0 = counter_sum("tpu_codec_dispatch_total", kernel="ec_reconstruct")
        mesh0 = counter_sum("tpu_mesh_engaged_total", kernel="ec_reconstruct")

        planner = RepairPlanner(
            victim,
            metadata_dir=os.path.join(tmp, f"meta{args.victim}"),
            params=PlanParams(
                tranquility=0,
                bytes_in_flight=args.bytes_in_flight,
                batch_blocks=args.batch,
            ),
        )
        # durability observatory (block/durability.py): the ledger's
        # time-to-redundancy-restored — the OPERATOR-visible "healed"
        # moment (zero locally-missing pieces confirmed by a scan pass),
        # not the planner's own done state
        scanner = DurabilityScanner(
            victim,
            params=ScanParams(tranquility=0, scan_batch=2048),
            planner_fn=lambda: planner,
        )
        before = await scanner.scan_pass()
        if before["localMissingPieces"] != len(hashes):
            raise RuntimeError(
                "ledger missed the wipe: "
                f"{before['localMissingPieces']}/{len(hashes)}"
            )
        t0 = time.perf_counter()
        scan_s = None
        for _ in range(1_000_000):
            res = await planner.work()
            state = res[0] if isinstance(res, tuple) else res
            if scan_s is None and planner.plan.state != "scanning":
                scan_s = time.perf_counter() - t0
                vlog(args, f"scan done in {scan_s:.1f}s, "
                           f"backlog={len(planner.plan.ledger)}")
            if state == WorkerState.DONE:
                break
        elapsed = time.perf_counter() - t0
        # ledger confirmation: scan until zero local missing pieces (one
        # pass at steady state; bounded so a broken repair fails loudly)
        restored_s = None
        for _ in range(5):
            after = await scanner.scan_pass()
            if after["localMissingPieces"] == 0:
                restored_s = time.perf_counter() - t0
                break
        if restored_s is None:
            raise RuntimeError(
                "ledger never confirmed restoration: "
                f"{after['localMissingPieces']} pieces still missing"
            )

        repaired = planner.plan.repaired
        restored = sum(1 for h in hashes if victim.local_pieces(h))
        if restored != len(hashes):
            raise RuntimeError(
                f"repair incomplete: {restored}/{len(hashes)} restored"
            )
        dispatches = int(
            counter_sum("tpu_codec_dispatch_total", kernel="ec_reconstruct")
            - disp0
        )
        mesh_engaged = int(
            counter_sum("tpu_mesh_engaged_total", kernel="ec_reconstruct")
            - mesh0
        )
        bps = len(hashes) / elapsed
        return {
            "metric": "repair_blocks_per_s",
            "value": round(bps, 1),
            "unit": "blocks/s",
            "repair_blocks_per_s": round(bps, 1),
            "blocks": len(hashes),
            "repaired": repaired,
            "dispatches": dispatches,
            "mesh_engaged": mesh_engaged,
            "rounds": planner.plan.rounds,
            "scan_s": round(scan_s or 0.0, 2),
            "elapsed_s": round(elapsed, 2),
            "time_to_redundancy_restored_s": round(restored_s, 2),
            "platform": resolved_platform(None),
            "devices": _mesh_width(victim),
            "k": k,
            "m": m,
            "block_bytes": args.block_bytes,
            "nodes": k + m,
            "batch": args.batch,
            "utc": time.strftime("%Y-%m-%d %H:%M:%S", time.gmtime()),
        }
    finally:
        for s in systems:
            await s.stop()
        for a in apps:
            await a.shutdown()


def main(argv=None):
    args = parse_args(argv)
    with tempfile.TemporaryDirectory(prefix="bench_repair_") as tmp:
        result = asyncio.run(run_bench(args, tmp))
    print(json.dumps(result))
    if args.artifact:
        # a CPU run must never DOWNGRADE a chip-banked artifact back to
        # loopback numbers
        try:
            with open(args.artifact) as f:
                old = json.load(f)
        except (OSError, json.JSONDecodeError):
            old = None
        if (
            old
            and old.get("platform") not in (None, "cpu", "none")
            and result["platform"] == "cpu"
        ):
            print(
                f"# keeping committed {args.artifact} "
                f"(platform={old.get('platform')}); cpu run not banked",
                file=sys.stderr,
            )
            return
        with open(args.artifact, "w") as f:
            json.dump(result, f, indent=1)
            f.write("\n")


if __name__ == "__main__":
    main()
