"""Repair plane: cluster-wide batched-reconstruction planner
(garage_tpu/block/repair_plan.py).

Covers the ISSUE 4 acceptance points on the CPU mesh (8 virtual devices,
conftest): mesh engagement metrics advance when the planner drives a
>= 2x-devices batch through bulk_reconstruct; the plan is restart-safe
(checkpointed ledger resumes without rescanning); tranquility and the
bytes-in-flight budget are respected; breaker-open peers defer stripes
instead of stalling the batch; and remote-only degradation is nudged to
the owning node's resync queue.
"""

import asyncio
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(__file__))

from test_block import make_block_cluster, stop_all  # noqa: E402

from garage_tpu.block.codec.ec import EcCodec  # noqa: E402
from garage_tpu.block.repair_plan import (  # noqa: E402
    PlanParams,
    RepairPlanner,
    classify,
)
from garage_tpu.utils.background import WorkerState  # noqa: E402
from garage_tpu.utils.data import blake2sum  # noqa: E402
from garage_tpu.utils.metrics import registry  # noqa: E402


def run(coro):
    return asyncio.run(coro)


def counter_sum(name, **want_labels):
    """Sum a registry counter over all labelsets matching want_labels."""
    total = 0.0
    for (n, labels), v in registry.counters.items():
        if n != name:
            continue
        d = dict(labels)
        if all(d.get(k) == v2 for k, v2 in want_labels.items()):
            total += v
    return total


def hist_count(name, **want_labels):
    total = 0
    for (n, labels), (cnt, _s, _b) in registry.durations.items():
        if n != name:
            continue
        d = dict(labels)
        if all(d.get(k) == v2 for k, v2 in want_labels.items()):
            total += cnt
    return total


async def drive(planner, max_iters=500):
    """Run the planner worker loop to completion (ignoring throttle
    sleeps — admission control is asserted separately)."""
    for _ in range(max_iters):
        res = await planner.work()
        state = res[0] if isinstance(res, tuple) else res
        if state == WorkerState.DONE:
            return
    raise AssertionError("planner did not finish")


async def populate(managers, n_blocks, block_bytes=4096, seed=0):
    """Write n_blocks through the EC put path and reference them on every
    node's rc (as the block_ref table hook would)."""
    import random

    rng = random.Random(seed)
    blocks = {}
    for _ in range(n_blocks):
        data = rng.randbytes(block_bytes)
        h = blake2sum(data)
        blocks[h] = data
        await managers[0].rpc_put_block(h, data)
    await asyncio.sleep(0.3)  # leftover background piece sends land
    for mgr in managers:
        hashes = list(blocks)
        mgr.db.transaction(
            lambda tx, hs=hashes, m=mgr: [m.rc.incr(tx, h) for h in hs]
            and None
        )
    return blocks


def wipe_local_pieces(mgr, hashes):
    lost = set()
    for h in hashes:
        for _pi, (path, _c) in mgr.local_pieces(h).items():
            os.remove(path)
            lost.add(h)
    return lost


def test_classify_urgency():
    # EC(8,3): 3 missing = critical (next loss is data loss), 2 = high,
    # 1 = low, 4 = lost (unrepairable)
    assert classify(4, 3) == "lost"
    assert classify(3, 3) == "critical"
    assert classify(2, 3) == "high"
    assert classify(1, 3) == "low"
    # EC(2,1): the single-parity stripe is always critical when degraded
    assert classify(1, 1) == "critical"


def test_planner_end_to_end_mesh_engaged(tmp_path):
    """A one-node piece wipe is fully repaired by the planner in a few
    coalesced rounds; the mesh-engagement counter and the dispatch
    batch-size histogram advance (ISSUE satellite: tests the >= 2x
    devices fan-out through bulk_reconstruct)."""

    async def main():
        codec = EcCodec(2, 1)
        if codec._tpu is None:
            pytest.skip("jax codec unavailable")
        apps, systems, managers = await make_block_cluster(
            tmp_path, codec=codec
        )
        try:
            blocks = await populate(managers, 64)
            vm = managers[1]
            lost = wipe_local_pieces(vm, blocks)
            assert len(lost) >= 2 * 8, "cluster placed too few pieces on vm"

            mesh0 = counter_sum("tpu_mesh_engaged_total")
            disp0 = hist_count("tpu_codec_batch_size", kernel="ec_reconstruct")
            blocks0 = counter_sum("repair_plan_blocks_total")
            rounds0 = counter_sum("repair_plan_rounds_total")
            bs0 = hist_count("repair_plan_batch_size")

            planner = RepairPlanner(
                vm,
                metadata_dir=str(tmp_path / "plan-meta"),
                params=PlanParams(tranquility=0, batch_blocks=64),
            )
            await drive(planner)

            assert planner.plan.state == "done"
            assert planner.plan.repaired == len(lost)
            for h in lost:
                assert vm.local_pieces(h), f"{h.hex()[:12]} not restored"
            # every block still decodes to its original content
            for h, data in list(blocks.items())[:8]:
                assert await vm.rpc_get_block(h) == data

            # mesh engagement: 64 stripes coalesced into per-pattern
            # groups of ~21 >= 2 x 8 virtual devices
            assert counter_sum("tpu_mesh_engaged_total") > mesh0
            assert (
                hist_count("tpu_codec_batch_size", kernel="ec_reconstruct")
                > disp0
            )
            assert (
                counter_sum("repair_plan_blocks_total") - blocks0
                == len(lost)
            )
            rounds = counter_sum("repair_plan_rounds_total") - rounds0
            assert 1 <= rounds <= 3, rounds  # coalesced, not per-block
            assert hist_count("repair_plan_batch_size") > bs0
            # planner gauges unregister at completion (transient workers
            # must not accumulate dead families — metrics-lint satellite)
            assert planner._gauge_keys == []
        finally:
            await stop_all(apps, systems)

    run(main())


def test_planner_checkpoint_resumes_without_rescan(tmp_path):
    """Kill the planner after the scan phase: a fresh instance resumes
    the checkpointed ledger (no rescan) and completes the repair."""

    async def main():
        codec = EcCodec(2, 1)
        if codec._tpu is None:
            pytest.skip("jax codec unavailable")
        apps, systems, managers = await make_block_cluster(
            tmp_path, codec=codec
        )
        try:
            blocks = await populate(managers, 24)
            vm = managers[1]
            lost = wipe_local_pieces(vm, blocks)
            meta = str(tmp_path / "plan-meta")

            p1 = RepairPlanner(
                vm, metadata_dir=meta, params=PlanParams(tranquility=0)
            )
            assert not p1.resumed
            # drive only the scan phase, then "crash"
            for _ in range(200):
                await p1.work()
                if p1.plan.state == "repairing":
                    break
            assert p1.plan.state == "repairing"
            assert p1.plan.cursor is None  # scan complete, checkpointed
            backlog = len(p1.plan.ledger)
            assert backlog == len(lost)
            assert RepairPlanner.resumable(meta)

            p2 = RepairPlanner(
                vm, metadata_dir=meta, params=PlanParams(tranquility=0)
            )
            assert p2.resumed, "checkpoint was not resumed"
            assert p2.plan.state == "repairing"
            assert len(p2.plan.ledger) == backlog
            assert p2.plan.scanned == p1.plan.scanned  # no rescan
            await drive(p2)
            assert p2.plan.repaired == len(lost)
            assert not RepairPlanner.resumable(meta)  # done plans don't resume

            # a third instance starts a FRESH plan (nothing left to do)
            p3 = RepairPlanner(
                vm, metadata_dir=meta, params=PlanParams(tranquility=0)
            )
            assert not p3.resumed
            await drive(p3)
            assert p3.plan.repaired == 0 and p3.plan.state == "done"
        finally:
            await stop_all(apps, systems)

    run(main())


def test_planner_bytes_budget_and_tranquility(tmp_path):
    """Admission control: a tiny bytes-in-flight budget splits the plan
    into many small rounds, and tranquility > 0 yields THROTTLED states
    with a positive delay."""

    async def main():
        codec = EcCodec(2, 1)
        if codec._tpu is None:
            pytest.skip("jax codec unavailable")
        apps, systems, managers = await make_block_cluster(
            tmp_path, codec=codec
        )
        try:
            blocks = await populate(managers, 24, block_bytes=4096)
            vm = managers[1]
            lost = wipe_local_pieces(vm, blocks)
            # piece_len(4096) with k=2 is 2048; k * plen = 4096 bytes per
            # stripe -> a 4-stripe budget
            params = PlanParams(
                tranquility=3, bytes_in_flight=4 * 4096, batch_blocks=None
            )
            planner = RepairPlanner(vm, metadata_dir=None, params=params)
            throttled_with_delay = 0
            for _ in range(500):
                res = await planner.work()
                state, delay = res if isinstance(res, tuple) else (res, 0.0)
                if state == WorkerState.DONE:
                    break
                if state == WorkerState.THROTTLED and delay > 0:
                    throttled_with_delay += 1
            assert planner.plan.repaired == len(lost)
            # budget of 4 stripes/round over len(lost) stripes
            assert planner.plan.rounds >= (len(lost) + 3) // 4
            assert throttled_with_delay > 0, "tranquility never throttled"
        finally:
            await stop_all(apps, systems)

    run(main())


def test_planner_defers_open_breaker_peers(tmp_path):
    """Stripes whose survivors sit behind an open circuit breaker are
    deferred (batch widens past them / retries later) instead of
    stalling the round; once the breaker closes the plan completes."""

    async def main():
        from garage_tpu.rpc.peer_health import CLOSED, OPEN

        codec = EcCodec(2, 1)
        if codec._tpu is None:
            pytest.skip("jax codec unavailable")
        apps, systems, managers = await make_block_cluster(
            tmp_path, codec=codec
        )
        try:
            blocks = await populate(managers, 12)
            vm = managers[1]
            lost = wipe_local_pieces(vm, blocks)
            ph = vm.helper.health
            peers = [m.system.id for m in managers if m is not vm]
            for nid in peers:
                p = ph._peer(nid)
                p.state = OPEN
                p.opened_at = ph.clock() + 3600  # no half-open for a while

            params = PlanParams(tranquility=0)
            planner = RepairPlanner(vm, metadata_dir=None, params=params)
            # scan: peers unreachable for Inv, their pieces conservatively
            # count missing; local ranks still enter the ledger
            deferred0 = counter_sum("repair_plan_deferred_total")
            for _ in range(50):
                await planner.work()
                if planner.plan.state == "repairing":
                    break
            assert planner.plan.state == "repairing"
            assert len(planner.plan.ledger) == len(lost)

            # repair rounds: every stripe deferred, nothing dispatched,
            # worker backs off instead of erroring
            res = await planner.work()
            state, delay = res if isinstance(res, tuple) else (res, 0.0)
            assert state == WorkerState.THROTTLED and delay > 0
            assert len(planner.plan.ledger) == len(lost)  # nothing dropped
            assert counter_sum("repair_plan_deferred_total") > deferred0

            for nid in peers:  # the peers heal
                ph._peer(nid).state = CLOSED
                ph._peer(nid).consecutive_failures = 0
            await drive(planner)
            assert planner.plan.repaired == len(lost)
        finally:
            await stop_all(apps, systems)

    run(main())


def test_planner_nudges_remote_holders(tmp_path):
    """Degradation whose missing ranks live on ANOTHER node is not
    repairable locally: the planner queues the hashes on the owning
    node's resync (bulk Queue RPC) and keeps its own ledger clean."""

    async def main():
        codec = EcCodec(2, 1)
        if codec._tpu is None:
            pytest.skip("jax codec unavailable")
        apps, systems, managers = await make_block_cluster(
            tmp_path, codec=codec
        )
        try:
            blocks = await populate(managers, 16)
            victim = managers[2]
            lost = wipe_local_pieces(victim, blocks)
            planner_node = managers[0]
            # planner node still holds its own pieces: nothing local
            wiped_own = [
                h for h in blocks if not planner_node.local_pieces(h)
            ]
            assert not wiped_own

            q0 = victim.resync.queue_len()
            planner = RepairPlanner(
                planner_node, metadata_dir=None,
                params=PlanParams(tranquility=0),
            )
            await drive(planner)
            assert planner.plan.repaired == 0
            assert planner.plan.nudged >= len(lost)
            assert victim.resync.queue_len() >= q0 + len(lost)
        finally:
            await stop_all(apps, systems)

    run(main())


def test_garage_launch_status_cancel_and_admin_ops(tmp_path):
    """The operator surface: Garage.launch_repair_plan / repair_plan
    status + cancel through the admin RPC handler, replica-mode refusal,
    and the `repair plan` admin op."""

    async def main():
        from test_ec_cluster import make_ec_cluster, stop_cluster

        from garage_tpu.cli.admin_rpc import AdminRpcHandler

        garages = await make_ec_cluster(tmp_path, mode="ec:2:1", spawn=True)
        try:
            g = garages[0]
            adm = AdminRpcHandler(g)
            st = await adm.op_repair({"what": "plan", "cmd": "status"})
            assert st["running"] is False and st["resumable"] is False
            assert st["params"]["tranquility"] == g.repair_params.tranquility

            st = await adm.op_repair({"what": "plan", "cmd": "launch"})
            assert st["running"] is True
            with pytest.raises(ValueError, match="already running"):
                g.launch_repair_plan()
            # healthy cluster: the plan finds nothing and finishes
            for _ in range(100):
                await asyncio.sleep(0.05)
                if g.repair_planner.finished:
                    break
            assert g.repair_planner.finished
            assert g.repair_planner.plan.state == "done"
            st = await adm.op_repair({"what": "plan", "cmd": "status"})
            assert st["running"] is False and st["state"] == "done"
            with pytest.raises(ValueError, match="no repair plan"):
                await adm.op_repair({"what": "plan", "cmd": "cancel"})

            # cancel path: relaunch then cancel before completion
            p = g.launch_repair_plan(fresh=True)
            p.cmd_cancel()
            for _ in range(100):
                await asyncio.sleep(0.05)
                if p.finished:
                    break
            assert p.finished and p.plan.state in ("cancelled", "done")
        finally:
            await stop_cluster(garages)

    run(main())


def test_resumable_tolerates_corrupt_checkpoint(tmp_path):
    """A corrupt / foreign-version checkpoint file answers resumable() =
    False (auto-resume runs inside daemon boot — one bad auxiliary file
    must not brick startup) and a new planner starts fresh."""
    meta = str(tmp_path)
    with open(os.path.join(meta, "repair_plan"), "wb") as f:
        f.write(b"NOT A CHECKPOINT")
    assert RepairPlanner.resumable(meta) is False


def test_replica_mode_refuses_planner(tmp_path):
    from garage_tpu.block.codec import ReplicaCodec

    class _Mgr:
        codec = ReplicaCodec()

    with pytest.raises(ValueError, match="erasure-coded"):
        RepairPlanner(_Mgr())
