"""A fresh PUT's block_ref row rides the piece `Put` of each stripe holder
beyond the meta ring, and leaves as no copy message of its own.

On EC(4,2) over 6 nodes (rf 6, meta ring 3) every block has 3 holders
beyond the ring:
  - after a PUT each of them holds every block's row and count, applied
    from its piece `Put`: no copy message left, and
    `table_rows_carried_total` counts blocks x 3;
  - the arrival check each of those rows makes settles in memory, with no
    check row left to examine (both orders of row and piece:
    tests/test_resync_decisions.py);
  - a holder whose `Put` fails has no row until anti-entropy brings it;
  - mid-migration, a holder of the row that the fan-out sends no piece
    gets a copy message;
  - a DELETE's tombstones still leave as copies;
  - the holder stores and acknowledges the piece whatever becomes of the
    row: one that does not apply, or that finds no table, is dropped and
    counted.
"""

import asyncio
import os
import sys
import types

import pytest

sys.path.insert(0, os.path.dirname(__file__))

from test_block import make_block_cluster, stop_all  # noqa: E402
from test_ec_cluster import make_ec_cluster, stop_cluster  # noqa: E402
from test_table_background_batching import mk_block_ref_data  # noqa: E402

from garage_tpu.api.s3.api_server import S3ApiServer  # noqa: E402
from garage_tpu.api.s3.client import S3Client  # noqa: E402
from garage_tpu.block.codec.ec import EcCodec  # noqa: E402
from garage_tpu.model.s3.block_ref_table import BlockRef  # noqa: E402
from garage_tpu.rpc.layout.types import NodeRole, partition_of  # noqa: E402
from garage_tpu.table.queue import InsertQueueWorker  # noqa: E402
from garage_tpu.table.table import Table  # noqa: E402
from garage_tpu.utils.background import WorkerState  # noqa: E402
from garage_tpu.utils.data import blake2sum  # noqa: E402
from garage_tpu.utils.metrics import registry  # noqa: E402
from garage_tpu.utils.serde import pack  # noqa: E402
from garage_tpu.utils.time_util import now_msec  # noqa: E402

BLOCK = 8192
BUCKET = "rides"
LBL = (("table_name", "block_ref"),)


def ctr(name: str) -> float:
    return registry.counters.get((name, LBL), 0.0)


def settled() -> float:
    return registry.counters.get(("block_resync_settled_total", ()), 0.0)


def run(coro, limit=120.0):
    return asyncio.run(asyncio.wait_for(coro, limit))


async def until(cond, what: str, limit: float = 15.0) -> None:
    deadline = asyncio.get_running_loop().time() + limit
    while not cond():
        assert asyncio.get_running_loop().time() < deadline, what
        await asyncio.sleep(0.02)


async def s3_cluster(tmp_path, n=6, assign=None):
    garages = await make_ec_cluster(
        tmp_path, n=n, mode="ec:4:2", block_size=BLOCK, assign=assign, spawn=False
    )
    s3 = S3ApiServer(garages[0])
    await s3.start("127.0.0.1", 0)
    key = await garages[0].helper.create_key("rides-key")
    key.params().allow_create_bucket.update(True)
    await garages[0].key_table.insert(key)
    client = S3Client(f"http://127.0.0.1:{s3.runner.addresses[0][1]}", key.key_id, key.secret())
    await client.create_bucket(BUCKET)
    return garages, s3, client


def beyond_ring(garages, h: bytes) -> list:
    """The block's stripe holders beyond the meta ring, as garages."""
    by_id = {g.node_id: g for g in garages}
    return [by_id[n] for n in garages[0].block_ref_table.replication.background_nodes(h)]


def has_row(g, h: bytes) -> bool:
    return bool(g.block_ref_table.data.read_range(h, None, None, 10))


def arrival_rows(g, h: bytes) -> list[bytes]:
    """The block's arrival-check rows in the node's resync queue (dated
    2 x rpc_timeout ahead; the coordinator's own delay-0 entry is not one)."""
    rs = g.block_manager.resync
    horizon = now_msec() + rs.arrival_delay_ms() // 2
    return [k for k, _v in rs.queue.iter_range()
            if k[8:] == h and int.from_bytes(k[:8], "big") > horizon]


def one_block_body(pred) -> tuple[bytes, bytes]:
    """A one-block body whose hash satisfies `pred`."""
    for _ in range(500):
        body = os.urandom(BLOCK - 192)
        h = blake2sum(body)
        if pred(h):
            return body, h
    raise AssertionError("no block hash found for the placement asked")


def test_a_put_sends_no_copy_message_and_every_holder_beyond_the_ring_has_the_row(tmp_path):
    n_blocks = 4

    async def main():
        garages, s3, client = await s3_cluster(tmp_path)
        try:
            body = os.urandom(n_blocks * BLOCK)
            hashes = [blake2sum(body[i:i + BLOCK]) for i in range(0, len(body), BLOCK)]
            holders = {h: beyond_ring(garages, h) for h in hashes}
            assert all(len(gs) == 3 for gs in holders.values())
            # piece first, then row: each check the rows make finds the
            # piece already written, and settles with no queue row at all
            found_piece: list[bool] = []
            for g in garages:
                rs = g.block_manager.resync

                def check(h, tx, rs=rs, real=rs.queue_arrival_check, g=g):
                    if g in holders.get(h, ()):
                        found_piece.append(h in rs._written)
                    real(h, tx)

                rs.queue_arrival_check = check
            msgs0, carried0, settled0 = (
                ctr("table_background_replicate_total"), ctr("table_rows_carried_total"), settled())
            await client.put_object(BUCKET, "obj", body)
            want = 3 * n_blocks
            # the PUT returns at its piece quorum: the last Puts may land after it
            await until(lambda: ctr("table_rows_carried_total") - carried0 == want,
                        "every holder beyond the ring applies every block's row")
            assert ctr("table_background_replicate_total") == msgs0
            for h, gs in holders.items():
                for g in gs:
                    assert has_row(g, h) and g.block_manager.rc.get(h) == 1
                    assert h not in g.block_manager.resync._arrivals
                    assert arrival_rows(g, h) == []
            assert found_piece == [True] * want
            assert settled() - settled0 >= want
            assert await client.get_object(BUCKET, "obj") == body
        finally:
            await stop_cluster(garages, [s3], [client])

    run(main())


def test_a_holder_whose_put_fails_gets_the_row_from_anti_entropy(tmp_path):
    async def main():
        garages, s3, client = await s3_cluster(tmp_path)
        try:
            body, h = one_block_body(lambda h: True)
            victim = next(g for g in beyond_ring(garages, h) if g is not garages[0])

            async def refuse(*_a, **_kw):
                raise OSError("injected: disk full")

            victim.block_manager.write_block_local = refuse
            carried0, msgs0 = ctr("table_rows_carried_total"), ctr("table_background_replicate_total")
            await client.put_object(BUCKET, "obj", body)  # 5 of 6 pieces: the quorum holds
            await until(lambda: ctr("table_rows_carried_total") - carried0 == 2,
                        "the two other holders apply the row")
            await asyncio.sleep(0.1)
            assert not has_row(victim, h) and victim.block_manager.rc.get(h) == 0
            assert ctr("table_background_replicate_total") == msgs0  # lost, as a failed copy
            del victim.block_manager.write_block_local

            # anti-entropy from a ring holder: its Merkle tree brought up
            # to date, then the block's partition synced with the victim
            by_id = {g.node_id: g for g in garages}
            ring = by_id[garages[0].block_ref_table.replication.write_sets(h)[0][0]]
            t = ring.block_ref_table
            for key, vh in list(t.data.merkle_todo.iter_range()):
                t.merkle.update_item(key, vh)
                t.data.merkle_todo.remove(key)
            assert await t.syncer._sync_with(partition_of(h), victim.node_id) >= 1
            assert has_row(victim, h) and victim.block_manager.rc.get(h) == 1
            # its piece never came: the row's arrival check waits for resync
            assert len(arrival_rows(victim, h)) == 1
            assert await client.get_object(BUCKET, "obj") == body
        finally:
            await stop_cluster(garages, [s3], [client])

    run(main())


def test_mid_migration_a_holder_the_fan_out_sends_no_piece_gets_a_copy(tmp_path):
    """7 nodes, the layout moving from 6 of them to all 7, held open.  The
    coordinator's fan-out places the block by the old version alone (its
    read of the layout came before the new version): the new node holds
    the row beyond the ring but receives no piece, and gets the row in a
    copy message; the other holders beyond the ring get it in their Put."""

    async def main():
        garages, s3, client = await s3_cluster(tmp_path, n=7, assign=range(6))
        try:
            lm = garages[0].layout_manager
            new = garages[6]
            lm.stage_role(new.node_id, NodeRole(zone="dc6", capacity=10**12))
            lm.apply_staged()
            await until(lambda: all(g.layout_manager.digest() == lm.digest() for g in garages),
                        "the new layout reaches every node")
            active = [v for v in lm.history.versions if v.ring_assignment]
            assert len(active) == 2, "the migration is open"
            body, h = one_block_body(lambda h: new in beyond_ring(garages, h))
            holders = beyond_ring(garages, h)

            bm = garages[0].block_manager
            real = bm._ec_piece_targets
            old_layout = types.SimpleNamespace(versions=[active[0]])
            bm._ec_piece_targets = lambda hash32, _layout: real(hash32, old_layout)
            assert new.node_id not in bm.piece_nodes_of(h)

            msgs0, rows0, carried0 = (ctr("table_background_replicate_total"),
                                      ctr("table_background_rows_total"), ctr("table_rows_carried_total"))
            await client.put_object(BUCKET, "obj", body)
            await until(lambda: all(has_row(g, h) for g in holders)
                        and ctr("table_rows_carried_total") - carried0 == len(holders) - 1,
                        "every holder beyond the ring has the row")
            assert ctr("table_background_replicate_total") - msgs0 == 1
            assert ctr("table_background_rows_total") - rows0 == 1
            assert new.block_manager.rc.get(h) == 1
            assert new.block_manager.local_pieces(h) == {}
        finally:
            await stop_cluster(garages, [s3], [client])

    run(main())


async def drain_insert_queues(garages, table: str) -> None:
    """The insert-queue workers' rounds, run to the end on every node."""
    for g in garages:
        w = InsertQueueWorker(getattr(g, f"{table}_table"))
        while await w.work() == WorkerState.BUSY:
            pass


def test_a_deletes_tombstones_still_leave_as_copies(tmp_path):
    n_blocks = 2

    async def main():
        garages, s3, client = await s3_cluster(tmp_path)
        try:
            body = os.urandom(n_blocks * BLOCK)
            hashes = [blake2sum(body[i:i + BLOCK]) for i in range(0, len(body), BLOCK)]
            carried0 = ctr("table_rows_carried_total")
            await client.put_object(BUCKET, "obj", body)
            await until(lambda: ctr("table_rows_carried_total") - carried0 == 3 * n_blocks,
                        "the PUT's rows ride its pieces")
            msgs0, rows0 = ctr("table_background_replicate_total"), ctr("table_background_rows_total")
            await client.delete_object(BUCKET, "obj")
            await drain_insert_queues(garages, "version")
            await drain_insert_queues(garages, "block_ref")
            await until(lambda: all(g.block_manager.rc.get(h) == 0
                                    for h in hashes for g in beyond_ring(garages, h)),
                        "every holder beyond the ring sees the tombstones")
            assert ctr("table_background_replicate_total") > msgs0
            assert ctr("table_background_rows_total") - rows0 >= 3 * n_blocks
            assert ctr("table_rows_carried_total") == carried0 + 3 * n_blocks
        finally:
            await stop_cluster(garages, [s3], [client])

    run(main())


@pytest.mark.parametrize("engine", ["memory", "sqlite"])
def test_the_holder_applies_a_carried_row_and_drops_one_that_does_not_decode(tmp_path, engine):
    data, mgr = mk_block_ref_data(tmp_path, "holder", engine)
    holder = types.SimpleNamespace(_mlbl=LBL, data=data, schema=data.schema)
    h = b"H" * 32
    carried0, dropped0 = ctr("table_rows_carried_total"), ctr("table_background_dropped_total")
    Table.apply_carried(holder, [pack(BlockRef(h, b"v1").to_obj())])
    assert data.db.transactions == 1
    assert mgr.rc.get(h) == 1 and mgr.resync.arrival_checks == [h]
    assert ctr("table_rows_carried_total") - carried0 == 1
    stored = dict(data.store.iter_range())
    Table.apply_carried(holder, [b"\xc1 is no msgpack"])
    assert ctr("table_background_dropped_total") - dropped0 == 1
    assert ctr("table_rows_carried_total") - carried0 == 1
    assert dict(data.store.iter_range()) == stored


def test_a_holder_with_no_block_ref_table_stores_the_piece_and_drops_the_row(tmp_path):
    async def main():
        apps, systems, managers = await make_block_cluster(
            tmp_path, n=3, codec=EcCodec(2, 1, tpu_enable=False))
        try:
            data = os.urandom(20_000)
            h = blake2sum(data)
            riders = managers[0].piece_nodes_of(h)
            assert len(riders) == 3
            dropped0 = ctr("table_background_dropped_total")
            await managers[0].rpc_put_block(h, data, b"row", riders)
            await until(lambda: ctr("table_background_dropped_total") - dropped0 == 3,
                        "every holder drops the row")
            assert all(m.local_pieces(h) for m in managers)
            assert await managers[1].rpc_get_block(h) == data
        finally:
            await stop_all(apps, systems)

    run(main())
