"""Block store: codecs, multi-drive layout, refcounts, manager + resync."""

import asyncio
import os
import random

import pytest

from garage_tpu.block.codec import get_codec
from garage_tpu.block.codec.ec import EcCodec
from garage_tpu.block.layout import DRIVE_NPART, DataLayout
from garage_tpu.block.manager import BlockManager
from garage_tpu.block.rc import BlockRc
from garage_tpu.db import open_db
from garage_tpu.net import NetApp
from garage_tpu.net.handshake import gen_node_key
from garage_tpu.rpc.layout.manager import LayoutManager
from garage_tpu.rpc.layout.types import NodeRole
from garage_tpu.rpc.replication_mode import ReplicationMode
from garage_tpu.rpc.rpc_helper import RpcHelper
from garage_tpu.rpc.system import System
from garage_tpu.utils.config import DataDir
from garage_tpu.utils.data import blake2sum

NETKEY = b"B" * 32


def run(coro):
    return asyncio.run(coro)


# --- codec -------------------------------------------------------------------


def test_replica_codec():
    c = get_codec(None)
    b = os.urandom(1000)
    assert c.encode(b) == [b]
    assert c.decode({0: b}, len(b)) == b


@pytest.mark.parametrize("k,m", [(4, 2), (8, 3)])
def test_ec_codec_roundtrip(k, m):
    c = EcCodec(k, m, tpu_enable=False)
    rng = random.Random(1)
    for blen in [1, 100, 4096, 70_001]:
        block = rng.randbytes(blen)
        pieces = c.encode(block)
        assert len(pieces) == k + m
        # decode from data shards only
        assert c.decode({i: pieces[i] for i in range(k)}, blen) == block
        # decode after losing m arbitrary pieces
        lost = sorted(rng.sample(range(k + m), m))
        have = {i: pieces[i] for i in range(k + m) if i not in lost}
        assert c.decode(have, blen) == block
        # reconstruct the lost pieces exactly
        rec = c.reconstruct_pieces(have, lost, blen)
        for i in lost:
            assert rec[i] == pieces[i]


def test_ec_codec_batched_matches_scalar():
    c = EcCodec(4, 2)  # TPU/jax path enabled (CPU backend under tests)
    rng = random.Random(2)
    blocks = [rng.randbytes(2048) for _ in range(10)]
    batched = [p for p, _h in c.encode_batch_hashed(blocks, impl="xla")]
    for b, pieces in zip(blocks, batched):
        assert pieces == c.encode(b)
    # batched reconstruction, mixed erasure patterns
    batches = []
    for i, b in enumerate(blocks):
        pieces = dict(enumerate(batched[i]))
        lost = [i % 6, (i + 1) % 6]
        for l in set(lost):
            pieces.pop(l)
        batches.append((pieces, sorted(set(lost)), len(b)))
    recs = c.reconstruct_batch(batches)
    for i, rec in enumerate(recs):
        for l, data in rec.items():
            assert data == batched[i][l], f"block {i} piece {l}"


# --- data layout -------------------------------------------------------------


@pytest.mark.parametrize(
    "op,n,impl,path",
    [
        # the fused encode: the device at any batch length once `impl`
        # resolves to xla; `auto` on a host backend keeps it on the host
        ("encode", 1, "xla", "tpu"),
        ("encode", 1, "auto", "numpy"),
        # degraded reads and the repair plane: the device from
        # TPU_BATCH_MIN entries up (ROADMAP D1 changes this knowingly)
        ("decode", 7, "xla", "numpy"),
        ("decode", 8, "xla", "tpu"),
        ("reconstruct", 7, None, "numpy"),
        ("reconstruct", 8, None, "tpu"),
    ],
)
def test_codec_path_by_op_and_batch(op, n, impl, path):
    """Which path served a batched call, as `block_codec_blocks_total`
    counted it (the benchmark's `device_block_share_pct` reads these
    labels): one row per outcome of `EcCodec._on_device`."""
    from garage_tpu.utils.metrics import registry

    c = EcCodec(2, 1)  # device codec built, on the tests' CPU backend
    rng = random.Random(3)
    blocks = [rng.randbytes(1024) for _ in range(n)]
    pieces = [c.encode(b) for b in blocks]
    # one data shard lost in every entry: a real decode / rebuild
    have = [{1: p[1], 2: p[2]} for p in pieces]
    counted = "encode" if op == "encode" else "reconstruct"

    def count(p):
        key = ("block_codec_blocks_total", (("op", counted), ("path", p)))
        return registry.counters.get(key, 0)

    before = {p: count(p) for p in ("tpu", "numpy")}
    if op == "encode":
        out = c.encode_batch_hashed(blocks, impl=impl)
        assert [p for p, _h in out] == pieces
    elif op == "decode":
        out = c.decode_batch([(h, 1024) for h in have], impl=impl)
        assert out == blocks
    else:
        out = c.reconstruct_batch([(h, [0], 1024) for h in have])
        assert [r[0] for r in out] == [p[0] for p in pieces]
    other = "numpy" if path == "tpu" else "tpu"
    assert count(path) - before[path] == n
    assert count(other) == before[other]


def test_data_layout_allocation(tmp_path):
    dirs = [
        DataDir(str(tmp_path / "d1"), capacity=100),
        DataDir(str(tmp_path / "d2"), capacity=300),
    ]
    lay = DataLayout.initial(dirs)
    counts = [lay.primary.count(i) for i in range(2)]
    assert counts[0] + counts[1] == DRIVE_NPART
    assert abs(counts[0] - DRIVE_NPART // 4) <= 1  # ∝ capacity
    lay.ensure_markers()
    lay.check_markers()

    # add a drive: minimal moves, old location kept as secondary
    dirs2 = dirs + [DataDir(str(tmp_path / "d3"), capacity=400)]
    lay2 = lay.update(dirs2)
    moved = sum(
        1
        for sp in range(DRIVE_NPART)
        if lay2.dirs[lay2.primary[sp]] != lay.dirs[lay.primary[sp]]
    )
    assert moved == lay2.primary.count(2)  # only moves onto the new drive
    for sp in range(DRIVE_NPART):
        if lay2.primary[sp] == 2:
            assert lay2.secondary[sp], "moved sub-partition lost its old location"

    # roundtrip
    lay3 = DataLayout.decode(lay2.encode())
    assert lay3.primary == lay2.primary


def test_rc_lifecycle(tmp_path, monkeypatch):
    import garage_tpu.block.rc as rc_mod

    db = open_db(str(tmp_path), engine="memory")
    rc = BlockRc(db)
    h = blake2sum(b"block")
    assert rc.get(h) == 0 and rc.is_deletable(h)
    db.transaction(lambda tx: rc.incr(tx, h))
    db.transaction(lambda tx: rc.incr(tx, h))
    assert rc.get(h) == 2 and rc.is_needed(h)
    db.transaction(lambda tx: rc.decr(tx, h))
    assert rc.get(h) == 1
    db.transaction(lambda tx: rc.decr(tx, h))
    assert rc.get(h) == 0 and not rc.is_needed(h)
    assert not rc.is_deletable(h)  # 10-min delay protects re-references
    monkeypatch.setattr(rc_mod, "BLOCK_GC_DELAY_MS", -1)
    db.transaction(lambda tx: rc.incr(tx, h))
    db.transaction(lambda tx: rc.decr(tx, h))
    assert rc.is_deletable(h)
    # re-reference after rc hit zero: block is needed again
    db.transaction(lambda tx: rc.incr(tx, h))
    assert rc.is_needed(h) and rc.get(h) == 1


# --- manager cluster ---------------------------------------------------------


async def make_block_cluster(tmp_path, n=3, rf=3, codec=None):
    apps, systems, managers = [], [], []
    for i in range(n):
        app = NetApp(NETKEY, gen_node_key())
        await app.listen("127.0.0.1", 0)
        apps.append(app)
    for i, app in enumerate(apps):
        peers = [(a.id, a.bind_addr) for a in apps if a is not app]
        lm = LayoutManager(app.id, rf)
        sysd = System(app, lm, ReplicationMode(rf), bootstrap=peers)
        await sysd.start()
        systems.append(sysd)
    for _ in range(100):
        await asyncio.sleep(0.05)
        if all(len(s.peering.connected_peers()) == n - 1 for s in systems):
            break
    lm0 = systems[0].layout_manager
    for app in apps:
        lm0.stage_role(app.id, NodeRole(zone="dc1", capacity=10**12))
    lm0.apply_staged()
    for _ in range(100):
        await asyncio.sleep(0.05)
        if all(s.layout_manager.digest() == lm0.digest() for s in systems):
            break
    for i, (app, sysd) in enumerate(zip(apps, systems)):
        meta = str(tmp_path / f"meta{i}")
        os.makedirs(meta, exist_ok=True)
        db = open_db(meta, engine="memory")
        mgr = BlockManager(
            sysd,
            RpcHelper(app.id, sysd.peering),
            db,
            [DataDir(str(tmp_path / f"data{i}"))],
            meta,
            codec=codec,
        )
        managers.append(mgr)
    return apps, systems, managers


async def stop_all(apps, systems):
    for s in systems:
        await s.stop()
    for a in apps:
        await a.shutdown()


def test_block_put_get(tmp_path):
    async def main():
        apps, systems, managers = await make_block_cluster(tmp_path)
        try:
            data = os.urandom(100_000)
            h = blake2sum(data)
            await managers[0].rpc_put_block(h, data)
            await asyncio.sleep(0.2)  # leftover background writes land
            stored = [m.has_block(h) for m in managers]
            assert all(stored), f"replicas missing block: {stored}"
            # read from a node (local) and via a fresh hash path (remote)
            got = await managers[1].rpc_get_block(h)
            assert got == data
            # remote fetch: delete the local copy on node2, read again
            path, _ = managers[2].find_block_file(h)
            os.remove(path)
            got2 = await managers[2].rpc_get_block(h)
            assert got2 == data
        finally:
            await stop_all(apps, systems)

    run(main())


def test_ram_budget_bounds_concurrent_puts(tmp_path):
    """The block_ram_buffer_max budget serializes payload buffers: total
    reserved bytes never exceed the limit, and everything completes."""

    async def main():
        from garage_tpu.block.manager import ByteBudget

        budget = ByteBudget(100_000)
        peak = 0
        done = 0

        async def one(n):
            nonlocal peak, done
            async with budget.reserve(40_000):
                peak = max(peak, budget.used)
                await asyncio.sleep(0.01)
                done += 1

        await asyncio.gather(*[one(i) for i in range(10)])
        assert done == 10
        assert peak <= 100_000, f"budget exceeded: {peak}"
        assert budget.used == 0

        # an oversized single item is clamped, not deadlocked
        async with budget.reserve(10**9):
            assert budget.used == budget.limit
        assert budget.used == 0

    run(main())


def test_put_payloads_ride_streams(tmp_path):
    """Block payloads must travel as attached streams, not msgpack bodies
    (zero-copy path): the Put body carries no payload element."""

    async def main():
        apps, systems, managers = await make_block_cluster(tmp_path)
        try:
            seen_bodies = []
            orig = managers[1].endpoint.handler

            async def spy(from_id, req):
                seen_bodies.append(req.body)
                return await orig(from_id, req)

            managers[1].endpoint.set_handler(spy)
            data = os.urandom(80_000)
            h = blake2sum(data)
            await managers[0].rpc_put_block(h, data)
            await asyncio.sleep(0.2)
            puts = [b for b in seen_bodies if b[0] == "Put"]
            assert puts, "no Put seen by replica"
            assert all(len(b) == 3 for b in puts), (
                "Put body carries an inline payload; expected streamed"
            )
            assert managers[1].has_block(h)
            # Get responses stream too (and still verify end-to-end)
            got = await managers[0].rpc_get_block(h)
            assert got == data
        finally:
            await stop_all(apps, systems)

    run(main())


def test_block_corruption_detected(tmp_path):
    async def main():
        apps, systems, managers = await make_block_cluster(tmp_path)
        try:
            data = b"A" * 50_000  # compressible: stored as .zst
            h = blake2sum(data)
            await managers[0].rpc_put_block(h, data)
            await asyncio.sleep(0.2)
            path, compressed = managers[0].find_block_file(h)
            # corrupt the stored file (valid zstd frame, wrong content)
            import zstandard

            evil = zstandard.compress(b"B" * 50_000, 1) if compressed else b"B" * 50_000
            with open(path, "wb") as f:
                f.write(evil)
            out = await managers[0].read_block_local(h)
            assert out is None, "corrupted block served!"
            assert os.path.exists(path + ".corrupted")
            assert managers[0].resync.queue_len() >= 1
            # rpc_get_block falls back to a healthy peer
            got = await managers[0].rpc_get_block(h)
            assert got == data
        finally:
            await stop_all(apps, systems)

    run(main())


def test_resync_fetch_and_delete(tmp_path, monkeypatch):
    async def main():
        import garage_tpu.block.rc as rc_mod

        monkeypatch.setattr(rc_mod, "BLOCK_GC_DELAY_MS", -1)
        apps, systems, managers = await make_block_cluster(tmp_path)
        try:
            data = os.urandom(40_000)
            h = blake2sum(data)
            # write only to nodes 0,1 (simulate node2 down during write)
            for m in managers[:2]:
                stored, comp = m._maybe_compress(data)
                await m.write_block_local(h, stored, comp)
            for m in managers:
                m.db.transaction(lambda tx: m.rc.incr(tx, h))
            assert not managers[2].has_block(h)
            # resync on node2 fetches the block
            managers[2].resync.queue_block(h)
            assert await managers[2].resync.resync_iter()
            assert managers[2].has_block(h)
            assert await managers[2].rpc_get_block(h) == data

            # now drop all references: resync deletes the local file after
            # confirming no storage node needs it
            for m in managers:
                m.db.transaction(lambda tx: m.rc.decr(tx, h))
            managers[2].resync.queue_block(h)
            assert await managers[2].resync.resync_iter()
            assert not managers[2].has_block(h)
        finally:
            await stop_all(apps, systems)

    run(main())


def test_ec_block_put_distinct_pieces(tmp_path):
    """EC(2,1) on a 3-node cluster: each node stores a distinct piece and
    the block reconstructs from any 2 pieces."""

    async def main():
        codecs = [EcCodec(2, 1, tpu_enable=False) for _ in range(3)]
        apps, systems, managers = await make_block_cluster(
            tmp_path, codec=codecs[0]
        )
        for m, c in zip(managers, codecs):
            m.codec = c
        try:
            data = os.urandom(50_000)
            h = blake2sum(data)
            await managers[0].rpc_put_block(h, data)
            await asyncio.sleep(0.2)
            # each node holds exactly one piece; together all 3 distinct
            from garage_tpu.block.manager import unwrap_piece

            held = {}
            for i, m in enumerate(managers):
                pieces = m.local_pieces(h)
                assert len(pieces) == 1, f"node {i} holds {len(pieces)} pieces"
                for p, (path, _c) in pieces.items():
                    blen, piece = unwrap_piece(open(path, "rb").read())
                    assert blen == len(data)
                    held[p] = piece
            assert set(held.keys()) == {0, 1, 2}
            c = codecs[0]
            assert c.decode({0: held[0], 1: held[1]}, len(data)) == data
            assert c.decode({1: held[1], 2: held[2]}, len(data)) == data
        finally:
            await stop_all(apps, systems)

    run(main())


def test_ec_read_and_reconstruct(tmp_path):
    """EC(2,1): reads decode from k pieces, survive a lost piece, and
    resync rebuilds a node's missing piece from the survivors."""

    async def main():
        codec = EcCodec(2, 1, tpu_enable=False)
        apps, systems, managers = await make_block_cluster(tmp_path, codec=codec)
        for m in managers:
            m.codec = EcCodec(2, 1, tpu_enable=False)
        try:
            data = os.urandom(37_123)  # deliberately unaligned length
            h = blake2sum(data)
            await managers[0].rpc_put_block(h, data)
            await asyncio.sleep(0.2)
            # normal read decodes exactly
            got = await managers[0].rpc_get_block(h)
            assert got == data
            # destroy one data piece: read must still succeed via parity
            victim = None
            for m in managers:
                pieces = m.local_pieces(h)
                if 0 in pieces:
                    victim = (m, pieces[0][0])
                    os.remove(pieces[0][0])
                    break
            assert victim is not None
            got2 = await managers[2].rpc_get_block(h)
            assert got2 == data
            # resync on the victim reconstructs its piece
            vm = victim[0]
            for m in managers:
                m.db.transaction(lambda tx: m.rc.incr(tx, h))
            vm.resync.queue_block(h)
            assert await vm.resync.resync_iter()
            assert vm.local_pieces(h), "piece not reconstructed"
            got3 = await vm.rpc_get_block(h)
            assert got3 == data
        finally:
            await stop_all(apps, systems)

    run(main())


def test_ec_bulk_reconstruct(tmp_path):
    """Batched repair: many lost pieces rebuilt in one grouped codec call
    (the TPU dispatch path; numpy codec here for speed)."""

    async def main():
        codec = EcCodec(2, 1, tpu_enable=False)
        apps, systems, managers = await make_block_cluster(tmp_path, codec=codec)
        for m in managers:
            m.codec = EcCodec(2, 1, tpu_enable=False)
        try:
            blocks = {}
            for i in range(12):
                data = os.urandom(8_000 + i)
                h = blake2sum(data)
                blocks[h] = data
                await managers[0].rpc_put_block(h, data)
            await asyncio.sleep(0.3)
            # reference the blocks (bulk repair refuses deleted blocks)
            for m in managers:
                for h in blocks:
                    m.db.transaction(lambda tx, h=h: m.rc.incr(tx, h))
            # wipe ALL of node1's pieces
            vm = managers[1]
            lost = []
            for h in blocks:
                for pi, (path, _c) in vm.local_pieces(h).items():
                    os.remove(path)
                    lost.append(h)
            assert lost
            n = await vm.bulk_reconstruct(list(blocks.keys()))
            assert n == len(set(lost)), f"rebuilt {n} != lost {len(set(lost))}"
            for h, data in blocks.items():
                assert await vm.rpc_get_block(h) == data
        finally:
            await stop_all(apps, systems)

    run(main())


def test_ec_piece_gc(tmp_path, monkeypatch):
    """Deleted blocks must have ALL their EC pieces reclaimed by resync,
    whatever rank the local piece has."""

    async def main():
        import garage_tpu.block.rc as rc_mod

        monkeypatch.setattr(rc_mod, "BLOCK_GC_DELAY_MS", -1)
        codec = EcCodec(2, 1, tpu_enable=False)
        apps, systems, managers = await make_block_cluster(tmp_path, codec=codec)
        for m in managers:
            m.codec = EcCodec(2, 1, tpu_enable=False)
        try:
            data = os.urandom(20_000)
            h = blake2sum(data)
            await managers[0].rpc_put_block(h, data)
            await asyncio.sleep(0.2)
            for m in managers:
                m.db.transaction(lambda tx: m.rc.incr(tx, h))
            assert all(m.local_pieces(h) for m in managers)
            # drop the reference everywhere, run resync on every node
            for m in managers:
                m.db.transaction(lambda tx: m.rc.decr(tx, h))
            for m in managers:
                m.resync.queue_block(h)
                assert await m.resync.resync_iter()
            leftover = [i for i, m in enumerate(managers) if m.local_pieces(h)]
            assert not leftover, f"nodes {leftover} kept pieces of a deleted block"
        finally:
            await stop_all(apps, systems)

    run(main())


def test_ec_piece_scrub_detects_corruption(tmp_path):
    """Per-piece BLAKE3 headers let scrub catch EC shard bit-rot (batched
    verification path) and heal via reconstruction."""

    async def main():
        from garage_tpu.block.repair import ScrubWorker

        codec = EcCodec(2, 1, tpu_enable=False)
        apps, systems, managers = await make_block_cluster(tmp_path, codec=codec)
        for m in managers:
            m.codec = EcCodec(2, 1, tpu_enable=False)
        try:
            data = os.urandom(25_000)
            h = blake2sum(data)
            await managers[0].rpc_put_block(h, data)
            await asyncio.sleep(0.2)
            for m in managers:
                m.db.transaction(lambda tx: m.rc.incr(tx, h))
            # flip one byte INSIDE the piece payload on node1
            vm = managers[1]
            ((pi, (path, _c)),) = vm.local_pieces(h).items()
            raw = bytearray(open(path, "rb").read())
            raw[-1] ^= 0xFF
            open(path, "wb").write(bytes(raw))
            # reads that unwrap this piece now reject it (integrity hash)
            from garage_tpu.block.manager import unwrap_piece
            from garage_tpu.utils.error import Error as GError

            with pytest.raises(GError):
                unwrap_piece(bytes(raw))
            # scrub quarantines the piece and queues resync
            w = ScrubWorker(vm)
            await w._scrub_pieces([h])
            assert w.state.corruptions == 1
            assert not vm.local_pieces(h)
            assert os.path.exists(path + ".corrupted")
            # resync reconstructs a fresh, valid piece
            assert await vm.resync.resync_iter()
            assert vm.local_pieces(h)
            assert await vm.rpc_get_block(h) == data
        finally:
            await stop_all(apps, systems)

    run(main())


def test_block_file_io_runs_off_the_event_loop(tmp_path, monkeypatch):
    """graft-lint loop-blocker remedy (ISSUE 7): the block-file
    write/fsync/rename sequence and whole-file reads run via
    asyncio.to_thread.  With a simulated 50 ms disk, 8 concurrent local
    writes + 8 concurrent reads must neither serialize on the loop
    (wall ~ max, not sum) nor stall it (a 5 ms heartbeat keeps beating;
    before the fix each fsync parked the WHOLE loop for the disk
    latency, which is exactly what fattened event_loop_lag_seconds
    under concurrent streamed GETs)."""

    async def main():
        import time


        apps, systems, managers = await make_block_cluster(tmp_path, n=1, rf=1)
        mgr = managers[0]
        try:
            slow = 0.05
            real_write = BlockManager._write_block_file_sync
            real_read = BlockManager._read_stored_sync

            def slow_write(self, d, path, stored):
                time.sleep(slow)  # worker thread: must NOT show as loop lag
                return real_write(self, d, path, stored)

            def slow_read(self, hash32):
                time.sleep(slow)
                return real_read(self, hash32)

            monkeypatch.setattr(
                BlockManager, "_write_block_file_sync", slow_write
            )
            monkeypatch.setattr(BlockManager, "_read_stored_sync", slow_read)

            loop = asyncio.get_event_loop()
            max_lag = 0.0
            stop = asyncio.Event()

            async def heartbeat():
                nonlocal max_lag
                last = loop.time()
                while not stop.is_set():
                    await asyncio.sleep(0.005)
                    now = loop.time()
                    max_lag = max(max_lag, now - last - 0.005)
                    last = now

            hb = asyncio.get_event_loop().create_task(heartbeat())
            # the lock shards on hash32[0]: pick blocks whose HASHES have
            # distinct first bytes, so lock sharding is not what makes
            # the writes concurrent
            blocks = {}
            while len(blocks) < 8:
                data = os.urandom(30_000)
                h = blake2sum(data)
                if h[0] not in {k[0] for k in blocks}:
                    blocks[h] = data
            t0 = loop.time()
            await asyncio.gather(
                *[
                    mgr.write_block_local(h, d, False)
                    for h, d in blocks.items()
                ]
            )
            write_wall = loop.time() - t0
            t0 = loop.time()
            reads = await asyncio.gather(
                *[mgr.read_block_local(h) for h in blocks]
            )
            read_wall = loop.time() - t0
            stop.set()
            await hb
            for (h, d), got in zip(blocks.items(), reads):
                assert got == d
            # concurrent, not serialized: 8 x 50 ms serial would be 0.4 s
            assert write_wall < 8 * slow * 0.75, write_wall
            assert read_wall < 8 * slow * 0.75, read_wall
            # and the loop kept beating: nothing close to one disk op
            # ever parked it (generous bound for CI jitter)
            assert max_lag < slow, f"event loop stalled {max_lag * 1000:.0f}ms"
        finally:
            await stop_all(apps, systems)

    run(main())
