"""Large objects read, stat'ed, written and deleted at once on a healthy
EC cluster: what the `ec83-mixed-8m` cell drives, tiny, on the CPU.

EC(4,2), 8 KiB blocks, 64 KiB objects (8 blocks an object, as the cell),
two frontends, seeded.

(a) the cell's deck from `benchmark/harness/traffic.py` against a plain
    dict model of S3 state: every GET's bytes, every HEAD's length, 404
    after an acknowledged DELETE through the OTHER frontend, every piece
    file of every live object against `benchmark/harness/reference.py`;
(b) a healthy 16-block GET asks exactly k pieces a block, all data ranks,
    all first asks, and serves every block systematic; with one data-rank
    holder slowed a parity piece is asked as a hedge, the block decoded,
    the bytes exact;
(c) the prefetch window never holds more than GET_PREFETCH_DEPTH
    unconsumed blocks, and a client that disconnects mid-GET leaves no
    read in flight; a 10-block GET on an EC(8,3) cluster of 11 nodes
    refills it as it streams, byte for byte;
(d) DELETE of an 8-block object: 8 `block_ref` tombstones, 8 counts at
    zero, every piece still on disk and every resync entry `noop` before
    the GC delay; a PUT of the same body revives the blocks with no
    piece rewritten;
(e) a second GET of an object is served from the read cache while it
    fits, and systematic again once a larger object has pushed it out;
(f) the serving side: a piece (a file of up to 1 MiB) is read whole
    in ONE worker-thread hop by the `Get` handler and answered as an
    in-memory stream that the connection's send loop never waits in; a
    larger file is still streamed chunk by chunk.

Every coroutine is bounded by its own `asyncio.wait_for`.
"""

import asyncio
import os
import random
import sys
import urllib.parse

import aiohttp
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(__file__))
sys.path.insert(0, os.path.join(REPO, "benchmark"))

from harness import check, reference, traffic  # noqa: E402 — numpy only, no JAX
from test_block import make_block_cluster, stop_all  # noqa: E402
from test_ec_cluster import make_ec_cluster, stop_cluster  # noqa: E402

import garage_tpu.api.s3.objects as objects_mod  # noqa: E402
from garage_tpu.api.s3.api_server import S3ApiServer  # noqa: E402
from garage_tpu.api.s3.client import S3Client, S3Error  # noqa: E402
from garage_tpu.api.common.signature import sign_request_headers  # noqa: E402
from garage_tpu.block.codec.ec import EcCodec  # noqa: E402
from garage_tpu.block.manager import WHOLE_READ_MAX, wrap_piece  # noqa: E402
from garage_tpu.net.fault import FaultPlan, FaultRule  # noqa: E402
from garage_tpu.net.message import Req  # noqa: E402
from garage_tpu.net.stream import BytesStream, read_stream_to_end  # noqa: E402
from garage_tpu.utils.data import blake2sum  # noqa: E402
from garage_tpu.utils.metrics import registry  # noqa: E402

K, M, BLOCK, OBJ_BYTES = 4, 2, 8192, 65536
FRONTENDS = (0, 3)
BUCKET = "mixed"


def run(coro, limit=60.0):
    return asyncio.run(asyncio.wait_for(coro, limit))


class Cluster:
    """Six nodes, two S3 frontends, a client on each, one bucket."""

    def __init__(self, tmp_path):
        self.tmp_path = tmp_path
        self.garages, self.servers, self.clients = [], [], []

    async def __aenter__(self) -> "Cluster":
        self.garages = await make_ec_cluster(
            self.tmp_path, n=K + M, mode=f"ec:{K}:{M}", block_size=BLOCK)
        self.servers = [S3ApiServer(self.garages[i]) for i in FRONTENDS]
        for s in self.servers:
            await s.start("127.0.0.1", 0)
        g0 = self.garages[0]
        key = await g0.helper.create_key("mixed-large")
        key.params().allow_create_bucket.update(True)
        await g0.key_table.insert(key)
        self.clients = [
            S3Client(f"http://127.0.0.1:{s.runner.addresses[0][1]}", key.key_id, key.secret())
            for s in self.servers
        ]
        await self.clients[0].create_bucket(BUCKET)
        # a loaded sandbox must not fire the hedge timer on a healthy read
        for i in FRONTENDS:
            self.garages[i].block_manager.block_config.read_hedge_min_msec = 5000.0
        return self

    async def __aexit__(self, *exc):
        await stop_cluster(self.garages, self.servers, self.clients)

    def frontend(self, i: int):
        return self.garages[FRONTENDS[i]]

    def pieces_on_disk(self) -> dict[tuple[bytes, int], str]:
        return {key: path for g in self.garages for dd in g.config.data_dir
                for key, path in check.pieces_under(dd.path)}

    async def wait_pieces(self, n_blocks: int) -> dict[tuple[bytes, int], str]:
        """A PUT is acknowledged at the write quorum: wait for the rest."""
        for _ in range(400):
            disk = self.pieces_on_disk()
            if len(disk) >= n_blocks * (K + M):
                return disk
            await asyncio.sleep(0.05)
        raise AssertionError(f"{len(disk)} piece files, want {n_blocks * (K + M)}")

    async def warm(self, front: int) -> None:
        """First contact legitimately hedges: one throwaway object through
        the frontend before anything is counted."""
        await self.clients[front].put_object(BUCKET, f"warm-{front}", os.urandom(OBJ_BYTES))
        await self.clients[front].get_object(BUCKET, f"warm-{front}")


def counters(name: str) -> dict[tuple, float]:
    return {lbl: v for (n, lbl), v in registry.counters.items() if n == name}


def delta(name: str, before: dict) -> dict[tuple, float]:
    now = counters(name)
    return {lbl: v - before.get(lbl, 0.0) for lbl, v in now.items() if v != before.get(lbl, 0.0)}


def served(before: dict) -> dict[str, float]:
    return {dict(lbl)["served"]: v for lbl, v in delta("block_read_blocks_total", before).items()}


def pieces(before: dict) -> dict[tuple[str, str], float]:
    return {(dict(lbl)["rank"], dict(lbl)["why"]): v
            for lbl, v in delta("block_read_pieces_total", before).items()}


# --- (a) the cell's deck against a dict model -------------------------------------


@pytest.mark.parametrize("seed", [34, 2147534007])
def test_mixed_deck_against_model(tmp_path, seed):
    t = traffic.validate({
        "clients": 3, "object_bytes": OBJ_BYTES, "preload_objects": 12, "preload_concurrency": 4,
        "mix": {"GET": 9, "STAT": 6, "PUT": 3, "DELETE": 2}, "key_choice": "uniform_own",
    })
    ops_per_client = 50

    async def main():
        async with Cluster(tmp_path) as c:
            model: dict[str, int] = {}  # key -> body id: S3 state as a plain dict
            deleted: set[str] = set()
            for i in range(t["preload_objects"]):
                await c.clients[i % 2].put_object(
                    BUCKET, traffic.preload_key(i), traffic.body(seed, i, OBJ_BYTES))
                model[traffic.preload_key(i)] = i
            done = {op: 0 for op in traffic.OPS}

            async def client(ci: int):
                plan = traffic.ClientPlan(t, seed, ci)
                for _ in range(ops_per_client):
                    op, key, bid, front = plan.next_op()
                    cl, other = c.clients[front], c.clients[1 - front]
                    if op == "PUT":
                        await cl.put_object(BUCKET, key, traffic.body(seed, bid, OBJ_BYTES))
                        model[key] = bid
                    elif op == "GET":
                        assert model[key] == bid
                        assert await cl.get_object(BUCKET, key) == traffic.body(seed, bid, OBJ_BYTES), key
                    elif op == "STAT":
                        assert int((await cl.head_object(BUCKET, key))["Content-Length"]) == OBJ_BYTES
                    else:
                        await cl.delete_object(BUCKET, key)
                        del model[key]
                        deleted.add(key)
                        # acknowledged: gone through the OTHER frontend too
                        with pytest.raises(S3Error) as e:
                            await other.get_object(BUCKET, key)
                        assert e.value.status == 404, key
                    done[op] += 1

            await asyncio.gather(*(client(ci) for ci in range(t["clients"])))
            assert sum(done.values()) == 3 * ops_per_client and all(done.values()), done
            assert model and deleted

            # what is live reads back through either frontend, what was deleted through neither
            for n, (key, bid) in enumerate(sorted(model.items())):
                assert await c.clients[n % 2].get_object(BUCKET, key) == traffic.body(seed, bid, OBJ_BYTES)
            for key in sorted(deleted):
                for cl in c.clients:
                    with pytest.raises(S3Error) as e:
                        await cl.head_object(BUCKET, key)
                    assert e.value.status == 404

            # every piece file of every live object is the reference's
            blocks = [b for bid in sorted(set(model.values()))
                      for b in check.blocks_of(traffic.body(seed, bid, OBJ_BYTES), BLOCK)]
            want_files = reference.expected_piece_files(blocks, K, M)
            want = {(reference.block_hash(b), r): f
                    for b, files in zip(blocks, want_files) for r, f in files.items()}
            for _ in range(400):
                disk = c.pieces_on_disk()
                if want.keys() <= disk.keys():
                    break
                await asyncio.sleep(0.05)
            assert want.keys() <= disk.keys()
            assert all(check.read_file(disk[hp]) == f for hp, f in want.items())

    run(main(), limit=120.0)


# --- (b) pieces asked per block, healthy and with a slowed holder -----------------


def test_healthy_get_asks_k_data_pieces_a_block_and_a_slowed_holder_is_hedged(tmp_path):
    async def main():
        async with Cluster(tmp_path) as c:
            rng = random.Random(5)
            body, body2 = rng.randbytes(16 * BLOCK), rng.randbytes(16 * BLOCK)
            await c.clients[0].put_object(BUCKET, "big", body)
            await c.clients[0].put_object(BUCKET, "big2", body2)
            await c.wait_pieces(32)
            await c.warm(1)
            await c.wait_pieces(32 + 8)

            p0, b0 = counters("block_read_pieces_total"), counters("block_read_blocks_total")
            h0 = counters("block_read_hedges_total")
            assert await c.clients[1].get_object(BUCKET, "big") == body
            assert pieces(p0) == {("data", "first"): 16 * K}
            assert served(b0) == {"systematic": 16}
            assert delta("block_read_hedges_total", h0) == {}

            # one holder of a data rank that is not the serving node, slowed
            g1 = c.frontend(1)
            mgr = g1.block_manager
            h = blake2sum(body2[:BLOCK])
            nodes = mgr.system.layout_manager.history.current().nodes_of(h)
            victim = next(n for n in nodes[:K] if n != g1.node_id)
            mgr.block_config.read_hedge_min_msec = 50.0
            g1.netapp.fault_plan = FaultPlan(11).set_rule(FaultRule(latency_ms=1500.0), peer=victim)
            p0, b0 = counters("block_read_pieces_total"), counters("block_read_blocks_total")
            assert await c.clients[1].get_object(BUCKET, "big2") == body2
            g1.netapp.fault_plan = None
            got, how = pieces(p0), served(b0)
            assert got[("data", "first")] == 16 * K
            assert got.get(("parity", "hedge"), 0) >= 1
            assert set(got) <= {("data", "first"), ("parity", "hedge"), ("parity", "failover")}
            assert how.get("decoded", 0) >= 1
            assert how.get("decoded", 0) + how.get("systematic", 0) == 16
            assert how["decoded"] <= got[("parity", "hedge")] + got.get(("parity", "failover"), 0)

    run(main())


# --- (c) the prefetch window ------------------------------------------------------


async def version_of(garage, key: str):
    """The object's current version row (its uuid, its block list), as
    `handle_get_object` reads it."""
    bucket_id = await garage.helper.resolve_bucket(BUCKET)
    obj = await garage.object_table.get(bucket_id, key.encode())
    version = objects_mod._pick_version(obj)
    return await garage.version_table.get(version.data["vid"], b"")


def test_prefetch_window_is_bounded_and_a_disconnect_leaves_no_read(tmp_path, monkeypatch):
    n_blocks = 24

    async def main():
        async with Cluster(tmp_path) as c:
            body = random.Random(6).randbytes(n_blocks * BLOCK)
            await c.clients[0].put_object(BUCKET, "long", body)
            g1 = c.frontend(1)
            mgr = g1.block_manager
            started = []
            real = mgr.start_block_read

            def start_block_read(*a, **kw):
                br = real(*a, **kw)
                started.append(br)
                return br

            monkeypatch.setattr(mgr, "start_block_read", start_block_read)
            blocks = (await version_of(g1, "long")).sorted_blocks()
            assert len(blocks) == n_blocks

            # streamed whole: at no chunk does the window hold more than the depth
            out, widest = bytearray(), 0
            agen = objects_mod.plain_block_stream(g1, blocks, 0, len(body), None)
            async for chunk in agen:
                out += chunk
                consumed = (len(out) - len(chunk)) // BLOCK  # blocks wholly handed out before this chunk
                widest = max(widest, len(started) - consumed)
                assert len(started) - consumed <= objects_mod.GET_PREFETCH_DEPTH
            assert bytes(out) == body and len(started) == n_blocks
            assert widest == objects_mod.GET_PREFETCH_DEPTH
            assert all(br.landed for br in started)

            # a client that goes away mid-GET: the peers answer slowly, the
            # client reads the headers and closes
            mgr.read_cache.set_max_bytes(0)
            started.clear()
            g1.netapp.fault_plan = FaultPlan(12).set_rule(FaultRule(latency_ms=150.0))
            cl = c.clients[1]
            signed = sign_request_headers(
                "GET", f"/{BUCKET}/long", [], {"host": cl.host}, b"", cl.key_id, cl.secret, cl.region)
            async with aiohttp.ClientSession() as sess:
                resp = await sess.get(cl.endpoint + urllib.parse.quote(f"/{BUCKET}/long"), headers=signed)
                assert resp.status == 200
                await resp.content.read(BLOCK)
                resp.close()
            for _ in range(200):
                if started and all(br.landed for br in started):
                    break
                await asyncio.sleep(0.05)
            g1.netapp.fault_plan = None
            assert 0 < len(started) < n_blocks  # the stream was cut, not finished
            assert all(br.landed for br in started)

    run(main())


def test_a_10_block_get_on_ec83_is_exact_while_the_prefetch_window_refills(tmp_path, monkeypatch):
    """warp's 10 MiB object is 10 blocks, the first longer than the window
    of GET_PREFETCH_DEPTH = 8: on an EC(8,3) cluster of 11 nodes its GET
    through `plain_block_stream` starts blocks 9 and 10 only as blocks 1
    and 2 are handed out, never holds more than the depth, and streams the
    written body byte for byte."""
    k, m, block, n_blocks = 8, 3, 8192, 10
    depth = objects_mod.GET_PREFETCH_DEPTH
    assert n_blocks > depth

    async def main():
        garages = await make_ec_cluster(tmp_path, n=k + m, mode=f"ec:{k}:{m}", block_size=block)
        server = S3ApiServer(garages[0])
        await server.start("127.0.0.1", 0)
        g0 = garages[0]
        key = await g0.helper.create_key("warp")
        key.params().allow_create_bucket.update(True)
        await g0.key_table.insert(key)
        client = S3Client(f"http://127.0.0.1:{server.runner.addresses[0][1]}", key.key_id, key.secret())
        try:
            await client.create_bucket(BUCKET)
            body = random.Random(10).randbytes(n_blocks * block)
            await client.put_object(BUCKET, "ten", body)
            g5 = garages[5]  # the cell's other frontend: it holds no copy of the blocks
            g5.block_manager.block_config.read_hedge_min_msec = 5000.0
            mgr = g5.block_manager
            out = bytearray()
            handed_out_at_start = []
            real = mgr.start_block_read

            def start_block_read(*a, **kw):
                handed_out_at_start.append(len(out) // block)
                return real(*a, **kw)

            monkeypatch.setattr(mgr, "start_block_read", start_block_read)
            blocks = (await version_of(g5, "ten")).sorted_blocks()
            assert len(blocks) == n_blocks
            async for chunk in objects_mod.plain_block_stream(g5, blocks, 0, len(body), None):
                out += chunk
                assert len(handed_out_at_start) - (len(out) - len(chunk)) // block <= depth
            assert bytes(out) == body
            # the first `depth` blocks start at once; each later one as one more is handed out
            assert handed_out_at_start[:depth] == [0] * depth
            assert handed_out_at_start[depth:] == list(range(1, n_blocks - depth + 1))
        finally:
            await stop_cluster(garages, [server], [client])

    run(main(), limit=120.0)


# --- (d) DELETE of an 8-block object ----------------------------------------------


def test_delete_of_a_large_object_tombstones_counts_and_keeps_pieces(tmp_path):
    async def main():
        async with Cluster(tmp_path) as c:
            body = random.Random(7).randbytes(OBJ_BYTES)
            hashes = [blake2sum(b) for b in check.blocks_of(body, BLOCK)]
            assert len(set(hashes)) == 8
            await c.clients[0].put_object(BUCKET, "doomed", body)
            disk = await c.wait_pieces(8)
            files = {hp: p for hp, p in disk.items() if hp[0] in hashes}
            assert len(files) == 8 * (K + M)
            stamp = {p: (os.stat(p).st_ino, os.stat(p).st_mtime_ns) for p in files.values()}
            g0 = c.garages[0]
            vid = (await version_of(g0, "doomed")).uuid
            holders = [g for g in c.garages if any(g.block_manager.rc.get(h) for h in hashes)]
            assert all(g.block_manager.rc.get(h) == 1 for g in holders for h in hashes)

            zeroed0 = registry.counters.get(("block_rc_zeroed_total", ()), 0.0)
            cascade = ("event_loop_busy_seconds_total", (("layer", "table"), ("span", "table:delete_cascade")))
            busy0 = registry.counters.get(cascade, 0.0)
            await c.clients[1].delete_object(BUCKET, "doomed")
            with pytest.raises(S3Error) as e:
                await c.clients[0].get_object(BUCKET, "doomed")
            assert e.value.status == 404

            # the tail runs after the 204: version tombstone -> 8 block_ref tombstones -> 8 counts to zero
            for _ in range(400):
                if all(g.block_manager.rc.get(h) == 0 for g in holders for h in hashes):
                    break
                await asyncio.sleep(0.05)
            for h in hashes:
                ref = await g0.block_ref_table.get(h, vid)
                assert ref is not None and ref.deleted.get()
            for g in holders:
                for h in hashes:
                    raw = g.block_manager.rc.tree.get(h)
                    assert raw.startswith(b"del")  # at zero, with its GC deadline
                    assert not g.block_manager.rc.is_deletable(h)
            assert registry.counters[("block_rc_zeroed_total", ())] - zeroed0 == 8 * len(holders)
            if registry.counters.get(("event_loop_steps_total", ()), 0.0):
                assert registry.counters.get(cascade, 0.0) > busy0  # the meter is on: the label has time

            # before the GC delay: every piece on disk, every examination a noop
            assert all(os.path.exists(p) for p in files.values())
            for g in c.garages:
                for h in hashes:
                    assert await g.block_manager.resync._resync_block(h) == "noop"
            assert {p: (os.stat(p).st_ino, os.stat(p).st_mtime_ns) for p in files.values()} == stamp

            # the same body again revives the blocks, and rewrites no piece
            await c.clients[1].put_object(BUCKET, "revived", body)
            for _ in range(400):
                if all(g.block_manager.rc.get(h) == 1 for g in holders for h in hashes):
                    break
                await asyncio.sleep(0.05)
            assert all(g.block_manager.rc.get(h) == 1 for g in holders for h in hashes)
            assert await c.clients[0].get_object(BUCKET, "revived") == body
            now = {hp: p for hp, p in c.pieces_on_disk().items() if hp[0] in hashes}
            assert now == files
            assert {p: (os.stat(p).st_ino, os.stat(p).st_mtime_ns) for p in files.values()} == stamp

    run(main())


# --- (e) the read cache on a data set larger than itself --------------------------


def test_second_get_is_cached_while_it_fits_and_systematic_once_pushed_out(tmp_path):
    async def main():
        async with Cluster(tmp_path) as c:
            rng = random.Random(8)
            small, large = rng.randbytes(OBJ_BYTES), rng.randbytes(2 * OBJ_BYTES)
            await c.clients[0].put_object(BUCKET, "small", small)
            await c.clients[0].put_object(BUCKET, "large", large)
            await c.warm(1)
            # room for the small object, not for the large one beside it
            c.frontend(1).block_manager.read_cache.set_max_bytes(OBJ_BYTES + OBJ_BYTES // 2)

            async def get(key: str, body: bytes) -> dict[str, float]:
                b0 = counters("block_read_blocks_total")
                assert await c.clients[1].get_object(BUCKET, key) == body
                return served(b0)

            assert await get("small", small) == {"systematic": 8}
            assert await get("small", small) == {"cache": 8}
            assert await get("large", large) == {"systematic": 16}
            assert await get("small", small) == {"systematic": 8}

    run(main())


# --- (f) the serving side of a piece ----------------------------------------------


@pytest.mark.parametrize("kind", ["piece", "large-block"])
def test_a_served_piece_is_read_in_one_hop(tmp_path, monkeypatch, kind):
    async def main():
        ec = kind == "piece"
        apps, systems, managers = await make_block_cluster(
            tmp_path, n=1, rf=1, codec=EcCodec(K, M) if ec else None)
        try:
            mgr = managers[0]
            rng = random.Random(9)
            if ec:  # one rank of a 1 MiB block at EC(4,2), as a holder stores it
                data = rng.randbytes(1 << 20)
                rank, stored = 2, wrap_piece(len(data), mgr.codec.encode(data)[2])
                assert len(stored) <= WHOLE_READ_MAX
            else:  # a replica-mode block above the size read whole
                data = rng.randbytes(WHOLE_READ_MAX + 4096)
                rank, stored = 0, data
            h = blake2sum(data)
            await mgr.write_block_local(h, stored, False, piece=rank)

            hops = []
            real_to_thread = asyncio.to_thread

            async def to_thread(fn, *a, **kw):
                hops.append(getattr(fn, "__name__", repr(fn)))
                return await real_to_thread(fn, *a, **kw)

            monkeypatch.setattr(asyncio, "to_thread", to_thread)
            resp = await mgr._handle(b"\x01" * 32, Req(["Get", h, rank]))
            assert resp.body[0] == "ok" and resp.body[1]["s"] == len(stored)
            # found (and, a piece, read) before the answer is queued: one hop
            assert hops == ["_read_stored_sync"]
            if ec:  # none left for the send loop
                assert isinstance(resp.stream, BytesStream) and resp.stream.total == len(stored)
            else:
                assert not isinstance(resp.stream, BytesStream)
            assert await read_stream_to_end(resp.stream) == stored
            if ec:
                assert hops == ["_read_stored_sync"]
            else:  # open, five reads of 256 KiB or less, the read that finds the end, close
                assert hops == ["_read_stored_sync", "open"] + ["read"] * 6 + ["close"]
        finally:
            await stop_all(apps, systems)

    run(main())
