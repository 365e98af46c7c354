"""A storage node's pieces lost and rebuilt: what `local_pieces` reads,
what it costs the file system, and the loss end to end through S3.

(a) `BlockManager.local_pieces` (one listing per data directory of the
    hash) against the exhaustive probe it replaced (`find_block_file`
    for every piece index), over seeded stores;
(b) file-system calls per hash of `local_pieces` and of a bulk `Inv`,
    counted by a patched `os` — counts, never times;
(c) a node loss at EC(4,2) with 8 KiB blocks: preload through S3, the
    victim's pieces removed, the repair plan launched under concurrent
    GETs; every GET returns its bytes, every restored piece file is the
    reference's (`benchmark/harness/reference.py`), every `Inv` answer
    is what lies on the answering node's disk.

Every coroutine is bounded by its own `asyncio.wait_for`.
"""

import asyncio
import os
import random
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(__file__))
sys.path.insert(0, os.path.join(REPO, "benchmark"))

from harness import check, reference  # noqa: E402 — numpy only, no JAX
from test_block import make_block_cluster, stop_all  # noqa: E402
from test_ec_cluster import make_ec_cluster, stop_cluster  # noqa: E402

from garage_tpu.api.s3.api_server import S3ApiServer  # noqa: E402
from garage_tpu.api.s3.client import S3Client  # noqa: E402
from garage_tpu.block.codec.ec import EcCodec  # noqa: E402
from garage_tpu.block.manager import INV_YIELD_EVERY  # noqa: E402
from garage_tpu.net.message import PRIO_BACKGROUND, Req  # noqa: E402
from garage_tpu.utils.data import blake2sum  # noqa: E402
from garage_tpu.utils.metrics import registry  # noqa: E402


def run(coro, limit=20.0):
    return asyncio.run(asyncio.wait_for(coro, limit))


def exhaustive_probe(mgr, h):
    """`local_pieces` as it was before the listing: up to 2 x n_pieces (+
    the legacy names) existence probes through `find_block_file`."""
    out = {}
    for i in range(mgr.codec.n_pieces):
        f = mgr.find_block_file(h, piece=i)
        if f:
            out[i] = f
    return out


def put_file(mgr, base, h, name, data=b"GTP2" + bytes(40) + b"x" * 64):
    d = mgr.data_layout.block_dir(base, h)
    os.makedirs(d, exist_ok=True)
    with open(os.path.join(d, name), "wb") as f:
        f.write(data)


async def one_manager(tmp_path, k=4, m=2, second_dir=False):
    """One node's BlockManager over an EC codec (no peer is ever asked);
    with `second_dir` every sub-partition also reads an older directory."""
    apps, systems, managers = await make_block_cluster(
        tmp_path, n=1, rf=1, codec=EcCodec(k, m)
    )
    mgr = managers[0]
    if second_dir:
        old = str(tmp_path / "data-old")
        os.makedirs(old, exist_ok=True)
        lay = mgr.data_layout
        lay.dirs.append(old)
        lay.secondary = [[len(lay.dirs) - 1] for _ in lay.secondary]
    return apps, systems, mgr


def seed_store(mgr, kind: str, rng: random.Random) -> list[bytes]:
    """Files for 12 hashes of one kind of store; returns the hashes."""
    hashes = [rng.randbytes(32) for _ in range(12)]
    n = mgr.codec.n_pieces
    for h in hashes:
        hx = h.hex()
        primary = mgr.data_layout.primary_dir(h)
        ranks = rng.sample(range(n), rng.randint(1, 3))
        if kind == "empty":
            continue
        if kind == "plain":
            for r in ranks:
                put_file(mgr, primary, h, f"{hx}.p{r}")
        elif kind == "zst_beside_plain":
            for r in ranks:
                put_file(mgr, primary, h, f"{hx}.p{r}")
                if rng.random() < 0.7:
                    put_file(mgr, primary, h, f"{hx}.p{r}.zst")
        elif kind == "legacy_replica":
            # a replica-format file from before the codec became EC reads
            # as piece 0, after any real piece 0, .zst before plain
            put_file(mgr, primary, h, hx + rng.choice(["", ".zst"]))
            if rng.random() < 0.5:
                put_file(mgr, primary, h, hx)
            if rng.random() < 0.4:
                put_file(mgr, primary, h, f"{hx}.p0")
            for r in ranks:
                if r:
                    put_file(mgr, primary, h, f"{hx}.p{r}")
        elif kind == "two_dirs":
            older = mgr.data_layout.all_dirs(h)[1]
            for r in ranks:
                where = rng.choice(["new", "old", "both"])
                if where in ("new", "both"):
                    put_file(mgr, primary, h, f"{hx}.p{r}")
                if where in ("old", "both"):
                    put_file(mgr, older, h, f"{hx}.p{r}" + rng.choice(["", ".zst"]))
        elif kind == "foreign_rank":
            # every rank at once (an older layout version's pieces, never
            # this node's own), a stray .tmp, and a neighbour hash that
            # shares the directory
            for r in range(n):
                put_file(mgr, primary, h, f"{hx}.p{r}")
            put_file(mgr, primary, h, f"{hx}.p1.tmp")
            put_file(mgr, primary, h, h[:2].hex() + rng.randbytes(30).hex() + ".p0")
        else:
            raise AssertionError(kind)
    return hashes


STORES = ["plain", "zst_beside_plain", "legacy_replica", "two_dirs", "foreign_rank", "empty"]


@pytest.mark.parametrize("kind", STORES)
def test_local_pieces_equals_exhaustive_probe(tmp_path, kind):
    async def main():
        apps, systems, mgr = await one_manager(tmp_path, second_dir=kind == "two_dirs")
        try:
            for seed in range(4):
                hashes = seed_store(mgr, kind, random.Random(1000 * seed + len(kind)))
                for h in hashes:
                    want = exhaustive_probe(mgr, h)
                    got = mgr.local_pieces(h)
                    assert got == want
                    assert list(got) == sorted(got)  # callers iterate in rank order
                    if kind == "empty":
                        assert got == {}
                    idxs, plen = mgr.piece_inventory(h)
                    assert idxs == sorted(want)
                    plain = [p for _i, (p, c) in sorted(want.items()) if not c]
                    assert plen == (64 if plain else 0)
        finally:
            await stop_all(apps, systems)

    run(main())


class CountedOs:
    """Counts the calls of `os` that reach the file system under a data
    directory (monkeypatched in; whatever else the process does with
    other paths is not counted)."""

    NAMES = ("stat", "listdir", "open", "fstat", "read", "close", "scandir")

    def __init__(self, monkeypatch, under: str):
        self.n = dict.fromkeys(self.NAMES, 0)
        self.fds: set[int] = set()
        for name in self.NAMES:
            monkeypatch.setattr(os, name, self._wrap(name, getattr(os, name), under))

    def _wrap(self, name, real, under):
        def counted(*a, **kw):
            first = a[0] if a else None
            if isinstance(first, (str, bytes)) and os.fsdecode(first).startswith(under):
                self.n[name] += 1
                out = real(*a, **kw)
                if name == "open":
                    self.fds.add(out)
                return out
            if isinstance(first, int) and first in self.fds:
                self.n[name] += 1
                if name == "close":
                    self.fds.discard(first)
            return real(*a, **kw)

        return counted

    def reset(self) -> None:
        self.n = dict.fromkeys(self.NAMES, 0)


def test_file_system_calls_per_hash(tmp_path, monkeypatch):
    """A hash costs `local_pieces` one listing per data directory — the
    exhaustive probe paid 2 x n_pieces + 2 stats — and a bulk `Inv` one
    listing and one 4-call header read per hash, with no thread hop and
    a yield every INV_YIELD_EVERY hashes."""

    async def main():
        apps, systems, mgr = await one_manager(tmp_path, k=8, m=3)
        try:
            rng = random.Random(7)
            hashes = [rng.randbytes(32) for _ in range(64)]
            for h in hashes[:48]:  # 48 hold one plain piece, 16 nothing
                put_file(mgr, mgr.data_layout.primary_dir(h), h, f"{h.hex()}.p3")
            counted = CountedOs(monkeypatch, str(tmp_path))

            for h in hashes:
                exhaustive_probe(mgr, h)
            # plain and .zst for each of 11 ranks and the two legacy names
            # of piece 0; a plain piece is found at its first name
            assert counted.n["stat"] == 64 * (2 * 11 + 2) - 48
            counted.reset()

            for h in hashes:
                mgr.local_pieces(h)
            assert counted.n == {**dict.fromkeys(CountedOs.NAMES, 0), "listdir": 64}
            counted.reset()

            hops = []
            real_to_thread = asyncio.to_thread

            async def to_thread(fn, *a, **kw):
                hops.append(fn)
                return await real_to_thread(fn, *a, **kw)

            monkeypatch.setattr(asyncio, "to_thread", to_thread)
            turns = 0

            async def ticker():
                nonlocal turns
                while True:
                    turns += 1
                    await asyncio.sleep(0)

            tick = asyncio.ensure_future(ticker())
            await asyncio.sleep(0)
            turns = 0
            resp = await mgr._handle(b"\x01" * 32, Req(["Inv", hashes]))
            tick.cancel()
            assert resp.body == [[[3], 64]] * 48 + [[[], 0]] * 16
            assert hops == []
            assert counted.n == {
                "listdir": 64, "open": 48, "fstat": 48, "read": 48, "close": 48,
                "stat": 0, "scandir": 0,
            }
            # the handler gave the loop back between groups of hashes
            assert turns >= 64 // INV_YIELD_EVERY - 1
        finally:
            await stop_all(apps, systems)

    run(main())


def pieces_on_disk(garage) -> dict[tuple[bytes, int], str]:
    return {key: path for dd in garage.config.data_dir for key, path in check.pieces_under(dd.path)}


def test_node_loss_repaired_under_gets(tmp_path):
    K, M, BLOCK, OBJECTS, OBJ_BYTES = 4, 2, 8192, 6, 65536

    async def main():
        garages = await make_ec_cluster(tmp_path, n=K + M, mode=f"ec:{K}:{M}", block_size=BLOCK)
        servers = [S3ApiServer(garages[i]) for i in (0, 3)]
        clients = []
        try:
            for s in servers:
                await s.start("127.0.0.1", 0)
            key = await garages[0].helper.create_key("node-loss")
            key.params().allow_create_bucket.update(True)
            await garages[0].key_table.insert(key)
            clients = [
                S3Client(f"http://127.0.0.1:{s.runner.addresses[0][1]}", key.key_id, key.secret())
                for s in servers
            ]
            await clients[0].create_bucket("lost")
            rng = random.Random(29)
            bodies = {f"obj-{i}": rng.randbytes(OBJ_BYTES) for i in range(OBJECTS)}
            for name, body in bodies.items():
                await clients[0].put_object("lost", name, body)
            blocks = [blk for b in bodies.values() for blk in check.blocks_of(b, BLOCK)]
            by_hash = {blake2sum(b): b for b in blocks}
            n_blocks = len(by_hash)
            # a PUT is acknowledged at the write quorum: wait for the rest
            for _ in range(200):
                if all(len(pieces_on_disk(g)) >= n_blocks for g in garages):
                    break
                await asyncio.sleep(0.05)

            victim = garages[1]  # not a frontend
            lost = {hp: check.read_file(p) for hp, p in pieces_on_disk(victim).items()}
            assert len(lost) == n_blocks
            hashes = sorted(by_hash)
            want_files = reference.expected_piece_files([by_hash[h] for h in hashes], K, M)
            want = {(h, r): f for h, files in zip(hashes, want_files) for r, f in files.items()}
            assert all(lost[hp] == want[hp] for hp in lost)

            surveyed0 = registry.counters.get(("repair_plan_surveyed_total", ()), 0.0)
            scans0 = registry.counters.get(("repair_plan_scan_seconds", ()), 0.0)
            for p in pieces_on_disk(victim).values():
                os.remove(p)
            victim.bg_vars.set("repair-tranquility", "0")
            planner = victim.launch_repair_plan(fresh=True)

            async def reader(c, seed):
                r = random.Random(seed)
                n = 0
                while not planner.finished or n < 3:
                    name = r.choice(sorted(bodies))
                    assert await c.get_object("lost", name) == bodies[name], name
                    n += 1
                return n

            served = await asyncio.gather(*(reader(clients[i % 2], i) for i in range(3)))
            assert planner.finished and planner.plan.state == "done"
            assert min(served) >= 3

            now = pieces_on_disk(victim)
            assert set(now) == set(lost)
            assert all(check.read_file(now[hp]) == want[hp] for hp in lost)
            # the victim's resync worker heals what a degraded GET or a
            # nudge queued while the plan ran: the plan rebuilt the rest
            assert planner.plan.repaired <= len(lost)
            assert registry.counters[("repair_plan_surveyed_total", ())] - surveyed0 == n_blocks
            assert registry.counters[("repair_plan_scan_seconds", ())] > scans0

            # every node's Inv answer is what lies on its disk
            mgr = victim.block_manager
            for g in garages:
                if g is victim:
                    continue
                resp = await mgr.helper.call(
                    mgr.endpoint, g.node_id, ["Inv", hashes],
                    prio=PRIO_BACKGROUND, idempotent=True,
                )
                disk = pieces_on_disk(g)
                for h, (idxs, plen) in zip(hashes, resp.body):
                    held = sorted(r for (hh, r) in disk if hh == h)
                    assert list(idxs) == held
                    assert plen == len(check.read_file(disk[(h, held[0])])) - 44
        finally:
            await stop_cluster(garages, servers, clients)

    run(main(), limit=60.0)
