"""A storage node's pieces lost and rebuilt while clients GET, HEAD, PUT
and DELETE: the repair round stays exact under writes and deletes, and
says what became of every stripe it was handed.

At EC(4,2) with 8 KiB blocks, through two S3 frontends:

(a) the repair plan under a seeded deck of GET / HEAD / PUT / DELETE over
    each client's own keys: every answer is the dict model's, every
    restored piece file is the reference's (`benchmark/harness/reference.py`),
    the round's outcome counts add up to the stripes it was handed and its
    phase seconds to `repair_plan_dispatch_duration`;
(b) a DELETE made while a round's gather is held: the block's count reads
    zero at the write, so no piece is written and the stripe counts
    `outcome=deleted`; while the gather is held neither its seconds nor
    its stripes are counted, once it ends both are;
(c) a gather that fails: the stripe counts `outcome=to_resync`, and the
    victim's resync worker then rebuilds the block;
(d) one round over a deleted, a whole, a lost and an ungatherable object:
    one count per stripe in each outcome, and the stripes gathered are
    the lost and the ungatherable ones.

Every coroutine is bounded by its own `asyncio.wait_for`.
"""

import asyncio
import os
import random
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(__file__))
sys.path.insert(0, os.path.join(REPO, "benchmark"))

from harness import check, reference  # noqa: E402 — numpy only, no JAX
from test_ec_cluster import make_ec_cluster, stop_cluster  # noqa: E402

from garage_tpu.api.s3.api_server import S3ApiServer  # noqa: E402
from garage_tpu.api.s3.client import S3Client, S3Error  # noqa: E402
from garage_tpu.block.repair_plan import drive_bulk  # noqa: E402
from garage_tpu.utils.data import blake2sum  # noqa: E402
from garage_tpu.utils.error import Error  # noqa: E402
from garage_tpu.utils.metrics import registry  # noqa: E402

K, M, BLOCK, OBJ_BYTES = 4, 2, 8192, 32768  # four blocks an object
BUCKET = "lost"
OUTCOMES = ("rebuilt", "to_resync", "deleted", "present")
PHASES = ("gather", "verify", "reconstruct", "write")


def run(coro, limit=60.0):
    return asyncio.run(asyncio.wait_for(coro, limit))


def pieces_on_disk(garage) -> dict[tuple[bytes, int], str]:
    return {key: path for dd in garage.config.data_dir for key, path in check.pieces_under(dd.path)}


def counters() -> dict:
    """The round's counters as they stand (window deltas are taken by the tests)."""
    c = registry.counters
    out = {o: c.get(("repair_round_stripes_total", (("outcome", o),)), 0.0) for o in OUTCOMES}
    out.update({p: c.get(("repair_round_seconds_total", (("phase", p),)), 0.0) for p in PHASES})
    d = registry.durations.get(("repair_plan_dispatch_duration", ()))
    b = registry.durations.get(("repair_plan_batch_size", ()))
    out["dispatch_s"] = d[1] if d else 0.0
    out["handed"] = b[1] if b else 0.0
    out["gathered"] = c.get(("repair_round_gathered_total", ()), 0.0)
    return out


def since(before: dict) -> dict:
    now = counters()
    return {k: now[k] - before[k] for k in now}


async def wait_until(pred, limit: float, what: str) -> None:
    for _ in range(int(limit / 0.05)):
        if pred():
            return
        await asyncio.sleep(0.05)
    raise AssertionError(f"{what} within {limit} s")


class Cluster:
    """Six EC(4,2) nodes, frontends 0 and 3, `objects` preloaded with
    every piece landed; node 1 (not a frontend) is the victim."""

    def __init__(self, garages, servers, clients, bodies):
        self.garages, self.servers, self.clients, self.bodies = garages, servers, clients, bodies
        self.victim = garages[1]
        self.mgr = self.victim.block_manager

    @classmethod
    async def start(cls, tmp_path, objects: int, seed: int) -> "Cluster":
        garages = await make_ec_cluster(tmp_path, n=K + M, mode=f"ec:{K}:{M}", block_size=BLOCK)
        servers = [S3ApiServer(garages[i]) for i in (0, 3)]
        self = cls(garages, servers, [], {})
        for s in servers:
            await s.start("127.0.0.1", 0)
        key = await garages[0].helper.create_key("node-loss-mixed")
        key.params().allow_create_bucket.update(True)
        await garages[0].key_table.insert(key)
        self.clients = [
            S3Client(f"http://127.0.0.1:{s.runner.addresses[0][1]}", key.key_id, key.secret())
            for s in servers
        ]
        await self.clients[0].create_bucket(BUCKET)
        rng = random.Random(seed)
        for i in range(objects):
            name = f"pre-{i}"
            self.bodies[name] = rng.randbytes(OBJ_BYTES)
            await self.clients[i % 2].put_object(BUCKET, name, self.bodies[name])
        n_blocks = objects * OBJ_BYTES // BLOCK
        # a PUT is acknowledged at the write quorum: wait for the rest
        await wait_until(lambda: all(len(pieces_on_disk(g)) >= n_blocks for g in garages),
                         10.0, "every piece landed")
        return self

    async def stop(self) -> None:
        await stop_cluster(self.garages, self.servers, self.clients)

    def hashes(self, name: str) -> list[bytes]:
        return [blake2sum(b) for b in check.blocks_of(self.bodies[name], BLOCK)]

    def want(self, names) -> dict[tuple[bytes, int], bytes]:
        """The reference's piece file for every rank of every block of `names`."""
        blocks = [b for n in names for b in check.blocks_of(self.bodies[n], BLOCK)]
        files = reference.expected_piece_files(blocks, K, M)
        return {(blake2sum(b), r): f for b, fs in zip(blocks, files) for r, f in fs.items()}

    def lose(self, names=None) -> dict[tuple[bytes, int], str]:
        """Remove the victim's pieces (of `names`, or all); returns what was there."""
        keep = None if names is None else {h for n in names for h in self.hashes(n)}
        held = {hp: p for hp, p in pieces_on_disk(self.victim).items() if keep is None or hp[0] in keep}
        for p in held.values():
            os.remove(p)
        return held

    def restored(self, lost) -> dict[tuple[bytes, int], bytes]:
        now = pieces_on_disk(self.victim)
        return {hp: check.read_file(now[hp]) for hp in lost if hp in now}

    async def delete_and_settle(self, name: str) -> None:
        """DELETE through S3, then wait until the victim's count of every
        block of it reads zero (the cascade runs in the tables' queues)."""
        await self.clients[1].delete_object(BUCKET, name)
        await wait_until(lambda: not any(self.mgr.rc.is_needed(h) for h in self.hashes(name)),
                         15.0, f"{name}'s blocks unreferenced on the victim")


def test_node_loss_repaired_under_a_mixed_deck(tmp_path):
    CLIENTS, OBJECTS = 4, 12

    async def main():
        cl = await Cluster.start(tmp_path, OBJECTS, seed=42)
        try:
            want = cl.want(cl.bodies)
            lost = cl.lose()
            assert len(lost) == OBJECTS * OBJ_BYTES // BLOCK
            before = counters()
            cl.victim.bg_vars.set("repair-tranquility", "0")
            planner = cl.victim.launch_repair_plan(fresh=True)
            deleted: set[str] = set()

            async def client(c: int) -> int:
                rng = random.Random(1000 + c)
                model = {n: cl.bodies[n] for n in sorted(cl.bodies)[c::CLIENTS]}
                deck: list[str] = []
                n_ops = n_put = 0
                while not planner.finished or n_ops < 12:
                    if not deck:
                        deck = ["GET"] * 9 + ["HEAD"] * 6 + ["PUT"] * 3 + ["DELETE"] * 2
                        rng.shuffle(deck)
                    op = deck.pop() if model else "PUT"
                    s3 = cl.clients[(c + n_ops) % 2]
                    n_ops += 1
                    if op == "PUT":
                        name, body = f"c{c}-{n_put}", rng.randbytes(OBJ_BYTES)
                        n_put += 1
                        await s3.put_object(BUCKET, name, body)
                        model[name] = body
                        continue
                    name = rng.choice(sorted(model))
                    if op == "GET":
                        assert await s3.get_object(BUCKET, name) == model[name], name
                    elif op == "HEAD":
                        h = await s3.head_object(BUCKET, name)
                        assert int(h["Content-Length"]) == len(model[name]), name
                    else:
                        await s3.delete_object(BUCKET, name)
                        del model[name]
                        deleted.add(name)
                        for s in cl.clients:
                            try:
                                await s.get_object(BUCKET, name)
                                raise AssertionError(f"{name} still answers after its DELETE")
                            except S3Error as e:
                                assert e.status == 404, e
                return n_ops

            done = await asyncio.gather(*(client(c) for c in range(CLIENTS)))
            assert planner.finished and planner.plan.state == "done"
            assert min(done) >= 12 and deleted

            # every restored piece is the reference's; every piece of a block
            # still referenced is back (the plan's rounds or the resync worker)
            got = cl.restored(lost)
            assert all(data == want[hp] for hp, data in got.items())
            live = {hp for hp in lost if cl.mgr.rc.is_needed(hp[0])}
            assert live and live <= set(got)

            d = since(before)
            assert d["handed"] > 0
            assert sum(d[o] for o in OUTCOMES) == d["handed"]
            assert all(d[p] >= 0 for p in PHASES)
            assert abs(sum(d[p] for p in PHASES) - d["dispatch_s"]) < 0.05
            # a stripe deleted at the write was gathered; one deleted before was not
            assert d["rebuilt"] + d["to_resync"] <= d["gathered"] <= d["rebuilt"] + d["to_resync"] + d["deleted"]
            assert registry.counters[("repair_plan_planned_total", ())] >= len(lost)
        finally:
            await cl.stop()

    run(main())


def test_a_delete_while_the_gather_is_held_writes_no_piece(tmp_path, monkeypatch):
    async def main():
        cl = await Cluster.start(tmp_path, 2, seed=43)
        try:
            gone, kept = "pre-0", "pre-1"
            lost = cl.lose()
            held, release = asyncio.Event(), asyncio.Event()
            real = cl.mgr.gather_pieces

            async def gate(*a, **kw):
                held.set()
                await release.wait()
                return await real(*a, **kw)

            monkeypatch.setattr(cl.mgr, "gather_pieces", gate)
            before = counters()
            hashes = cl.hashes(gone) + cl.hashes(kept)
            round_ = asyncio.ensure_future(drive_bulk(cl.mgr, hashes))
            await asyncio.wait_for(held.wait(), 10.0)
            # every count read at the round's start was live
            assert all(cl.mgr.rc.is_needed(h) for h in hashes)
            await cl.delete_and_settle(gone)
            # the gather phase has not ended: its seconds and its stripes wait for it
            held_d = since(before)
            assert held_d["gathered"] == held_d["gather"] == 0
            release.set()
            rebuilt = await asyncio.wait_for(round_, 20.0)

            assert rebuilt == len(cl.hashes(kept))
            got = cl.restored(lost)
            assert not {hp for hp in got if hp[0] in set(cl.hashes(gone))}
            want = cl.want([kept])
            assert set(got) == {hp for hp in lost if hp[0] in set(cl.hashes(kept))}
            assert all(data == want[hp] for hp, data in got.items())
            d = since(before)
            assert d["deleted"] == len(cl.hashes(gone))
            assert d["rebuilt"] == len(cl.hashes(kept))
            assert d["to_resync"] == d["present"] == 0
            assert d["gathered"] == len(hashes) and d["gather"] > 0
        finally:
            await cl.stop()

    run(main())


def test_a_failed_gather_goes_to_resync_which_rebuilds_it(tmp_path, monkeypatch):
    async def main():
        cl = await Cluster.start(tmp_path, 1, seed=44)
        try:
            name = "pre-0"
            lost = cl.lose()
            real = cl.mgr.gather_pieces
            failed: set[bytes] = set()

            async def fail_once(h, *a, **kw):
                if h not in failed:
                    failed.add(h)
                    raise Error("survivors unreachable (test)")
                return await real(h, *a, **kw)

            monkeypatch.setattr(cl.mgr, "gather_pieces", fail_once)
            before = counters()
            assert await asyncio.wait_for(drive_bulk(cl.mgr, cl.hashes(name)), 20.0) == 0
            d = since(before)
            assert d["to_resync"] == len(cl.hashes(name)) == len(failed) == d["gathered"]
            assert d["rebuilt"] == d["deleted"] == d["present"] == 0
            assert not cl.restored(lost)

            # the victim's resync worker takes the queued blocks: one-block
            # rebuilds through the same (now answering) gather
            await wait_until(lambda: len(cl.restored(lost)) == len(lost), 20.0,
                             "resync rebuilt every queued block")
            want = cl.want([name])
            assert all(data == want[hp] for hp, data in cl.restored(lost).items())
        finally:
            await cl.stop()

    run(main())


def test_one_count_per_stripe_and_the_gathered_stripes_counted(tmp_path, monkeypatch):
    async def main():
        cl = await Cluster.start(tmp_path, 4, seed=45)
        try:
            deleted, whole, lost_one, ungatherable = "pre-0", "pre-1", "pre-2", "pre-3"
            lost = cl.lose([deleted, lost_one, ungatherable])
            await cl.delete_and_settle(deleted)
            real = cl.mgr.gather_pieces
            refuse = set(cl.hashes(ungatherable))

            async def gather(h, *a, **kw):
                if h in refuse:
                    raise Error("survivors unreachable (test)")
                return await real(h, *a, **kw)

            monkeypatch.setattr(cl.mgr, "gather_pieces", gather)
            before = counters()
            names = (deleted, whole, lost_one, ungatherable)
            hashes = [h for n in names for h in cl.hashes(n)]
            rebuilt = await asyncio.wait_for(drive_bulk(cl.mgr, hashes), 20.0)
            d = since(before)
            per = len(cl.hashes(whole))
            assert rebuilt == per
            assert {o: d[o] for o in OUTCOMES} == {"deleted": per, "present": per, "rebuilt": per, "to_resync": per}
            assert sum(d[o] for o in OUTCOMES) == d["handed"] == len(hashes)
            assert abs(sum(d[p] for p in PHASES) - d["dispatch_s"]) < 0.05
            assert d["gather"] > 0 and d["verify"] > 0 and d["reconstruct"] > 0 and d["write"] > 0
            # the deleted and the whole object's stripes never reach the gathers
            assert d["gathered"] == 2 * per
            got = cl.restored(lost)
            assert {hp[0] for hp in got} <= set(cl.hashes(lost_one)) | refuse
            want = cl.want([lost_one])
            assert all(got[hp] == want[hp] for hp in got if hp[0] in set(cl.hashes(lost_one)))
        finally:
            await cl.stop()

    run(main())
