"""The resync worker's decisions, one entry at a time (ISSUE 28).

A decision table for the EC branch of `BlockResyncManager._resync_block`:
block needed x node a holder x own piece present x a stray piece of another
rank present.  The expected outcome of every row is what the code before the
one-row read / own-rank probe decided (`parent_decision` below is that code's
conditions, on the same observations), and the label the entry is counted
under in `block_resync_entries_total{outcome}`.  Whatever the outcome, after
a successful examination neither the queue key nor an error row for the hash
remains.
"""

import asyncio
import os
import sys

import msgpack
import pytest

sys.path.insert(0, os.path.dirname(__file__))

from test_block import make_block_cluster, stop_all  # noqa: E402

from garage_tpu.block.codec.ec import EcCodec  # noqa: E402
from garage_tpu.block.manager import wrap_piece  # noqa: E402
from garage_tpu.utils.data import blake2sum  # noqa: E402
from garage_tpu.utils.metrics import registry  # noqa: E402
from garage_tpu.utils.time_util import now_msec  # noqa: E402

OUTCOMES = ("noop", "reconstruct", "delete", "handoff", "fetch", "deferred", "error")


def entries() -> dict[str, float]:
    return {
        o: registry.counters.get(("block_resync_entries_total", (("outcome", o),)), 0)
        for o in OUTCOMES
    }


def counted(before: dict[str, float]) -> dict[str, float]:
    """Outcomes counted since `before`, zeros left out."""
    return {o: n - before[o] for o, n in entries().items() if n != before[o]}


def parent_decision(needed, my_ranks, local, deletable) -> str:
    """`_resync_block`'s EC branch as it stood before ISSUE 28, on what it
    observed: `local` is the full `local_pieces()` walk."""
    is_holder = bool(my_ranks)
    if needed and is_holder and any(r not in local for r in my_ranks):
        return "reconstruct"
    if local and not needed and deletable:
        return "delete"
    if local and needed and not is_holder:
        return "handoff"
    return "noop"


async def ec_cluster(tmp_path, n=4):
    """EC(2,1) over `n` nodes (rf 3): with 4, one node holds no piece."""
    apps, systems, managers = await make_block_cluster(
        tmp_path, n=n, codec=EcCodec(2, 1, tpu_enable=False)
    )
    for m in managers:
        m.codec = EcCodec(2, 1, tpu_enable=False)
    return apps, systems, managers


async def put_block(managers, needed: bool):
    data = os.urandom(20_000)
    h = blake2sum(data)
    await managers[0].rpc_put_block(h, data)
    await asyncio.sleep(0.2)  # leftover background piece writes land
    for m in managers:
        m.db.transaction(lambda tx, m=m: m.rc.incr(tx, h))
        if not needed:
            m.db.transaction(lambda tx, m=m: m.rc.decr(tx, h))
    return h, data


def write_error_row(m, h, next_try_ms: int, count: int = 2) -> None:
    m.resync.errors.insert(h, msgpack.packb([count, next_try_ms, now_msec() - 5000]))


class CountingExists:
    """`os.path.exists` with the paths it was asked about."""

    def __init__(self, monkeypatch):
        self.paths: list[str] = []
        real = os.path.exists

        def exists(p):
            self.paths.append(os.fspath(p))
            return real(p)

        monkeypatch.setattr(os.path, "exists", exists)


# (needed, holder, own piece present, stray piece of another rank present) -> outcome
TABLE = [
    (True, True, True, False, "noop"),
    (True, True, True, True, "noop"),
    (True, True, False, False, "reconstruct"),
    (True, True, False, True, "reconstruct"),
    (True, False, None, True, "handoff"),
    (True, False, None, False, "noop"),
    (False, True, True, False, "delete"),
    (False, True, True, True, "delete"),
    (False, True, False, True, "delete"),
    (False, True, False, False, "noop"),
    (False, False, None, True, "delete"),
    (False, False, None, False, "noop"),
]


def _case_id(case) -> str:
    needed, holder, own, stray, outcome = case
    return "-".join([
        "needed" if needed else "deleted",
        "holder" if holder else "nonholder",
        {True: "own", False: "ownmissing", None: "norank"}[own],
        "stray" if stray else "nostray",
        outcome,
    ])


@pytest.mark.parametrize("had_error", [False, True], ids=["fresh", "errored-before"])
@pytest.mark.parametrize("case", TABLE, ids=_case_id)
def test_ec_decision_table(tmp_path, monkeypatch, case, had_error):
    needed, holder, own, stray, expected = case

    async def main():
        import garage_tpu.block.rc as rc_mod

        monkeypatch.setattr(rc_mod, "BLOCK_GC_DELAY_MS", -1)
        apps, systems, managers = await ec_cluster(tmp_path)
        try:
            h, data = await put_block(managers, needed)
            m = next(x for x in managers if bool(x.ec_ranks_of(h)) == holder)
            my_ranks = m.ec_ranks_of(h)
            assert len(my_ranks) == (1 if holder else 0)
            # arrange the node's data directory
            if holder and not own:
                os.remove(m.find_block_file(h, piece=my_ranks[0])[0])
            if stray:
                r = next(i for i in range(m.codec.n_pieces) if i not in my_ranks)
                pieces = m.codec.encode(data)
                await m.write_block_local(h, wrap_piece(len(data), pieces[r]), False, piece=r)
            local = m.local_pieces(h)
            assert set(local) == set(my_ranks if own else []) | ({r} if stray else set())
            assert parent_decision(needed, my_ranks, local, m.rc.is_deletable(h)) == expected

            if had_error:
                write_error_row(m, h, now_msec() - 1)  # its retry is due
            m.resync.queue_block(h)
            (key,) = [k for k, _ in m.resync.queue.iter_range()]
            before = entries()
            probes = CountingExists(monkeypatch)
            assert await m.resync.resync_iter()
            probed = list(probes.paths)
            monkeypatch.undo()

            assert counted(before) == {expected: 1}
            # a success leaves neither the queue key nor an error row
            assert m.resync.queue.get(key) is None and m.resync.queue_len() == 0
            assert m.resync.errors.get(h) is None and m.resync.errors_len() == 0
            after = m.local_pieces(h)
            if expected == "noop":
                assert set(after) == set(local)
            elif expected == "reconstruct":
                assert set(after) == set(local) | set(my_ranks)
                assert await m.rpc_get_block(h) == data
            else:  # delete, handoff: every local piece reclaimed, strays too
                assert after == {}
            if expected == "noop" and needed and holder:
                # the healthy PUT's entry looks for this node's own rank
                # file only: .zst, then plain (and, for rank 0, found before
                # the legacy names are tried)
                own_names = {
                    m._file_name(h, my_ranks[0], c) for c in (True, False)
                }
                assert {os.path.basename(p) for p in probed} <= own_names, probed
                assert 1 <= len(probed) <= 2, probed
            # the others are unchanged: every holder still has its own piece
            for x in managers:
                if x is not m and expected != "delete":
                    assert set(x.ec_ranks_of(h)) <= set(x.local_pieces(h))
        finally:
            await stop_all(apps, systems)

    asyncio.run(main())


def test_deletion_delay_pending_is_a_noop(tmp_path):
    """rc 0 inside the GC delay: nothing is deleted, the entry is done."""

    async def main():
        apps, systems, managers = await ec_cluster(tmp_path, n=3)
        try:
            h, _data = await put_block(managers, needed=False)
            m = managers[1]
            assert not m.rc.is_deletable(h)
            local = m.local_pieces(h)
            m.resync.queue_block(h)
            before = entries()
            assert await m.resync.resync_iter()
            assert counted(before) == {"noop": 1}
            assert m.local_pieces(h) == local and local
            assert m.resync.queue_len() == 0 and m.resync.errors_len() == 0
        finally:
            await stop_all(apps, systems)

    asyncio.run(main())


def test_deferred_and_error_entries_are_requeued_in_one_commit(tmp_path, monkeypatch):
    """An entry whose retry is not due moves to its retry time (`deferred`);
    a failed examination writes the error row and moves the entry along the
    back-off ladder (`error`) — each as ONE transaction, and the due-time
    arithmetic is the ladder's (1 min, doubling)."""

    async def main():
        from garage_tpu.block import resync as resync_mod

        apps, systems, managers = await ec_cluster(tmp_path, n=3)
        try:
            h, _data = await put_block(managers, needed=True)
            m = managers[2]
            rs = m.resync
            commits = []
            real_tx = m.db.transaction
            monkeypatch.setattr(
                m.db, "transaction", lambda fn: (commits.append(1), real_tx(fn))[1]
            )
            for tree in (rs.queue, rs.errors):  # auto-commit ops would be extra commits
                monkeypatch.setattr(tree, "remove", None)
            # deferred: the error row's retry lies ahead
            retry = now_msec() + 90_000
            write_error_row(m, h, retry)
            rs.queue_block(h)
            monkeypatch.setattr(rs.queue, "insert", None)
            monkeypatch.setattr(rs.errors, "insert", None)
            before = entries()
            assert await rs.resync_iter()
            assert counted(before) == {"deferred": 1} and len(commits) == 1
            assert [k for k, _ in rs.queue.iter_range()] == [retry.to_bytes(8, "big") + h]
            assert rs.errors.get(h) is not None
            assert not await rs.resync_iter()  # nothing due, nothing counted
            assert counted(before) == {"deferred": 1}

            # error: the examination raises; third failure -> 4 min back-off
            async def boom(_h):
                raise RuntimeError("injected")

            monkeypatch.setattr(rs, "_resync_block", boom)
            (old_key,) = [k for k, _ in rs.queue.iter_range()]
            real_tx(lambda tx: (tx.remove(rs.queue, old_key), tx.insert(rs.queue, b"\0" * 8 + h, b"")))
            real_tx(lambda tx: tx.insert(
                rs.errors, h, msgpack.packb([2, now_msec() - 1, 12345])))
            del commits[:]
            t0 = now_msec()
            assert await rs.resync_iter()
            assert counted(before) == {"deferred": 1, "error": 1} and len(commits) == 1
            count, next_try, first = resync_mod.unpack_error(rs.errors.get(h))
            assert (count, first) == (3, 12345)
            assert t0 + 4 * 60_000 <= next_try <= now_msec() + 4 * 60_000
            assert [k for k, _ in rs.queue.iter_range()] == [next_try.to_bytes(8, "big") + h]
        finally:
            await stop_all(apps, systems)

    asyncio.run(main())


def test_noop_entry_is_one_row_read_and_one_commit(tmp_path, monkeypatch):
    """The healthy PUT's entry on a sqlite node: the queue's head is read
    as one row however deep the queue, and the entry ends in ONE commit
    that also takes an error row another worker wrote meanwhile
    (n_workers > 1)."""

    async def main():
        from test_db import _fetched_rows

        from garage_tpu.block.rc import BlockRc
        from garage_tpu.block.resync import BlockResyncManager
        from garage_tpu.db import open_db

        apps, systems, managers = await ec_cluster(tmp_path, n=3)
        db = open_db(str(tmp_path / "sqlite-meta"), engine="sqlite", fsync=False)
        try:
            h, _data = await put_block(managers, needed=True)
            # the same block manager over a sqlite metadata db
            m = managers[0]
            m.db, m.rc = db, BlockRc(db)
            rs = m.resync = BlockResyncManager(m)
            db.transaction(lambda tx: m.rc.incr(tx, h))
            rs.queue_block(h)
            # 600 entries dated ahead, as a mix with DELETEs leaves them
            rs.queue_blocks([os.urandom(32) for _ in range(600)], delay_ms=600_000)

            class CountingConn:
                commits = 0

                def commit(self):
                    CountingConn.commits += 1
                    return conn.commit()

                def __getattr__(self, name):
                    return getattr(conn, name)

            conn, db.conn = db.conn, CountingConn()
            real_block = rs._resync_block

            async def block_then_another_workers_error(hh):
                out = await real_block(hh)
                db.transaction(lambda tx: tx.insert(
                    rs.errors, hh, msgpack.packb([1, now_msec() + 60_000, now_msec()])))
                CountingConn.commits = 0
                return out

            monkeypatch.setattr(rs, "_resync_block", block_then_another_workers_error)
            before = entries()
            assert await rs.resync_iter()
            db.conn = conn
            assert counted(before) == {"noop": 1}
            assert CountingConn.commits == 1
            assert rs.errors.get(h) is None and rs.errors_len() == 0
            assert rs.queue_len() == 600
            # what is left is dated ahead; finding that out is one row too
            assert _fetched_rows(db, lambda: rs.due_empty() or pytest.fail("due")) == 1
            assert not await rs.resync_iter()
        finally:
            db.close()
            await stop_all(apps, systems)

    asyncio.run(main())


def test_replica_branch_outcomes(tmp_path, monkeypatch):
    """The replica (non-EC) branch decides as before and is counted:
    fetch, noop (stored and needed), delete."""

    async def main():
        import garage_tpu.block.rc as rc_mod

        monkeypatch.setattr(rc_mod, "BLOCK_GC_DELAY_MS", -1)
        apps, systems, managers = await make_block_cluster(tmp_path)
        try:
            data = os.urandom(30_000)
            h = blake2sum(data)
            await managers[0].rpc_put_block(h, data)
            await asyncio.sleep(0.2)
            m = managers[2]
            os.remove(m.find_block_file(h)[0])
            for x in managers:
                x.db.transaction(lambda tx, x=x: x.rc.incr(tx, h))
            for expected in ("fetch", "noop"):
                m.resync.queue_block(h)
                before = entries()
                assert await m.resync.resync_iter()
                assert counted(before) == {expected: 1}
                assert m.has_block(h) and m.resync.queue_len() == 0
            for x in managers:
                x.db.transaction(lambda tx, x=x: x.rc.decr(tx, h))
            m.resync.queue_block(h)
            before = entries()
            assert await m.resync.resync_iter()
            assert counted(before) == {"delete": 1}
            assert not m.has_block(h)
            assert m.resync.queue_len() == 0 and m.resync.errors_len() == 0
        finally:
            await stop_all(apps, systems)

    asyncio.run(main())


# --- the arrival check (ISSUE 33) ---------------------------------------------
#
# The entry of a newly referenced block (rc 0 -> 1) is due 2 x the piece
# write's own timeout after its block_ref row, and whichever of row and
# piece comes second settles it: no queue row is left and nothing is
# examined.  What the arrival cannot vouch for stays queued for the worker.


def settled() -> float:
    return registry.counters.get(("block_resync_settled_total", ()), 0)


def ref_row(m, h, deleted=False):
    """The block_ref table's hook on this node, as a row landing runs it."""
    from garage_tpu.model.s3.block_ref_table import BlockRef, BlockRefTable
    from garage_tpu.utils.crdt import Bool

    old = BlockRef(h, b"v" * 32) if deleted else None
    new = BlockRef(h, b"v" * 32, Bool(deleted))
    m.db.transaction(lambda tx: BlockRefTable(m).updated(tx, old, new))


def queue_keys(m) -> list[bytes]:
    return [k for k, _ in m.resync.queue.iter_range()]


async def holder_and_piece(managers, ec: bool):
    """A block nobody has stored yet, a node that is to hold it, its piece
    index and the bytes `write_block_local` gets for it."""
    data = os.urandom(20_000)
    h = blake2sum(data)
    if not ec:
        m = next(x for x in managers if x.system.id in x.storage_nodes_of(h))
        return h, data, m, 0, data
    m = next(x for x in managers if x.ec_ranks_of(h))
    (r,) = m.ec_ranks_of(h)
    return h, data, m, r, wrap_piece(len(data), m.codec.encode(data)[r])


SETTLE_CASES = [
    # what happens, EC mode?, settled?
    ("row-then-piece", True, True),
    ("piece-then-row", True, True),
    ("row-then-equal-copy", True, True),
    ("replica-row-then-block", False, True),
    ("replica-block-then-row", False, True),
    ("deletion-entry", True, False),
    ("error-row-then-piece", True, False),
    ("piece-then-error-row", True, False),
    ("error-row-written-meanwhile", True, False),
    ("second-rank-outstanding", True, False),
    ("second-rank-outstanding-piece-first", True, False),
    ("not-a-holder", True, False),
    ("failed-write", True, False),
    ("quarantined-meanwhile", True, False),
    ("restart", True, False),
    ("due-before-the-piece", True, False),
]


@pytest.mark.parametrize("case", SETTLE_CASES, ids=lambda c: c[0])
def test_arrival_check_settle_rule(tmp_path, monkeypatch, case):
    name, ec, expect_settled = case

    async def main():
        from garage_tpu.block.resync import BlockResyncManager

        if ec:
            apps, systems, managers = await ec_cluster(tmp_path)
        else:
            apps, systems, managers = await make_block_cluster(tmp_path)
        try:
            h, data, m, r, stored = await holder_and_piece(managers, ec)
            rs = m.resync
            m.helper.default_timeout = 5.0
            before, settled_before = entries(), settled()

            async def piece():
                await m.write_block_local(h, stored, False, piece=r)

            if name in ("row-then-piece", "replica-row-then-block"):
                ref_row(m, h)
                (key,) = queue_keys(m)
                assert rs._arrivals == {h: key}
                await piece()
            elif name in ("piece-then-row", "replica-block-then-row"):
                await piece()
                assert list(rs._written) == [h]
                ref_row(m, h)
            elif name == "row-then-equal-copy":
                await piece()  # e.g. an earlier PUT of the same content
                rs._written.clear()  # ... longer ago than the delay
                ref_row(m, h)
                assert len(queue_keys(m)) == 1
                await piece()  # write_block_local's early return
            elif name == "deletion-entry":
                ref_row(m, h)
                await piece()
                before, settled_before = entries(), settled()
                ref_row(m, h, deleted=True)  # rc -> 0: queued past the GC delay
                await piece()
            elif name == "error-row-then-piece":
                write_error_row(m, h, now_msec() + 60_000)
                ref_row(m, h)
                assert rs._arrivals == {}
                await piece()
            elif name == "piece-then-error-row":
                await piece()
                write_error_row(m, h, now_msec() + 60_000)
                ref_row(m, h)
            elif name == "error-row-written-meanwhile":
                ref_row(m, h)
                (key,) = queue_keys(m)
                # an examination of another entry of the hash fails (ONE
                # reading of the clock: a millisecond's tick between the
                # insert and the remove left the scratch entry in the queue)
                when = now_msec() + 60_000
                rs._requeue(b"\0" * 8 + h, when,
                            error=msgpack.packb([1, when, now_msec()]))
                rs.queue.remove(when.to_bytes(8, "big") + h)
                assert rs._arrivals == {}
                await piece()
            elif name.startswith("second-rank-outstanding"):
                other = next(i for i in range(m.codec.n_pieces) if i != r)
                monkeypatch.setattr(m, "ec_ranks_of", lambda _h: [r, other])
                if name.endswith("piece-first"):
                    await piece()
                    ref_row(m, h)
                else:
                    ref_row(m, h)
                    await piece()
            elif name == "not-a-holder":
                m = next(x for x in managers if not x.ec_ranks_of(h))
                rs = m.resync
                m.helper.default_timeout = 5.0
                ref_row(m, h)
                await piece()
            elif name == "failed-write":
                ref_row(m, h)

                def boom(*_a):
                    raise OSError("injected: no space left on device")

                monkeypatch.setattr(m, "_write_block_file_sync", boom)
                with pytest.raises(OSError):
                    await piece()
            elif name == "quarantined-meanwhile":
                await piece()
                await m._quarantine(h, m.find_block_file(h, piece=r)[0])
                ref_row(m, h)
            elif name == "restart":
                ref_row(m, h)
                rs = m.resync = BlockResyncManager(m)  # the maps are memory
                assert rs._arrivals == {} and rs._written == {}
                await piece()
            elif name == "due-before-the-piece":
                m.helper.default_timeout = 0.01
                ref_row(m, h)
                await asyncio.sleep(0.05)
                monkeypatch.setattr(rs, "_resync_block", lambda _h: asyncio.sleep(0, "noop"))
                assert await rs.resync_iter()  # the worker's from its due time on
                before, settled_before = entries(), settled()
                assert rs._arrivals == {}
                m.helper.default_timeout = 5.0
                await piece()
            else:
                raise AssertionError(name)

            keys = [k for k in queue_keys(m) if k[8:] == h]
            if expect_settled:
                assert settled() - settled_before == 1
                assert counted(before) == {"noop": 1}
                assert keys == [] and rs._arrivals == {} and rs._written == {}
                assert rs.errors_len() == 0
                assert not await rs.resync_iter()  # nothing left to examine
                return
            assert settled() - settled_before == 0 and counted(before) == {}
            if name == "due-before-the-piece":
                assert keys == [] and h not in rs._arrivals
                return
            # the entry stays a queue row, dated as it was queued
            assert len(keys) == 1 and rs._arrivals.get(h) in (None, keys[0])
            due = int.from_bytes(keys[0][:8], "big") - now_msec()
            if name == "deletion-entry":
                import garage_tpu.block.rc as rc_mod

                assert rc_mod.BLOCK_GC_DELAY_MS < due <= rc_mod.BLOCK_GC_DELAY_MS + 1000
                return
            assert 9_000 < due <= 10_000
            if name == "restart":
                # examined at its due time, it reads noop
                monkeypatch.setattr(
                    "garage_tpu.block.resync.now_msec", lambda: now_msec() + 10_001
                )
                assert await rs.resync_iter()
                assert counted(before) == {"noop": 1} and settled() == settled_before
                assert queue_keys(m) == []
        finally:
            await stop_all(apps, systems)

    asyncio.run(main())


@pytest.mark.parametrize("timeout_s", [0.5, 10.0, 30.0])
def test_arrival_check_is_due_after_twice_the_piece_writes_timeout(tmp_path, timeout_s):
    """The delay is derived from the timeout `_rpc_put_block` gives the piece
    `Put` (`helper.default_timeout`), whatever that is."""

    async def main():
        apps, systems, managers = await ec_cluster(tmp_path, n=3)
        try:
            h = os.urandom(32)
            m = managers[0]
            m.helper.default_timeout = timeout_s
            t0 = now_msec()
            ref_row(m, h)
            t1 = now_msec()
            (key,) = queue_keys(m)
            due = int.from_bytes(key[:8], "big")
            assert t0 + 2000 * timeout_s <= due <= t1 + 2000 * timeout_s
            assert not m.resync._kick.is_set()  # dated ahead: no worker is woken
            assert m.resync.due_empty()  # and layout sync is not gated by it
        finally:
            await stop_all(apps, systems)

    asyncio.run(main())


@pytest.mark.parametrize("producer", ["queue_blocks", "failed-send", "read-fault"])
def test_other_producers_stay_at_delay_0(tmp_path, monkeypatch, producer):
    """Repair nudges, the coordinator's record of a PUT acknowledged with a
    send still out, and read faults are due at once and kick the worker."""

    async def main():
        if producer == "read-fault":
            apps, systems, managers = await make_block_cluster(tmp_path)
        else:  # EC(2,2): a PUT is acknowledged at 3 of 4 pieces
            apps, systems, managers = await make_block_cluster(
                tmp_path, n=4, rf=4, codec=EcCodec(2, 2, tpu_enable=False)
            )
            for m in managers:
                m.codec = EcCodec(2, 2, tpu_enable=False)
        try:
            data = os.urandom(20_000)
            h = blake2sum(data)
            m = managers[0]
            if producer == "queue_blocks":
                await m._handle(m.system.id, type("Req", (), {"body": ["Queue", [h]]})())
            elif producer == "failed-send":
                victim = managers[3]

                async def refuse(_from, _req):
                    raise RuntimeError("injected: disk full")

                victim.endpoint.set_handler(refuse)
                await m.rpc_put_block(h, data)
            else:
                await m.rpc_put_block(h, data)
                await asyncio.sleep(0.2)
                from garage_tpu.net.fault import FaultPlan, FaultRule

                m.fault_plan = FaultPlan(1).set_rule(FaultRule(disk_read_fail=1.0))
                assert await m.read_block_local(h) is None
            (key,) = queue_keys(m)
            assert key[8:] == h and int.from_bytes(key[:8], "big") <= now_msec()
            assert m.resync._kick.is_set() and not m.resync.due_empty()
        finally:
            await stop_all(apps, systems)

    asyncio.run(main())


def test_examined_before_its_first_row_a_fresh_piece_is_not_garbage(tmp_path):
    """The coordinator's delay-0 entry can be examined before the block's
    first ref row has landed there: no rc row reads as "not needed" and as
    deletable.  The piece the PUT has just written stays, and its row then
    settles the check."""

    async def main():
        apps, systems, managers = await ec_cluster(tmp_path, n=3)
        try:
            h, _data, m, r, stored = await holder_and_piece(managers, True)
            await m.write_block_local(h, stored, False, piece=r)
            assert m.rc.tree.get(h) is None and m.rc.is_deletable(h)
            m.resync.queue_block(h)
            before, settled_before = entries(), settled()
            assert await m.resync.resync_iter()
            assert counted(before) == {"noop": 1}
            assert set(m.local_pieces(h)) == {r}
            ref_row(m, h)
            assert settled() - settled_before == 1 and queue_keys(m) == []
        finally:
            await stop_all(apps, systems)

    asyncio.run(main())


@pytest.mark.parametrize("mode", ["ec", "replica"])
def test_row_before_piece_on_a_loaded_loop_repairs_nothing(tmp_path, monkeypatch, mode):
    """The race of PERF.md 7.9: every node's block_ref row lands well before
    its piece, with the resync workers running on a loop that other work
    holds.  No entry is examined before the write has had its time, so EC
    mode reconstructs nothing and replica mode leaves no error row."""

    async def main():
        import time

        from garage_tpu.utils.background import BackgroundRunner

        if mode == "ec":
            apps, systems, managers = await ec_cluster(tmp_path, n=3)
        else:
            apps, systems, managers = await make_block_cluster(tmp_path)
        bg = BackgroundRunner()
        stop = asyncio.Event()

        async def load():
            while not stop.is_set():
                time.sleep(0.005)  # the loop is held 5 ms of every 6
                await asyncio.sleep(0.001)

        loader = asyncio.ensure_future(load())
        try:
            for m in managers:
                m.helper.default_timeout = 5.0
                m.resync.spawn_workers(bg)
                real = m._write_block_file_sync

                def slow(d, path, stored, real=real):
                    time.sleep(0.3)  # the piece lands long after its row
                    real(d, path, stored)

                monkeypatch.setattr(m, "_write_block_file_sync", slow)
            before, settled_before = entries(), settled()
            blocks = [os.urandom(20_000) for _ in range(6)]

            async def put(data):
                h = blake2sum(data)
                for m in managers:
                    ref_row(m, h)
                await managers[0].rpc_put_block(h, data)

            await asyncio.gather(*[put(b) for b in blocks])
            await asyncio.sleep(0.6)  # the writes the quorum did not wait for
            got = counted(before)
            assert got == {"noop": 18}, got
            assert settled() - settled_before == 18
            for m in managers:
                assert m.resync.errors_len() == 0 and m.resync.queue_len() == 0
                for b in blocks:
                    assert m.local_pieces(blake2sum(b)) if mode == "ec" else m.has_block(blake2sum(b))
        finally:
            stop.set()
            await loader
            await bg.shutdown()
            await stop_all(apps, systems)

    asyncio.run(main())
