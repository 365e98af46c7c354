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
