"""DB abstraction tests, run against every engine (reference src/db/test.rs)."""

import pytest

from garage_tpu.db import TxAbort, open_db


def test_basic_ops(db):
    t = db.open_tree("t1")
    assert t.get(b"k") is None
    t.insert(b"k", b"v")
    assert t.get(b"k") == b"v"
    t.insert(b"k", b"v2")
    assert t.get(b"k") == b"v2"
    assert len(t) == 1
    t.remove(b"k")
    assert t.get(b"k") is None
    assert len(t) == 0


def test_range_iter(db):
    t = db.open_tree("t2")
    for i in range(10):
        t.insert(bytes([i]), bytes([i * 2]))
    allkv = list(t.iter_range())
    assert [k for k, _ in allkv] == [bytes([i]) for i in range(10)]
    part = list(t.iter_range(start=bytes([3]), end=bytes([7])))
    assert [k for k, _ in part] == [bytes([i]) for i in range(3, 7)]
    rev = list(t.iter_range(reverse=True))
    assert [k for k, _ in rev] == [bytes([i]) for i in reversed(range(10))]


def test_prefix_iter(db):
    t = db.open_tree("t3")
    t.insert(b"aa1", b"1")
    t.insert(b"aa2", b"2")
    t.insert(b"ab1", b"3")
    assert [k for k, _ in t.iter_prefix(b"aa")] == [b"aa1", b"aa2"]
    # prefix ending in 0xff
    t.insert(b"\xff\x01", b"x")
    t.insert(b"\xff\x02", b"y")
    assert len(list(t.iter_prefix(b"\xff"))) == 2


@pytest.mark.parametrize("rows", [8, 2000])
def test_get_gt_first(db, rows):
    """first() / get_gt() return the right row over a small tree and over
    one several pages deep, on every engine; the sqlite engine hands back
    ONE row for each (the resync worker reads the head of its queue once
    per entry: a 256-row page there cost a third of the entry)."""
    t = db.open_tree("t4")
    keys = [b"k%05d" % (2 * i) for i in range(rows)]

    def fill(tx):
        for k in reversed(keys):
            tx.insert(t, k, b"v" + k)

    db.transaction(fill)
    assert t.first() == (keys[0], b"v" + keys[0])
    assert t.get_gt(keys[0]) == (keys[1], b"v" + keys[1])
    # between two keys, below the first, at and past the last
    assert t.get_gt(keys[0] + b"\x00") == (keys[1], b"v" + keys[1])
    assert t.get_gt(b"") == (keys[0], b"v" + keys[0])
    mid = rows // 2
    assert t.get_gt(keys[mid - 1]) == (keys[mid], b"v" + keys[mid])
    assert t.get_gt(keys[-2]) == (keys[-1], b"v" + keys[-1])
    assert t.get_gt(keys[-1]) is None
    # agrees with the iteration it replaces
    assert t.first() == next(iter(t.iter_range()))
    assert t.get_gt(keys[3]) == next(iter(t.iter_range(start=keys[3] + b"\x00")))
    # the head moves as the queue's does: remove the first, insert a new one
    t.remove(keys[0])
    assert t.first() == (keys[1], b"v" + keys[1])
    t.insert(b"a", b"new")
    assert t.first() == (b"a", b"new")
    if db.engine == "sqlite":
        fetched = _fetched_rows(db, lambda: (t.first(), t.get_gt(keys[5])))
        assert fetched == 2, fetched  # one row each, whatever the depth
    empty = db.open_tree("t4-empty")
    assert empty.first() is None and empty.get_gt(b"") is None


def _fetched_rows(db, fn) -> int:
    """Rows the sqlite connection handed to Python while `fn` ran."""
    import sqlite3

    n = 0

    class Counting(sqlite3.Cursor):
        def fetchone(self):
            nonlocal n
            row = super().fetchone()
            n += row is not None
            return row

        def fetchall(self):
            nonlocal n
            rows = super().fetchall()
            n += len(rows)
            return rows

    class Conn:
        def __init__(self, conn):
            self._conn = conn

        def execute(self, *a):
            return self._conn.cursor(Counting).execute(*a)

        def __getattr__(self, name):
            return getattr(self._conn, name)

    real, db.conn = db.conn, Conn(db.conn)
    try:
        fn()
    finally:
        db.conn = real
    return n


def test_transaction_commit_rollback(db):
    t1 = db.open_tree("ta")
    t2 = db.open_tree("tb")

    def txf(tx):
        tx.insert(t1, b"x", b"1")
        tx.insert(t2, b"y", b"2")
        return "ok"

    assert db.transaction(txf) == "ok"
    assert t1.get(b"x") == b"1" and t2.get(b"y") == b"2"

    def txfail(tx):
        tx.insert(t1, b"x", b"changed")
        tx.remove(t2, b"y")
        raise RuntimeError("boom")

    with pytest.raises(RuntimeError):
        db.transaction(txfail)
    assert t1.get(b"x") == b"1" and t2.get(b"y") == b"2"

    def txabort(tx):
        tx.insert(t1, b"x", b"changed")
        raise TxAbort(value=42)

    assert db.transaction(txabort) == 42
    assert t1.get(b"x") == b"1"


def test_tx_read_your_writes(db):
    t = db.open_tree("tc")

    def txf(tx):
        tx.insert(t, b"k", b"v")
        assert tx.get(t, b"k") == b"v"
        tx.remove(t, b"k")
        assert tx.get(t, b"k") is None
        tx.insert(t, b"k", b"v2")
        return tx.len(t)

    assert db.transaction(txf) == 1
    assert t.get(b"k") == b"v2"


def test_list_trees(db):
    db.open_tree("z_tree")
    db.open_tree("a_tree")
    names = db.list_trees()
    assert "z_tree" in names and "a_tree" in names


def test_iterate_while_mutating(db):
    """GC/sync workers iterate a tree and delete as they go — both engines
    must tolerate mutation mid-iteration."""
    t = db.open_tree("mut")
    for i in range(50):
        t.insert(bytes([i]), b"v")
    seen = []
    for k, _v in t.iter_range():
        seen.append(k)
        t.remove(k)
    assert len(seen) == 50 and len(t) == 0
    # reverse direction too
    for i in range(50):
        t.insert(bytes([i]), b"v")
    seen = []
    for k, _v in t.iter_range(reverse=True):
        seen.append(k)
        t.remove(k)
    assert seen == [bytes([i]) for i in reversed(range(50))] and len(t) == 0


def test_autocommit_op_inside_tx_refused(db):
    """Auto-commit Tree ops inside a transaction() would break atomicity;
    both engines must refuse them."""
    t = db.open_tree("guard")

    def bad(tx):
        tx.insert(t, b"a", b"1")
        t.insert(b"b", b"2")  # wrong: bypasses the Tx handle

    with pytest.raises(RuntimeError):
        db.transaction(bad)
    assert t.get(b"a") is None and t.get(b"b") is None


# --- log-engine durability ----------------------------------------------------


def _reopen_log(path):
    from garage_tpu.db.log_engine import LogDb

    return LogDb(str(path), fsync=False)


def test_log_engine_survives_reopen(tmp_path):
    p = tmp_path / "d.log"
    db = _reopen_log(p)
    t = db.open_tree("a")
    for i in range(100):
        t.insert(f"k{i:03d}".encode(), f"v{i}".encode())
    t.remove(b"k050")
    db.transaction(lambda tx: tx.insert(db.open_tree("b"), b"x", b"y"))
    db.close()

    db2 = _reopen_log(p)
    t2 = db2.open_tree("a")
    assert len(t2) == 99
    assert t2.get(b"k007") == b"v7"
    assert t2.get(b"k050") is None
    assert db2.open_tree("b").get(b"x") == b"y"
    db2.close()


def test_log_engine_torn_tail_rolls_back_only_last_commit(tmp_path):
    """A crash mid-commit (torn frame at the tail) must roll back that
    commit alone; earlier commits survive."""
    p = tmp_path / "d.log"
    db = _reopen_log(p)
    t = db.open_tree("a")
    t.insert(b"durable", b"1")
    t.insert(b"victim", b"2")
    db._f.flush()
    db._f.close()
    db._f = None  # simulate crash: skip close() compaction

    # chop bytes off the last frame
    size = p.stat().st_size
    with open(p, "r+b") as f:
        f.truncate(size - 3)

    db2 = _reopen_log(p)
    t2 = db2.open_tree("a")
    assert t2.get(b"durable") == b"1"
    assert t2.get(b"victim") is None, "torn commit must not replay"
    # the file was truncated to the last valid frame and stays writable
    t2.insert(b"after", b"3")
    db2.close()
    db3 = _reopen_log(p)
    assert db3.open_tree("a").get(b"after") == b"3"
    db3.close()


def test_log_engine_compaction_bounds_file(tmp_path):
    """Overwriting the same keys forever must not grow the log without
    bound; compaction keeps only live state and loses nothing."""
    import garage_tpu.db.log_engine as le

    p = tmp_path / "d.log"
    db = _reopen_log(p)
    old_min = le.COMPACT_MIN_BYTES
    le.COMPACT_MIN_BYTES = 4096
    try:
        t = db.open_tree("a")
        val = b"x" * 512
        for round_ in range(40):
            for i in range(20):
                t.insert(f"k{i}".encode(), val + str(round_).encode())
        live = sum(len(k) + len(v) for k, v in t.iter_range())
        assert p.stat().st_size < 10 * live, "log grew without bound"
        assert len(t) == 20
        assert t.get(b"k7") == val + b"39"
    finally:
        le.COMPACT_MIN_BYTES = old_min
        db.close()


def test_convert_db_between_durable_engines(tmp_path):
    """convert-db round-trips sqlite <-> log (reference cli/convert_db.rs
    pattern, now across two durable engines)."""
    from garage_tpu.cli.main import convert_db

    src = open_db(str(tmp_path / "src"), engine="sqlite", fsync=False)
    t = src.open_tree("objects")
    rows = {f"k{i:04d}".encode(): f"value-{i}".encode() for i in range(500)}
    for k, v in rows.items():
        t.insert(k, v)
    src.open_tree("meta").insert(b"version", b"1")
    src.close()

    class Args:
        input = str(tmp_path / "src")
        input_engine = "sqlite"
        output = str(tmp_path / "dst")
        output_engine = "log"

    convert_db(Args)
    dst = open_db(str(tmp_path / "dst"), engine="log", fsync=False)
    t2 = dst.open_tree("objects")
    assert len(t2) == 500
    assert all(t2.get(k) == v for k, v in rows.items())
    assert dst.open_tree("meta").get(b"version") == b"1"
    dst.close()

    # and back again
    class Args2:
        input = str(tmp_path / "dst")
        input_engine = "log"
        output = str(tmp_path / "back")
        output_engine = "sqlite"

    convert_db(Args2)
    back = open_db(str(tmp_path / "back"), engine="sqlite", fsync=False)
    assert len(back.open_tree("objects")) == 500
    back.close()


# --- native-engine durability + WAL interop -----------------------------------


def _reopen_native(path):
    from garage_tpu.db.native_engine import NativeDb

    return NativeDb(str(path), fsync=False)


def _native_or_skip():
    from garage_tpu import _native

    if not _native.available():
        pytest.skip("native library unavailable")


def test_native_engine_survives_reopen(tmp_path):
    _native_or_skip()
    p = tmp_path / "d.log"
    db = _reopen_native(p)
    t = db.open_tree("a")
    for i in range(100):
        t.insert(f"k{i:03d}".encode(), f"v{i}".encode())
    t.remove(b"k050")
    db.transaction(lambda tx: tx.insert(db.open_tree("b"), b"x", b"y"))
    db.close()

    db2 = _reopen_native(p)
    t2 = db2.open_tree("a")
    assert len(t2) == 99
    assert t2.get(b"k007") == b"v7"
    assert t2.get(b"k050") is None
    assert db2.open_tree("b").get(b"x") == b"y"
    db2.close()


def test_native_engine_torn_tail_rolls_back_only_last_commit(tmp_path):
    """Crash mid-commit: the C++ replay must truncate the torn frame and
    keep everything before it (same contract as the Python engine)."""
    _native_or_skip()
    p = tmp_path / "d.log"
    db = _reopen_native(p)
    t = db.open_tree("a")
    t.insert(b"durable", b"1")
    t.insert(b"victim", b"2")
    db.h = None  # simulate crash: skip close() compaction (fd leaks, ok)

    size = p.stat().st_size
    with open(p, "r+b") as f:
        f.truncate(size - 3)

    db2 = _reopen_native(p)
    t2 = db2.open_tree("a")
    assert t2.get(b"durable") == b"1"
    assert t2.get(b"victim") is None, "torn commit must not replay"
    t2.insert(b"after", b"3")
    db2.close()
    db3 = _reopen_native(p)
    assert db3.open_tree("a").get(b"after") == b"3"
    db3.close()


def test_native_log_wal_interop_both_directions(tmp_path):
    """The native engine's WAL format is byte-identical to the Python log
    engine's: a store written by either must open in the other (so
    switching db_engine needs no convert-db)."""
    _native_or_skip()

    # Python log engine writes, native reads
    p1 = tmp_path / "d1.log"
    db = _reopen_log(p1)
    t = db.open_tree("tree/α")  # non-ascii tree name crosses too
    for i in range(200):
        t.insert(f"k{i:04d}".encode(), (b"v\x00" * 7) + bytes([i]))
    t.remove(b"k0100")
    db.close()  # compacts with the Python writer
    ndb = _reopen_native(p1)
    nt = ndb.open_tree("tree/α")
    assert len(nt) == 199
    assert nt.get(b"k0042") == (b"v\x00" * 7) + bytes([42])
    assert nt.get(b"k0100") is None
    assert [k for k, _ in nt.iter_range(b"k0000", b"k0003")] == [
        b"k0000", b"k0001", b"k0002",
    ]
    nt.insert(b"native-added", b"nv")
    ndb.close()  # compacts with the C++ writer

    # ...and back: the native-compacted file opens in the Python engine
    pdb = _reopen_log(p1)
    pt = pdb.open_tree("tree/α")
    assert len(pt) == 200
    assert pt.get(b"native-added") == b"nv"
    assert pt.get(b"k0042") == (b"v\x00" * 7) + bytes([42])
    pdb.close()


@pytest.mark.parametrize("engine", ["log", "native"])
def test_daemon_runs_on_durable_engine(tmp_path, engine):
    """Full S3 daemon on each durable non-sqlite engine, with data
    surviving a restart."""
    import asyncio
    import os as _os
    import sys as _sys

    if engine == "native":
        _native_or_skip()

    _sys.path.insert(0, _os.path.dirname(__file__))
    from garage_tpu.api.s3.api_server import S3ApiServer
    from garage_tpu.api.s3.client import S3Client
    from garage_tpu.model.garage import Garage
    from garage_tpu.rpc.layout.types import NodeRole
    from garage_tpu.utils.config import config_from_dict

    def cfg():
        return config_from_dict(
            {
                "metadata_dir": str(tmp_path / "meta"),
                "data_dir": str(tmp_path / "data"),
                "db_engine": engine,
                "replication_factor": 1,
                "rpc_bind_addr": "127.0.0.1:0",
                "rpc_secret": "cc" * 32,
                "block_size": 4096,
                "s3_api": {"api_bind_addr": "127.0.0.1:0"},
            }
        )

    async def main():
        garage = Garage(cfg())
        await garage.start()
        garage.layout_manager.stage_role(
            garage.node_id, NodeRole(zone="dc1", capacity=10**12)
        )
        garage.layout_manager.apply_staged()
        garage.spawn_workers()
        s3 = S3ApiServer(garage)
        await s3.start("127.0.0.1", 0)
        ep = f"http://127.0.0.1:{s3.runner.addresses[0][1]}"
        key = await garage.helper.create_key("log-test")
        key.params().allow_create_bucket.update(True)
        await garage.key_table.insert(key)
        c = S3Client(ep, key.key_id, key.secret())
        await c.create_bucket("logdb")
        body = _os.urandom(20_000)
        await c.put_object("logdb", "obj", body)
        await c.close()
        await s3.stop()
        await garage.stop()

        # restart on the same store
        garage2 = Garage(cfg())
        await garage2.start()
        garage2.spawn_workers()
        s3b = S3ApiServer(garage2)
        await s3b.start("127.0.0.1", 0)
        ep2 = f"http://127.0.0.1:{s3b.runner.addresses[0][1]}"
        c2 = S3Client(ep2, key.key_id, key.secret())
        assert await c2.get_object("logdb", "obj") == body
        await c2.close()
        await s3b.stop()
        await garage2.stop()

    asyncio.run(main())


def test_iter_range_mid_iteration_contract(db):
    """Pins the documented (weak) mid-iteration consistency contract of
    Tree.iter_range (ADVICE r3): engines differ on whether keys inserted
    ahead of a live cursor are observed (log engine snapshots, native
    pages through the live map) — but ALL engines must (a) never crash,
    (b) never skip or duplicate keys that existed when iteration started
    and weren't touched, and (c) honor the end bound."""
    t = db.open_tree("iterc")
    for i in range(0, 100, 2):
        t.insert(b"k%03d" % i, b"v%d" % i)
    preexisting = {b"k%03d" % i for i in range(0, 100, 2)}

    seen = []
    inserted_ahead = False
    for k, _v in t.iter_range(b"k000", b"k100"):
        seen.append(k)
        if not inserted_ahead and k == b"k010":
            # mutate ahead of and behind the cursor mid-iteration
            t.insert(b"k095", b"new")  # odd key: ahead, not preexisting
            t.insert(b"k001", b"new")  # behind: must NOT appear later
            inserted_ahead = True

    # (b): every untouched preexisting key in range seen exactly once
    seen_pre = [k for k in seen if k in preexisting]
    assert seen_pre == sorted(preexisting)
    # behind-the-cursor insert never shows up (ordered iteration)
    assert b"k001" not in seen
    # (c): end bound respected even with mid-iteration inserts
    assert all(k < b"k100" for k in seen)
    # ahead-of-cursor insert: MAY be seen (native/sqlite) or not (log) —
    # both are within contract; just record that it didn't corrupt order
    assert seen == sorted(seen)


def test_native_group_commit_sigkill_durability(tmp_path):
    """Group commit durability contract (VERDICT r3 #6): a SIGKILLed
    process loses at most the bounded flusher window of ACKED commits
    (not arbitrary history), the log replays cleanly (torn tail
    truncated, no crash), and every surviving key is a prefix-contiguous
    acked key."""
    import os
    import signal
    import subprocess
    import sys as _sys
    import time as _time

    from garage_tpu import _native

    if not _native.available():
        import pytest

        pytest.skip("native engine unavailable")

    path = str(tmp_path / "db.log")
    child = subprocess.Popen(
        [_sys.executable, os.path.join(os.path.dirname(__file__), "_group_commit_child.py"), path],
        stdout=subprocess.PIPE, text=True,
    )
    # let it ack a few thousand commits, then SIGKILL mid-flight
    acked = -1
    t0 = _time.time()
    while _time.time() - t0 < 15 and acked < 3000:
        line = child.stdout.readline()
        if not line:
            break
        acked = int(line)
    child.send_signal(signal.SIGKILL)
    child.wait()
    assert acked >= 1000, f"child too slow, acked only {acked}"

    from garage_tpu.db import open_db

    db = open_db(path, engine="native", fsync="group")
    t = db.open_tree("gc")
    n = len(t)
    # prefix-contiguous: exactly keys 0..n-1 survive
    assert t.get(b"k%08d" % (n - 1)) is not None
    assert t.get(b"k%08d" % n) is None
    # bounded loss: the flusher syncs continuously (~200us/fdatasync);
    # even pessimistically the window is far below 2000 acked commits
    assert n >= acked - 2000, (n, acked)
    # regression note (advisor round 4, fixed with the observability PR):
    # flusher_main now checks the ::fdatasync(sfd) return value — on
    # failure seq_durable does NOT advance (kv_sync_barrier can no longer
    # report unsynced commits as durable; it fails fast on a sick
    # flusher), and a dup/fdatasync failure paces a bounded retry instead
    # of busy-spinning.  kv_sync_failures(h) counts those failures: on a
    # healthy disk it must be 0 after a full barrier round-trip.
    db.sync_barrier()
    assert db.kv.sync_failures(db.h) == 0
    db.close()
