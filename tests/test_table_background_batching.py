"""A table's best-effort background copies and the holder's side of them.

Sender (`table/background.py BackgroundCopies`, behind a fake RPC helper
whose calls can be held open, failed or left hanging):
  - a lone row leaves at once, at background priority;
  - at most one call is in flight to a destination, and the rows that
    arrive meanwhile leave together in the next call;
  - a failed call drops only its own rows, and the next call still goes;
  - a destination that hangs holds up no other;
  - a message carries at most `max_rows()` rows, read live;
  - the pending rows are bounded: the oldest are dropped and counted;
  - `close()` reaps the calls in flight.

Receiver (`TableData.update_entries`, reached through `Table._handle`):
  - a ``["U", values]`` of N values is one transaction;
  - an unchanged row writes nothing and runs no hook;
  - block_ref's `updated()` runs once per changed row: the counts and the
    arrival checks are those of N one-row messages;
  - the change waiters are notified once;
  - a row that raises rolls the whole message back.
"""

import asyncio
import os
import sys
import types

import pytest

sys.path.insert(0, os.path.dirname(__file__))

from garage_tpu.block.rc import BlockRc
from garage_tpu.db import open_db
from garage_tpu.model.s3.block_ref_table import BlockRef, BlockRefTable
from garage_tpu.net.message import PRIO_BACKGROUND, Req
from garage_tpu.table.background import PENDING_MESSAGES, BackgroundCopies
from garage_tpu.table.data import TableData
from garage_tpu.table.table import Table
from garage_tpu.utils.crdt import Bool, Lww
from garage_tpu.utils.metrics import registry
from garage_tpu.utils.serde import pack
from test_table import KvEntry, KvSchema  # noqa: E402


def nid(i: int) -> bytes:
    return bytes([i]) * 32


def run(coro, limit=10.0):
    return asyncio.run(asyncio.wait_for(coro, limit))


async def settle(turns: int = 5) -> None:
    for _ in range(turns):
        await asyncio.sleep(0)


# --- sender ---------------------------------------------------------------------


class FakeHelper:
    """Records every call; a node's calls wait on its gate while one is
    set, and fail while the node is in `fail`."""

    def __init__(self):
        self.calls: list[tuple[bytes, list, int]] = []
        self.gates: dict[bytes, asyncio.Event] = {}
        self.fail: set[bytes] = set()
        self.open: dict[bytes, int] = {}
        self.most_open: dict[bytes, int] = {}

    async def call(self, endpoint, node, msg, prio=None, **kw):
        assert msg[0] == "U"
        self.calls.append((node, list(msg[1]), prio))
        self.open[node] = self.open.get(node, 0) + 1
        self.most_open[node] = max(self.most_open.get(node, 0), self.open[node])
        try:
            gate = self.gates.get(node)
            if gate is not None:
                await gate.wait()
            if node in self.fail:
                raise ConnectionError("injected")
        finally:
            self.open[node] -= 1

    def rows_to(self, node: bytes) -> list[list]:
        return [rows for n, rows, _p in self.calls if n == node]


def counter(name: str, table: str = "spy") -> float:
    return registry.counters.get((name, (("table_name", table),)), 0.0)


def mk_copies(helper, max_rows=256, table="spy") -> BackgroundCopies:
    cap = max_rows if callable(max_rows) else (lambda: max_rows)
    return BackgroundCopies(helper, types.SimpleNamespace(path=f"table/{table}"), table, cap)


def test_a_lone_row_leaves_at_once_to_every_holder():
    async def main():
        h = FakeHelper()
        c = mk_copies(h)
        m0, r0 = counter("table_background_replicate_total"), counter("table_background_rows_total")
        c.send({nid(1), nid(2)}, [b"a"])
        # the calls are launched before send returns: nothing lingers
        assert set(c.in_flight) == {nid(1), nid(2)}
        await settle()
        assert sorted(h.calls) == [(nid(1), [b"a"], PRIO_BACKGROUND), (nid(2), [b"a"], PRIO_BACKGROUND)]
        assert counter("table_background_replicate_total") - m0 == 2  # one per message
        assert counter("table_background_rows_total") - r0 == 2
        assert not c.in_flight and not c.pending
        await c.close()

    run(main())


def test_one_call_in_flight_per_destination_and_the_rows_meanwhile_leave_together():
    async def main():
        h = FakeHelper()
        gate = h.gates[nid(1)] = asyncio.Event()
        c = mk_copies(h)
        c.send([nid(1)], [b"a"])
        await settle()
        for v in (b"b", b"c", b"d"):
            c.send([nid(1)], [v])
            await settle()
        assert h.rows_to(nid(1)) == [[b"a"]]  # still the first call
        assert c.pending[nid(1)] == [b"b", b"c", b"d"]
        gate.set()
        await settle(10)
        assert h.rows_to(nid(1)) == [[b"a"], [b"b", b"c", b"d"]]
        assert h.most_open[nid(1)] == 1
        assert not c.in_flight and not c.pending
        await c.close()

    run(main())


def test_a_failed_call_drops_only_its_rows_and_the_next_call_goes():
    async def main():
        h = FakeHelper()
        gate = h.gates[nid(1)] = asyncio.Event()
        h.fail.add(nid(1))
        c = mk_copies(h)
        c.send([nid(1)], [b"lost"])
        await settle()
        c.send([nid(1)], [b"next"])
        h.fail.clear()
        gate.set()
        await settle(10)
        assert h.rows_to(nid(1)) == [[b"lost"], [b"next"]]
        # and the destination takes a lone row at once again
        c.send([nid(1)], [b"later"])
        await settle()
        assert h.rows_to(nid(1))[-1] == [b"later"]
        await c.close()

    run(main())


def test_a_hanging_destination_holds_up_no_other():
    async def main():
        h = FakeHelper()
        h.gates[nid(1)] = asyncio.Event()  # never set
        c = mk_copies(h)
        for v in (b"a", b"b", b"c"):
            c.send([nid(1), nid(2)], [v])
            await settle()
        assert h.rows_to(nid(2)) == [[b"a"], [b"b"], [b"c"]]
        assert h.rows_to(nid(1)) == [[b"a"]]
        assert c.pending == {nid(1): [b"b", b"c"]}
        await c.close()

    run(main())


def test_a_message_carries_at_most_max_rows_read_live():
    async def main():
        h = FakeHelper()
        cap = {"n": 3}
        c = mk_copies(h, max_rows=lambda: cap["n"])
        rows = [bytes([i]) for i in range(7)]
        c.send([nid(1)], rows)
        await settle(10)
        assert h.rows_to(nid(1)) == [rows[:3], rows[3:6], rows[6:]]
        cap["n"] = 5  # `worker set meta-coalesce-max-entries`
        c.send([nid(1)], rows)
        await settle(10)
        assert h.rows_to(nid(1))[3:] == [rows[:5], rows[5:]]
        await c.close()

    run(main())


def test_the_pending_rows_are_bounded_oldest_dropped_and_counted():
    async def main():
        h = FakeHelper()
        gate = h.gates[nid(1)] = asyncio.Event()
        c = mk_copies(h, max_rows=2)
        bound = PENDING_MESSAGES * 2
        d0 = counter("table_background_dropped_total")
        c.send([nid(1)], [b"first"])
        await settle()
        rows = [b"r%d" % i for i in range(bound + 3)]
        for r in rows:
            c.send([nid(1)], [r])
        assert c.pending[nid(1)] == rows[3:]
        assert counter("table_background_dropped_total") - d0 == 3
        gate.set()
        await settle(20)
        sent = h.rows_to(nid(1))
        assert sent[0] == [b"first"]
        assert [r for msg in sent[1:] for r in msg] == rows[3:]
        assert all(len(msg) <= 2 for msg in sent)
        await c.close()

    run(main())


def test_close_reaps_the_calls_in_flight_and_sends_nothing_more():
    async def main():
        h = FakeHelper()
        h.gates[nid(1)] = asyncio.Event()  # never set
        c = mk_copies(h)
        c.send([nid(1)], [b"a"])
        c.send([nid(1)], [b"b"])
        await settle()
        task = c.in_flight[nid(1)]
        await c.close()
        assert task.cancelled()
        assert not c.in_flight and not c.pending
        c.send([nid(1), nid(2)], [b"c"])
        await settle()
        assert [rows for _n, rows, _p in h.calls] == [[b"a"]]

    run(main())


# --- receiver -------------------------------------------------------------------


class SpyDb:
    """The engine under test, its transactions counted."""

    def __init__(self, db):
        self.db = db
        self.transactions = 0

    def __getattr__(self, name):
        return getattr(self.db, name)

    def transaction(self, fn):
        self.transactions += 1
        return self.db.transaction(fn)


class HookedKv(KvSchema):
    """KvSchema whose `updated()` records each call and raises for the
    sort key b"boom"."""

    def __init__(self):
        self.hooks: list[tuple] = []

    def updated(self, tx, old, new):
        if new.sk == b"boom":
            raise RuntimeError("hook failed")
        self.hooks.append((new.sk, new.value.get()))


def kv(sk: bytes, ts: int, val: bytes) -> bytes:
    return pack(KvEntry(b"pk", sk, Lww(val, ts)).to_obj())


def mk_table_data(db, schema) -> TableData:
    return TableData(SpyDb(db), schema, types.SimpleNamespace(partition_of=lambda h: h[0]))


def handle_u(data: TableData, values: list[bytes]):
    """`Table._handle` of a ["U", values] request, as a holder runs it."""
    me = types.SimpleNamespace(_mlbl=(("table_name", data.schema.table_name),), data=data)
    return asyncio.run(Table._handle(me, nid(9), Req(["U", values])))


def test_a_message_of_n_values_is_one_transaction(db):
    data = mk_table_data(db, HookedKv())
    vals = [kv(b"k%d" % i, 1, b"v%d" % i) for i in range(5)]
    handle_u(data, vals)
    assert data.db.transactions == 1
    assert [data.decode(data.read_entry(b"pk", b"k%d" % i)).value.get() for i in range(5)] == [
        b"v%d" % i for i in range(5)]
    assert len(data.merkle_todo) == 5
    assert len(data.schema.hooks) == 5


def test_an_unchanged_row_writes_nothing_and_runs_no_hook(db):
    schema = HookedKv()
    data = mk_table_data(db, schema)
    assert data.update_entries([kv(b"a", 2, b"new"), kv(b"b", 1, b"x")]) == 2
    for k, _v in list(data.merkle_todo.iter_range()):
        data.merkle_todo.remove(k)
    schema.hooks.clear()
    stored = dict(data.store.iter_range())
    # the same rows again, and an older write of one: nothing changes
    assert data.update_entries([kv(b"a", 2, b"new"), kv(b"b", 1, b"x"), kv(b"a", 1, b"old")]) == 0
    assert schema.hooks == []
    assert len(data.merkle_todo) == 0
    assert dict(data.store.iter_range()) == stored
    # beside a changed row, only the changed one is written and hooked
    assert data.update_entries([kv(b"a", 2, b"new"), kv(b"c", 1, b"y")]) == 1
    assert schema.hooks == [(b"c", b"y")]
    assert [k for k, _v in data.merkle_todo.iter_range()] == [data.schema.tree_key(b"pk", b"c")]


def test_the_change_waiters_are_notified_once_a_message(db):
    data = mk_table_data(db, HookedKv())
    woken = []
    data.change_waiters.append(lambda: woken.append(1))
    data.update_entries([kv(b"k%d" % i, 1, b"v") for i in range(4)])
    assert len(woken) == 1
    data.update_entries([kv(b"k0", 1, b"v")])  # nothing changed: no wake-up
    assert len(woken) == 1


def test_a_row_that_raises_rolls_the_whole_message_back(db):
    schema = HookedKv()
    data = mk_table_data(db, schema)
    with pytest.raises(RuntimeError, match="hook failed"):
        handle_u(data, [kv(b"a", 1, b"x"), kv(b"boom", 1, b"y"), kv(b"c", 1, b"z")])
    assert data.read_entry(b"pk", b"a") is None
    assert data.read_entry(b"pk", b"c") is None
    assert len(data.merkle_todo) == 0
    assert len(data.store) == 0


class FakeResync:
    def __init__(self):
        self.arrival_checks: list[bytes] = []
        self.queued: list[bytes] = []

    def queue_arrival_check(self, hash32, tx):
        self.arrival_checks.append(hash32)

    def queue_block(self, hash32, delay_ms=0, tx=None):
        self.queued.append(hash32)


def mk_block_ref_data(tmp_path, name, engine):
    db = open_db(str(tmp_path / name), engine=engine)
    mgr = types.SimpleNamespace(rc=BlockRc(db), resync=FakeResync())
    return mk_table_data(db, BlockRefTable(mgr)), mgr


@pytest.mark.parametrize("engine", ["memory", "sqlite"])
def test_block_ref_hook_runs_once_per_changed_row_as_in_one_row_messages(tmp_path, engine):
    """Counts and arrival checks after one message of N rows equal those
    after the same rows sent one to a message: two versions of block A
    (one count each, one arrival check), block B referenced then its
    reference tombstoned in the same message (back to zero, queued), a
    duplicate row (no hook)."""
    ha, hb = b"A" * 32, b"B" * 32

    def ref(h, v, deleted=False):
        return pack(BlockRef(h, v, deleted=Bool(deleted)).to_obj())

    rows = [ref(ha, b"v1"), ref(ha, b"v2"), ref(hb, b"v1"), ref(ha, b"v1"), ref(hb, b"v1", deleted=True)]
    batched, mgr_b = mk_block_ref_data(tmp_path, "batched", engine)
    single, mgr_s = mk_block_ref_data(tmp_path, "single", engine)
    assert batched.update_entries(rows) == 4
    assert batched.db.transactions == 1
    for r in rows:
        single.update_entry(r)
    assert single.db.transactions == len(rows)
    for mgr in (mgr_b, mgr_s):
        assert mgr.rc.get(ha) == 2
        assert mgr.rc.get(hb) == 0
        assert mgr.resync.arrival_checks == [ha, hb]
        assert mgr.resync.queued == [hb]
    assert dict(batched.store.iter_range()) == dict(single.store.iter_range())
    assert dict(mgr_b.rc.tree.iter_range()).keys() == dict(mgr_s.rc.tree.iter_range()).keys()
