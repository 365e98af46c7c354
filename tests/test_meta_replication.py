"""ISSUE 15: metadata-ring replication + coalesced table write path.

Tier-1 coverage for the meta plane:
  - meta-ring derivation: distinctness, stability under layout
    versions, fallback when meta_rf exceeds the layout's own rf;
  - read-your-writes quorum arithmetic as a property over factors;
  - config validation of the `[meta]` section;
  - the block_ref hybrid (meta-ring quorums, full-stripe anti-entropy);
  - InsertCoalescer: cross-caller batching, error fan-out, linger.
"""

import asyncio
import types

import pytest

from garage_tpu.rpc.layout.history import LayoutHistory
from garage_tpu.rpc.layout.types import N_PARTITIONS, NodeRole
from garage_tpu.rpc.replication_mode import (
    ReplicationMode,
    read_quorum_for,
    write_quorum_for,
)
from garage_tpu.table.replication import (
    TableMetaReplication,
    TableStripeSyncedReplication,
    partition_first_hash,
)
from garage_tpu.utils.config import config_from_dict


def nid(i):
    return bytes([i]) * 32


def mk_history(rf, n, zones=None):
    h = LayoutHistory.initial(rf)
    for i in range(n):
        z = f"z{i}" if zones is None else f"z{i % zones}"
        h.staging.stage_role(nid(i), NodeRole(zone=z, capacity=10**11))
    h.apply_staged_changes()
    return h


def mk_sys(history):
    return types.SimpleNamespace(
        layout_manager=types.SimpleNamespace(history=history)
    )


def meta_rep(history, meta_rf=3, consistency="consistent"):
    return TableMetaReplication(
        mk_sys(history), ReplicationMode(meta_rf, consistency)
    )


# --- ring derivation ----------------------------------------------------------


def test_meta_ring_is_small_distinct_subset_of_the_stripe():
    """ec:8:3 shape: layout rf 11, meta rf 3 — every partition's meta
    set is exactly 3 DISTINCT nodes, a prefix of the partition's node
    list; block placement (the raw layout) still spans all 11."""
    h = mk_history(11, 11)
    rep = meta_rep(h, 3)
    assert rep.effective_rf() == 3
    for p in range(0, N_PARTITIONS, 17):
        fh = partition_first_hash(p)
        raw = h.read_nodes_of(fh)
        assert len(raw) == 11  # blocks keep the full stripe
        meta = rep.read_nodes(fh)
        assert len(meta) == 3
        assert len(set(meta)) == 3  # distinct
        assert meta == raw[:3]  # prefix of the layout order
        for s, raw_s in zip(rep.write_sets(fh), h.write_sets_of(fh)):
            assert s == raw_s[:3]
    assert (rep.read_quorum(), rep.write_quorum()) == (2, 2)


def test_meta_ring_stable_under_layout_versions():
    """A layout change that does not move a partition must not move its
    meta set either (the layout orders previous holders first), and
    during the transition every ACTIVE version contributes one meta
    write set."""
    h = mk_history(3, 6)
    rep = meta_rep(h, 3)
    before = {
        p: rep.read_nodes(partition_first_hash(p))
        for p in range(N_PARTITIONS)
    }
    # add a node: some partitions move, most don't
    h.staging.stage_role(nid(9), NodeRole(zone="z0", capacity=10**11))
    h.apply_staged_changes()
    assert len(h.versions) == 2  # migration open
    moved = 0
    for p in range(N_PARTITIONS):
        fh = partition_first_hash(p)
        sets = rep.write_sets(fh)
        assert len(sets) == 2  # one meta subset per active version
        old_v, new_v = h.versions
        assert sets[0] == rep.meta_nodes_of(old_v.nodes_of_partition(p))
        assert sets[1] == rep.meta_nodes_of(new_v.nodes_of_partition(p))
        if set(new_v.nodes_of_partition(p)) == set(
            old_v.nodes_of_partition(p)
        ):
            # unmoved partition: the meta subset is bit-identical
            assert sets[1] == before[p]
        else:
            moved += 1
    assert moved > 0  # the new node actually took partitions


def test_meta_ring_read_your_writes_across_transition():
    """Reads come from the read_version's meta subset; writes quorum in
    EVERY active version's subset — so the read subset is one of the
    write subsets and rq + wq > |subset| guarantees intersection."""
    h = mk_history(3, 4)
    h.staging.stage_role(nid(7), NodeRole(zone="z1", capacity=10**11))
    h.apply_staged_changes()
    rep = meta_rep(h, 3)
    for p in range(0, N_PARTITIONS, 31):
        fh = partition_first_hash(p)
        read_set = rep.read_nodes(fh)
        assert read_set in rep.write_sets(fh)
        assert rep.read_quorum() + rep.write_quorum() > len(read_set)


def test_meta_ring_fallback_when_rf_exceeds_storage():
    """Replica-mode layouts whose own rf is below the configured meta
    rf keep the full partition node list and quorum at the smaller
    effective factor."""
    for layout_rf in (1, 2):
        h = mk_history(layout_rf, 3)
        rep = meta_rep(h, 3)
        assert rep.effective_rf() == layout_rf
        fh = partition_first_hash(42)
        assert rep.read_nodes(fh) == h.read_nodes_of(fh)
        rq, wq = rep.read_quorum(), rep.write_quorum()
        assert rq + wq > layout_rf  # read-your-writes at the fallback rf
        assert rep.background_nodes(fh) == []


# --- quorum arithmetic property -----------------------------------------------


def test_quorum_arithmetic_read_your_writes_property():
    for rf in range(1, 13):
        rq = read_quorum_for(rf, "consistent")
        wq = write_quorum_for(rf, "consistent")
        assert rq + wq == rf + 1  # minimal intersecting pair
        assert rq + wq > rf
        m = ReplicationMode(rf, "consistent")
        assert (m.read_quorum(), m.write_quorum()) == (rq, wq)
        assert m.is_read_after_write_consistent()
        # degraded reads drop to 1 but writes rise to rf, so the pair
        # still intersects; only `dangerous` (1/1) gives up RYW
        assert ReplicationMode(rf, "degraded").is_read_after_write_consistent()
        if rf > 1:
            d = ReplicationMode(rf, "dangerous")
            assert not d.is_read_after_write_consistent()


# --- block_ref hybrid ---------------------------------------------------------


def test_stripe_synced_blockref_quorums_small_storage_wide():
    """block_ref: quorum sets are the meta ring, but storage / sync /
    local-partition ownership span the full stripe (every piece holder
    eventually stores the refs feeding its rc tree), and the non-quorum
    holders are exactly the background-copy targets."""
    h = mk_history(11, 11)
    rep = TableStripeSyncedReplication(
        mk_sys(h), ReplicationMode(3, "consistent")
    )
    fh = partition_first_hash(7)
    quorum_nodes = {n for s in rep.write_sets(fh) for n in s}
    assert len(quorum_nodes) == 3
    stripe = rep.storage_nodes(fh)
    assert len(stripe) == 11
    extra = rep.background_nodes(fh)
    assert set(extra) == set(stripe) - quorum_nodes
    # every stripe holder owns the partition for sync purposes
    for i in range(11):
        owned = {p for p, _fh in rep.local_partitions(nid(i))}
        held = {
            p
            for p in range(N_PARTITIONS)
            if nid(i) in h.current().nodes_of_partition(p)
        }
        assert owned == held
    # ...but a pure meta table only claims partitions whose meta subset
    # contains the node
    mrep = meta_rep(h, 3)
    for i in range(11):
        owned = {p for p, _fh in mrep.local_partitions(nid(i))}
        held = {
            p
            for p in range(N_PARTITIONS)
            if nid(i) in h.current().nodes_of_partition(p)[:3]
        }
        assert owned == held


# --- config validation --------------------------------------------------------


def base_cfg(**extra):
    d = {
        "metadata_dir": "/tmp/x",
        "data_dir": "/tmp/y",
        "rpc_secret": "aa" * 32,
    }
    d.update(extra)
    return d


def test_meta_config_defaults_and_validation():
    cfg = config_from_dict(base_cfg())
    assert cfg.meta.replication_factor == 3
    assert cfg.meta.coalesce_enabled

    with pytest.raises(ValueError, match="replication_factor must be >= 1"):
        config_from_dict(base_cfg(meta={"replication_factor": 0}))
    with pytest.raises(ValueError, match="coalesce_linger_msec"):
        config_from_dict(base_cfg(meta={"coalesce_linger_msec": -1}))
    with pytest.raises(ValueError, match="coalesce_max_entries"):
        config_from_dict(base_cfg(meta={"coalesce_max_entries": 0}))


def test_meta_config_explicit_rf_above_cluster_minimum_rejected():
    # replica mode "3": minimum cluster is 3 nodes — an explicit meta rf
    # of 5 could never place its ring
    with pytest.raises(ValueError, match="exceeds the cluster"):
        config_from_dict(
            base_cfg(replication_mode="3", meta={"replication_factor": 5})
        )
    # ec:8:3 (rf 11) happily takes meta rf 5
    cfg = config_from_dict(
        base_cfg(replication_mode="ec:8:3", meta={"replication_factor": 5})
    )
    assert cfg.meta.replication_factor == 5
    # the DEFAULT (unconfigured) meta rf never errors, even on rf-1
    # clusters — the ring falls back at runtime
    cfg = config_from_dict(base_cfg(replication_mode="1"))
    assert cfg.replication_factor == 1
    assert cfg.meta.replication_factor == 3


# --- insert coalescer ---------------------------------------------------------


class _SpyHelper:
    def __init__(self, fail=False, delay=0.0):
        self.calls = []
        self.fail = fail
        self.delay = delay

    async def try_write_many_sets(self, endpoint, write_sets, msg, quorum):
        if self.delay:
            await asyncio.sleep(self.delay)
        self.calls.append((write_sets, list(msg[1]), quorum))
        if self.fail:
            raise RuntimeError("injected quorum failure")


class _SpyTable:
    def __init__(self, helper):
        self.schema = types.SimpleNamespace(table_name="spy")
        self.helper = helper
        self.endpoint = None
        self.replication = types.SimpleNamespace(write_quorum=lambda: 2)
        self.background = []

    def replicate_background(self, rows_by_node):
        for node, rows in sorted(rows_by_node.items()):
            self.background.append(([node], list(rows)))


def _mk_coalescer(helper, **kw):
    from garage_tpu.table.coalesce import InsertCoalescer

    return InsertCoalescer(_SpyTable(helper), **kw)


def test_coalescer_merges_concurrent_callers_into_one_rpc():
    async def main():
        helper = _SpyHelper()
        c = _mk_coalescer(helper, linger_msec=20.0, max_entries=256)
        ws = [[nid(0), nid(1), nid(2)]]
        key = b"dest-key"
        await asyncio.gather(
            c.submit([(key, ws, [b"v1"], {nid(5): [b"v1"]})]),
            c.submit([(key, ws, [b"v2"], {})]),
            c.submit([(key, ws, [b"v3"], {nid(5): [b"v3"], nid(6): [b"v3"]})]),
        )
        # all three callers' entries shared ONE dispatch
        assert len(helper.calls) == 1
        sets, values, quorum = helper.calls[0]
        assert sorted(values) == [b"v1", b"v2", b"v3"]
        assert quorum == 2
        # background copies shipped once, after the quorum held, each
        # row to its own holders only
        assert c.table.background == [([nid(5)], [b"v1", b"v3"]), ([nid(6)], [b"v3"])]
        # different destinations never share a dispatch
        await asyncio.gather(
            c.submit([(b"k-a", ws, [b"a"], {})]),
            c.submit([(b"k-b", [[nid(3), nid(4), nid(5)]], [b"b"], {})]),
        )
        assert len(helper.calls) == 3
        await c.close()

    asyncio.run(main())


def test_coalescer_failure_fans_to_every_contributor():
    async def main():
        helper = _SpyHelper(fail=True)
        c = _mk_coalescer(helper, linger_msec=1.0)
        ws = [[nid(0), nid(1), nid(2)]]
        r = await asyncio.gather(
            c.submit([(b"k", ws, [b"v1"], {})]),
            c.submit([(b"k", ws, [b"v2"], {})]),
            return_exceptions=True,
        )
        assert all(isinstance(e, RuntimeError) for e in r)
        assert len(helper.calls) == 1  # one shared (failed) dispatch
        assert not c.table.background  # no background copies on failure
        await c.close()

    asyncio.run(main())


def test_coalescer_full_batch_flushes_before_linger():
    async def main():
        helper = _SpyHelper()
        # a generous linger, but max_entries=2 must flush immediately
        c = _mk_coalescer(helper, linger_msec=5_000.0, max_entries=2)
        ws = [[nid(0), nid(1), nid(2)]]
        await asyncio.wait_for(
            asyncio.gather(
                c.submit([(b"k", ws, [b"v1"], {})]),
                c.submit([(b"k", ws, [b"v2"], {})]),
            ),
            timeout=5.0,
        )
        assert len(helper.calls) == 1
        await c.close()

    asyncio.run(main())


def test_coalescer_close_fails_pending_waiters():
    async def main():
        helper = _SpyHelper()
        c = _mk_coalescer(helper, linger_msec=60_000.0)
        ws = [[nid(0), nid(1), nid(2)]]
        t = asyncio.create_task(c.submit([(b"k", ws, [b"v"], {})]))
        await asyncio.sleep(0.05)
        await c.close()
        with pytest.raises(RuntimeError, match="closed"):
            await t

    asyncio.run(main())


# --- background copies on an EC(8,3) cluster ------------------------------------


def test_every_stripe_holder_gets_every_block_ref_row_after_a_multi_block_put(tmp_path):
    """EC(8,3) on 11 nodes: block_ref quorums over a 3-node meta ring and
    the 8 other holders of each stripe get the rows best effort (a fresh
    PUT's row rides each holder's piece `Put`; any other row leaves as a
    background copy).  With no anti-entropy worker running, every one of
    the 11 nodes holds every block's count after a multi-block PUT, and
    nothing was dropped."""
    import os
    import sys

    sys.path.insert(0, os.path.dirname(__file__))
    from test_ec_cluster import make_ec_cluster, stop_cluster

    from garage_tpu.api.s3.api_server import S3ApiServer
    from garage_tpu.api.s3.client import S3Client
    from garage_tpu.utils.data import blake2sum
    from garage_tpu.utils.metrics import registry

    block, n_blocks = 8192, 6
    lbl = (("table_name", "block_ref"),)

    def counter(name):
        return registry.counters.get((name, lbl), 0.0)

    async def main():
        garages = await make_ec_cluster(
            tmp_path, n=11, mode="ec:8:3", block_size=block, spawn=False
        )
        s3 = S3ApiServer(garages[0])
        await s3.start("127.0.0.1", 0)
        key = await garages[0].helper.create_key("bg-copies")
        key.params().allow_create_bucket.update(True)
        await garages[0].key_table.insert(key)
        client = S3Client(
            f"http://127.0.0.1:{s3.runner.addresses[0][1]}", key.key_id, key.secret()
        )
        try:
            await client.create_bucket("bg-copies")
            body = os.urandom(n_blocks * block)
            hashes = [blake2sum(body[i : i + block]) for i in range(0, len(body), block)]
            rows0, dropped0 = counter("table_background_rows_total"), counter(
                "table_background_dropped_total"
            )
            carried0 = counter("table_rows_carried_total")
            await client.put_object("bg-copies", "obj", body)
            for _ in range(200):
                if all(g.block_manager.rc.get(h) == 1 for g in garages for h in hashes):
                    break
                await asyncio.sleep(0.05)
            missing = [
                (i, h.hex()[:8])
                for i, g in enumerate(garages)
                for h in hashes
                if g.block_manager.rc.get(h) != 1
            ]
            assert not missing, missing
            # each block's row reached its 8 non-ring holders, in their
            # piece `Put` or as a copy
            reached = counter("table_background_rows_total") - rows0
            reached += counter("table_rows_carried_total") - carried0
            assert reached >= 8 * n_blocks
            assert counter("table_background_dropped_total") == dropped0
            assert await client.get_object("bg-copies", "obj") == body
        finally:
            await stop_cluster(garages, [s3], [client])

    asyncio.run(asyncio.wait_for(main(), 120.0))
