"""Durability observatory (block/durability.py): redundancy ledger,
zone-loss exposure, repair ETA, layout-transition progress, resync
error ages, and the federated `dur.*` digest surfaces (ISSUE 14).
"""

import asyncio
import json
import os
import shutil
import sys
from types import SimpleNamespace

import pytest

sys.path.insert(0, os.path.dirname(__file__))
sys.path.insert(
    0, os.path.join(os.path.dirname(__file__), os.pardir, "script")
)

from dashboard_lint import lint_exposition

from garage_tpu.block.durability import (
    DUR_AT_RISK,
    DUR_DEGRADED,
    DUR_HEALTHY,
    DUR_UNREADABLE,
    classify_block,
    durability_response,
    zone_exposed,
)
from garage_tpu.utils.config import config_from_dict


def run(coro):
    return asyncio.run(coro)


# --- unit: classification -----------------------------------------------------


def test_classify_block_unit():
    # EC(8,3): width 11, k 8
    assert classify_block(11, 8, 11) == DUR_HEALTHY
    assert classify_block(10, 8, 11) == DUR_DEGRADED
    assert classify_block(9, 8, 11) == DUR_DEGRADED
    assert classify_block(8, 8, 11) == DUR_AT_RISK
    assert classify_block(7, 8, 11) == DUR_UNREADABLE
    assert classify_block(0, 8, 11) == DUR_UNREADABLE
    # replica rf=3: k=1 — any single live copy serves
    assert classify_block(3, 1, 3) == DUR_HEALTHY
    assert classify_block(2, 1, 3) == DUR_DEGRADED
    assert classify_block(1, 1, 3) == DUR_AT_RISK
    assert classify_block(0, 1, 3) == DUR_UNREADABLE


def test_zone_exposed_unit():
    # one live piece per zone, k=2: losing any zone leaves exactly k —
    # at_risk, but not BELOW the decode threshold: no exposure
    assert zone_exposed({"a": 1, "b": 1, "c": 1}, 3, 2) == []
    # k=3 over the same spread: any single zone loss drops below k
    assert set(zone_exposed({"a": 1, "b": 1, "c": 1}, 3, 3)) == {
        "a", "b", "c",
    }
    # k=2 with a zone holding 2 of 3 live pieces: only that zone exposes
    assert zone_exposed({"a": 2, "b": 1}, 3, 2) == ["a"]
    # full-width stripe with per-zone spread wide enough: nothing exposed
    assert zone_exposed({"a": 4, "b": 4, "c": 3}, 11, 7) == []
    # zones holding no live piece never expose
    assert zone_exposed({"a": 2, "b": 0}, 2, 1) == ["a"]


def test_zone_exposure_on_synthetic_layouts():
    """MAXIMUM zone redundancy spreads each partition over every zone
    (no single-zone loss drops below k); a FIXED zone_redundancy of 2
    lets a partition put 2 of 3 replicas in one zone — that zone's loss
    drops those stripes below k=2."""
    from garage_tpu.rpc.layout.types import NodeRole, ZoneRedundancy
    from garage_tpu.rpc.layout.version import LayoutVersion

    def build(zones, zr):
        roles = {
            bytes([i]) * 32: NodeRole(zone=z, capacity=1000)
            for i, z in enumerate(zones)
        }
        lv = LayoutVersion(1, 3, zr, roles=roles)
        lv.compute_assignment()
        return lv

    def exposed_partitions(lv, k):
        out = 0
        for p in range(len(lv.ring_assignment)):
            nodes = lv.nodes_of_partition(p)
            by_zone = {}
            for n in nodes:
                z = lv.roles[n].zone
                by_zone[z] = by_zone.get(z, 0) + 1
            if zone_exposed(by_zone, len(nodes), k):
                out += 1
        return out

    # 3 zones, MAXIMUM -> effective z=3, one replica per zone: losing a
    # zone leaves exactly k=2 — never BELOW k, nothing exposed
    lv = build(["a", "b", "c"], ZoneRedundancy.MAXIMUM)
    assert exposed_partitions(lv, k=2) == 0

    # same nodes, fixed zone_redundancy=2: partitions may double up in
    # a zone; every such partition is exposed to that zone's loss
    lv2 = build(["a", "a", "b", "c"], 2)
    assert exposed_partitions(lv2, k=2) > 0


def test_durability_config_validation():
    base = {"metadata_dir": "/tmp/x", "rpc_secret": "aa" * 32}
    cfg = config_from_dict({**base, "durability": {"tranquility": 5}})
    assert cfg.durability.tranquility == 5 and cfg.durability.enabled
    for bad in (
        {"scan_batch": 0},
        {"interval_secs": 0},
        {"tranquility": -1},
        {"stuck_error_secs": 0},
    ):
        with pytest.raises(ValueError):
            config_from_dict({**base, "durability": bad})


# --- helpers: in-process cluster + direct block population --------------------


async def _populate(garages, n_blocks, block_bytes=4096):
    """Write `n_blocks` EC-encoded blocks directly into each assigned
    node's store and reference them on every node's rc (the metadata
    tables are irrelevant to the scanner — fast and deterministic)."""
    from garage_tpu.block.manager import wrap_piece
    from garage_tpu.utils.data import blake2sum

    codec = garages[0].block_manager.codec
    layout = garages[0].layout_manager.history.current()
    by_id = {g.node_id: g for g in garages}
    hashes = []
    for i in range(n_blocks):
        data = os.urandom(block_bytes)
        h = blake2sum(data)
        pieces = codec.encode(data)
        nodes = layout.nodes_of(h)[: codec.n_pieces]
        for rank, nid in enumerate(nodes):
            await by_id[nid].block_manager.write_block_local(
                h, wrap_piece(len(data), pieces[rank]), False, piece=rank
            )
        hashes.append(h)
    for g in garages:
        bm = g.block_manager
        g.db.transaction(
            lambda tx, bm=bm: [bm.rc.incr(tx, h) for h in hashes] and None
        )
    return hashes


async def _scan_and_gossip(garages):
    for g in garages:
        g.telemetry.min_interval = 0.0
        await g.durability_scanner.scan_pass()
    for _ in range(2):
        for g in garages:
            await g.system.status_exchange_once()
        await asyncio.sleep(0.05)


async def _wait_disconnected(garages, victim_id, deadline=10.0):
    for _ in range(int(deadline / 0.05)):
        if all(
            not g.netapp.is_connected(victim_id) for g in garages
        ):
            return
        await asyncio.sleep(0.05)
    raise AssertionError("survivors never saw the victim disconnect")


def _agg(garage):
    return durability_response(garage)["cluster"]["aggregate"]


# --- tier-1 acceptance: kill m ranks -> degraded -> repair -> healthy ---------


def test_durability_convergence_ec21(tmp_path):
    """ISSUE 14 acceptance shape on the fast geometry (ec:2:1, 3
    nodes, spawn=False so every phase is driven deterministically):

      steady state      -> 100% healthy, exact totals, min margin m
      kill m=1 node     -> every block at_risk, exact count, alert event
      kill another      -> unreadable (live < k), min margin negative
      restart both (one with a wiped disk), drain resync -> healthy
      wipe the OWNER's disk in place, heal one block, scan
                        -> finite repair ETA mid-drain, then 100%
                           healthy again — cluster-wide via
                           /v1/cluster/durability and the CLI table

    NOTE on ownership: with rf == n the ring sorts every partition
    identically, so ONE node (lowest id) owns every block while
    connected — victims are chosen relative to it, and the ETA phase
    wipes the owner itself (its own-disk evidence is exact)."""
    import aiohttp

    from test_ec_cluster import make_ec_cluster

    from garage_tpu.api.admin.api_server import AdminApiServer
    from garage_tpu.model.garage import Garage
    from garage_tpu.utils import flight

    N = 24

    async def main():
        garages = await make_ec_cluster(tmp_path, n=3, spawn=False)
        extra = []  # restarted Garage instances to stop at teardown
        rec = flight.SlowRequestRecorder(threshold_ms=10 ** 9)
        flight.attach_recorder(rec)
        try:
            hashes = await _populate(garages, N)
            # rf == n: every stripe lists the same lowest-id node first,
            # so that node owns every block while connected
            layout = garages[0].layout_manager.history.current()
            owner_id = layout.nodes_of(hashes[0])[0]
            g0 = next(g for g in garages if g.node_id == owner_id)
            others = [g for g in garages if g is not g0]
            await _scan_and_gossip(garages)

            # --- steady state: 100% healthy, exact totals ---
            agg = _agg(g0)
            assert agg["blocksTotal"] == N and agg["healthy"] == N
            assert agg["healthyFraction"] == 1.0
            assert agg["minRedundancy"] == 1  # m = 1
            assert agg["atRisk"] == 0 and agg["unreadable"] == 0
            assert agg["missingPieces"] == 0
            assert agg["zoneExposure"] == {}  # ec:2:1 over 3 zones: any
            # single-zone loss leaves exactly k=2, never below
            # ETA is 0 (no backlog), and the digest round-trips it
            assert g0.durability_scanner.repair_eta_secs() == 0.0
            d = g0.telemetry.collect()
            assert d["dur"]["h"] == d["dur"]["tot"]
            rows = durability_response(g0)["cluster"]["nodes"]
            assert sum(r["durability"]["tot"] for r in rows) == N
            # layout settled: no transition in flight
            assert d["dur"]["lt"] == 1.0

            # --- admin endpoint + federated exposition + CLI ---
            g0.config.admin.admin_token = "tok"
            adm = AdminApiServer(g0)
            await adm.start("127.0.0.1", 0)
            base = f"http://127.0.0.1:{adm.runner.addresses[0][1]}"
            try:
                async with aiohttp.ClientSession(
                    headers={"Authorization": "Bearer tok"}
                ) as sess:
                    async with sess.get(
                        base + "/v1/cluster/durability"
                    ) as r:
                        assert r.status == 200
                        body = await r.json()
                    assert (
                        body["cluster"]["aggregate"]["healthyFraction"]
                        == 1.0
                    )
                    assert body["local"]["snapshot"]["healthy"] >= 0
                    async with sess.get(base + "/metrics/cluster") as r:
                        text = await r.text()
                    lint_exposition(text)  # raises on violations
                    for fam in (
                        "cluster_node_durability_blocks_healthy",
                        "cluster_node_durability_blocks_total",
                        "cluster_node_layout_sync_fraction",
                    ):
                        rows_ = [
                            ln for ln in text.splitlines()
                            if ln.startswith(fam + "{")
                        ]
                        assert len(rows_) == 3, (fam, rows_)
                    # minr is per-OWNED-block: non-owner rows have no
                    # sample (rf == n makes one node own everything)
                    minr_rows = [
                        ln for ln in text.splitlines()
                        if ln.startswith(
                            "cluster_node_durability_min_redundancy{"
                        )
                    ]
                    assert minr_rows and minr_rows[0].endswith(" 1")
                    # node-local registry gauges live after the passes
                    async with sess.get(base + "/metrics") as r:
                        mtext = await r.text()
                    assert 'durability_blocks{class="healthy"' in mtext
                    assert "durability_scan_age_seconds" in mtext
                    assert (
                        "block_resync_oldest_error_age_seconds" in mtext
                    )
            finally:
                await adm.stop()

            # CLI table through the real admin-RPC handler
            from garage_tpu.cli.admin_rpc import AdminRpcHandler
            from garage_tpu.cli.main import dispatch
            from garage_tpu.net.message import Req

            rpc = AdminRpcHandler(g0)

            async def call(op, a=None):
                resp = await rpc._handle(b"\x00" * 32, Req([op, a or {}]))
                return resp.body

            out = await dispatch(
                SimpleNamespace(
                    cmd="cluster", cluster_cmd="durability", json=False
                ),
                call, g0.config,
            )
            assert "observatory" in out and "100.0% healthy" in out
            out_json = await dispatch(
                SimpleNamespace(
                    cmd="cluster", cluster_cmd="durability", json=True
                ),
                call, g0.config,
            )
            assert json.loads(out_json)["cluster"]["aggregate"][
                "healthy"
            ] == N

            # --- kill m=1 (non-owner) rank: every block -> live == k ---
            v2 = others[1]
            v2_id, v2_cfg = v2.node_id, v2.config
            await v2.stop()
            await _wait_disconnected([g0, others[0]], v2_id)
            n_alerts0 = len(rec.records)
            await _scan_and_gossip([g0, others[0]])
            agg = _agg(g0)
            assert agg["atRisk"] == N, agg  # exact degraded count
            assert agg["healthy"] == 0 and agg["blocksTotal"] == N
            assert agg["minRedundancy"] == 0
            # backlog with NO observed drain (and no planner): ETA is
            # null — "stalled/unknown", deliberately distinct from 0
            assert g0.durability_scanner.repair_eta_secs() is None
            assert agg["repairEtaUnknownNodes"] == 1
            # the transition emitted a flight-recorder slow-ring event
            alerts = [
                r for r in rec.records
                if r.get("event") and r["name"].startswith(
                    "durability-alert"
                )
            ]
            assert alerts and len(rec.records) > n_alerts0
            assert any("at_risk" in a["name"] for a in alerts)
            # transitions alert ONCE: a re-scan adds no new events
            n_after = len(rec.records)
            for g in (g0, others[0]):
                await g.durability_scanner.scan_pass()
            assert len(rec.records) == n_after

            # --- kill the second non-owner rank: below k -> unreadable ---
            v1 = others[0]
            v1_id, v1_cfg = v1.node_id, v1.config
            await v1.stop()
            await _wait_disconnected([g0], v1_id)
            await _scan_and_gossip([g0])
            agg = _agg(g0)
            assert agg["unreadable"] == N and agg["atRisk"] == 0
            assert agg["minRedundancy"] == -1
            assert any(
                "unreadable" in r["name"]
                for r in rec.records
                if r.get("event")
            )

            # --- restore: restart both, v2 with a WIPED data dir ---
            for d_ in v2_cfg.data_dir:
                shutil.rmtree(d_.path, ignore_errors=True)
            v1b, v2b = Garage(v1_cfg), Garage(v2_cfg)
            extra += [v1b, v2b]
            await v1b.start()
            await v2b.start()
            assert v1b.node_id == v1_id and v2b.node_id == v2_id
            for gb in (v1b, v2b):
                for g in (g0, v1b, v2b):
                    if g is gb:
                        continue
                    await gb.netapp.connect(
                        g.netapp.bind_addr, g.node_id
                    )
            live = [g0, v1b, v2b]
            for _ in range(100):
                await asyncio.sleep(0.05)
                if all(
                    len(g.system.peering.connected_peers()) == 2
                    for g in live
                ):
                    break
            # memory db: the restarted nodes lost their rc entries —
            # re-reference directly (stands in for table anti-entropy
            # repopulating block_ref -> rc, which spawn=False skips)
            for gb in (v1b, v2b):
                bm = gb.block_manager
                gb.db.transaction(
                    lambda tx, bm=bm: [bm.rc.incr(tx, h) for h in hashes]
                    and None
                )

            # v1b kept its disk: immediately whole.  v2b's disk is gone
            # — invisible to the OWNER's liveness-based classification
            # (documented limit: a connected peer is assumed to hold its
            # pieces), but exact in v2b's OWN local-evidence ledger:
            sc2 = v2b.durability_scanner
            first = await sc2.scan_pass()
            assert first["localMissingPieces"] == N
            # resync reconstructs the wiped pieces from the survivors
            resync = v2b.block_manager.resync
            resync.queue_blocks(hashes)
            while await resync.resync_iter():
                pass
            done = await sc2.scan_pass()
            assert done["localMissingPieces"] == 0

            # --- cluster-wide: back to 100% healthy ---
            await _scan_and_gossip(live)
            agg = _agg(g0)
            assert agg["blocksTotal"] == N and agg["healthy"] == N
            assert agg["healthyFraction"] == 1.0
            assert agg["minRedundancy"] == 1

            # --- repair ETA: wipe the OWNER's disk in place ---
            # (its own ranks are DISK evidence -> every owned block
            # reads at_risk; healing one block between passes gives the
            # drain-rate EWMA a sample -> finite ETA while backlog > 0)
            for d_ in g0.config.data_dir:
                shutil.rmtree(d_.path, ignore_errors=True)
            sc0 = g0.durability_scanner
            wiped = await sc0.scan_pass()
            assert wiped["atRisk"] == N and wiped["missingPieces"] == N
            # the earlier restore drain seeded the rate EWMA: a backlog
            # against REMEMBERED throughput prices immediately
            assert sc0.repair_eta_secs() is not None
            r0 = g0.block_manager.resync
            r0.queue_blocks([hashes[0]])
            assert await r0.resync_iter()
            mid = await sc0.scan_pass()
            assert mid["missingPieces"] == N - 1
            eta = sc0.repair_eta_secs()
            assert eta is not None and 0 < eta < 10 ** 6
            r0.queue_blocks(hashes)
            while await r0.resync_iter():
                pass
            final = await sc0.scan_pass()
            assert final["missingPieces"] == 0
            assert final["healthy"] == N
            assert sc0.repair_eta_secs() == 0.0
            await _scan_and_gossip(live)
            assert _agg(g0)["healthyFraction"] == 1.0
        finally:
            flight.detach_recorder(rec)
            # the killed originals already ran stop(); g0 and the
            # restarted instances still hold sockets/dbs
            for g in [g0] + extra:
                try:
                    await g.stop()
                except Exception as e:  # noqa: BLE001 — teardown best-effort
                    print(f"teardown: {e!r}")

    run(main())


# --- resync error ages --------------------------------------------------------


def test_resync_error_age_tracking(tmp_path):
    """Error entries carry their FIRST-failure timestamp across
    retries; legacy 2-element entries read as unknown age; the worker
    status / admin op / digest surface the ages; success clears."""
    import msgpack

    from test_ec_cluster import make_ec_cluster, stop_cluster

    from garage_tpu.block.resync import _ResyncWorker, unpack_error
    from garage_tpu.cli.admin_rpc import AdminRpcHandler
    from garage_tpu.net.message import Req
    from garage_tpu.utils.time_util import now_msec

    async def main():
        garages = await make_ec_cluster(tmp_path, n=3, spawn=False)
        g0 = garages[0]
        resync = g0.block_manager.resync
        try:
            h = b"\x77" * 32
            boom = {"n": 0}

            async def failing(_h):
                boom["n"] += 1
                raise RuntimeError("injected resync failure")

            orig = resync._resync_block
            resync._resync_block = failing
            resync.queue_block(h)
            assert await resync.resync_iter()
            c1, _n1, first1 = unpack_error(resync.errors.get(h))
            assert c1 == 1 and first1 is not None
            # second failure: count advances, FIRST timestamp survives
            entry = unpack_error(resync.errors.get(h))
            resync.errors.insert(
                h, msgpack.packb([entry[0], now_msec() - 1, entry[2]])
            )
            resync.queue_block(h)
            assert await resync.resync_iter()
            c2, _n2, first2 = unpack_error(resync.errors.get(h))
            assert c2 == 2 and first2 == first1
            resync._age_cache = None
            age = resync.oldest_error_age_secs()
            assert age is not None and age >= 0.0

            # stuck-vs-transient: backdate the entry far past the cutoff
            resync.errors.insert(
                h,
                msgpack.packb(
                    [c2, now_msec() + 10_000, now_msec() - 3_600_000]
                ),
            )
            # plus a legacy 2-element entry: unknown age counts transient
            h2 = b"\x78" * 32
            resync.errors.insert(
                h2, msgpack.packb([1, now_msec() + 10_000])
            )
            assert unpack_error(resync.errors.get(h2))[2] is None
            transient, stuck = resync.error_age_counts(900.0)
            assert (transient, stuck) == (1, 1)
            resync._age_cache = None
            assert resync.oldest_error_age_secs() >= 3590

            # worker status + admin op + digest all carry the age
            st = _ResyncWorker(resync, 0).status()
            assert st["oldest_error_secs"] >= 3590
            rpc = AdminRpcHandler(g0)
            resp = await rpc._handle(
                b"\x00" * 32, Req(["block-list-errors", {}])
            )
            by_hash = {e["hash"]: e for e in resp.body}
            assert by_hash[h.hex()]["age_secs"] >= 3590
            assert by_hash[h2.hex()]["age_secs"] is None
            g0.telemetry.min_interval = 0.0
            d = g0.telemetry.collect()
            assert d["resync"]["age"] >= 3590
            # the ledger folds the split in
            snap = await g0.durability_scanner.scan_pass()
            assert snap["resyncErrors"]["stuck"] == 1
            assert snap["resyncErrors"]["transient"] == 1

            # success clears the entry (and the age with it)
            resync._resync_block = orig

            async def ok(_h):
                return "noop"  # _resync_block returns the entry's outcome label

            resync._resync_block = ok
            resync.errors.insert(
                h, msgpack.packb([c2, now_msec() - 1, first1])
            )
            resync.queue_block(h)
            assert await resync.resync_iter()
            assert resync.errors.get(h) is None
        finally:
            await stop_cluster(garages)

    run(main())


# --- digest / rollup plumbing -------------------------------------------------


def test_repair_urgency_digest_keys(tmp_path):
    """While a plan runs, the digest carries the urgency breakdown; a
    node without a plan gossips zeros (keys always present)."""
    from test_ec_cluster import make_ec_cluster, stop_cluster

    async def main():
        garages = await make_ec_cluster(tmp_path, n=3, spawn=False)
        g0 = garages[0]
        g0.telemetry.min_interval = 0.0
        try:
            d = g0.telemetry.collect()
            assert d["repair"] == {
                "backlog": 0, "cr": 0, "hi": 0, "lo": 0, "lost": 0,
            }
            planner = g0.launch_repair_plan()
            try:
                d = g0.telemetry.collect()
                urg = planner.backlog_by_urgency()
                assert d["repair"]["cr"] == urg["critical"]
                assert d["repair"]["lost"] == urg["lost"]
            finally:
                planner.cmd_cancel()
        finally:
            await stop_cluster(garages)

    run(main())


def test_durability_rollup_tolerates_missing_and_stale_rows():
    """Pure rollup math: digest-less peers render durability: null;
    disconnected peers' stale rows are excluded from aggregates."""
    from garage_tpu.block.durability import _num

    rows = [
        {"id": "a", "isUp": True,
         "durability": {"tot": 10, "h": 10, "dg": 0, "ar": 0, "ur": 0,
                        "mp": 0, "minr": 1, "eta": 0.0, "bkb": 0.0,
                        "zl": {"z1": 0}}},
        {"id": "dead", "isUp": False,
         "durability": {"tot": 10, "h": 10, "minr": 1}},
        {"id": "old", "isUp": True, "durability": None},
    ]
    up = [
        r for r in rows
        if r.get("isUp") and isinstance(r.get("durability"), dict)
        and r["durability"].get("tot") is not None
    ]
    assert [r["id"] for r in up] == ["a"]
    assert _num("nope") is None and _num("3.5") == 3.5


# --- slow: the full ec:8:3 geometry ------------------------------------------


@pytest.mark.slow
def test_durability_acceptance_ec83(tmp_path):
    """ISSUE 14 acceptance on the north-star geometry: in-process
    EC(8,3) 11-node cluster — steady state 100% healthy with exact
    totals; killing m=3 ranks converges every block to at_risk with the
    EXACT degraded count in the federated rollup; restarting the ranks
    (one disk wiped) and draining resync restores 100% healthy with a
    finite ETA observed mid-repair."""
    from test_ec_cluster import make_ec_cluster, stop_cluster

    from garage_tpu.model.garage import Garage

    N = 48

    async def main():
        garages = await make_ec_cluster(
            tmp_path, n=11, mode="ec:8:3", spawn=False
        )
        extra = []
        stopped = set()
        try:
            hashes = await _populate(garages, N, block_bytes=2048)
            # rf == n: the lowest-id node owns every block (see the
            # ec:2:1 test's note); victims must exclude it
            layout = garages[0].layout_manager.history.current()
            owner_id = layout.nodes_of(hashes[0])[0]
            g0 = next(g for g in garages if g.node_id == owner_id)
            await _scan_and_gossip(garages)
            agg = _agg(g0)
            assert agg["blocksTotal"] == N and agg["healthy"] == N
            assert agg["healthyFraction"] == 1.0
            assert agg["minRedundancy"] == 3  # m

            # kill exactly m = 3 non-owner ranks
            victims = [g for g in garages if g is not g0][:3]
            vids = [v.node_id for v in victims]
            vcfgs = [v.config for v in victims]
            for v in victims:
                await v.stop()
                stopped.add(id(v))
            survivors = [g for g in garages if id(g) not in stopped]
            for vid in vids:
                await _wait_disconnected(survivors, vid)
            await _scan_and_gossip(survivors)
            agg = _agg(g0)
            # every stripe lost exactly its 3 dead ranks: live == k
            assert agg["atRisk"] == N, agg
            assert agg["healthy"] == 0 and agg["blocksTotal"] == N
            assert agg["minRedundancy"] == 0
            assert agg["unreadable"] == 0
            # no drain ever observed, no planner: ETA reads null
            assert g0.durability_scanner.repair_eta_secs() is None

            # restart the three (first one with a wiped data dir)
            for d_ in vcfgs[0].data_dir:
                shutil.rmtree(d_.path, ignore_errors=True)
            restarted = [Garage(cfg) for cfg in vcfgs]
            extra += restarted
            for gb in restarted:
                await gb.start()
            live = survivors + restarted
            for gb in restarted:
                for g in live:
                    if g is gb:
                        continue
                    await gb.netapp.connect(
                        g.netapp.bind_addr, g.node_id
                    )
            for _ in range(200):
                await asyncio.sleep(0.05)
                if all(
                    len(g.system.peering.connected_peers()) == 10
                    for g in live
                ):
                    break
            for gb in restarted:
                bm = gb.block_manager
                gb.db.transaction(
                    lambda tx, bm=bm: [bm.rc.incr(tx, h) for h in hashes]
                    and None
                )
            # the wiped node reconstructs through resync; its OWN ledger
            # carries the disk truth (localMissingPieces)
            wiped = restarted[0]
            resync = wiped.block_manager.resync
            sc = wiped.durability_scanner
            first = await sc.scan_pass()
            assert first["localMissingPieces"] == N
            resync.queue_blocks(hashes)
            while await resync.resync_iter():
                pass
            done = await sc.scan_pass()
            assert done["localMissingPieces"] == 0

            await _scan_and_gossip(live)
            agg = _agg(g0)
            assert agg["blocksTotal"] == N and agg["healthy"] == N
            assert agg["healthyFraction"] == 1.0
            assert agg["minRedundancy"] == 3

            # finite repair ETA: wipe the OWNER in place (disk evidence
            # is exact), heal one block between passes -> drain EWMA
            for d_ in g0.config.data_dir:
                shutil.rmtree(d_.path, ignore_errors=True)
            sc0 = g0.durability_scanner
            w = await sc0.scan_pass()
            # one missing rank of 11: degraded (urgency low), not at_risk
            assert w["degraded"] == N and w["missingPieces"] == N
            assert w["minMargin"] == 2
            r0 = g0.block_manager.resync
            r0.queue_blocks([hashes[0]])
            assert await r0.resync_iter()
            mid = await sc0.scan_pass()
            assert mid["missingPieces"] == N - 1
            eta = sc0.repair_eta_secs()
            assert eta is not None and 0 < eta < 10 ** 6
            r0.queue_blocks(hashes)
            while await r0.resync_iter():
                pass
            final = await sc0.scan_pass()
            assert final["healthy"] == N
            await _scan_and_gossip(live)
            assert _agg(g0)["healthyFraction"] == 1.0
        finally:
            for g in [g for g in garages if id(g) not in stopped] + extra:
                try:
                    await g.stop()
                except Exception as e:  # noqa: BLE001 — teardown best-effort
                    print(f"teardown: {e!r}")

    run(main())
