"""Traffic heat observatory (ISSUE 12, rpc/traffic.py): streaming
hot-object analytics at the S3 request path, per-peer piece-fetch
attribution on the EC read path, gossiped `trf.*` digest keys, the
`/v1/traffic` + `/v1/traffic/profile` surfaces, and the 11-node EC(8,3)
acceptance gate (zipfian top-K precision, federated rollup, FaultPlan
slow-peer ranking)."""

import asyncio
import json
import os
import random
import sys
from collections import Counter
from types import SimpleNamespace

import pytest

sys.path.insert(0, os.path.dirname(__file__))
sys.path.insert(
    0, os.path.join(os.path.dirname(__file__), os.pardir, "script")
)

from garage_tpu.rpc import traffic as traffic_mod
from garage_tpu.rpc.traffic import (
    TrafficObservatory,
    classify_op,
    observatory,
)


def run(coro):
    return asyncio.run(coro)


# --- unit: op classification + observatory ------------------------------------


def test_classify_op():
    assert classify_op("GET", "k", {}) == "get"
    assert classify_op("GET", "", {}) == "list"
    assert classify_op("HEAD", "k", {}) == "head"
    assert classify_op("PUT", "k", {}) == "put"
    assert classify_op("DELETE", "k", {}) == "delete"
    assert classify_op("POST", "", {"delete": ""}) == "delete"
    # multipart initiate/complete are control-plane: their XML bodies
    # must not become "put" size samples the workload profile replays
    assert classify_op("POST", "k", {"uploads": ""}) == "other"
    assert classify_op("POST", "k", {"uploadId": "u1"}) == "other"
    assert classify_op("POST", "k", {}) == "put"  # PostObject form
    assert classify_op("OPTIONS", "k", {}) == "other"


def _fill(obs, n_keys=50, n=4000, s=1.2, seed=11):
    rng = random.Random(seed)
    weights = [1.0 / (i + 1) ** s for i in range(n_keys)]
    seq = rng.choices(range(n_keys), weights, k=n)
    for i in seq:
        obs.record_http("GET", "bench", f"k{i:03d}", {}, 4096, 0.005)
    return Counter(f"k{i:03d}" for i in seq)


def test_observatory_snapshot_and_digest():
    obs = TrafficObservatory(topk=64, halflife=None)
    obs.enabled = True
    true = _fill(obs)
    obs.record_http("PUT", "bench", "w", {}, 65536, 0.01)
    obs.record_http("GET", "", "", {}, 0, 0.001)  # list
    snap = obs.snapshot()
    assert snap["totalOps"] == 4002
    assert snap["opMix"]["get"] == 4000 and snap["opMix"]["list"] == 1
    assert 0.99 <= snap["readFraction"] <= 1.0
    # top-K tracks the true hot set
    got = [o["key"] for o in snap["hotObjects"][:10]]
    want = [k for k, _ in true.most_common(10)]
    assert len(set(got) & set(want)) >= 8
    # estimate brackets truth
    o0 = snap["hotObjects"][0]
    assert (
        o0["count"] - o0["errorBound"]
        <= true[o0["key"]]
        <= o0["count"] + 1e-9
    )
    assert snap["hotBuckets"][0]["bucket"] == "bench"
    assert snap["zipfS"] and snap["zipfS"] > 0.6
    assert sum(b["count"] for b in snap["sizeHistogram"]) == 4001
    # digest block: compact, numeric, additive
    d = obs.digest_fields(rps=3.5)
    assert d["ops"] == 4002 and d["rps"] == 3.5
    assert d["rd"] == 4000 and d["wr"] == 1 and d["ls"] == 1
    assert d["hb"] == "bench" and d["hbo"] > 0
    assert d["zipf"] == snap["zipfS"]
    # disabled observatory records nothing
    obs.enabled = False
    obs.record_http("GET", "bench", "k000", {}, 1, 0.001)
    assert obs.snapshot()["totalOps"] == 4002


def test_observatory_profile_is_replayable_contract():
    t = [0.0]
    obs = TrafficObservatory(topk=64, halflife=None, clock=lambda: t[0])
    obs.enabled = True
    for i in range(100):
        t[0] += 0.05  # steady 20 ops/s arrival process
        op = "put" if i % 10 == 0 else "get"
        obs.record_http(
            op.upper(), "b", f"k{i % 7}", {}, 1 << (10 + i % 3), 0.002
        )
    p = obs.profile()
    assert p["profileVersion"] == 1
    assert abs(sum(p["opMix"].values()) - 1.0) < 0.01
    assert p["opMix"]["get"] == 0.9 and p["opMix"]["put"] == 0.1
    assert abs(p["interArrival"]["meanSecs"] - 0.05) < 1e-6
    assert abs(p["interArrival"]["opsPerSec"] - 20.0) < 0.01
    assert p["interArrival"]["cv"] == 0.0  # perfectly periodic
    fr = [b["fraction"] for b in p["sizeDistribution"]["logTwoBuckets"]]
    assert abs(sum(fr) - 1.0) < 0.01
    assert p["popularity"]["topShares"][0] >= p["popularity"]["topShares"][-1]


def test_slow_peer_ranking_unit():
    from garage_tpu.rpc.peer_health import PeerHealth

    ph = PeerHealth(b"\x00" * 32)
    fast, slow, sick = b"\x01" * 32, b"\x02" * 32, b"\x03" * 32
    for _ in range(10):
        ph.record_piece_fetch(fast, 0.002, 4096)
        ph.record_piece_fetch(slow, 0.300, 4096)
    # breaker opens on the sick peer
    for _ in range(ph.open_after):
        ph.record_failure(sick)
    rows = ph.piece_fetch_ranking()
    assert [r["peer"] for r in rows] == [
        sick.hex(), slow.hex(), fast.hex()
    ]
    assert rows[0]["sick"] and rows[0]["state"] == "open"
    assert rows[1]["latMsecEwma"] > rows[2]["latMsecEwma"]
    assert rows[1]["pieceFetches"] == 10
    # our own id never ranks
    ph.record_piece_fetch(b"\x00" * 32, 9.0, 1)
    assert b"\x00" * 32 not in {bytes.fromhex(r["peer"]) for r in rows}


# --- live daemon: endpoints, digest keys, CLI ---------------------------------


def test_traffic_endpoints_and_digest_live(tmp_path):
    import aiohttp
    from test_s3_api import make_client, make_daemon, teardown

    from garage_tpu.api.admin.api_server import AdminApiServer
    from garage_tpu.cli.admin_rpc import AdminRpcHandler
    from garage_tpu.cli.main import dispatch
    from garage_tpu.net.message import Req

    async def main():
        garage, s3, endpoint = await make_daemon(tmp_path)
        garage.config.admin.admin_token = "tok"
        garage.telemetry.min_interval = 0.0  # uncached digests
        adm = AdminApiServer(garage)
        await adm.start("127.0.0.1", 0)
        rpc = AdminRpcHandler(garage)
        observatory.reset()
        try:
            client = await make_client(garage, endpoint)
            await client.create_bucket("hotb")
            for i in range(4):
                await client.put_object("hotb", f"k{i}", b"x" * 9000)
            for _ in range(20):
                await client.get_object("hotb", "k0")
            await client.get_object("hotb", "k1")
            # in-process client + server share the loop: the handler's
            # finally (where the record lands) can run after the client
            # coroutine resumed — give the server task a tick
            await asyncio.sleep(0.05)

            # gossiped digest carries the trf block
            trf = garage.telemetry.collect()["trf"]
            assert trf["ops"] >= 25 and trf["hb"] == "hotb"
            assert trf["rd"] >= 21 and trf["wr"] >= 4

            port = adm.runner.addresses[0][1]
            hdr = {"Authorization": "Bearer tok"}
            async with aiohttp.ClientSession(headers=hdr) as sess:
                async with sess.get(
                    f"http://127.0.0.1:{port}/v1/traffic"
                ) as r:
                    assert r.status == 200
                    t = await r.json()
                async with sess.get(
                    f"http://127.0.0.1:{port}/v1/traffic/profile"
                ) as r:
                    assert r.status == 200
                    prof = await r.json()
                async with sess.get(
                    f"http://127.0.0.1:{port}/metrics/cluster"
                ) as r:
                    fed = await r.text()

            assert t["enabled"] is True
            hot = t["local"]["hotObjects"]
            assert hot[0]["bucket"] == "hotb" and hot[0]["key"] == "k0"
            assert t["cluster"]["nodesReporting"] == 1
            assert t["cluster"]["hotBucket"]["bucket"] == "hotb"
            # the self row is present and carries traffic
            self_row = next(
                n for n in t["cluster"]["nodes"] if n["isSelf"]
            )
            assert self_row["traffic"]["ops"] >= 25

            assert prof["opMix"]["get"] > 0.5
            assert prof["interArrival"]["opsPerSec"] is not None

            # canary-bucket traffic is synthetic and never recorded —
            # an idle cluster must not report the prober as its hot
            # bucket nor bake probe noise into the replayable profile
            before = observatory.total_ops
            from garage_tpu.api.s3.client import S3Error

            try:
                await client.get_object(
                    garage.config.admin.canary_bucket, "probe-x"
                )
            except S3Error:
                pass
            await asyncio.sleep(0.05)
            assert observatory.total_ops == before

            # federated families render (and lint clean)
            from dashboard_lint import lint_exposition

            lint_exposition(fed)
            assert "cluster_node_traffic_ops_total{node=" in fed
            assert "cluster_node_traffic_read_fraction{node=" in fed
            # the hot bucket NAME never becomes a label
            assert 'bucket="hotb"' not in fed

            # CLI: cluster hot renders the operator table over admin RPC
            async def call(op, a=None):
                return (
                    await rpc._handle(b"\x00" * 32, Req([op, a or {}]))
                ).body

            out = await dispatch(
                SimpleNamespace(
                    json=False, cmd="cluster", cluster_cmd="hot",
                    profile=False, top=5,
                ),
                call, garage.config,
            )
            assert "hotb/k0" in out and "== hot objects ==" in out
            assert "op mix" in out
            out = await dispatch(
                SimpleNamespace(
                    json=False, cmd="cluster", cluster_cmd="hot",
                    profile=True, top=5,
                ),
                call, garage.config,
            )
            assert json.loads(out)["profileVersion"] == 1
            # cluster top: the hot column shows the hottest bucket
            out = await dispatch(
                SimpleNamespace(
                    json=False, cmd="cluster", cluster_cmd="top",
                    once=True, interval=1.0,
                ),
                call, garage.config,
            )
            header = next(
                ln for ln in out.splitlines() if "cnry" in ln
            )
            assert "hot" in header
            assert "hotb" in out
        finally:
            await adm.stop()
            await teardown(garage, s3)

    run(main())


def test_wire_schema_has_trf_keys():
    """Wire satellite: the committed wire schema snapshot was
    regenerated for the additive `trf` digest block (the graft-lint
    committed-and-current test separately pins schema == tree)."""
    path = os.path.join(
        os.path.dirname(__file__), os.pardir, "script", "wire_schema.json"
    )
    with open(path) as f:
        schema = json.load(f)
    assert "trf" in schema["digest_keys"]
    from garage_tpu.rpc.telemetry_digest import DIGEST_VERSION

    assert schema["digest_version"] == DIGEST_VERSION  # additive keys, no bump


def test_traffic_rollup_digestless_old_peer(tmp_path):
    """Wire satellite: a peer gossiping an old-style NodeStatus without
    the digest still renders a clean `traffic: null` row in /v1/traffic's
    cluster rollup — never an error, never dropped."""
    from test_ec_cluster import make_ec_cluster, stop_cluster

    from garage_tpu.rpc.system import NodeStatus
    from garage_tpu.rpc.traffic import traffic_response

    async def main():
        garages = await make_ec_cluster(tmp_path, n=3, spawn=False)
        try:
            old_obj = garages[1].system.local_status().to_obj()
            old_obj.pop("tm", None)  # digest-less old peer
            fake_id = b"\x42" * 32
            garages[0].system._record_status(
                fake_id, NodeStatus.from_obj(old_obj)
            )
            t = traffic_response(garages[0])
            row = next(
                n for n in t["cluster"]["nodes"]
                if n["id"] == fake_id.hex()
            )
            assert row["traffic"] is None and row["isUp"] is False
            # the row is excluded from aggregates, not defaulted to 0
            assert t["cluster"]["nodesReporting"] <= len(
                t["cluster"]["nodes"]
            ) - 1
            json.dumps(t)  # fully serializable
        finally:
            await stop_cluster(garages)

    run(main())


def test_piece_fetch_attribution_live(tmp_path):
    """EC read path feeds per-peer EWMAs + the bounded-label histogram."""
    from test_ec_cluster import make_ec_cluster, stop_cluster

    from garage_tpu.utils.metrics import registry

    async def main():
        garages = await make_ec_cluster(tmp_path, n=3, mode="ec:2:1")
        try:
            data = os.urandom(20_000)
            from garage_tpu.utils.data import blake2sum

            h = blake2sum(data)
            await garages[0].block_manager.rpc_put_block(h, data)
            # read from a node so remote piece fetches must happen
            got = await garages[2].block_manager.rpc_get_block(h)
            assert got == data
            ranking = garages[2].peer_health.piece_fetch_ranking()
            assert ranking, "remote piece fetches must rank peers"
            assert all(r["latMsecEwma"] is not None for r in ranking)
            fams = {
                n for (n, _l) in registry.durations
                if n == "block_piece_fetch_duration"
            }
            assert fams, "per-peer piece-fetch histogram observed"
            # label space is peer-bounded: never a key/bucket label
            for (n, labels) in registry.durations:
                if n == "block_piece_fetch_duration":
                    assert [k for k, _v in labels] == ["peer"]
        finally:
            await stop_cluster(garages)

    run(main())


# --- acceptance: 11-node EC(8,3) ----------------------------------------------


@pytest.mark.slow
def test_traffic_acceptance_11node_zipfian(tmp_path):
    """ISSUE 12 acceptance: under an injected zipfian workload on an
    11-node EC(8,3) cluster, /v1/traffic's top-K contains the true hot
    keys (precision >= 0.8 vs ground truth), the federated rollup
    aggregates all nodes, and with one FaultPlan-slowed peer the
    slow-peer ranking names it first."""
    import aiohttp
    from test_ec_cluster import make_ec_cluster, stop_cluster
    from test_s3_api import make_client

    from garage_tpu.api.admin.api_server import AdminApiServer
    from garage_tpu.api.s3.api_server import S3ApiServer
    from garage_tpu.net.fault import FaultPlan, FaultRule

    async def main():
        garages = await make_ec_cluster(
            tmp_path, n=11, mode="ec:8:3", block_size=4096
        )
        g0 = garages[0]
        g0.config.admin.admin_token = "tok"
        for g in garages:
            g.telemetry.min_interval = 0.0
            # an in-process 11-node cluster easily burns the default
            # latency SLO; the shedding ladder 503ing writes mid-test
            # would corrupt the workload
            if g.shedder is not None:
                g.shedder.signals = lambda consume=True: (0.0, 0.0)
            g.overload.set_shed_tier(None)
        s3 = S3ApiServer(g0)
        await s3.start("127.0.0.1", 0)
        adm = AdminApiServer(g0)
        await adm.start("127.0.0.1", 0)
        ep = f"http://127.0.0.1:{s3.runner.addresses[0][1]}"
        observatory.reset()
        clients = []
        try:
            client = await make_client(g0, ep)
            clients.append(client)
            await client.create_bucket("zipf")
            n_keys, n_reads = 40, 260
            body = os.urandom(12_000)  # 3 blocks/object at 4 KiB
            for i in range(n_keys):
                await client.put_object("zipf", f"obj{i:03d}", body)

            rng = random.Random(1234)
            weights = [1.0 / (i + 1) ** 1.2 for i in range(n_keys)]
            seq = rng.choices(range(n_keys), weights, k=n_reads)
            true = Counter(seq)
            sem = asyncio.Semaphore(8)

            async def one(i):
                async with sem:
                    assert await client.get_object(
                        "zipf", f"obj{i:03d}"
                    ) == body

            await asyncio.gather(*[one(i) for i in seq])
            await asyncio.sleep(0.05)  # let trailing records land

            # --- top-K precision vs ground truth ---------------------
            port = adm.runner.addresses[0][1]
            hdr = {"Authorization": "Bearer tok"}
            async with aiohttp.ClientSession(headers=hdr) as sess:
                async with sess.get(
                    f"http://127.0.0.1:{port}/v1/traffic"
                ) as r:
                    assert r.status == 200
                    t = await r.json()
            got = [
                o["key"] for o in t["local"]["hotObjects"]
                if o["bucket"] == "zipf"
            ][:10]
            want = {f"obj{i:03d}" for i, _ in true.most_common(10)}
            precision = len(set(got) & want) / 10
            assert precision >= 0.8, (precision, got, sorted(want))
            assert t["local"]["zipfS"] and t["local"]["zipfS"] > 0.5

            # --- federated rollup aggregates all nodes ---------------
            for _ in range(2):
                for g in garages:
                    await g.system.status_exchange_once()
                await asyncio.sleep(0.05)
            async with aiohttp.ClientSession(headers=hdr) as sess:
                async with sess.get(
                    f"http://127.0.0.1:{port}/v1/traffic"
                ) as r:
                    t = await r.json()
            rows = t["cluster"]["nodes"]
            assert len(rows) == 11
            assert t["cluster"]["nodesReporting"] == 11
            assert t["cluster"]["aggregate"]["ops"] > 0

            # --- FaultPlan-slowed peer ranks first -------------------
            # slow the MOST-FETCHED ranked peer by 600 ms (far above
            # loaded-box noise; a rarely-fetched victim might miss the
            # systematic rank sets of the re-read objects) and drive
            # hot-object GETs until its EWMA crosses the noise floor —
            # convergence-based, bounded by a deadline, because EWMA
            # alpha 0.2 needs several slowed samples and the box may be
            # under load
            import time as _time

            ranking0 = g0.peer_health.piece_fetch_ranking()
            assert ranking0, "EC reads should have ranked peers already"
            victim = bytes.fromhex(
                max(ranking0, key=lambda r: r["pieceFetches"])["peer"]
            )
            g0.netapp.fault_plan = FaultPlan(7).set_rule(
                FaultRule(latency_ms=600.0), peer=victim
            )
            deadline = _time.monotonic() + 90.0
            while True:
                for i, _n in true.most_common(12):
                    await client.get_object("zipf", f"obj{i:03d}")
                ranking = g0.peer_health.piece_fetch_ranking()
                if ranking and ranking[0]["peer"] == victim.hex():
                    break
                assert _time.monotonic() < deadline, (
                    "slowed peer never topped the ranking",
                    victim.hex(),
                    ranking[:3],
                )
            # surfaced through the endpoint too
            async with aiohttp.ClientSession(headers=hdr) as sess:
                async with sess.get(
                    f"http://127.0.0.1:{port}/v1/traffic"
                ) as r:
                    t = await r.json()
            assert t["slowPeers"][0]["peer"] == victim.hex()
        finally:
            await adm.stop()
            await stop_cluster(garages, [s3], clients)

    run(main())
