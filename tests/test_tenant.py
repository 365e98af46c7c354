"""Tenant observatory (ISSUE 20, rpc/tenant.py): per-tenant usage
accounting fed from the authenticated S3 request path, bounded
cardinality under tenant churn, per-SLO-class burn math, the gossiped
`tn.*` digest keys, claimed-vs-authenticated reconciliation, the
`/v1/cluster/tenants` + CLI surfaces, and the 11-node acceptance gate
(cluster-summed consumption, fairness rollup, `tenant-hog` in the
merged cluster event timeline)."""

import asyncio
import json
import os
import sys
from types import SimpleNamespace

import pytest

sys.path.insert(0, os.path.dirname(__file__))
sys.path.insert(
    0, os.path.join(os.path.dirname(__file__), os.pardir, "script")
)

from garage_tpu.rpc import tenant as tenant_mod
from garage_tpu.rpc.tenant import (
    DEFAULT_CLASS,
    TenantObservatory,
    class_for,
    observatory,
    tenants_response,
)
from garage_tpu.utils.config import TenantClassConfig, config_from_dict
from garage_tpu.utils.metrics import Metrics


def run(coro):
    return asyncio.run(coro)


def _obs(topk=16, clock=None):
    """Fresh, enabled observatory with an isolated metrics registry
    (the module singleton is process-wide; units must not pollute it)."""
    o = TenantObservatory(
        topk=topk, halflife=None, clock=clock or (lambda: 0.0)
    )
    o.enabled = True
    o.registry = Metrics()
    return o


# --- unit: class resolution ---------------------------------------------------


def test_class_for():
    cfg = SimpleNamespace(
        tenants={
            "premium": TenantClassConfig(
                availability_target=99.99,
                latency_target_msec=250.0,
                keys=["GKPREM"],
            ),
            "batch": TenantClassConfig(
                availability_target=99.0,
                latency_target_msec=5000.0,
                keys=["GKBATCH"],
            ),
        }
    )
    def check(got, want):
        assert got[0] == want[0]
        assert got[1] == pytest.approx(want[1])
        assert got[2] == pytest.approx(want[2])

    check(class_for(cfg, "GKPREM"), ("premium", 0.9999, 0.25))
    check(class_for(cfg, "GKBATCH"), ("batch", 0.99, 5.0))
    # unknown keys fall to the built-in default targets
    check(class_for(cfg, "GKWHO"), (DEFAULT_CLASS, 0.999, 1.0))
    # ... unless a `default` class overrides them
    cfg.tenants["default"] = TenantClassConfig(
        availability_target=95.0, latency_target_msec=2000.0
    )
    check(class_for(cfg, "GKWHO"), (DEFAULT_CLASS, 0.95, 2.0))
    # a config with no [tenants] at all resolves too
    assert class_for(SimpleNamespace(), "GKX")[0] == DEFAULT_CLASS


# --- unit: bounded cardinality under churn ------------------------------------


def test_bounded_cardinality_under_tenant_churn():
    o = _obs(topk=16)
    # a hot tenant, then 500 one-shot churners trying to flood the rows
    for _ in range(50):
        o.record_request("GKHOT", "get", 0, 100, 0.001, is_err=False)
    for i in range(500):
        o.record_request(f"GKCHURN{i:04d}", "put", 64, 0, 0.002, is_err=False)
        assert len(o.tenants) <= 16, "row dict outgrew the sketch cap"
    # the hot tenant survived the churn with its exact row intact
    assert "GKHOT" in o.tenants
    assert o.tenants["GKHOT"]["ops"]["get"] == 50
    # pure-shed abusers ride the same admission: they must surface even
    # though no authenticated request ever lands
    for _ in range(40):
        o.record_shed("GKSHEDONLY")
    assert len(o.tenants) <= 16
    assert o.tenants["GKSHEDONLY"]["shed"] == 40
    snap = o.snapshot(top_n=16)
    assert snap["trackedTenants"] <= 16
    ids = {t["id"] for t in snap["tenants"]}
    assert "GKHOT" in ids and "GKSHEDONLY" in ids


# --- unit: burn math per SLO class --------------------------------------------


def test_burn_math_per_slo_class():
    o = _obs()
    batch = ("batch", 0.99, 5.0)      # allowed error fraction 0.01
    premium = ("premium", 0.999, 0.1)  # allowed 0.001, 100 ms target
    # identical failure pattern, different classes: 2% 5xx
    for i in range(100):
        err = i < 2
        o.record_request("GKB", "get", 0, 10, 0.001, is_err=err,
                         tenant_class=batch)
        o.record_request("GKP", "get", 0, 10, 0.001, is_err=err,
                         tenant_class=premium)
    rows = {t["id"]: t for t in o.snapshot(top_n=10)["tenants"]}
    # burn = bad-fraction / allowed-fraction, against the OWN class
    assert rows["GKB"]["burn"]["availability"] == pytest.approx(2.0)
    assert rows["GKP"]["burn"]["availability"] == pytest.approx(20.0)
    # latency burn: half the requests over the 100 ms premium target
    for i in range(100):
        o.record_request("GKL", "get", 0, 10,
                         0.2 if i % 2 else 0.001, is_err=False,
                         tenant_class=premium)
    rows = {t["id"]: t for t in o.snapshot(top_n=10)["tenants"]}
    assert rows["GKL"]["burn"]["latency"] == pytest.approx(500.0)
    assert rows["GKL"]["burn"]["worst"] == pytest.approx(500.0)
    # the 5 s batch target was never violated by 1 ms requests
    assert rows["GKB"]["burn"]["latency"] == 0.0
    # per-class exposition counters rode along, class-labelled
    c = o.registry.counters
    assert c[("api_tenant_class_requests_total",
              (("class", "batch"),))] == 100
    assert c[("api_tenant_class_errors_total",
              (("class", "premium"),))] == 2
    assert c[("api_tenant_class_over_latency_total",
              (("class", "premium"),))] == 50


def test_shed_class_resolution():
    o = _obs()
    o.class_resolver = lambda kid: "batch" if kid == "GKB" else None
    o.record_shed("GKB")
    o.record_shed("GKUNKNOWN")
    c = o.registry.counters
    assert c[("api_tenant_class_sheds_total", (("class", "batch"),))] == 1
    assert c[("api_tenant_class_sheds_total",
              (("class", DEFAULT_CLASS),))] == 1
    # a broken resolver must not turn a shed into a crash
    o.class_resolver = lambda kid: 1 / 0
    o.record_shed("GKB")
    assert o.total_sheds == 3
    assert c[("api_tenant_class_sheds_total",
              (("class", DEFAULT_CLASS),))] == 2


# --- unit: mismatch counter + enabled gate ------------------------------------


def test_mismatch_counter_and_enabled_gate():
    o = _obs()
    o.record_mismatch()
    o.record_mismatch()
    assert o.mismatches == 2
    assert o.snapshot()["claimedMismatches"] == 2
    # disabled: nothing records (the request path calls unconditionally)
    o.enabled = False
    o.record_mismatch()
    o.record_request("GKX", "get", 0, 0, 0.001, is_err=False)
    o.record_shed("GKX")
    assert o.mismatches == 2 and not o.tenants and o.total_sheds == 0


# --- unit: digest block -------------------------------------------------------


def test_digest_fields_bounded_and_serializable():
    o = _obs(topk=32)
    for i in range(20):
        for _ in range(20 - i):
            o.record_request(f"GKT{i:02d}", "get", 10, 10, 0.001,
                             is_err=(i == 0))
    o.record_shed("GKT00")
    o.record_mismatch()
    d = o.digest_fields(rps=4.5, top_n=5)
    assert d["trk"] == 20 and d["ops"] == sum(range(1, 21))
    assert d["rps"] == 4.5 and d["shed"] == 1 and d["mm"] == 1
    # bounded: top-N rows only, but top1/wburn summarize everything
    assert len(d["rows"]) == 5
    assert d["rows"][0]["id"] == "GKT00"  # hottest tenant leads
    assert d["top1"] == pytest.approx(20 / d["ops"], abs=1e-4)
    assert d["wburn"] > 0  # GKT00's errors burn its default budget
    # every row carries the window counts the rollup re-derives from
    for r in d["rows"]:
        assert {"id", "cls", "ops", "rps", "by", "shed", "burn",
                "an", "abad", "ln", "lbad"} <= set(r)
    json.dumps(d)  # wire-clean


# --- unit: config validation --------------------------------------------------


def test_tenant_config_validation():
    def cfg(extra):
        return config_from_dict(
            {"metadata_dir": "/tmp/x", "rpc_secret": "aa" * 32, **extra}
        )

    ok = cfg({"tenants": {"premium": {
        "availability_target": 99.99, "latency_target_msec": 250.0,
        "keys": ["GK1"]}}})
    assert ok.tenants["premium"].keys == ["GK1"]
    assert ok.admin.tenant_observatory is True
    assert ok.admin.tenant_topk == 64
    assert ok.admin.tenant_hog_share == 3.0
    for bad in (
        # class-name shape is the BOUNDED_LABEL_VALUES contract
        {"tenants": {"bad name!": {}}},
        {"tenants": {"": {}}},
        # 100% availability = zero allowed errors = infinite burn
        {"tenants": {"a": {"availability_target": 100.0}}},
        {"tenants": {"a": {"availability_target": 0.0}}},
        {"tenants": {"a": {"latency_target_msec": 0}}},
        # one key in two classes would make burn order-dependent
        {"tenants": {"a": {"keys": ["GK1"]}, "b": {"keys": ["GK1"]}}},
        {"admin": {"tenant_topk": 4}},
        {"admin": {"tenant_hog_share": 0.5}},
    ):
        with pytest.raises(ValueError):
            cfg(bad)


# --- unit: fairness rollup on synthetic rows ----------------------------------


def _tn_block(rows, *, ops, shed=0, mm=0, trk=None):
    return {
        "trk": trk if trk is not None else len(rows), "ops": ops,
        "rps": 1.0, "shed": shed, "mm": mm, "top1": 0.5, "wburn": 0.0,
        "rows": rows,
    }


def _tn_row(tid, cls, ops, an=0, abad=0, ln=0, lbad=0, shed=0):
    return {"id": tid, "cls": cls, "ops": ops, "rps": ops / 100.0,
            "by": ops * 100, "shed": shed, "burn": 0.0,
            "an": an, "abad": abad, "ln": ln, "lbad": lbad}


def _fake_garage(tn_blocks, tenants_cfg=None, hog_share=3.0,
                 digestless_peers=0):
    from garage_tpu.rpc.telemetry_digest import DIGEST_VERSION

    self_id = b"\x01" * 32
    peers = {}
    for i, tn in enumerate(tn_blocks[1:], start=2):
        peers[bytes([i]) * 32] = (
            SimpleNamespace(telemetry={"v": DIGEST_VERSION, "tn": tn}),
            0.0,
        )
    for i in range(digestless_peers):
        peers[bytes([0x40 + i]) * 32] = (
            SimpleNamespace(telemetry=None), 0.0
        )
    return SimpleNamespace(
        node_id=self_id,
        config=SimpleNamespace(
            tenants=tenants_cfg or {},
            admin=SimpleNamespace(tenant_hog_share=hog_share),
        ),
        system=SimpleNamespace(
            id=self_id,
            node_status=peers,
            expire_node_status=lambda: None,
            netapp=SimpleNamespace(is_connected=lambda pid: True),
        ),
        telemetry=SimpleNamespace(
            collect=lambda: {"v": DIGEST_VERSION, "tn": tn_blocks[0]}
        ),
    )


def test_fairness_rollup_on_synthetic_rows():
    # two nodes each saw A doing 4x B's and C's traffic; A is in the
    # cheap class and 2% of its requests erred
    node = [
        _tn_row("GKA", "batch", 400, an=400, abad=8),
        _tn_row("GKB", "premium", 100, an=100),
        _tn_row("GKC", "standard", 100, an=100),
    ]
    g = _fake_garage(
        [_tn_block(node, ops=600, mm=1), _tn_block(node, ops=600, mm=1)],
        tenants_cfg={
            "batch": TenantClassConfig(availability_target=99.0),
            "premium": TenantClassConfig(availability_target=99.99),
            "standard": TenantClassConfig(),
        },
        hog_share=1.5,
        digestless_peers=1,
    )
    r = tenants_response(g)
    c = r["cluster"]
    # the digest-less peer renders a clean null row, never an error
    assert len(c["nodes"]) == 3 and c["nodesReporting"] == 2
    assert [n for n in c["nodes"] if n["tenant"] is None]
    assert c["aggregate"]["ops"] == 1200
    assert c["aggregate"]["claimedMismatches"] == 2
    # cluster-summed consumption, sorted hottest first
    tl = c["tenants"]
    assert [t["id"] for t in tl] == ["GKA", "GKB", "GKC"]
    a = tl[0]
    assert a["ops"] == 800 and a["nodesReporting"] == 2
    assert a["share"] == pytest.approx(800 / 1200, abs=1e-4)
    # cluster-wide burn re-derived from SUMMED window counts against
    # the class targets: (16/800) / 0.01 = 2.0
    assert a["burn"]["availability"] == pytest.approx(2.0)
    f = c["fairness"]
    assert f["tenants"] == 3
    assert f["fairShare"] == pytest.approx(1 / 3, abs=1e-4)
    assert f["top1Share"] == a["share"]
    assert f["maxMedianRatio"] == pytest.approx(4.0)
    assert f["worstBurn"] >= 2.0
    # hog verdict: share 0.667 > 1.5 x fair (0.5)
    assert c["hog"] and c["hog"]["id"] == "GKA"
    assert c["hog"]["multiple"] == pytest.approx(2.0)
    json.dumps(r)
    # raising the warn multiple clears the verdict
    g.config.admin.tenant_hog_share = 3.0
    assert tenants_response(g)["cluster"]["hog"] is None


# --- live daemon: feed, digest, endpoints, CLI --------------------------------


def test_tenant_endpoints_and_digest_live(tmp_path):
    import aiohttp
    from test_s3_api import make_client, make_daemon, teardown

    from garage_tpu.api.admin.api_server import AdminApiServer
    from garage_tpu.cli.admin_rpc import AdminRpcHandler
    from garage_tpu.cli.main import dispatch
    from garage_tpu.net.message import Req
    from garage_tpu.utils.metrics import registry as global_reg

    async def main():
        garage, s3, endpoint = await make_daemon(tmp_path)
        garage.config.admin.admin_token = "tok"
        garage.telemetry.min_interval = 0.0
        adm = AdminApiServer(garage)
        await adm.start("127.0.0.1", 0)
        rpc = AdminRpcHandler(garage)
        observatory.reset()
        try:
            client = await make_client(garage, endpoint)
            garage.config.tenants = {
                "gold": TenantClassConfig(
                    availability_target=99.9,
                    latency_target_msec=30000.0,
                    keys=[client.key_id],
                )
            }
            req0 = global_reg.counters.get(
                ("api_tenant_class_requests_total", (("class", "gold"),)),
                0,
            )
            await client.create_bucket("tenb")
            for i in range(4):
                await client.put_object("tenb", f"k{i}", b"x" * 4000)
            for _ in range(10):
                await client.get_object("tenb", "k0")
            # in-process client + server share the loop: the finally
            # where the record lands can run after the client resumed
            await asyncio.sleep(0.05)

            # the authenticated feed landed in the observatory
            snap = observatory.snapshot()
            me = next(
                t for t in snap["tenants"] if t["id"] == client.key_id
            )
            assert me["class"] == "gold"
            assert me["ops"] >= 14 and me["opMix"]["get"] >= 10
            assert me["bytesIn"] >= 4 * 4000 and me["bytesOut"] >= 4000
            # claimed == authenticated for honest clients
            assert snap["claimedMismatches"] == 0
            # per-class counters rode the process registry
            assert global_reg.counters.get(
                ("api_tenant_class_requests_total", (("class", "gold"),)),
                0,
            ) - req0 >= 14

            # gossiped digest carries the additive tn block
            tn = garage.telemetry.collect()["tn"]
            assert tn["trk"] >= 1 and tn["ops"] >= 14
            assert tn["rows"][0]["id"] == client.key_id

            # canary-bucket traffic is synthetic: never attributed
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

            port = adm.runner.addresses[0][1]
            hdr = {"Authorization": "Bearer tok"}
            async with aiohttp.ClientSession(headers=hdr) as sess:
                async with sess.get(
                    f"http://127.0.0.1:{port}/v1/cluster/tenants"
                ) as r:
                    assert r.status == 200
                    t = await r.json()
                async with sess.get(
                    f"http://127.0.0.1:{port}/metrics/cluster"
                ) as r:
                    fed = await r.text()
            assert t["enabled"] is True
            assert t["cluster"]["nodesReporting"] == 1
            assert t["cluster"]["aggregate"]["ops"] >= 14
            top = t["cluster"]["tenants"][0]
            assert top["id"] == client.key_id and top["class"] == "gold"
            assert top["nodesReporting"] == 1

            # federated families render, lint clean, and the tenant KEY
            # ID never becomes a label (PR 12 cardinality rule)
            from dashboard_lint import lint_exposition

            lint_exposition(fed)
            assert "cluster_node_tenant_ops_total{node=" in fed
            assert "cluster_node_tenant_top1_share{node=" in fed
            assert client.key_id not in fed

            # CLI: cluster tenants renders the operator tables
            async def call(op, a=None):
                return (
                    await rpc._handle(b"\x00" * 32, Req([op, a or {}]))
                ).body

            out = await dispatch(
                SimpleNamespace(
                    json=False, cmd="cluster", cluster_cmd="tenants",
                    sort="ops", top=10,
                ),
                call, garage.config,
            )
            assert "== tenants (cluster-summed) ==" in out
            # the table truncates tenant ids to 20 chars for width
            assert client.key_id[:20] in out and "gold" in out
            # cluster top grew the hog column
            out = await dispatch(
                SimpleNamespace(
                    json=False, cmd="cluster", cluster_cmd="top",
                    once=True, interval=1.0,
                ),
                call, garage.config,
            )
            header = next(ln for ln in out.splitlines() if "cnry" in ln)
            assert "hog" in header
        finally:
            await adm.stop()
            await teardown(garage, s3)

    run(main())


# --- wire satellites ----------------------------------------------------------


def test_wire_schema_has_tn_keys():
    """The committed wire schema snapshot was regenerated for the
    additive `tn` digest block (graft-lint's committed-and-current test
    separately pins schema == tree)."""
    path = os.path.join(
        os.path.dirname(__file__), os.pardir, "script", "wire_schema.json"
    )
    with open(path) as f:
        schema = json.load(f)
    assert "tn" in schema["digest_keys"]
    from garage_tpu.rpc.telemetry_digest import DIGEST_VERSION

    assert schema["digest_version"] == DIGEST_VERSION  # additive keys, no bump


def test_tenant_rollup_digestless_old_peer(tmp_path):
    """A peer gossiping an old-style NodeStatus without the digest
    renders a clean `tenant: null` row — never an error, never
    dropped."""
    from test_ec_cluster import make_ec_cluster, stop_cluster

    from garage_tpu.rpc.system import NodeStatus

    async def main():
        garages = await make_ec_cluster(tmp_path, n=3, spawn=False)
        try:
            old_obj = garages[1].system.local_status().to_obj()
            old_obj.pop("tm", None)  # digest-less old peer
            fake_id = b"\x42" * 32
            garages[0].system._record_status(
                fake_id, NodeStatus.from_obj(old_obj)
            )
            t = tenants_response(garages[0])
            row = next(
                n for n in t["cluster"]["nodes"]
                if n["id"] == fake_id.hex()
            )
            assert row["tenant"] is None and row["isUp"] is False
            assert t["cluster"]["nodesReporting"] <= len(
                t["cluster"]["nodes"]
            ) - 1
            json.dumps(t)
        finally:
            await stop_cluster(garages)

    run(main())


# --- acceptance: 11-node EC(8,3) ----------------------------------------------


@pytest.mark.slow
def test_tenant_acceptance_11node(tmp_path):
    """ISSUE 20 acceptance: 3 tenants in distinct SLO classes + 1
    abusive tenant against an 11-node EC(8,3) cluster — the rollup on
    node0 reports all 11 nodes, the abusive tenant tops the
    cluster-summed consumption table with a hog verdict, and the
    `tenant-hog` event reaches the merged cluster event timeline."""
    import aiohttp
    from test_ec_cluster import make_ec_cluster, stop_cluster
    from test_s3_api import make_client

    from garage_tpu.api.admin.api_server import AdminApiServer
    from garage_tpu.api.s3.api_server import S3ApiServer
    from garage_tpu.rpc.transition import cluster_events_response

    async def main():
        garages = await make_ec_cluster(
            tmp_path, n=11, mode="ec:8:3", block_size=4096
        )
        g0 = garages[0]
        g0.config.admin.admin_token = "tok"
        for g in garages:
            g.telemetry.min_interval = 0.0
            # the in-process 11-node cluster easily burns the default
            # latency SLO; the ladder 503ing writes would corrupt the
            # workload (same pinning as the traffic acceptance test)
            if g.shedder is not None:
                g.shedder.signals = lambda consume=True: (0.0, 0.0)
            g.overload.set_shed_tier(None)
            g.config.admin.tenant_hog_share = 2.0
        s3 = S3ApiServer(g0)
        await s3.start("127.0.0.1", 0)
        adm = AdminApiServer(g0)
        await adm.start("127.0.0.1", 0)
        ep = f"http://127.0.0.1:{s3.runner.addresses[0][1]}"
        observatory.reset()
        clients = []
        try:
            names = ("premium", "standard", "batch", "abuser")
            tenants = {}
            for name in names:
                c = await make_client(g0, ep)
                clients.append(c)
                tenants[name] = c
            classes = {
                "premium": TenantClassConfig(
                    availability_target=99.99, latency_target_msec=250.0,
                    keys=[tenants["premium"].key_id],
                ),
                "standard": TenantClassConfig(
                    availability_target=99.9, latency_target_msec=1000.0,
                    keys=[tenants["standard"].key_id],
                ),
                "batch": TenantClassConfig(
                    availability_target=99.0, latency_target_msec=5000.0,
                    keys=[tenants["batch"].key_id,
                          tenants["abuser"].key_id],
                ),
            }
            for g in garages:
                g.config.tenants = classes

            body = os.urandom(1024)
            for name in names:
                await tenants[name].create_bucket(f"t-{name}")
                await tenants[name].put_object(f"t-{name}", "seed", body)
            for name in ("premium", "standard", "batch"):
                for _ in range(5):
                    await tenants[name].get_object(f"t-{name}", "seed")
            sem = asyncio.Semaphore(8)

            async def abuse(i):
                async with sem:
                    await tenants["abuser"].put_object(
                        "t-abuser", f"o{i:04d}", body
                    )

            await asyncio.gather(*[abuse(i) for i in range(90)])
            await asyncio.sleep(0.05)

            # every node's digest carries the tn block
            for _ in range(2):
                for g in garages:
                    await g.system.status_exchange_once()
                await asyncio.sleep(0.05)

            port = adm.runner.addresses[0][1]
            hdr = {"Authorization": "Bearer tok"}
            async with aiohttp.ClientSession(headers=hdr) as sess:
                async with sess.get(
                    f"http://127.0.0.1:{port}/v1/cluster/tenants"
                ) as r:
                    assert r.status == 200
                    t = await r.json()
                async with sess.get(
                    f"http://127.0.0.1:{port}/metrics/cluster"
                ) as r:
                    fed = await r.text()

            c = t["cluster"]
            assert len(c["nodes"]) == 11
            assert c["nodesReporting"] == 11, [
                n["id"] for n in c["nodes"] if n["tenant"] is None
            ]
            # the abusive tenant tops the cluster-summed table
            top = c["tenants"][0]
            assert top["id"] == tenants["abuser"].key_id
            assert top["class"] == "batch"
            assert top["share"] > 0.5, c["tenants"]
            assert c["fairness"]["tenants"] == 4
            assert c["fairness"]["top1Share"] == top["share"]
            # hog verdict at the 2.0x fair-share multiple
            assert c["hog"] and c["hog"]["id"] == top["id"]

            # tenant key ids stay out of the exposition labels
            from dashboard_lint import lint_exposition

            lint_exposition(fed)
            assert "cluster_node_tenant_ops_total{node=" in fed
            for cl in clients:
                assert cl.key_id not in fed

            # the tenant-hog event (emitted by the rollup above) reaches
            # the merged, skew-corrected cluster event timeline
            ev = await cluster_events_response(g0, since=0.0)
            assert len(ev["nodesResponding"]) == 11, ev["nodesFailed"]
            hogs = [e for e in ev["events"] if e["name"] == "tenant-hog"]
            assert hogs, {e["name"] for e in ev["events"]}
            assert hogs[0]["attrs"]["tenant"] == top["id"]
            assert hogs[0]["severity"] == "warn"
        finally:
            await adm.stop()
            await stop_cluster(garages, [s3], clients)

    run(main())
