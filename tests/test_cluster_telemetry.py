"""Cluster telemetry plane (rpc/telemetry_digest.py): gossiped node
digests, one-stop federated rollup, SLO error budgets, outlier-node
detection."""

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

from dashboard_lint import families_in_exposition, lint_exposition

from garage_tpu.rpc.telemetry_digest import (
    DIGEST_VERSION,
    SloTracker,
    detect_outliers,
    rollup,
)
from garage_tpu.utils.metrics import Metrics


def run(coro):
    return asyncio.run(coro)


# --- unit: outlier detector ---------------------------------------------------


def _row(nid, p99=0.002, eps=0.0, rps=10.0, lag=0.001):
    return {
        "id": nid,
        "isSelf": False,
        "isUp": True,
        "ageSecs": 0.0,
        "digest": {
            "v": DIGEST_VERSION,
            "s3": {"rps": rps, "eps": eps, "p50": p99 / 2, "p99": p99},
            "loop": {"p99": lag, "blocked": 0},
        },
    }


def test_outlier_detection_unit():
    # one slow node among five near-identical ones: flagged, with reason
    rows = [_row(f"n{i}") for i in range(4)] + [_row("slow", p99=2.0)]
    out = detect_outliers(rows)
    assert set(out) == {"slow"}
    assert any("p99" in r for r in out["slow"])

    # a tight healthy cluster never flags noise-level deviation
    rows = [_row(f"n{i}", p99=0.002 + i * 0.0001) for i in range(5)]
    assert detect_outliers(rows) == {}

    # absolute minimum: 8 ms vs 2 ms is a big z-score but still healthy
    rows = [_row(f"n{i}") for i in range(4)] + [_row("meh", p99=0.008)]
    assert detect_outliers(rows) == {}

    # error-rate outlier (fraction of requests failing)
    rows = [_row(f"n{i}") for i in range(4)] + [_row("erry", eps=5.0)]
    assert set(detect_outliers(rows)) == {"erry"}

    # noise floor: a single transient 500 in a low-traffic window
    # (eps < 0.3/s) must NOT flag the node
    rows = [_row(f"n{i}", rps=1.0) for i in range(4)] + [
        _row("blip", rps=1.0, eps=0.1)
    ]
    assert detect_outliers(rows) == {}

    # malformed values inside a version-valid digest: skipped, not a crash
    bad = _row("weird")
    bad["digest"]["s3"]["p99"] = {"value": 2.0}
    rows = [_row(f"n{i}") for i in range(3)] + [bad]
    assert detect_outliers(rows) == {}

    # fewer than 3 nodes reporting: detector stays silent
    rows = [_row("a"), _row("b", p99=5.0)]
    assert detect_outliers(rows) == {}

    # digest-less (old-version) peers are skipped, not defaulted to 0
    rows = [_row(f"n{i}") for i in range(3)] + [
        {"id": "old", "isUp": True, "ageSecs": 0.0, "digest": None}
    ]
    assert detect_outliers(rows) == {}


# --- unit: SLO tracker --------------------------------------------------------


def test_slo_tracker_unit():
    m = Metrics()
    clock = [1000.0]
    tr = SloTracker(
        registry=m,
        availability_target=99.0,
        latency_target_msec=128.0,
        window_secs=60.0,
        clock=lambda: clock[0],
    )
    # no traffic: full budget, zero burn
    c = tr.compute()
    assert c["availability"]["budget_remaining"] == 1.0
    assert c["latency_p99"]["burn_rate"] == 0.0

    # 100 ok requests, all fast -> budget still full
    for _ in range(100):
        m.incr("api_s3_request_counter", (("method", "GET"),))
        m.observe("api_s3_request_duration", (("method", "GET"),), 0.004)
    clock[0] += 10
    c = tr.compute()
    assert c["availability"]["budget_remaining"] == 1.0
    assert c["latency_p99"]["budget_remaining"] == 1.0

    # 2 5xx out of the next 100: 2% bad vs 1% allowed -> budget blown
    for i in range(100):
        m.incr("api_s3_request_counter", (("method", "GET"),))
        m.observe("api_s3_request_duration", (("method", "GET"),), 0.004)
        if i < 2:
            m.incr(
                "api_s3_error_counter",
                (("method", "GET"), ("code", "500")),
            )
    # 4xx never burn availability budget
    m.incr("api_s3_error_counter", (("method", "GET"), ("code", "404")))
    clock[0] += 10
    c = tr.compute()
    assert abs(c["availability"]["bad_fraction"] - 0.01) < 1e-9  # 2/200
    assert abs(c["availability"]["burn_rate"] - 1.0) < 1e-9
    assert abs(c["availability"]["budget_remaining"]) < 1e-9
    assert c["latency_p99"]["budget_remaining"] == 1.0

    # 10 slow requests: latency budget burns independently
    for _ in range(10):
        m.incr("api_s3_request_counter", (("method", "PUT"),))
        m.observe("api_s3_request_duration", (("method", "PUT"),), 1.5)
    clock[0] += 10
    c = tr.compute()
    assert c["latency_p99"]["budget_remaining"] < 0  # 10/210 >> 1%

    # the rolling window forgets: an hour later the budget recovers
    clock[0] += 120  # > window
    c = tr.compute()
    assert c["availability"]["budget_remaining"] == 1.0
    assert c["latency_p99"]["budget_remaining"] == 1.0


def test_latency_threshold_snaps_to_nearest_bucket():
    """family_count_over snaps the SLO latency target to the NEAREST
    bucket bound: with a 1000 ms target, healthy 600-900 ms traffic must
    NOT be scored over-target (largest-bound-below would use 512 ms and
    blow the budget for a met SLO)."""
    m = Metrics()
    for _ in range(10):
        m.observe("api_s3_request_duration", (), 0.7)
    m.observe("api_s3_request_duration", (), 3.0)
    total, over = m.family_count_over("api_s3_request_duration", 1.0)
    assert (total, over) == (11, 1)


@pytest.mark.parametrize("buckets, over", [("request", 1), ("default", 11)])
def test_a_latency_target_of_tens_of_seconds_is_read_at_its_own_value(buckets, over):
    """The HTTP frontends' request histograms reach 65.5 s: a 30 s SLO
    target is cut at 32.768 s, so ten 10 s PUTs are under it and one of
    40 s over it, and the latency budget burns for the one alone.  On
    the 16 default buckets the cut was their last bound, 8.192 s, and
    all eleven burned it — which stepped the overload ladder to shedding
    writes under 20 clients of 10 MiB objects, where a PUT takes ~10 s."""
    from garage_tpu.utils.metrics import REQUEST_BUCKETS, registry as process_registry

    for fam in ("api_s3_request_duration", "api_k2v_request_duration", "web_request_duration"):
        assert process_registry._family_buckets[fam] == REQUEST_BUCKETS
    m = Metrics()
    if buckets == "request":
        m.set_buckets("api_s3_request_duration", REQUEST_BUCKETS)
    clock = [0.0]
    tr = SloTracker(registry=m, availability_target=99.9, latency_target_msec=30000.0,
                    window_secs=600.0, clock=lambda: clock[0])
    tr.compute()
    for lat in [10.0] * 10 + [40.0]:
        m.incr("api_s3_request_counter", (("method", "PUT"),))
        m.observe("api_s3_request_duration", (("method", "PUT"),), lat)
    clock[0] += 10
    c = tr.compute()["latency_p99"]
    assert (c["window_total"], c["window_bad"]) == (11, over)


def test_malformed_v1_digest_does_not_crash_aggregates():
    """A buggy peer can ship non-numeric values in a version-valid
    digest: the rollup aggregates and cluster-SLO sums must degrade
    (treat as 0/absent), never raise."""
    from garage_tpu.rpc.telemetry_digest import _dsum, _num

    assert _num("x") is None and _num({"v": 1}) is None
    assert _num("1.5") == 1.5 and _num(2) == 2.0
    rows = [
        {"digest": {"s3": {"rps": 2.0}}},
        {"digest": {"s3": {"rps": "garbage"}}},
        {"digest": {"s3": {"rps": {"nested": 1}}}},
    ]
    assert _dsum(rows, "s3", "rps") == 2.0


def test_digest_rates_use_fixed_window():
    """Frequent collect() triggers (scrapes, health checks) must not
    shrink the rate window: rates advance only every rate_window."""
    from test_s3_api import make_daemon, teardown

    async def main(tmp):
        garage, s3, _ep = await make_daemon(tmp)
        try:
            m = Metrics()
            tm = garage.telemetry
            tm.registry = m
            tm.min_interval = 0.0
            clock = [100.0]
            tm.clock = lambda: clock[0]
            tm.rate_window = 10.0
            # daemon boot already collected with the real clock; reset
            tm._prev = tm._rates = tm._cached = None

            m.incr("api_s3_request_counter", (), by=100)
            tm.collect()  # baseline
            m.incr("api_s3_request_counter", (), by=50)
            clock[0] += 3.0
            # a scrape-triggered collect INSIDE the window must not
            # reset the baseline or emit a partial-window rate
            assert tm.collect()["s3"]["rps"] == 0.0
            clock[0] += 7.0
            d = tm.collect()  # window complete: 50 requests / 10 s
            assert abs(d["s3"]["rps"] - 5.0) < 1e-9
            clock[0] += 3.0
            assert tm.collect()["s3"]["rps"] == 5.0  # held, not reset
        finally:
            await teardown(garage, s3)

    import tempfile
    from pathlib import Path

    run(main(Path(tempfile.mkdtemp())))


def test_newer_version_digest_degrades_to_no_digest():
    """A peer gossiping a FUTURE digest schema (or garbage) degrades to
    a digest-less row instead of crashing the rollup/federation."""
    from garage_tpu.rpc.telemetry_digest import _valid_digest

    assert _valid_digest({"v": DIGEST_VERSION, "s3": {}}) is not None
    assert _valid_digest(
        {"v": DIGEST_VERSION + 1, "s3": {"p99": {"value": 1}}}
    ) is None
    assert _valid_digest({"v": DIGEST_VERSION - 1, "s3": {}}) is None
    assert _valid_digest("garbage") is None
    assert _valid_digest(None) is None


# --- cluster: gossip convergence, federation, outliers, SLO -------------------


async def _converge(garages, waves=2, settle=0.05):
    for _ in range(waves):
        for g in garages:
            await g.system.status_exchange_once()
        await asyncio.sleep(settle)


def _isolate_digests(garages):
    """Give every in-process node its own metrics registry for digest
    assembly (they share the process-global one) and make collections
    uncached so each gossip wave refreshes."""
    regs = []
    for g in garages:
        m = Metrics()
        g.telemetry.registry = m
        g.telemetry.min_interval = 0.0
        regs.append(m)
    return regs


def _observe_latency(m, seconds, n=20):
    for _ in range(n):
        m.incr("api_s3_request_counter", (("method", "GET"),))
        m.observe("api_s3_request_duration", (("method", "GET"),), seconds)


def test_cluster_telemetry_acceptance(tmp_path):
    """ISSUE 5 acceptance: in an in-process 3-node cluster, ONE node's
    `GET /metrics/cluster` exposes digest families for every live node
    (distinct `node` labels) and passes the metrics-lint parser;
    `GET /v1/cluster/telemetry` flags the artificially slowed node as an
    outlier; `slo_error_budget_remaining` responds to injected S3
    errors."""
    import aiohttp

    from test_ec_cluster import make_ec_cluster, stop_cluster
    from test_s3_api import make_client

    from garage_tpu.api.admin.api_server import AdminApiServer
    from garage_tpu.api.s3.api_server import S3ApiServer

    async def main():
        garages = await make_ec_cluster(tmp_path, n=3, spawn=False)
        regs = _isolate_digests(garages)
        # healthy latency profile on nodes 0-1, a slowed node 2
        _observe_latency(regs[0], 0.002)
        _observe_latency(regs[1], 0.003)
        _observe_latency(regs[2], 2.0)

        garages[0].config.admin.admin_token = "tok"
        adm = AdminApiServer(garages[0])
        await adm.start("127.0.0.1", 0)
        s3 = S3ApiServer(garages[0])
        await s3.start("127.0.0.1", 0)
        ep = f"http://127.0.0.1:{s3.runner.addresses[0][1]}"
        base = f"http://127.0.0.1:{adm.runner.addresses[0][1]}"
        hdr = {"Authorization": "Bearer tok"}
        client = await make_client(garages[0], ep)
        try:
            # baseline the SLO window, then drive HEALTHY traffic
            async with aiohttp.ClientSession(headers=hdr) as sess:
                async with sess.get(base + "/metrics") as r:
                    assert r.status == 200
            await client.create_bucket("slo")
            for i in range(20):
                await client.put_object("slo", f"k{i}", b"x" * 100)
            await _converge(garages)

            async with aiohttp.ClientSession(headers=hdr) as sess:
                # --- federated exposition: all 3 nodes, lint-clean ---
                async with sess.get(base + "/metrics/cluster") as r:
                    assert r.status == 200
                    text = await r.text()
                types = lint_exposition(text)  # raises on violations
                assert types["cluster_node_up"] == "gauge"
                for fam in (
                    "cluster_node_s3_p99_seconds",
                    "cluster_node_s3_requests_per_second",
                    "cluster_node_resync_queue_length",
                    "cluster_node_uptime_seconds",
                ):
                    labels = {
                        ln.split('node="')[1].split('"')[0]
                        for ln in text.splitlines()
                        if ln.startswith(fam + "{")
                    }
                    assert labels == {
                        g.node_id.hex()[:16] for g in garages
                    }, (fam, labels)

                # --- the slowed node is the outlier ---
                slow_id = garages[2].node_id.hex()
                assert (
                    f'cluster_node_outlier{{node="{slow_id[:16]}"}} 1' in text
                )
                assert "cluster_outlier_nodes 1" in text

                async with sess.get(base + "/v1/cluster/telemetry") as r:
                    assert r.status == 200
                    roll = await r.json()
                assert len(roll["nodes"]) == 3
                assert roll["nodesReporting"] == 3
                assert set(roll["outliers"]) == {slow_id}
                assert any("p99" in s for s in roll["outliers"][slow_id])
                assert roll["clusterHealth"]["outlier_nodes"] == [slow_id]
                # aggregates sum the digests
                assert roll["aggregate"]["s3P99SecondsWorst"] >= 1.0

                # /v1/health surfaces the outlier set too (camelCase)
                async with sess.get(base + "/v1/health") as r:
                    assert (await r.json())["outlierNodes"] == [slow_id]

                # --- SLO budget responds to injected S3 errors ---
                async def budget(kind="availability"):
                    async with sess.get(base + "/metrics") as r:
                        txt = await r.text()
                    line = next(
                        ln for ln in txt.splitlines()
                        if ln.startswith(
                            f'slo_error_budget_remaining{{slo="{kind}"}}'
                        )
                    )
                    return float(line.rsplit(" ", 1)[1])

                before = await budget()
                assert before == 1.0  # healthy traffic only

                async def boom(*a, **kw):
                    raise RuntimeError("injected backend failure")

                orig = garages[0].helper.resolve_bucket
                garages[0].helper.resolve_bucket = boom
                try:
                    for i in range(10):
                        try:
                            await client.get_object("slo", f"k{i}")
                        except Exception:
                            pass  # 500s are the point
                finally:
                    garages[0].helper.resolve_bucket = orig
                await asyncio.sleep(0.15)  # past the compute() cache
                after = await budget()
                assert after < before, (before, after)
                # 10 bad / ~30 total vs 0.1% allowed: budget deeply blown
                assert after < 0

                async with sess.get(base + "/v1/cluster/telemetry") as r:
                    roll = await r.json()
                assert roll["slo"]["availability"]["budgetRemaining"] < 1.0
        finally:
            await adm.stop()
            await stop_cluster(garages, [s3], [client])

    run(main())


def test_stale_status_expiry_and_digestless_peers(tmp_path):
    """Satellites: a killed node ages out of node_status (and so out of
    the rollup and the federated exposition); a peer that sends an
    old-style digest-less NodeStatus keeps a row (no crash, no digest
    families, skipped by the outlier detector)."""
    from test_ec_cluster import make_ec_cluster, stop_cluster

    from garage_tpu.rpc.system import NodeStatus
    from garage_tpu.rpc.telemetry_digest import render_cluster_metrics

    async def main():
        garages = await make_ec_cluster(tmp_path, n=3, spawn=False)
        _isolate_digests(garages)
        await _converge(garages)
        roll = rollup(garages[0])
        assert len(roll["nodes"]) == 3

        # --- old peer: NodeStatus without the "tm" field -------------
        old_obj = garages[1].system.local_status().to_obj()
        old_obj.pop("tm", None)
        fake_id = b"\x42" * 32
        garages[0].system._record_status(
            fake_id, NodeStatus.from_obj(old_obj)
        )
        roll = rollup(garages[0])
        row = next(
            n for n in roll["nodes"] if n["id"] == fake_id.hex()
        )
        assert row["digest"] is None and row["isUp"] is False
        assert fake_id.hex() not in roll["outliers"]
        text = render_cluster_metrics(garages[0])
        lint_exposition(text)
        assert f'cluster_node_up{{node="{fake_id.hex()[:16]}"}} 0' in text
        # no digest families for the digest-less row
        assert (
            f'cluster_node_uptime_seconds{{node="{fake_id.hex()[:16]}"}}'
            not in text
        )

        # --- staleness: killed node + the fake peer age out ----------
        dead_id = garages[2].node_id
        await garages[2].stop()
        garages[0].system.status_expiry = 0.05
        await asyncio.sleep(0.15)
        roll = rollup(garages[0])  # _node_rows expires inline
        ids = {n["id"] for n in roll["nodes"]}
        assert dead_id.hex() not in ids
        assert fake_id.hex() not in ids
        assert len(roll["nodes"]) == 2
        text = render_cluster_metrics(garages[0])
        assert dead_id.hex()[:16] not in text

        await stop_cluster(garages[:2])

    run(main())


def test_digest_collects_with_running_repair_plan(tmp_path):
    """Regression: the digest's repair backlog reads the planner's
    queue_length() (the ledger lives on planner.plan, not the planner) —
    collection must not raise while a plan is active, which is exactly
    when the operator needs the rollup."""
    from test_ec_cluster import make_ec_cluster, stop_cluster

    async def main():
        garages = await make_ec_cluster(tmp_path, n=3, spawn=False)
        _isolate_digests(garages)
        g = garages[0]
        planner = g.launch_repair_plan()
        try:
            # a fresh planner is mid-scan: backlog must read as an int
            d = g.telemetry.collect()
            assert d["repair"]["backlog"] == planner.queue_length()
            assert g.system.local_status().telemetry is not None
        finally:
            planner.cmd_cancel()
            await stop_cluster(garages)

    run(main())


def test_cluster_cli_and_admin_rpc(tmp_path):
    """`cluster top --once` renders the rollup as a table and `cluster
    telemetry` as JSON through the real AdminRpc handler; `garage
    status` no longer lists an aged-out peer's hostname."""
    from test_s3_api import make_client, make_daemon, teardown

    from garage_tpu.cli.admin_rpc import AdminRpcHandler
    from garage_tpu.cli.main import dispatch
    from garage_tpu.net.message import Req

    async def main():
        garage, s3, endpoint = await make_daemon(tmp_path)
        adm = AdminRpcHandler(garage)

        async def call(op, a=None):
            return (await adm._handle(b"\x00" * 32, Req([op, a or {}]))).body

        def ns(**kw):
            return SimpleNamespace(json=False, **kw)

        try:
            client = await make_client(garage, endpoint)
            await client.create_bucket("top")
            await client.put_object("top", "k", b"z" * 5_000)
            garage.telemetry.min_interval = 0.0

            out = await dispatch(
                ns(cmd="cluster", cluster_cmd="top", once=True, interval=2.0),
                call, garage.config,
            )
            assert "cluster health" in out
            assert garage.node_id.hex()[:16] in out
            assert "slo budget" in out and "self" in out

            out = await dispatch(
                ns(cmd="cluster", cluster_cmd="telemetry"),
                call, garage.config,
            )
            roll = json.loads(out)
            assert roll["node"] == garage.node_id.hex()
            assert roll["nodes"][0]["digest"]["v"] == DIGEST_VERSION
            assert roll["slo"]["availability"]["budgetRemaining"] <= 1.0
        finally:
            await teardown(garage, s3)

    run(main())


def test_federation_families_match_doc_catalogue():
    """Every family the federated exposition can render is catalogued in
    doc/monitoring.md (the dashboard lint's allowlist)."""
    from dashboard_lint import DOC, families_in_doc

    from garage_tpu.rpc.telemetry_digest import _CLUSTER_FAMILIES

    doc = families_in_doc(DOC)
    fams = {f for f, _h, _s in _CLUSTER_FAMILIES} | {
        "cluster_node_outlier",
        "cluster_outlier_nodes",
        "cluster_nodes_reporting",
        "cluster_slo_error_budget_remaining",
        "cluster_slo_burn_rate",
        "slo_error_budget_remaining",
        "slo_burn_rate",
        "api_s3_error_counter",
    }
    missing = {f for f in fams if f not in doc}
    assert not missing, f"undocumented families: {missing}"


def test_exposition_family_extraction_helpers():
    text = (
        "# TYPE foo_total counter\nfoo_total 3\n"
        "# TYPE bar_duration histogram\n"
        'bar_duration_bucket{le="+Inf"} 1\nbar_duration_count 1\n'
        "bar_duration_sum 0.5\n"
    )
    assert lint_exposition(text) == {
        "foo_total": "counter",
        "bar_duration": "histogram",
    }
    assert families_in_exposition(text) >= {"foo_total", "bar_duration"}
