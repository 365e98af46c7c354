"""Overload-control plane (ISSUE 8): per-tenant admission control
(api/overload.py) + SLO-driven shedding ladder (rpc/shedding.py).

Tier-1: token-bucket math, tier classification, ladder hysteresis
(fake clock), 503 SlowDown XML shape + Retry-After, queue-rather-than-
reject for the interactive tier, canary exemption at max shed level,
digest/admin/CLI surfaces, config validation, and the SLO-protection
invariant (a shed is not an S3 error).

Slow: the 11-node EC(8,3) burst — 4x offered load sheds the lowest
tier, admitted traffic stays within the declared latency SLO, the
ladder steps up and back down, and the canary stays live throughout.
"""

import asyncio
import os
import sys
from types import SimpleNamespace

import pytest

sys.path.insert(0, os.path.dirname(__file__))

from garage_tpu.api.overload import (
    TIER_ANON,
    TIER_INTERACTIVE,
    TIER_LIST,
    TIER_WRITE,
    AdmissionController,
    TokenBucket,
)
from garage_tpu.utils.config import OverloadConfig, config_from_dict
from garage_tpu.utils.metrics import Metrics


def run(coro):
    return asyncio.run(coro)


class FakeClock:
    def __init__(self, t=1000.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


def _req(method="GET", auth=True, query=None, key_id="GKtest"):
    headers = {}
    if auth:
        headers["Authorization"] = (
            f"AWS4-HMAC-SHA256 Credential={key_id}/20260804/garage/s3/"
            "aws4_request, SignedHeaders=host, Signature=deadbeef"
        )
    return SimpleNamespace(method=method, headers=headers, query=query or {})


# --- token bucket -------------------------------------------------------------


def test_token_bucket_refill_and_burst():
    clk = FakeClock()
    b = TokenBucket(rate=10.0, burst=20.0, clock=clk)
    # full burst available up front
    for _ in range(20):
        assert b.take()
    assert not b.take()
    assert b.time_until() == pytest.approx(0.1)
    # refills at `rate`, capped at `burst`
    clk.advance(0.5)
    assert b.level() == pytest.approx(5.0)
    clk.advance(100.0)
    assert b.level() == pytest.approx(20.0)


# --- classification -----------------------------------------------------------


def test_classify_tiers():
    c = AdmissionController.classify
    kid = "GKtest"
    # interactive: authenticated object GET/HEAD
    assert c(_req("GET"), "obj", kid) == TIER_INTERACTIVE
    assert c(_req("HEAD"), "obj", kid) == TIER_INTERACTIVE
    # writes: PUT/POST/DELETE objects + multipart legs
    assert c(_req("PUT"), "obj", kid) == TIER_WRITE
    assert c(_req("POST", query={"uploads": ""}), "obj", kid) == TIER_WRITE
    assert c(_req("DELETE"), "obj", kid) == TIER_WRITE
    assert c(_req("PUT"), "", kid) == TIER_WRITE  # CreateBucket
    # list/batch: bucket-level reads, ListParts, DeleteObjects
    assert c(_req("GET"), "", kid) == TIER_LIST
    assert c(_req("GET", query={"uploadId": "u"}), "obj", kid) == TIER_LIST
    assert c(_req("POST", query={"delete": ""}), "", kid) == TIER_LIST
    # anonymous: no credential anywhere
    assert c(_req("GET", auth=False), "obj", None) == TIER_ANON


def test_claimed_key_id():
    ck = AdmissionController.claimed_key_id
    assert ck(_req(key_id="GKabc")) == "GKabc"
    assert ck(_req(auth=False)) is None
    presigned = SimpleNamespace(
        method="GET", headers={},
        query={"X-Amz-Credential": "GKpre/20260804/garage/s3/aws4_request"},
    )
    assert ck(presigned) == "GKpre"


# --- admission unit -----------------------------------------------------------


def _ctl(registry=None, clock=None, **over):
    cfg = OverloadConfig(**over)
    return AdmissionController(
        cfg, registry=registry or Metrics(), clock=clock or FakeClock()
    )


def test_admit_token_exhaustion_sheds_lower_tiers():
    async def main():
        ctl = _ctl(key_rate=1.0, key_burst=2.0)
        r = _req("PUT")
        t1 = await ctl.admit(r, "b", "k")
        t2 = await ctl.admit(r, "b", "k")
        assert t1.admitted and t2.admitted
        t3 = await ctl.admit(r, "b", "k")
        assert not t3.admitted
        assert t3.retry_after >= 1.0
        assert ctl.counts["shed"][TIER_WRITE] == 1
        t1.release()
        t2.release()
        assert ctl.in_flight == 0
        # tenant isolation: a different key still has its own budget
        t4 = await ctl.admit(_req("PUT", key_id="GKother"), "b2", "k")
        assert t4.admitted
        t4.release()

    run(main())


def test_interactive_queues_for_in_flight_slot():
    async def main():
        ctl = _ctl(max_in_flight=1, queue_wait_msec=2000.0)
        ctl.clock = __import__("time").monotonic  # real clock for the wait
        first = await ctl.admit(_req("GET"), "b", "k")
        assert first.admitted

        async def second():
            return await ctl.admit(_req("GET"), "b", "k2")

        task = asyncio.create_task(second())
        await asyncio.sleep(0.05)
        assert not task.done()  # queued, not shed
        first.release()
        t2 = await asyncio.wait_for(task, 2.0)
        assert t2.admitted and t2.queued
        # the ticket reports how long it sat in the queue — the api
        # server folds this into api_s3_request_duration so queueing
        # under load is visible to the latency-SLO burn signal
        assert t2.queued_secs > 0.0
        assert ctl.counts["queued"][TIER_INTERACTIVE] == 1
        t2.release()
        # a WRITE at the cap sheds immediately instead of queueing
        hold = await ctl.admit(_req("GET"), "b", "k")
        w = await ctl.admit(_req("PUT"), "b", "k3")
        assert not w.admitted
        hold.release()

    run(main())


def test_interactive_queue_bounded_wait_then_sheds():
    async def main():
        ctl = _ctl(max_in_flight=1, queue_wait_msec=80.0)
        ctl.clock = __import__("time").monotonic
        first = await ctl.admit(_req("GET"), "b", "k")
        t2 = await ctl.admit(_req("GET"), "b", "k2")
        assert not t2.admitted  # slot never freed within the bound
        assert ctl.counts["shed"][TIER_INTERACTIVE] == 1
        first.release()

    run(main())


def test_shed_tier_actuator_and_exemption():
    async def main():
        ctl = _ctl()
        ctl.set_shed_tier(TIER_WRITE)
        assert not (await ctl.admit(_req("PUT"), "b", "k")).admitted
        assert not (await ctl.admit(_req("GET"), "", "")).admitted  # list
        # interactive is never shed by the ladder (floor is TIER_WRITE)
        ctl.set_shed_tier(0)
        assert ctl.shed_from_tier == TIER_WRITE
        g = await ctl.admit(_req("GET"), "b", "k")
        assert g.admitted
        g.release()
        # exempt key sails through a full shed
        ctl.exempt_key("GKcanary")
        t = await ctl.admit(_req("PUT", key_id="GKcanary"), "b", "k")
        assert t.admitted
        t.release()
        assert ctl.exempt_admitted == 1
        ctl.set_shed_tier(None)
        assert (await ctl.admit(_req("PUT"), "b", "k")).admitted

    run(main())


def test_per_tenant_gauges_registered_and_evicted():
    async def main():
        reg = Metrics()
        ctl = _ctl(registry=reg, max_tracked_tenants=2)
        for i in range(4):
            (await ctl.admit(_req("PUT", key_id=f"GK{i}"), f"b{i}", "k")).release()
        keys = [k for (n, k) in reg._gauge_fns if n == "api_admission_key_tokens"]
        assert len(keys) == 2  # LRU-bounded, evicted gauges unregistered
        ctl.close()
        assert not any(
            n.startswith("api_admission_") for (n, _l) in reg._gauge_fns
        )

    run(main())


def test_exempt_bypass_is_concurrency_bounded():
    """The exemption is keyed on the CLAIMED (pre-auth) key id, which is
    not a secret — a spoofer replaying it must not buy an unbounded
    bypass of the ladder/cap.  Over _EXEMPT_MAX_IN_FLIGHT concurrent
    exempt admissions the claim falls through to normal admission."""
    from garage_tpu.api.overload import _EXEMPT_MAX_IN_FLIGHT

    async def main():
        ctl = _ctl()
        ctl.exempt_key("GKcanary")
        ctl.set_shed_tier(TIER_WRITE)  # full ladder shed for writes
        held = []
        for _ in range(_EXEMPT_MAX_IN_FLIGHT):
            t = await ctl.admit(_req("PUT", key_id="GKcanary"), "b", "k")
            assert t.admitted and t.exempt
            held.append(t)
        # the bound is hit: the same claim now takes the normal path,
        # where the ladder shed applies like for any other tenant
        over = await ctl.admit(_req("PUT", key_id="GKcanary"), "b", "k")
        assert not over.admitted
        # releasing one slot re-arms the exemption (canary probes are
        # serial, so the real canary never gets near the bound)
        held.pop().release()
        again = await ctl.admit(_req("PUT", key_id="GKcanary"), "b", "k")
        assert again.admitted and again.exempt
        again.release()
        for t in held:
            t.release()
        assert ctl._exempt_in_flight == 0

    run(main())


def test_malicious_tenant_ids_cannot_corrupt_metrics():
    """Per-tenant gauge labels carry the pre-auth claimed key id and the
    raw URL bucket name: exposition must escape them, or one request
    with a quote in its Credential makes the node metrics-dark."""
    async def main():
        reg = Metrics()
        ctl = _ctl(registry=reg)
        evil_key = 'GK"}\ninjected'
        (await ctl.admit(_req("PUT", key_id=evil_key), 'b"{evil', "k")).release()
        import re
        line_re = re.compile(
            r'^[a-zA-Z_:][a-zA-Z0-9_:]*(\{([a-zA-Z_][a-zA-Z0-9_]*='
            r'"(\\.|[^"\\])*",?)*\})? [0-9eE.+-]+$'
        )
        for line in reg.render():
            if line.startswith("#"):
                continue
            assert line_re.match(line), f"unparseable exposition: {line!r}"
        ctl.close()

    run(main())


def test_tenant_eviction_churn_does_not_mint_fresh_bursts():
    """Cycling fake claimed ids past max_tracked_tenants evicts real
    tenants; on recreate-under-pressure a bucket starts at one second's
    refill, not the full burst, so churn can't reset budgets."""
    async def main():
        clk = FakeClock()
        reg = Metrics()
        ctl = _ctl(registry=reg, clock=clk, max_tracked_tenants=2,
                   key_rate=1.0, key_burst=10.0)
        # drain the victim's budget
        victim = _req("PUT", key_id="GKvictim")
        for _ in range(10):
            assert (await ctl.admit(victim, "b", "k")).admitted
        assert not (await ctl.admit(victim, "b", "k")).admitted
        # attacker cycles fake ids until the victim's bucket is evicted
        for i in range(4):
            await ctl.admit(_req("PUT", key_id=f"GKfake{i}"), "b", "k")
        assert "GKvictim" not in ctl._key_buckets
        # recreated under churn pressure: one second's refill (1 token),
        # NOT the 10-token burst — one request passes, the next sheds
        assert (await ctl.admit(victim, "b", "k")).admitted
        assert not (await ctl.admit(victim, "b", "k")).admitted
        assert reg.counters.get(
            ("api_admission_tenant_evictions_total", (("kind", "key"),))
        )
        ctl.close()

    run(main())


# --- ladder hysteresis --------------------------------------------------------


class _FakeScrub:
    def __init__(self):
        self.paused = False

    def cmd_pause(self):
        self.paused = True

    def cmd_resume(self):
        self.paused = False


def _fake_garage_for_ladder(clock):
    from garage_tpu.utils.background import BgVars

    cfg = SimpleNamespace(
        overload=OverloadConfig(
            check_interval_secs=1.0,
            ladder_burn_up=2.0,
            ladder_burn_down=0.5,
            loop_lag_p99_msec=500.0,
            ladder_hold_secs=10.0,
        )
    )
    state = {"tranq": 2, "bif": 128 * 1024 * 1024, "sync": 600.0}
    bv = BgVars()
    bv.register_rw(
        "repair-tranquility",
        lambda: str(state["tranq"]),
        lambda v: state.__setitem__("tranq", int(v)),
    )
    bv.register_rw(
        "repair-bytes-in-flight",
        lambda: str(state["bif"]),
        lambda v: state.__setitem__("bif", int(v)),
    )
    bv.register_rw(
        "sync-interval-secs",
        lambda: str(state["sync"]),
        lambda v: state.__setitem__("sync", float(v)),
    )
    g = SimpleNamespace(
        config=cfg,
        bg_vars=bv,
        block_manager=SimpleNamespace(scrub_worker=_FakeScrub()),
        overload=AdmissionController(
            cfg.overload, registry=Metrics(), clock=clock
        ),
        slo_tracker=None,  # signals() is monkeypatched below
        telemetry=None,
    )
    return g, state


def test_ladder_hysteresis_and_knob_restore():
    from garage_tpu.rpc.shedding import SheddingController

    clk = FakeClock()
    g, state = _fake_garage_for_ladder(clk)
    sh = SheddingController(g, clock=clk)
    sig = {"burn": 0.0, "lag": 0.0}
    sh.signals = lambda consume=True: (sig["burn"], sig["lag"])

    # healthy: nothing moves
    sh.evaluate()
    assert sh.level == 0

    # overload: one step per evaluation, knobs actually move
    sig["burn"] = 5.0
    sh.evaluate()
    assert sh.level == 1 and state["tranq"] == 8
    assert state["bif"] == 32 * 1024 * 1024
    sh.evaluate()
    assert sh.level == 2 and state["sync"] == 2400.0
    sh.evaluate()
    assert sh.level == 3 and g.block_manager.scrub_worker.paused
    sh.evaluate()
    assert sh.level == 4 and g.overload.shed_from_tier == TIER_ANON
    sh.evaluate()
    assert sh.level == 5 and g.overload.shed_from_tier == TIER_LIST
    sh.evaluate()
    assert sh.level == 6 and g.overload.shed_from_tier == TIER_WRITE
    sh.evaluate()
    assert sh.level == 6  # clamped at the top
    assert sh.steps_up == 6

    # gray zone (between burn_down and burn_up): hold position forever
    sig["burn"] = 1.0
    for _ in range(50):
        clk.advance(5.0)
        sh.evaluate()
    assert sh.level == 6 and sh.steps_down == 0

    # recovery: no step down before hold_secs of CONTINUOUS calm
    sig["burn"] = 0.0
    sh.evaluate()
    clk.advance(5.0)
    sh.evaluate()
    assert sh.level == 6  # only 5 s calm, hold is 10
    # a blip of overload resets the recovery timer (anti-flap)
    sig["burn"] = 5.0
    sh.evaluate()
    assert sh.level == 6  # already at max, no extra step
    sig["burn"] = 0.0
    sh.evaluate()
    clk.advance(9.0)
    sh.evaluate()
    assert sh.level == 6  # timer restarted by the blip
    clk.advance(2.0)
    sh.evaluate()
    assert sh.level == 5  # one step down, shed tier relaxes
    assert g.overload.shed_from_tier == TIER_LIST

    # the hold re-arms after every step: full descent takes 6 holds
    for _ in range(12):
        clk.advance(11.0)
        sh.evaluate()
    assert sh.level == 0
    assert sh.steps_down == 6
    # every actuator restored to its pre-overload value
    assert state["tranq"] == 2
    assert state["bif"] == 128 * 1024 * 1024
    assert state["sync"] == 600.0
    assert not g.block_manager.scrub_worker.paused
    assert g.overload.shed_from_tier is None

    # loop-lag signal alone also steps the ladder
    sig["lag"] = 0.9  # 900 ms > 500 ms threshold
    sh.evaluate()
    assert sh.level == 1


# --- config validation --------------------------------------------------------


def test_overload_config_validation():
    def cfg(over):
        return config_from_dict(
            {"metadata_dir": "/tmp/x", "rpc_secret": "aa" * 32, "overload": over}
        )

    assert cfg({"max_in_flight": 8}).overload.max_in_flight == 8
    for bad in (
        {"max_in_flight": 0},
        {"key_rate": 0},
        {"bucket_burst": -1},
        # a burst in (0, 1) caps the bucket below one whole token:
        # take(1) can never succeed and every tenant wedges forever
        {"key_burst": 0.5},
        {"bucket_burst": 0.5},
        {"ladder_burn_up": 0.5, "ladder_burn_down": 0.5},
        {"check_interval_secs": 0},
        {"ladder_hold_secs": 0},
        {"loop_lag_p99_msec": 0},
        {"queue_depth": -1},
    ):
        with pytest.raises(ValueError):
            cfg(bad)
    # unknown keys are ignored (forward compat, _known pattern)
    assert cfg({"future_knob": 1}).overload.enabled


# --- end-to-end: 503 SlowDown through the real S3 frontend --------------------


def test_slowdown_response_shape_and_slo_protection(tmp_path):
    from test_s3_api import make_client, make_daemon, teardown

    from garage_tpu.utils.metrics import registry

    async def main():
        garage, s3, endpoint = await make_daemon(tmp_path)
        try:
            client = await make_client(garage, endpoint)
            await client.create_bucket("bkt")
            await client.put_object("bkt", "k", b"x" * 100)
            err_before = registry.counter_family_sum("api_s3_error_counter")
            req_before = registry.counter_family_sum("api_s3_request_counter")
            # choke this key: writes shed immediately once the burst is gone
            ov = garage.config.overload
            ov.key_rate, ov.key_burst = 0.001, 1.0
            st1, _h, _d = await client._req("PUT", "/bkt/k2", body=b"y")
            assert st1 == 200  # the single burst token
            st2, h2, d2 = await client._req("PUT", "/bkt/k3", body=b"z")
            assert st2 == 503
            # S3-semantic body: <Error><Code>SlowDown</Code>...
            import xml.etree.ElementTree as ET

            root = ET.fromstring(d2.decode())
            assert root.findtext("Code") == "SlowDown"
            assert root.findtext("Message")
            assert int(h2["Retry-After"]) >= 1
            # SLO protection: the shed is NOT an S3 request/error — an
            # intentional 503 must not burn the availability budget the
            # shedding controller steers by
            assert (
                registry.counter_family_sum("api_s3_error_counter")
                == err_before
            )
            assert (
                registry.counter_family_sum("api_s3_request_counter")
                == req_before + 1  # only the admitted PUT counted
            )
            assert (
                registry.counter_family_sum(
                    "api_admission_shed_total",
                    lambda lbls: ("tier", "write") in lbls,
                )
                >= 1
            )
            # S3Client surfaces it as a typed error too
            from garage_tpu.api.s3.client import S3Error

            with pytest.raises(S3Error) as ei:
                await client.put_object("bkt", "k4", b"w")
            assert ei.value.status == 503 and ei.value.code == "SlowDown"
            # an admitted request still works for another tenant under
            # sane rates (the knob is global; the choked key's bucket
            # keeps its drained token count)
            ov.key_rate, ov.key_burst = 200.0, 400.0
            c2 = await make_client(garage, endpoint)
            await c2.create_bucket("bkt2")
            await c2.put_object("bkt2", "k", b"ok")
            await c2.close()
            await client.close()
        finally:
            await teardown(garage, s3)

    run(main())


def test_canary_exempt_while_ladder_sheds_writes(tmp_path):
    """Satellite acceptance: at ladder level >= the second shed rung the
    canary's PUT/GET/DELETE probes still land (its key is exempt), while
    a normal tenant's write is shed."""
    from test_s3_api import make_client, make_daemon, teardown

    async def main():
        garage, s3, endpoint = await make_daemon(tmp_path)
        try:
            client = await make_client(garage, endpoint)
            await client.create_bucket("bkt")
            # drive the REAL ladder to the top through the shedding
            # controller (not by poking the admission tier directly)
            assert garage.shedder is not None
            garage.shedder.signals = lambda consume=True: (10.0, 0.0)
            for _ in range(len(garage.shedder.ladder)):
                garage.shedder.evaluate()
            assert garage.shedder.level == len(garage.shedder.ladder)
            assert garage.overload.shed_from_tier == TIER_WRITE

            from garage_tpu.api.s3.canary import CanaryWorker

            w = CanaryWorker(garage, endpoint, interval=60, object_bytes=512)
            await w.work()
            assert w.probes == 1 and w.failed == 0 and w.healthy == 1.0
            await w.stop_client()

            # ... while a normal tenant's write is shed
            from garage_tpu.api.s3.client import S3Error

            with pytest.raises(S3Error) as ei:
                await client.put_object("bkt", "nope", b"x")
            assert ei.value.code == "SlowDown"
            # interactive reads are still ADMITTED at max shed level:
            # a GET of a missing key comes back 404, not 503
            with pytest.raises(S3Error) as ei2:
                await client.get_object("bkt", "missing")
            assert ei2.value.status == 404
            await client.close()
        finally:
            await teardown(garage, s3)

    run(main())


def test_interactive_get_survives_max_shed(tmp_path):
    from test_s3_api import make_client, make_daemon, teardown

    async def main():
        garage, s3, endpoint = await make_daemon(tmp_path)
        try:
            client = await make_client(garage, endpoint)
            await client.create_bucket("bkt")
            await client.put_object("bkt", "k", b"payload")
            garage.overload.set_shed_tier(TIER_WRITE)
            assert await client.get_object("bkt", "k") == b"payload"
            from garage_tpu.api.s3.client import S3Error

            with pytest.raises(S3Error):  # listing is tier 2: shed
                await client.list_objects_v2("b")
            await client.close()
        finally:
            await teardown(garage, s3)

    run(main())


# --- surfaces: digest, admin endpoint, CLI ------------------------------------


def test_digest_and_admin_endpoint_and_cli(tmp_path):
    import aiohttp

    from test_s3_api import make_client, make_daemon, teardown

    from garage_tpu.api.admin.api_server import AdminApiServer

    async def main():
        garage, s3, endpoint = await make_daemon(tmp_path)
        garage.config.admin.admin_token = "tok"
        adm = AdminApiServer(garage)
        await adm.start("127.0.0.1", 0)
        try:
            client = await make_client(garage, endpoint)
            await client.create_bucket("bkt")
            await client.put_object("bkt", "k", b"x")
            garage.shedder.signals = lambda consume=True: (10.0, 0.0)
            garage.shedder.evaluate()
            # digest carries the ovl block (additive, version stays 1)
            garage.telemetry._cached = None
            dig = garage.telemetry.collect()
            from garage_tpu.rpc.telemetry_digest import DIGEST_VERSION

            assert dig["v"] == DIGEST_VERSION
            assert dig["ovl"]["lvl"] >= 1
            assert dig["ovl"]["adm"] >= 2
            # admin endpoint
            aport = adm.runner.addresses[0][1]
            async with aiohttp.ClientSession() as sess:
                async with sess.get(
                    f"http://127.0.0.1:{aport}/v1/overload",
                    headers={"Authorization": "Bearer tok"},
                ) as resp:
                    assert resp.status == 200
                    body = await resp.json()
            assert body["admission"]["maxInFlight"] == 256
            assert body["ladder"]["level"] >= 1
            assert body["ladder"]["ladder"][0] == {
                "name": "repair-slow", "applied": True,
            }
            assert body["admission"]["tiers"]["write"]["admitted"] >= 1
            # CLI rendering path (dispatch with a fake RPC call)
            from garage_tpu.cli.main import dispatch

            async def call(op, op_args=None):
                assert op == "overload-status"
                return garage.overload_status()

            args = SimpleNamespace(
                cmd="overload", overload_cmd="status", json=False
            )
            out = await dispatch(args, call, None)
            assert "ladder level" in out and "repair-slow" in out
            # federated exposition includes the new per-node families
            from garage_tpu.rpc.telemetry_digest import render_cluster_metrics

            garage.telemetry._cached = None
            text = render_cluster_metrics(garage)
            assert "cluster_node_overload_ladder_level" in text
            assert "cluster_node_shed_requests" in text
            # cluster top flags the shedding node
            from garage_tpu.cli.main import _render_cluster_top
            from garage_tpu.rpc.telemetry_digest import rollup

            frame = _render_cluster_top(rollup(garage))
            assert "SHED-L" in frame
            await client.close()
        finally:
            await adm.stop()
            await teardown(garage, s3)

    run(main())


def test_overload_max_in_flight_bgvar(tmp_path):
    from test_s3_api import make_daemon, teardown

    async def main():
        garage, s3, _ep = await make_daemon(tmp_path)
        try:
            assert garage.bg_vars.get("overload-max-in-flight") == "256"
            garage.bg_vars.set("overload-max-in-flight", "16")
            assert garage.config.overload.max_in_flight == 16
        finally:
            await teardown(garage, s3)

    run(main())


# --- slow: the 11-node EC(8,3) 4x burst --------------------------------


@pytest.mark.slow
def test_overload_burst_11_node_ec_cluster(tmp_path):
    """Acceptance: at 4x offered load on an 11-node EC(8,3) cluster the
    lowest offered tier sheds with 503 SlowDown, admitted traffic p99
    stays within the declared latency SLO, `overload_ladder_level`
    steps up and back down without flapping, and the canary stays live
    throughout.  The scenario itself (tuning, tenants, canary, burst,
    recovery) lives in overload_burst.py."""
    from overload_burst import p99_ms, run_overload_burst
    from test_ec_cluster import make_ec_cluster, stop_cluster

    from garage_tpu.api.s3.api_server import S3ApiServer
    from garage_tpu.utils.metrics import registry

    # the declared latency SLO for admitted traffic: queue_wait (600 ms)
    # + service under the in-flight cap.  Generous because this "11-node
    # cluster" shares ONE event loop and a CPU numpy codec — the bound
    # still proves admitted traffic is protected (unadmitted closed-loop
    # overload pushes well past it)
    SLO_MS = 2500.0

    async def main():
        garages = await make_ec_cluster(
            tmp_path, n=11, mode="ec:8:3", block_size=65536
        )
        g0 = garages[0]
        s3 = S3ApiServer(g0)
        await s3.start("127.0.0.1", 0)
        ep = f"http://127.0.0.1:{s3.runner.addresses[0][1]}"
        clients = []
        try:
            res = await run_overload_burst(g0, ep, duration=8.0)
            clients += res["clients"]
            stats, max_level = res["stats"], res["max_level"]
            canary, levels_seen = res["canary"], res["levels"]

            # --- assertions ---------------------------------------------------
            # the lowest offered tier shed a visible fraction
            assert stats["list"]["shed"] > 0, stats
            # admitted interactive traffic stayed within the SLO
            p99 = p99_ms(stats["interactive"]["times"])
            assert p99 is not None, stats
            assert p99 <= SLO_MS, f"admitted p99 {p99:.0f}ms"
            # interactive was not starved (queue-rather-than-reject)
            assert stats["interactive"]["ok"] > 50, stats
            # ladder stepped up under the burst and recovered after it
            assert max_level >= 1, levels_seen[-20:]
            assert g0.shedder.level == 0, levels_seen
            assert g0.shedder.steps_up == g0.shedder.steps_down
            # no flapping: the level trace rises then falls, at most one
            # extra up/down pair beyond the peak's worth of steps
            assert g0.shedder.steps_up <= max_level + 2
            # visible in /v1/overload state + the metric family
            st = g0.overload_status()
            assert st["ladder"]["stepsUp"] >= 1
            assert registry.counter_family_sum(
                "overload_ladder_steps_total",
                lambda lbls: ("direction", "up") in lbls,
            ) >= 1
            # the canary stayed live THROUGH the burst and shedding
            assert canary.probes > 0
            assert canary.failed == 0, canary.last_error
            assert canary.healthy == 1.0
        finally:
            await stop_cluster(garages, [s3], clients)

    run(main())
