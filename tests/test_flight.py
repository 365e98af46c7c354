"""Flight recorder acceptance (ISSUE 3): sampling profiler, event-loop
watchdog, slow-request ring buffer, worker runtime vars + CLI paths."""

import asyncio
import json
import logging
import os
import sys
import time
from types import SimpleNamespace

import pytest

sys.path.insert(0, os.path.dirname(__file__))

from test_s3_api import make_client, make_daemon, teardown  # noqa: E402

from garage_tpu.cli.admin_rpc import AdminRpcHandler  # noqa: E402
from garage_tpu.net.message import Req  # noqa: E402


def run(coro):
    return asyncio.run(coro)


async def rpc(handler, op, args=None):
    resp = await handler._handle(b"\x00" * 32, Req([op, args or {}]))
    return resp.body


def _hot_spin_marker():
    """Deliberately hot function: its name must appear in the profile."""
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < 0.01:
        sum(i * i for i in range(500))


async def _spin(stop: asyncio.Event) -> None:
    while not stop.is_set():
        _hot_spin_marker()
        await asyncio.sleep(0)


ADMIN_HDR = {"Authorization": "Bearer test-admin-token"}


async def _make_admin(garage):
    from garage_tpu.api.admin.api_server import AdminApiServer

    garage.config.admin.admin_token = "test-admin-token"
    admin = AdminApiServer(garage)
    await admin.start("127.0.0.1", 0)
    return admin, f"http://127.0.0.1:{admin.runner.addresses[0][1]}"


# --- sampling profiler --------------------------------------------------------


def test_debug_profile_endpoint_captures_hot_function(tmp_path):
    """Acceptance: GET /v1/debug/profile?seconds=2 on a live node returns
    non-empty folded stacks containing a known hot function; the
    speedscope variant is valid sampled-profile JSON."""
    import aiohttp

    async def main():
        garage, s3, endpoint = await make_daemon(tmp_path)
        admin, base = await _make_admin(garage)
        stop = asyncio.Event()
        spin = asyncio.create_task(_spin(stop))
        try:
            async with aiohttp.ClientSession() as sess:
                async with sess.get(base + "/v1/debug/profile?seconds=2", headers=ADMIN_HDR) as r:
                    assert r.status == 200
                    folded = await r.text()
                assert folded.strip(), "profile returned no stacks"
                for line in folded.strip().splitlines():
                    stack, _, count = line.rpartition(" ")
                    assert stack and count.isdigit(), line
                assert "_hot_spin_marker" in folded
                assert "thread:MainThread" in folded
                # the asyncio task set is sampled too (suspended tasks)
                assert "task:" in folded

                async with sess.get(
                    base + "/v1/debug/profile?seconds=0.2&format=speedscope",
                    headers=ADMIN_HDR,
                ) as r:
                    assert r.status == 200
                    sc = await r.json()
            prof = sc["profiles"][0]
            assert prof["type"] == "sampled"
            assert len(prof["samples"]) == len(prof["weights"]) > 0
            nframes = len(sc["shared"]["frames"])
            assert all(0 <= i < nframes for s in prof["samples"] for i in s)
        finally:
            stop.set()
            await spin
            await admin.stop()
            await teardown(garage, s3)

    run(main())


# --- event-loop watchdog ------------------------------------------------------


def test_watchdog_counts_blocked_loop_and_dumps_tasks(caplog):
    """Acceptance: a sync sleep on the loop increments
    event_loop_blocked_total and logs a task dump (with the culprit
    stack); the lag histogram records the stall."""
    from garage_tpu.utils.flight import EventLoopWatchdog
    from garage_tpu.utils.metrics import registry
    from garage_tpu.utils.tracing import Tracer

    key = ("event_loop_blocked_total", ())
    tr = Tracer()
    tr.sink = "http://sink.invalid"
    traced_id = {}

    async def traced():
        with tr.span("blocked-op") as s:
            traced_id["hex"] = s.trace_id.hex()
            await asyncio.sleep(10)

    async def main():
        wd = EventLoopWatchdog(threshold=0.05, tick=0.02)
        wd.start()
        before = registry.counters[key]
        lurk = asyncio.create_task(asyncio.sleep(10), name="lurker-task")
        span_task = asyncio.create_task(traced(), name="traced-task")
        try:
            await asyncio.sleep(0.1)  # let the beat establish a baseline
            time.sleep(0.4)  # deliberately block the event loop
            await asyncio.sleep(0.1)  # loop-side beat observes the lag
            assert registry.counters[key] == before + 1
            d = registry.durations[("event_loop_lag_seconds", ())]
            assert d[0] > 0 and d[1] >= 0.3  # the 400 ms stall is in the sum
        finally:
            lurk.cancel()
            span_task.cancel()
            wd.stop()

    with caplog.at_level(logging.WARNING, logger="garage.flight"):
        run(main())
    assert "event loop blocked" in caplog.text
    assert "lurker-task" in caplog.text  # task dump names live tasks
    assert "blocked in" in caplog.text  # culprit loop-thread stack
    # the dump correlates tasks with their active trace ids (works on
    # py3.10's C tasks via the frame-locals fallback)
    assert f"trace={traced_id['hex']}" in caplog.text


# --- slow-request flight recorder ---------------------------------------------


def test_slow_requests_recorded_with_trace_ids(tmp_path):
    """Acceptance: a slow PUT appears in GET /v1/debug/slow with its
    trace id (= the x-amz-request-id the client saw), a span tree, and
    parent links back to the root."""
    import aiohttp

    async def main():
        garage, s3, endpoint = await make_daemon(tmp_path)
        admin, base = await _make_admin(garage)
        try:
            assert garage.flight_recorder is not None  # default-on
            garage.flight_recorder.threshold_ms = 0.0  # record everything
            client = await make_client(garage, endpoint)
            await client.create_bucket("slowb")
            await client.put_object("slowb", "k", b"x" * 20_000)
            head = await client.head_object("slowb", "k")
            req_id = head.get("x-amz-request-id")
            assert req_id and len(req_id) == 32  # trace id, hex
            # streamed responses (multi-block GET prepares in-handler)
            # carry the id too, via the on_response_prepare signal
            st, gh, _ = await client._req("GET", "/slowb/k")
            assert st == 200 and len(gh.get("x-amz-request-id", "")) == 32

            async with aiohttp.ClientSession() as sess:
                async with sess.get(base + "/v1/debug/slow", headers=ADMIN_HDR) as r:
                    assert r.status == 200
                    body = await r.json()
            assert body["enabled"]
            puts = [
                q for q in body["requests"]
                if q["name"] == "api:s3" and q["attrs"].get("method") == "PUT"
                and q["attrs"].get("path") == "/slowb/k"
            ]
            assert puts, body["requests"]
            put = puts[0]
            assert len(put["traceId"]) == 32 and put["durationMs"] > 0
            names = [s["name"] for s in put["spans"]]
            assert any(n.startswith("table:insert") for n in names)
            assert any(n.startswith("block:put") for n in names)
            ids = {s["spanId"] for s in put["spans"]}
            root = put["spans"][0]
            assert root["parentSpanId"] is None
            for s in put["spans"][1:]:
                assert s["parentSpanId"] in ids, s["name"]
            # the HEAD's trace id round-trips client-side as the request id
            heads = [
                q for q in body["requests"]
                if q["attrs"].get("method") == "HEAD"
            ]
            assert any(q["traceId"] == req_id for q in heads)
        finally:
            await admin.stop()
            await teardown(garage, s3)

    run(main())


def test_slow_request_ring_is_bounded_and_thresholded():
    """Unit: below-threshold roots are dropped, the ring keeps top_k."""
    from garage_tpu.utils.flight import SlowRequestRecorder
    from garage_tpu.utils.tracing import Tracer

    t = Tracer()
    rec = SlowRequestRecorder(threshold_ms=5.0, top_k=3)
    t.add_hook(rec.on_span_end)
    try:
        assert t.enabled  # the hook alone enables span creation
        with t.span("fast-root"):
            pass
        assert rec.snapshot() == [] and not rec.pending
        for i in range(5):
            with t.span(f"slow-{i}", idx=i) as s:
                with t.span("child"):
                    pass
                s.start_ns -= 50_000_000  # fake 50 ms
        snap = rec.snapshot()
        assert len(snap) == 3  # ring bounded at top_k
        assert all(r["durationMs"] >= 5.0 for r in snap)
        assert not rec.pending  # roots finalize their trees
        assert len(snap[0]["spans"]) == 2  # root + child
        assert t._buf == []  # hooks alone must not fill the export buffer
    finally:
        t.remove_hook(rec.on_span_end)
        assert not t.enabled


# --- worker vars / CLI paths --------------------------------------------------


def test_worker_set_adjusts_running_workers(tmp_path):
    """Acceptance: `worker set` changes resync tranquility (and friends)
    on the RUNNING daemon, no restart."""

    async def main():
        garage, s3, endpoint = await make_daemon(tmp_path)
        adm = AdminRpcHandler(garage)
        try:
            out = await rpc(
                adm, "worker-set", {"var": "resync-tranquility", "value": "7"}
            )
            assert out == {"resync-tranquility": "7"}
            assert garage.block_manager.resync.tranquility == 7

            await rpc(adm, "worker-set", {"var": "resync-worker-count", "value": "3"})
            assert garage.block_manager.resync.n_workers == 3

            await rpc(adm, "worker-set", {"var": "scrub-tranquility", "value": "9"})
            assert garage.block_manager.scrub_worker.state.tranquility == 9

            await rpc(adm, "worker-set", {"var": "sync-interval-secs", "value": "30"})
            for t in garage.tables:
                assert t.syncer.anti_entropy_interval == 30.0

            allv = await rpc(adm, "worker-get", {})
            for var in (
                "resync-tranquility", "resync-worker-count",
                "scrub-tranquility", "sync-interval-secs",
            ):
                assert var in allv
            with pytest.raises(KeyError):
                await rpc(adm, "worker-set", {"var": "no-such-var", "value": "1"})
        finally:
            await teardown(garage, s3)

    run(main())


def test_worker_and_debug_cli_paths(tmp_path):
    """CLI formatting paths: worker list/get/set, stats, debug
    profile/slow — driven through cli.main.dispatch against the real
    AdminRpc handler."""
    from garage_tpu.cli.main import dispatch

    async def main():
        garage, s3, endpoint = await make_daemon(tmp_path)
        adm = AdminRpcHandler(garage)
        garage.flight_recorder.threshold_ms = 0.0

        async def call(op, a=None):
            return (await adm._handle(b"\x00" * 32, Req([op, a or {}]))).body

        def ns(**kw):
            return SimpleNamespace(json=False, **kw)

        try:
            client = await make_client(garage, endpoint)
            await client.create_bucket("cli")
            await client.put_object("cli", "k", b"y" * 9_000)
            await asyncio.sleep(0.3)  # let workers iterate (rate/last cols)

            out = await dispatch(
                ns(cmd="worker", worker_cmd="list", var=None, value=None),
                call, garage.config,
            )
            assert "resync:0" in out and "scrub" in out
            assert "tranq" in out and "rate" in out

            out = await dispatch(
                ns(cmd="worker", worker_cmd="get", var=None, value=None),
                call, garage.config,
            )
            assert "resync-tranquility" in json.loads(out)

            out = await dispatch(
                ns(cmd="worker", worker_cmd="set",
                   var="resync-tranquility", value="4"),
                call, garage.config,
            )
            assert garage.block_manager.resync.tranquility == 4

            # stats: human table by default (folds in the local
            # telemetry digest), raw JSON with --json
            out = await dispatch(ns(cmd="stats"), call, garage.config)
            assert "==== TABLES ====" in out and "object" in out
            assert "TELEMETRY" in out and "s3 req/s" in out
            out = await dispatch(
                SimpleNamespace(json=True, cmd="stats"), call, garage.config
            )
            st = json.loads(out)
            assert "tables" in st and "blocks" in st
            from garage_tpu.rpc.telemetry_digest import DIGEST_VERSION

            assert st["telemetry"]["v"] == DIGEST_VERSION

            out = await dispatch(
                ns(cmd="debug", debug_cmd="profile", seconds=0.3, hz=50,
                   speedscope=False, output=None),
                call, garage.config,
            )
            assert "thread:" in out

            path = str(tmp_path / "prof.json")
            out = await dispatch(
                ns(cmd="debug", debug_cmd="profile", seconds=0.2, hz=50,
                   speedscope=True, output=path),
                call, garage.config,
            )
            assert "wrote" in out
            with open(path) as f:
                assert json.load(f)["profiles"][0]["type"] == "sampled"

            out = await dispatch(
                ns(cmd="debug", debug_cmd="slow"), call, garage.config
            )
            assert "api:s3" in out and "trace" in out
        finally:
            await teardown(garage, s3)

    run(main())
