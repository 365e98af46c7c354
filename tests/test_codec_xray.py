"""Codec X-ray acceptance (ISSUE 17): dispatch-observatory units
(pad-waste math, compile-event accounting, overlap gauge, lane linger),
sampling-profiler units (collapsed-stack shape, [event-loop] tag,
start/stop, overhead bound, stall auto-capture), and the slow 11-node
EC(8,3) federation test asserting the same numbers on every surface."""

import asyncio
import os
import sys
import threading
import time

import pytest

sys.path.insert(0, os.path.dirname(__file__))

from garage_tpu.ops import telemetry as xray  # noqa: E402
from garage_tpu.utils import flight  # noqa: E402
from garage_tpu.utils import profiler as profiler_mod  # noqa: E402
from garage_tpu.utils.compile_cache import instrumented_cache  # noqa: E402
from garage_tpu.utils.metrics import Metrics, registry  # noqa: E402


def run(coro):
    return asyncio.run(coro)


@pytest.fixture
def fresh_xray(monkeypatch):
    """Private registry + cold shape/EWMA state for ops.telemetry so pad
    and compile assertions are exact: the production registry is
    process-wide (shared by every in-process node and every other
    test), and shape-class compile accounting is first-dispatch-wins."""
    r = Metrics()
    monkeypatch.setattr(xray, "registry", r)
    # note_platform registers its gauge on whatever registry is live:
    # isolate the seen-set too, or "cpu" would be marked seen while the
    # gauge sits on this private registry (starving the real one)
    monkeypatch.setattr(xray, "_platforms_seen", set())
    xray.reset_xray_state()
    yield r
    xray.reset_xray_state()


# --- pad-waste accounting -----------------------------------------------------


def test_pad_waste_accounting(fresh_xray):
    r = fresh_xray
    xray.record_pad("ec_encode", 3, 4)
    xray.record_pad("ec_encode", 5, 8)
    lbl = (("kernel", "ec_encode"),)
    assert r.counters[("tpu_codec_pad_requested_total", lbl)] == 8
    assert r.counters[("tpu_codec_pad_padded_total", lbl)] == 12
    assert r.gauges[("tpu_codec_pad_waste", lbl)] == pytest.approx(
        1 - 8 / 12, abs=1e-3
    )
    # exact-shape host dispatches report an honest zero, not an absence
    xray.record_pad("ec_encode_host", 7, 7)
    host = (("kernel", "ec_encode_host"),)
    assert r.gauges[("tpu_codec_pad_waste", host)] == 0.0

    snap = xray.codec_snapshot(r)
    assert snap["kernels"]["ec_encode"]["padWaste"] == pytest.approx(
        1 - 8 / 12, abs=1e-3
    )
    assert snap["kernels"]["ec_encode_host"]["padWaste"] == 0.0
    # cross-kernel waste is the pooled quotient, not a mean of ratios
    assert snap["padWaste"] == pytest.approx(1 - 15 / 19, abs=1e-3)
    # pow2 bucketing bounds waste at 0.5 (one row past a boundary)
    assert snap["padWaste"] <= 0.5


def test_dispatch_record_pad_first_call_wins(fresh_xray):
    r = fresh_xray
    with xray.dispatch("ec_reconstruct", "cpu", 3, 1024) as rec:
        rec.pad(3, 4)
        rec.pad(3, 8)  # mesh attempt fell back: must not double-count
    lbl = (("kernel", "ec_reconstruct"),)
    assert r.counters[("tpu_codec_pad_requested_total", lbl)] == 3
    assert r.counters[("tpu_codec_pad_padded_total", lbl)] == 4


# --- compile-event accounting -------------------------------------------------


def test_shape_class_compile_event_once(fresh_xray):
    r = fresh_xray

    def one(batch, padded):
        with xray.dispatch("ec_encode", "cpu", batch, 0) as rec:
            rec.pad(batch, padded)

    key = ("tpu_compile_duration", (("cache", "ec_encode"),))
    one(3, 4)
    assert r.durations[key][0] == 1  # cold (kernel, bucket): lowering
    one(4, 4)
    assert r.durations[key][0] == 1  # executable-cache hit: nothing
    one(5, 8)
    assert r.durations[key][0] == 2  # new bucket = new shape class
    # native host paths have no lowering step at all
    with xray.dispatch("ec_encode_host", "host", 5, 0) as rec:
        rec.pad(5, 5)
    assert (
        "tpu_compile_duration",
        (("cache", "ec_encode_host"),),
    ) not in r.durations

    snap = xray.codec_snapshot(r)
    assert snap["compileEvents"] == 2
    assert snap["compileSecs"] >= 0.0
    assert snap["compile"]["ec_encode"]["events"] == 2


def test_instrumented_cache_hit_records_no_compile_time():
    """A cache HIT must never reach the compile-duration histogram —
    only the timed miss path is a compile event (delta-based: the
    process registry is shared)."""
    calls = []

    @instrumented_cache("ec_apply_legacy")
    def build(x):
        calls.append(x)
        return x * 2

    key = ("tpu_compile_duration", (("cache", "ec_apply_legacy"),))
    before = registry.durations.get(key, (0, 0.0, None))[0]
    assert build(21) == 42  # miss: timed
    assert registry.durations[key][0] == before + 1
    assert build(21) == 42  # hit: records nothing
    assert registry.durations[key][0] == before + 1
    assert calls == [21]


# --- the dispatch split: copies, device wait, the thread's CPU ------------------


def test_dispatch_split_and_cpu_seconds(fresh_xray):
    r = fresh_xray
    with xray.dispatch("ec_encode", "cpu", 2, 0) as rec:
        rec.pad(2, 2)
        with rec.transfer("pad"):
            time.sleep(0.02)
        with rec.compute():
            time.sleep(0.02)
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < 0.02:  # the thread on the CPU
            pass
    # both phase histograms saw the dispatch, each its own 20 ms
    for fam in ("tpu_codec_transfer_duration", "tpu_codec_compute_duration"):
        n, total, _b = r.durations[(fam, (("kernel", "ec_encode"),))]
        assert n == 1 and 0.015 <= total <= 0.2, (fam, total)
    lbl = (("kernel", "ec_encode"), ("platform", "cpu"))
    wall = r.durations[("tpu_codec_dispatch_duration", lbl)][1]
    cpu = r.counters[("tpu_codec_dispatch_cpu_seconds_total", lbl)]
    # the sleeps are off the CPU, the spin is on it
    assert 0.015 <= cpu <= wall - 0.03, (cpu, wall)
    assert "overlapEfficiency" not in xray.codec_snapshot(r)


# --- batcher lane linger ------------------------------------------------------


def test_batcher_lane_linger_joined_with_flush_reason():
    from garage_tpu.block.codec.ec import EcCodec
    from garage_tpu.block.codec_batch import CodecBatcher

    name = "block_codec_batch_lane_linger"

    def count(flush):
        d = registry.durations.get(
            (name, (("lane", "encode"), ("flush", flush)))
        )
        return d[0] if d else 0

    before = count("full") + count("linger")
    before_linger = count("linger")

    async def main():
        batcher = CodecBatcher(
            EcCodec(2, 1, tpu_enable=False), linger_msec=5.0, max_blocks=4
        )
        try:
            payload = b"x" * 512
            # 4 concurrent blocks hit max_blocks -> a "full" flush
            await asyncio.gather(*(batcher.encode(payload) for _ in range(4)))
            # a lone block waits out its linger window
            await batcher.encode(payload)
        finally:
            await batcher.close()

    run(main())
    # every block's lane time lands in the histogram, joined with WHY
    # its batch flushed (the lone block is always a linger flush; the
    # gathered four are "full" unless a loaded box splits them)
    assert count("full") + count("linger") == before + 5
    assert count("linger") >= before_linger + 1

    snap = xray.codec_snapshot()
    enc = snap["lanes"]["encode"]["flush"]
    assert sum(f["blocks"] for f in enc.values()) >= 5
    for f in enc.values():
        assert f["lingerSecsTotal"] >= 0.0


# --- sampling profiler --------------------------------------------------------


def test_profile_collapsed_stacks_and_event_loop_tag():
    async def main():
        stop = asyncio.Event()

        async def spin():
            while not stop.is_set():
                sum(i * i for i in range(200))
                await asyncio.sleep(0)

        task = asyncio.create_task(spin())
        try:
            return await profiler_mod.profile(0.3, hz=100)
        finally:
            stop.set()
            await task

    res = run(main())
    assert res.samples > 0
    folded = res.folded()
    lines = folded.strip().splitlines()
    assert lines
    attributed = 0
    for line in lines:
        stack, _, cnt = line.rpartition(" ")
        assert stack and cnt.isdigit(), line
        root = stack.split(";")[0]
        assert root.startswith(("thread:", "task:")), root
        if root.startswith("thread:"):
            attributed += int(cnt)
    # >= 80% of sampling rounds attributed an on-CPU thread stack
    # (ISSUE 17 acceptance bar; in practice every round samples the
    # loop thread, so this only fails if attribution breaks)
    assert attributed >= 0.8 * res.samples
    # profiling from the loop tags the loop thread's stack root
    assert "[event-loop]" in folded
    assert len(res.top_stacks(3)) <= 3
    sc = res.speedscope()
    prof = sc["profiles"][0]
    assert prof["type"] == "sampled"
    assert len(prof["samples"]) == len(prof["weights"]) > 0


def test_profiler_stop_ends_run_early():
    prof = profiler_mod.SamplingProfiler(None, hz=500)
    t = threading.Thread(target=prof.run, args=(30.0,), daemon=True)
    t0 = time.perf_counter()
    t.start()
    time.sleep(0.1)
    prof.stop()
    t.join(timeout=5.0)
    assert not t.is_alive(), "stop() did not end the sampling run"
    assert time.perf_counter() - t0 < 10.0
    assert prof.result.samples > 0


def test_profiler_overhead_under_five_percent():
    """The ISSUE 17 overhead bound: per-sample cost x the default 100 Hz
    must stay under 5% of wall time, measured against a busy process
    (several runnable threads whose stacks the sampler walks)."""
    stop = threading.Event()

    def busy():
        while not stop.is_set():
            sum(i * i for i in range(100))

    threads = [threading.Thread(target=busy, daemon=True) for _ in range(3)]
    for t in threads:
        t.start()
    try:
        prof = profiler_mod.SamplingProfiler(None, hz=100)
        # best-of-batches: a contended CI box inflates any single batch
        # with scheduler preemption; the minimum is the honest cost
        batch, costs = 60, []
        for _ in range(5):
            t0 = time.perf_counter()
            for _ in range(batch):
                prof._sample()
            costs.append((time.perf_counter() - t0) / batch)
        cost = min(costs)
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=2.0)
    assert prof.result.samples == 5 * batch
    assert cost * 100 < 0.05, (
        f"per-sample cost {cost * 1e6:.0f}us -> "
        f"{cost * 100:.1%} of wall at 100 Hz"
    )


def test_stall_profiler_records_flight_event_and_rate_limits():
    rec = flight.SlowRequestRecorder(threshold_ms=10**9)
    flight.attach_recorder(rec)
    try:
        sp = profiler_mod.StallProfiler(
            seconds=0.05, hz=200, top=3, min_interval=30.0
        )
        # production shape: on_stall runs on the watchdog MONITOR thread
        # (the sampler skips its own thread, so the stalled loop thread
        # — here MainThread — is what gets captured)
        t = threading.Thread(
            target=sp.on_stall,
            args=(0.5, None, threading.get_ident()),
            daemon=True,
        )
        t.start()
        t.join(timeout=5.0)
        assert sp.captures == 1
        events = [
            r for r in rec.records if r["name"] == "loop-stall-profile"
        ]
        assert len(events) == 1
        attrs = events[0]["attrs"]
        assert attrs["overdueMs"] == "500.0"
        assert int(attrs["samples"]) > 0
        assert "thread:" in attrs["topStacks"]
        assert len(attrs["topStacks"].splitlines()) <= 3
        # a loop thrashing in and out of stalls must not turn the
        # profiler into the load: second episode inside min_interval
        sp.on_stall(0.5)
        assert sp.captures == 1
        assert (
            len([r for r in rec.records if r["name"] == "loop-stall-profile"])
            == 1
        )
    finally:
        flight.detach_recorder(rec)


def test_watchdog_invokes_stall_hook():
    """The watchdog's stall branch calls the opt-in on_stall hook with
    the overdue time and the loop thread's ident (what StallProfiler
    needs to tag [event-loop] in the captured burst)."""
    calls = []
    expect_ident = {}

    async def main():
        expect_ident["id"] = threading.get_ident()
        wd = flight.EventLoopWatchdog(threshold=0.05, tick=0.02)
        wd.on_stall = lambda overdue, loop, ident: calls.append(
            (overdue, ident)
        )
        wd.start()
        try:
            await asyncio.sleep(0.1)  # let the beat establish a baseline
            time.sleep(0.3)  # deliberately block the loop
            await asyncio.sleep(0.1)
        finally:
            wd.stop()

    run(main())
    assert calls, "stall episode did not invoke on_stall"
    overdue, ident = calls[0]
    assert overdue >= 0.05
    assert ident == expect_ident["id"]


# --- 11-node EC(8,3) federation acceptance ------------------------------------


ADMIN_HDR = {"Authorization": "Bearer test-admin-token"}


@pytest.mark.slow
def test_codec_xray_11_node_federation(tmp_path):
    """ISSUE 17 acceptance: on an 11-node EC(8,3) in-process cluster,
    `GET /v1/codec` reports nonzero dispatches with pad-waste, compile,
    lane-linger and overlap fields; all 11 nodes federate via the
    gossiped `codec.*` digest keys; the digest, the federated
    exposition and the snapshot agree; and a deliberately cold shape
    class records exactly ONE compile event no matter how many nodes
    dispatch it (the in-process cluster shares one registry and one
    executable cache — per-process in a real deployment)."""
    import aiohttp

    from test_cluster_telemetry import _converge
    from test_ec_cluster import make_ec_cluster, stop_cluster

    from garage_tpu.api.admin.api_server import AdminApiServer
    from garage_tpu.api.s3.api_server import S3ApiServer
    from garage_tpu.api.s3.client import S3Client
    from garage_tpu.rpc.telemetry_digest import render_cluster_metrics

    async def main():
        garages = await make_ec_cluster(tmp_path, n=11, mode="ec:8:3")
        for g in garages:
            g.telemetry.min_interval = 0.0  # every gossip wave recollects
        s3 = S3ApiServer(garages[0])
        await s3.start("127.0.0.1", 0)
        ep = f"http://127.0.0.1:{s3.runner.addresses[0][1]}"
        garages[0].config.admin.admin_token = "test-admin-token"
        admin = AdminApiServer(garages[0])
        await admin.start("127.0.0.1", 0)
        base = f"http://127.0.0.1:{admin.runner.addresses[0][1]}"
        key = await garages[0].helper.create_key("xray")
        key.params().allow_create_bucket.update(True)
        await garages[0].key_table.insert(key)
        client = S3Client(ep, key.key_id, key.secret())
        try:
            await client.create_bucket("xray-bucket")
            data = os.urandom(100_000)  # 13 blocks through EC(8,3)
            await client.put_object("xray-bucket", "obj", data)
            assert await client.get_object("xray-bucket", "obj") == data

            # deliberately cold shape class: several nodes dispatch it,
            # the shared executable cache compiles it exactly once
            xray.reset_xray_state()
            ckey = ("tpu_compile_duration", (("cache", "ec_encode"),))
            before = registry.durations.get(ckey, (0, 0.0, None))[0]
            for _g in garages[:3]:
                with xray.dispatch("ec_encode", "cpu", 3, 0) as drec:
                    drec.pad(3, 4)
            assert registry.durations[ckey][0] == before + 1

            await _converge(garages)

            async with aiohttp.ClientSession() as sess:
                async with sess.get(
                    base + "/v1/codec", headers=ADMIN_HDR
                ) as r:
                    assert r.status == 200
                    resp = await r.json()

            local = resp["local"]
            assert local["dispatches"] > 0
            for field in (
                "padWaste",
                "compileEvents",
                "compileSecs",
                "laneLingerP99",
            ):
                assert field in local, field
            assert local["compileEvents"] >= 1
            assert 0.0 <= local["padWaste"] <= 0.5
            assert local["kernels"], "no per-kernel pad accounting"
            # the EC PUT rode the codec batcher: encode-lane linger
            assert "encode" in local["lanes"]

            cl = resp["cluster"]
            assert cl["nodesReporting"] == 11, cl
            assert len(cl["nodes"]) == 11
            agg = cl["aggregate"]
            assert agg["dispatches"] > 0
            assert agg["compileEvents"] >= 1
            assert agg["padWasteWorst"] is not None

            # the same numbers on every surface (idle cluster: the
            # digest, the snapshot and the federated exposition are
            # read back-to-back from the same process registry)
            dg = garages[0].telemetry.collect()["codec"]
            snap = xray.codec_snapshot()
            assert dg["dsp"] == snap["dispatches"]
            assert dg["ce"] == snap["compileEvents"]
            assert dg["pw"] == pytest.approx(snap["padWaste"], abs=1e-3)
            text = render_cluster_metrics(garages[0])
            fed = [
                ln
                for ln in text.splitlines()
                if ln.startswith("cluster_node_codec_dispatch_total{")
            ]
            assert len(fed) == 11
            node0 = garages[0].system.id.hex()[:16]
            mine = [ln for ln in fed if node0 in ln]
            assert mine and float(mine[0].rsplit(" ", 1)[1]) == float(
                dg["dsp"]
            )
            for fam in (
                "cluster_node_codec_pad_waste",
                "cluster_node_codec_compile_events",
                "cluster_node_codec_compile_seconds",
                "cluster_node_codec_lane_linger_p99_seconds",
            ):
                assert f"{fam}{{" in text, fam
        finally:
            await admin.stop()
            await stop_cluster(garages, servers=(s3,), clients=(client,))

    run(main())
