"""Observability: latency histograms, scrape-time gauges, span tracing
with OTLP export (reference: OTel meters + tracing_setup.rs)."""

import asyncio
import contextlib
import os
import sys

sys.path.insert(0, os.path.dirname(__file__))
sys.path.insert(
    0, os.path.join(os.path.dirname(__file__), os.pardir, "script")
)

from garage_tpu.utils.metrics import BUCKETS, Metrics
from garage_tpu.utils.tracing import Tracer


def run(coro):
    return asyncio.run(coro)


def test_histogram_buckets_and_quantiles():
    m = Metrics()
    for ms in [1, 1, 2, 4, 100]:
        m.observe("op_duration", (), ms / 1000.0)
    lines = m.render()
    # cumulative bucket counts, +Inf == count
    assert any("op_duration_bucket" in ln and 'le="+Inf"' in ln and ln.endswith(" 5") for ln in lines)
    assert "op_duration_count 5" in lines
    # p50 should be around 1-2 ms, p99 near the 100 ms outlier
    assert m.quantile("op_duration", (), 0.5) <= 0.004
    assert m.quantile("op_duration", (), 0.99) >= 0.1
    assert m.quantile("op_duration", (), 0.99) <= 0.3
    assert m.quantile("missing", (), 0.5) is None


def test_gauges_render_and_failures_dropped():
    m = Metrics()
    m.set_gauge("queue_depth", (), 7)
    m.register_gauge("live_value", (("t", "x"),), lambda: 42)
    m.register_gauge("dead_value", (), lambda: 1 / 0)
    lines = m.render()
    assert "queue_depth 7" in lines
    assert 'live_value{t="x"} 42' in lines
    assert not any("dead_value" in ln for ln in lines)
    m.unregister_gauge("live_value", (("t", "x"),))
    assert not any("live_value" in ln for ln in m.render())


def test_daemon_metrics_endpoint_has_gauges_and_histograms(tmp_path):
    from test_s3_api import make_client, make_daemon, teardown

    from garage_tpu.api.admin.api_server import AdminApiServer

    async def main():
        garage, s3, endpoint = await make_daemon(tmp_path)
        admin = AdminApiServer(garage)
        await admin.start("127.0.0.1", 0)
        try:
            client = await make_client(garage, endpoint)
            await client.create_bucket("obs")
            await client.put_object("obs", "k", b"x" * 10_000)
            await client.get_object("obs", "k")

            import aiohttp

            port = admin.runner.addresses[0][1]
            async with aiohttp.ClientSession() as sess:
                async with sess.get(f"http://127.0.0.1:{port}/metrics") as resp:
                    assert resp.status == 200
                    text = await resp.text()
            assert "block_resync_queue_length" in text
            assert "table_merkle_updater_todo_queue_length" in text
            assert 'api_s3_request_duration_bucket' in text
            # latency histograms render the Prometheus-standard `_sum`
            # (in seconds), not the old `_seconds_total`
            assert 'api_s3_request_duration_sum{method=' in text
            # (the `*_seconds_total` COUNTERS of the event-loop meter and
            # the dispatch CPU clock are counters of seconds, not that)
            assert "_duration_seconds_total" not in text
            assert "_lag_seconds_total" not in text
            assert 'le="+Inf"' in text
            assert "cluster_connected_nodes 0" in text
            # per-endpoint rpc + per-table op families (reference
            # rpc_helper.rs:172-217, monitoring.md): the PUT/GET above
            # drove table + block endpoints through the rpc layer
            assert 'rpc_request_counter{endpoint=' in text
            assert 'rpc_request_duration_bucket{endpoint=' in text
            assert 'table_put_request_counter{table_name=' in text
            assert 'table_put_request_duration_bucket{table_name=' in text
            assert 'table_internal_update_counter{table_name=' in text
        finally:
            await admin.stop()
            await teardown(garage, s3)

    run(main())


def test_metrics_exposition_lint(tmp_path):
    """Satellite: /metrics from a live node parses as clean Prometheus
    exposition — every family declares `# TYPE` before its first sample,
    no family is declared twice (the old inline/registry duplication of
    the resync/merkle/gc queue gauges), no duplicate (name, labelset)
    pairs, and the bare `worker_errors` gauge is gone in favour of the
    registry-backed `worker_*` families.  The strict parser itself is
    the shared script/dashboard_lint.py lint_exposition."""
    from dashboard_lint import lint_exposition
    from test_s3_api import make_client, make_daemon, teardown

    from garage_tpu.api.admin.api_server import AdminApiServer

    async def main():
        garage, s3, endpoint = await make_daemon(tmp_path)
        admin = AdminApiServer(garage)
        await admin.start("127.0.0.1", 0)
        try:
            client = await make_client(garage, endpoint)
            await client.create_bucket("lint")
            await client.put_object("lint", "k", b"z" * 9_000)
            await client.get_object("lint", "k")
            await asyncio.sleep(0.3)  # watchdog beats + worker iterations

            import aiohttp

            port = admin.runner.addresses[0][1]
            async with aiohttp.ClientSession() as sess:
                async with sess.get(f"http://127.0.0.1:{port}/metrics") as r:
                    assert r.status == 200
                    text = await r.text()

            types = lint_exposition(text)  # raises on format violations
            # standard histogram exposition ONLY: the nonstandard
            # `_seconds_total` suffix latency families used to render
            # is gone in favour of `_sum` (in seconds)
            # (the `*_seconds_total` COUNTERS of the event-loop meter and
            # the dispatch CPU clock are counters of seconds, not that)
            assert "_duration_seconds_total" not in text
            assert "_lag_seconds_total" not in text

            # the formerly-duplicated families exist exactly once, from
            # the registry
            for fam in (
                "block_resync_queue_length",
                "table_merkle_updater_todo_queue_length",
                "table_gc_todo_queue_length",
                "cluster_connected_nodes",
            ):
                assert fam in types, fam
            # registry-backed per-worker health replaces bare worker_errors
            assert "worker_errors" not in types
            for fam in ("worker_errors_total", "worker_state", "worker_queue_length"):
                assert fam in types, fam
            assert 'worker_queue_length{worker="resync:0"' in text
            # the watchdog's lag histogram renders in standard form
            assert types.get("event_loop_lag_seconds") == "histogram"
            assert "event_loop_lag_seconds_bucket" in text
            assert "event_loop_lag_seconds_sum" in text

            # latency-X-ray phase cardinality: every {op,phase} label
            # combination of api_s3_phase_duration comes from the fixed
            # catalogue (utils/latency.py) — an ad-hoc span name leaking
            # into the label space is a lint failure, not a new series
            import re as _re

            from garage_tpu.utils.latency import OPS, PHASES

            assert types.get("api_s3_phase_duration") == "histogram"
            combos = set(
                _re.findall(
                    r'api_s3_phase_duration_count\{op="([^"]+)",'
                    r'phase="([^"]+)"\}',
                    text,
                )
            )
            assert combos, "no phase samples from the PUT/GET above"
            for op, phase in combos:
                assert op in OPS, f"op {op!r} outside the catalogue"
                assert phase in PHASES, f"phase {phase!r} outside the catalogue"
            # overlap-efficiency gauge rides along, op-labelled only
            for m in _re.finditer(
                r'api_s3_overlap_efficiency\{op="([^"]+)"\}', text
            ):
                assert m.group(1) in OPS
        finally:
            await admin.stop()
            await teardown(garage, s3)

    run(main())


def test_tracer_spans_nest_and_export():
    """Spans nest via contextvars and export OTLP/HTTP JSON to the sink."""
    from aiohttp import web

    received = []

    async def collector(request):
        received.append(await request.json())
        return web.Response(status=200)

    async def main():
        app = web.Application()
        app.router.add_post("/v1/traces", collector)
        runner = web.AppRunner(app)
        await runner.setup()
        site = web.TCPSite(runner, "127.0.0.1", 0)
        await site.start()
        port = runner.addresses[0][1]

        t = Tracer()
        t.configure(f"http://127.0.0.1:{port}")
        with t.span("outer", kind="test"):
            outer = t.current()
            with t.span("inner"):
                inner = t.current()
                assert inner.trace_id == outer.trace_id
                assert inner.parent_id == outer.span_id
            # sibling after inner closed: parent restored
            assert t.current() is outer
        assert t.current() is None
        await t._flush()
        await t.stop()
        await runner.cleanup()

        assert received, "collector got no spans"
        spans = received[0]["resourceSpans"][0]["scopeSpans"][0]["spans"]
        by_name = {s["name"]: s for s in spans}
        assert set(by_name) == {"outer", "inner"}
        assert by_name["inner"]["parentSpanId"] == by_name["outer"]["spanId"]
        assert by_name["inner"]["traceId"] == by_name["outer"]["traceId"]
        assert "parentSpanId" not in by_name["outer"]
        assert int(by_name["outer"]["endTimeUnixNano"]) >= int(
            by_name["outer"]["startTimeUnixNano"]
        )
        attrs = {a["key"]: a["value"] for a in by_name["outer"]["attributes"]}
        assert attrs["kind"] == {"stringValue": "test"}

    run(main())


def test_tracer_disabled_is_noop():
    t = Tracer()
    with t.span("x") as s:
        assert s is None
    assert t._buf == []


def test_traceparent_inject_extract_roundtrip():
    from garage_tpu.utils.tracing import TRACEPARENT_LEN, Tracer

    t = Tracer()
    assert t.inject() is None  # disabled
    t.sink = "http://sink.invalid"
    assert t.inject() is None  # enabled, no active span
    with t.span("op") as s:
        tp = t.inject()
        assert tp is not None and len(tp) == TRACEPARENT_LEN
        rp = t.extract(tp)
        assert rp.trace_id == s.trace_id and rp.span_id == s.span_id
        assert rp.sampled
    # malformed input degrades to a local root, never an error
    assert t.extract(None) is None
    assert t.extract(b"short") is None
    assert t.extract(b"x" * 99) is None
    # a remote parent wins over an (absent) context parent
    rp2 = t.extract(tp)
    with t.span("remote-child", remote_parent=rp2) as c:
        assert c.trace_id == s.trace_id
        assert c.parent_id == s.span_id
    t.sink = None


@contextlib.contextmanager
def _global_tracer_enabled():
    """Enable the process tracer WITHOUT a flusher task (sink attribute
    set directly, configure() not called) so tests can inspect _buf."""
    from garage_tpu.utils.tracing import tracer

    tracer.sink = "http://sink.invalid"
    tracer._buf.clear()
    try:
        yield tracer
    finally:
        tracer.sink = None
        tracer._buf.clear()


def _span_noise(name: str) -> bool:
    # peering keepalives trace too; they are concurrent unrelated roots
    return "net/ping" in name or "net/peer_list" in name


def test_cluster_single_trace_and_retry_spans(tmp_path):
    """Tentpole acceptance: ONE trace id per S3 PUT across all 3 nodes'
    spans, table/block sub-spans parented under it, and a retried RPC
    shows per-attempt child spans tagged with attempt + breaker state."""
    from test_ec_cluster import make_ec_cluster, stop_cluster

    from garage_tpu.api.s3.api_server import S3ApiServer
    from garage_tpu.api.s3.client import S3Client
    from garage_tpu.net.message import Resp
    from garage_tpu.net.netapp import RpcError

    async def main():
        # spawn=False: background sync workers would trace their own
        # unrelated root spans into the shared buffer
        garages = await make_ec_cluster(tmp_path, n=3, spawn=False)
        s3 = S3ApiServer(garages[0])
        await s3.start("127.0.0.1", 0)
        ep = f"http://127.0.0.1:{s3.runner.addresses[0][1]}"
        key = await garages[0].helper.create_key("obs")
        key.params().allow_create_bucket.update(True)
        await garages[0].key_table.insert(key)
        client = S3Client(ep, key.key_id, key.secret())
        try:
            await client.create_bucket("trace")
            with _global_tracer_enabled() as tracer:
                await client.put_object("trace", "k", b"x" * 20_000)
                spans = [
                    s for s in tracer._buf if not _span_noise(s.name)
                ]
                roots = [s for s in spans if s.name == "api:s3"]
                assert len(roots) == 1
                tid = roots[0].trace_id
                # EXACTLY one trace id across every span of the PUT
                assert {s.trace_id for s in spans} == {tid}
                handles = [
                    s for s in spans if s.name.startswith("rpc-handle:")
                ]
                # ...including handler spans running on the two REMOTE
                # nodes (the `node` attr says who handled it) — these
                # only join the trace via traceparent extraction, not
                # contextvars
                remote = {
                    s.attrs["node"]
                    for s in handles
                    if s.attrs["node"] != garages[0].node_id.hex()[:16]
                }
                assert len(remote) == 2, remote
                # table/block sub-spans correctly parented (non-root)
                assert any(s.name.startswith("table:insert") for s in spans)
                assert any(s.name.startswith("block:put") for s in spans)
                sids = {s.span_id for s in spans}
                for s in spans:
                    if s is not roots[0]:
                        assert s.parent_id in sids, s.name

                # --- retried RPC: per-attempt child spans ---------------
                ep_h = garages[1].netapp.endpoint("test/obs-retry")

                async def h(frm, req):
                    return Resp("ok")

                ep_h.set_handler(h)
                ep_c = garages[0].netapp.endpoint("test/obs-retry")
                orig_call = garages[0].netapp.call
                fail_left = {"n": 1}

                async def flaky(target, path, req, **kw):
                    if path == "test/obs-retry" and fail_left["n"]:
                        fail_left["n"] -= 1
                        raise RpcError("injected transport failure")
                    return await orig_call(target, path, req, **kw)

                garages[0].netapp.call = flaky
                try:
                    tracer._buf.clear()
                    with tracer.span("quorum-write") as root2:
                        resp = await garages[0].helper_rpc.call(
                            ep_c, garages[1].node_id, {"x": 1},
                            idempotent=True,
                        )
                    assert resp.body == "ok"
                finally:
                    garages[0].netapp.call = orig_call
                attempts = sorted(
                    (
                        s for s in tracer._buf
                        if s.name == "rpc-attempt:test/obs-retry"
                    ),
                    key=lambda s: s.start_ns,
                )
                assert [s.attrs["attempt"] for s in attempts] == [0, 1]
                assert attempts[0].ok is False and attempts[1].ok is True
                assert all(s.attrs["breaker"] == "closed" for s in attempts)
                assert all(s.trace_id == root2.trace_id for s in attempts)
                assert all(s.parent_id == root2.span_id for s in attempts)
                # the remote handler joined the same trace THROUGH the retry
                rhandles = [
                    s for s in tracer._buf
                    if s.name == "rpc-handle:test/obs-retry"
                ]
                assert rhandles
                assert all(s.trace_id == root2.trace_id for s in rhandles)
        finally:
            await stop_cluster(garages, [s3], [client])

    run(main())


def test_tracing_disabled_rpc_hot_path_is_allocation_free():
    """Acceptance: no trace_sink => the RPC hot path creates ZERO Span
    objects, buffers nothing, and puts no traceparent on the wire."""
    import garage_tpu.utils.tracing as tracing_mod
    from garage_tpu.net.handshake import gen_node_key
    from garage_tpu.net.message import Resp
    from garage_tpu.net.netapp import NetApp

    async def main():
        a = NetApp(b"k" * 32, gen_node_key())
        b = NetApp(b"k" * 32, gen_node_key())
        await a.listen("127.0.0.1", 0)
        await b.listen("127.0.0.1", 0)
        seen_tp = []

        async def h(frm, req):
            seen_tp.append(req.traceparent)
            return Resp("ok")

        b.endpoint("test/noop").set_handler(h)
        await a.connect(b.bind_addr, b.id)
        ep = a.endpoint("test/noop")

        n_spans = {"n": 0}
        real_span = tracing_mod.Span

        class CountingSpan(real_span):
            def __init__(self, *args, **kw):
                n_spans["n"] += 1
                super().__init__(*args, **kw)

        tracing_mod.Span = CountingSpan
        try:
            assert not tracing_mod.tracer.enabled
            for _ in range(20):
                await ep.call(b.id, {"x": 1})
            assert n_spans["n"] == 0, "disabled tracing allocated spans"
            assert tracing_mod.tracer._buf == []
            assert tracing_mod.tracer.inject() is None
            assert seen_tp == [None] * 20  # nothing on the wire either
        finally:
            tracing_mod.Span = real_span
            await a.shutdown()
            await b.shutdown()

    run(main())


def test_metrics_exposition_tpu_families(tmp_path):
    """Tentpole acceptance: after one EC encode, /metrics includes the
    tpu_codec_* families, compile-cache hit/miss counters, and the
    backend-platform gauge with non-placeholder values."""
    import numpy as np

    from test_s3_api import make_daemon, teardown

    from garage_tpu.api.admin.api_server import AdminApiServer
    from garage_tpu.block.codec.ec import EcCodec

    async def main():
        garage, s3, endpoint = await make_daemon(tmp_path)
        admin = AdminApiServer(garage)
        await admin.start("127.0.0.1", 0)
        try:
            codec = EcCodec(2, 1, tpu_enable=True)
            rng = np.random.default_rng(0)
            blocks = [
                bytes(rng.integers(0, 256, 4096, dtype=np.uint8))
                for _ in range(8)
            ]
            # the fused encode the batcher drives, then two repairs of
            # >= TPU_BATCH_MIN entries (they share one jitted program)
            out = [p for p, _h in codec.encode_batch_hashed(blocks, impl="xla")]
            codec.reconstruct_batch(
                [({0: o[0], 2: o[2]}, [1], 4096) for o in out]
            )
            codec.reconstruct_batch(
                [({1: o[1], 2: o[2]}, [0], 4096) for o in out]
            )

            import aiohttp

            port = admin.runner.addresses[0][1]
            async with aiohttp.ClientSession() as sess:
                async with sess.get(f"http://127.0.0.1:{port}/metrics") as r:
                    assert r.status == 200
                    text = await r.text()
            # dispatch counter with full label set (tests run with
            # JAX_PLATFORMS=cpu, so the resolved platform is "cpu" —
            # non-placeholder: "unknown" would mean resolution failed)
            assert 'tpu_codec_dispatch_total{kernel="ec_encode_hash",platform="cpu"}' in text
            assert 'tpu_codec_dispatch_total{kernel="ec_reconstruct",platform="cpu"}' in text
            # batch-size histogram: 8 blocks -> le="8" bucket, _sum line
            assert 'tpu_codec_batch_size_bucket{kernel="ec_encode_hash",le="8"}' in text
            assert 'tpu_codec_batch_size_sum{kernel="ec_encode_hash"}' in text
            # duration histogram + bytes
            assert 'tpu_codec_dispatch_duration_bucket{kernel="ec_encode_hash",platform="cpu"' in text
            assert 'tpu_codec_bytes_total{kernel="ec_encode_hash",platform="cpu"}' in text
            # compile-cache families: first build is a miss, the two
            # reconstruct dispatches share the jitted fn -> a hit too
            assert 'tpu_compile_cache_miss_total{cache="ec_apply"}' in text
            assert 'tpu_compile_cache_hit_total{cache="ec_apply"}' in text
            assert 'tpu_compile_cache_miss_total{cache="ec_recon_matrix"}' in text
            # resolved-backend gauge (scrape-time)
            assert 'jax_backend_platform{platform="cpu"} 1' in text
            assert 'platform="unknown"' not in text
            # codec-layer offload accounting (registry is process-global:
            # other tests may have encoded too, so assert >= our batch)
            line = next(
                ln for ln in text.splitlines()
                if ln.startswith('block_codec_blocks_total{op="encode",path="tpu"}')
            )
            assert float(line.rsplit(" ", 1)[1]) >= 8
            assert 'block_codec_bytes_total{op="encode",path="tpu"}' in text
        finally:
            await admin.stop()
            await teardown(garage, s3)

    run(main())


def test_log_formatter_trace_stamping():
    """Satellite: records under an active span carry trace_id/span_id in
    both JSON-lines and text output; records outside a span carry empty
    fields (stable schema, never missing keys)."""
    import io
    import json as _json
    import logging

    from garage_tpu.utils.log_fmt import (
        JsonLinesFormatter,
        TextFormatter,
        TraceContextFilter,
        setup_logging,
    )

    buf = io.StringIO()
    h = logging.StreamHandler(buf)
    h.setFormatter(JsonLinesFormatter())
    h.addFilter(TraceContextFilter())
    lg = logging.getLogger("garage.test.obs")
    lg.addHandler(h)
    lg.setLevel("INFO")
    lg.propagate = False
    try:
        with _global_tracer_enabled() as tracer:
            with tracer.span("logged-op") as s:
                lg.info("inside")
            span_ids = (s.trace_id.hex(), s.span_id.hex())
        lg.info("outside")
        rec_in, rec_out = [
            _json.loads(ln) for ln in buf.getvalue().splitlines()
        ]
        assert (rec_in["trace_id"], rec_in["span_id"]) == span_ids
        assert rec_in["msg"] == "inside" and rec_in["level"] == "INFO"
        assert rec_out["trace_id"] == "" and rec_out["span_id"] == ""

        # text mode: suffix only when traced
        buf.truncate(0)
        buf.seek(0)
        h.setFormatter(TextFormatter())
        with _global_tracer_enabled() as tracer:
            with tracer.span("op2"):
                lg.info("traced line")
        lg.info("plain line")
        traced, plain = buf.getvalue().splitlines()
        assert "[trace=" in traced and "[trace=" not in plain
    finally:
        lg.removeHandler(h)

    # setup_logging is idempotent: repeated calls keep exactly one
    # garage-managed handler on the root logger
    setup_logging("json")
    setup_logging("text")
    root = logging.getLogger()
    ours = [
        x for x in root.handlers if getattr(x, "_garage_log_fmt", False)
    ]
    assert len(ours) == 1
    root.removeHandler(ours[0])
