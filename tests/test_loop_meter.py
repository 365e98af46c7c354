"""Event-loop meter (utils/flight.py LoopMeter): on-loop busy time for
every span, by layer, summing to the loop's whole time — and the device
dispatch split (ops/telemetry.py) that now waits for the device inside
`compute()`."""

from __future__ import annotations

import asyncio
import os
import re
import sys
import time

import pytest

sys.path.insert(
    0, os.path.join(os.path.dirname(os.path.dirname(__file__)), "script")
)

from garage_tpu.utils import flight, latency, tracing
from garage_tpu.utils.metrics import registry
from garage_tpu.utils.tracing import LAYERS, loop_label, tracer

BUSY = "event_loop_busy_seconds_total"


def run(coro):
    return asyncio.run(coro)


def busy_by_span() -> dict[str, float]:
    return {
        dict(lbl)["span"]: v
        for (name, lbl), v in registry.counters.items()
        if name == BUSY
    }


def delta(before: dict, after: dict) -> dict:
    return {k: v - before.get(k, 0.0) for k, v in after.items() if v != before.get(k, 0.0)}


def spin(secs: float) -> None:
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < secs:
        pass


@pytest.fixture
def metered():
    """Spans on (the X-ray hook) and the meter installed by the test's
    own loop: `async with metered()`-style helper returning a context."""

    class Ctx:
        async def __aenter__(self):
            latency.enable()
            flight.loop_meter.install()
            return flight.loop_meter

        async def __aexit__(self, *exc):
            flight.loop_meter.remove()
            latency.disable()

    return Ctx


def test_spinner_and_sleeper_busy_against_wall(metered):
    """(a) two tasks under different spans, one spinning 30 ms and one
    sleeping 30 ms: the spinner's span gets ~30 ms busy, the sleeper's
    ~0, and both have ~30 ms wall."""

    async def spinner():
        with tracer.span("t:spin", layer="block") as s:
            await asyncio.sleep(0)
            spin(0.03)
        return s

    async def sleeper():
        with tracer.span("t:sleep", layer="table") as s:
            await asyncio.sleep(0.03)
        return s

    async def main():
        async with metered():
            before = busy_by_span()
            a, b = await asyncio.gather(
                asyncio.create_task(spinner()), asyncio.create_task(sleeper())
            )
            d = delta(before, busy_by_span())
        return a, b, d

    a, b, d = run(main())
    assert 0.029 <= a.busy_ns / 1e9 <= 0.045
    assert b.busy_ns / 1e9 < 0.003
    for s in (a, b):
        assert 0.029 <= (s.end_ns - s.start_ns) / 1e9 <= 0.08
    assert d["t:spin"] == pytest.approx(a.busy_ns / 1e9, abs=1e-6)
    assert d["t:sleep"] == pytest.approx(b.busy_ns / 1e9, abs=1e-6)


def test_step_entering_a_span_half_way_is_split_at_the_entry(metered):
    """(b) one step spins 20 ms, enters a span, spins 20 ms inside, leaves
    it and spins 10 ms more: the span gets its 20, its parent the 30."""

    async def main():
        async with metered():
            with tracer.span("t:outer", layer="api") as outer:
                await asyncio.sleep(0)  # a fresh step under `outer`
                spin(0.02)
                with tracer.span("t:inner", layer="codec") as inner:
                    spin(0.02)
                spin(0.01)
                await asyncio.sleep(0)
        return outer, inner

    outer, inner = run(main())
    assert 0.019 <= inner.busy_ns / 1e9 <= 0.03
    assert 0.029 <= outer.busy_ns / 1e9 <= 0.045
    # self time: the child's steps are the child's
    assert outer.busy_ns + inner.busy_ns <= outer.end_ns - outer.start_ns


def test_busy_over_labels_plus_wait_is_the_elapsed_time(metered):
    """(c) busy summed over labels + wait = elapsed, within 2 %; the
    steps are counted and the loop thread's CPU follows the spinning."""

    def loop_counters():
        c = registry.counters
        return {
            "busy": sum(v for (n, _l), v in c.items() if n == BUSY),
            "wait": c[("event_loop_wait_seconds_total", ())],
            "cpu": c[("event_loop_cpu_seconds_total", ())],
            "steps": c[("event_loop_steps_total", ())],
            "meter": c[("event_loop_meter_seconds_total", ())],
        }

    async def worker(i):
        with tracer.span(f"t:w{i % 3}", layer=LAYERS[i % 3]):
            for _ in range(20):
                spin(0.001)
                await asyncio.sleep(0.001)

    async def main():
        async with metered() as m:
            await asyncio.sleep(0)  # a step's end: the clocks start together
            m.publish()
            before, t0 = loop_counters(), m.last_end
            await asyncio.gather(*(worker(i) for i in range(6)))
            await asyncio.sleep(0)
            m.publish()
            return before, loop_counters(), (m.last_end - t0) / 1e9, m.step_cost_s

    before, after, elapsed, step_cost = run(main())
    d = {k: after[k] - before[k] for k in after}
    assert d["busy"] + d["wait"] == pytest.approx(elapsed, rel=0.02)
    assert d["busy"] >= 0.12  # 6 x 20 x 1 ms of spinning
    assert d["steps"] >= 6 * 20
    assert 0.5 * d["busy"] <= d["cpu"] <= elapsed
    # the bracket's own cost: calibrated, small, and what the counter says
    assert 0 < step_cost < 20e-6
    assert d["meter"] == pytest.approx(d["steps"] * step_cost, rel=1e-6)


def test_handler_spawned_by_a_recv_loop_is_not_charged_to_the_dialer(metered):
    """(d) a connection dialed inside a request's span: its loops run
    under plain labels, and the handler task the recv loop spawns opens
    its `rpc-handle:` span as its own — none of their time goes to the
    span that happened to open the connection."""
    from garage_tpu.net.handshake import gen_node_key
    from garage_tpu.net.message import Resp
    from garage_tpu.net.netapp import NetApp

    async def main():
        async with metered():
            a = NetApp(b"k" * 32, gen_node_key())
            b = NetApp(b"k" * 32, gen_node_key())
            await b.listen("127.0.0.1", 0)
            seen = {}

            async def h(frm, req):
                seen["span"] = tracer.current()
                spin(0.02)
                return Resp("ok")

            b.endpoint("block/test").set_handler(h)
            try:
                before = busy_by_span()
                with tracer.span("t:dialer", layer="api") as dialer:
                    await a.connect(b.bind_addr, b.id)
                # the request that opened the connection is over
                await a.endpoint("block/test").call(b.id, {"x": 1})
                d = delta(before, busy_by_span())
            finally:
                await a.shutdown()
                await b.shutdown()
            return dialer, seen["span"], d

    dialer, handle, d = run(main())
    assert handle.name == "rpc-handle:block/test" and handle.layer == "block"
    assert handle.trace_id != dialer.trace_id  # a root under the label
    assert d["rpc-handle:block/test"] >= 0.02
    assert dialer.busy_ns / 1e9 < 0.01
    assert d.get("t:dialer", 0.0) < 0.01
    assert {"net:recv", "net:send"} <= set(d)


def test_span_opened_in_a_thread_leaves_the_meter_untouched(metered):
    """(e) `asyncio.to_thread` copies the context: the span opened there
    nests under the caller's, gets no loop time, and the loop's holder
    is not moved while the thread runs."""

    def in_thread():
        with tracer.span("t:thread", layer="codec") as s:
            spin(0.03)
        return s

    async def main():
        async with metered() as m:
            with tracer.span("t:caller", layer="api") as caller:
                fut = asyncio.create_task(asyncio.to_thread(in_thread))
                holders = []
                while not fut.done():
                    await asyncio.sleep(0.002)
                    holders.append(m.cur)
                s = await fut
        return caller, s, holders

    caller, s, holders = run(main())
    assert s.parent_id == caller.span_id
    assert s.busy_ns == 0
    assert "t:thread" not in busy_by_span()
    assert holders and all(h is caller for h in holders)


def test_install_and_remove_are_refcounted_and_restore_handle_run():
    """(f) two nodes on one loop share one bracket; the last stop puts
    `Handle._run` back and unhooks the tracer."""
    orig = asyncio.events.Handle._run
    m = flight.loop_meter

    async def main():
        m.install()
        bracket = asyncio.events.Handle._run
        assert bracket is not orig and tracing._meter is m
        m.install()
        assert asyncio.events.Handle._run is bracket and m.refs == 2
        m.remove()
        assert asyncio.events.Handle._run is bracket and tracing._meter is m
        m.remove()
        assert asyncio.events.Handle._run is orig and tracing._meter is None
        m.remove()  # one stop too many changes nothing
        assert m.refs == 0 and asyncio.events.Handle._run is orig

    run(main())

    # handles of another loop (run here while this loop stands inside one
    # step) pass through the bracket unmetered
    async def other_loop():
        for _ in range(50):
            await asyncio.sleep(0)

    async def holder():
        import threading

        m.install()
        try:
            m.publish()  # brings the bracket's own count up to date
            steps, busy = m.steps, sum(busy_by_span().values())
            t = threading.Thread(target=lambda: asyncio.run(other_loop()))
            t.start()
            t.join(timeout=10)
            assert not t.is_alive()
            m.publish()
            return m.steps - steps, sum(busy_by_span().values()) - busy
        finally:
            m.remove()

    assert run(holder()) == (0, 0.0)


def test_label_sets_are_closed():
    """(g) `layer` comes from LAYERS and nowhere else; `span` is a span
    or label NAME — a phase, an endpoint path, a worker's name — never
    an id.  (The live-node exposition lint holds the same contract:
    script/dashboard_lint.py BOUNDED_LABEL_VALUES.)"""
    from dashboard_lint import BOUNDED_LABEL_VALUES

    from garage_tpu.net.netapp import _handle_layer

    with pytest.raises(ValueError):
        tracing.Span("x", None, {}, layer="kernel")
    with pytest.raises(ValueError):
        loop_label("x", "device")
    assert set(latency.PHASE_LAYER) == set(latency.PHASES)
    assert set(latency.PHASE_LAYER.values()) <= set(LAYERS)
    for path, layer in (
        ("block/data", "block"), ("table/object", "table"),
        ("table/block_ref/sync", "background"), ("table/version/gc", "background"),
        ("rpc/system/status", "background"), ("net/ping", "background"),
        ("k2v/rpc", "rpc"), ("admin/rpc", "rpc"),
    ):
        assert _handle_layer(path) == layer
    declared = BOUNDED_LABEL_VALUES[BUSY]
    assert declared["layer"] == frozenset(LAYERS)
    for (name, lbl) in registry.counters:
        if name != BUSY:
            continue
        lbl = dict(lbl)
        assert set(lbl) == {"layer", "span"}
        assert lbl["layer"] in LAYERS
        assert declared["span"].fullmatch(lbl["span"]), lbl["span"]
        assert not re.search(r"[0-9a-f]{12}", lbl["span"]), lbl["span"]


def test_compute_of_a_dispatch_covers_the_wait_for_the_device():
    """(h) on the CPU backend a jitted loop long enough to outlast its
    enqueue: `compute()` holds `block_until_ready`, so it is >= 80 % of
    the dispatch's wall, and the copies are the rest."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from garage_tpu.ops import ec_tpu, telemetry

    @jax.jit
    def slow(bitmat, x):
        def body(_i, acc):
            return (acc * 31 + 7) % 251

        y = jax.lax.fori_loop(0, 400, body, x.astype(jnp.int32))
        return y.astype(jnp.uint8)[:, :1, :]

    ec = ec_tpu.EcTpu(2, 1, platform="cpu", n_devices=1)
    x = np.zeros((4, 2, 65536), dtype=np.uint8)
    real = ec_tpu.ec_apply_fn
    ec_tpu.ec_apply_fn = lambda *_a, **_k: slow
    try:
        ec.encode(x)  # compile outside the measured dispatch
        lbl = (("kernel", "ec_encode"),)
        plat = (("kernel", "ec_encode"), ("platform", "cpu"))

        def read():
            d = registry.durations
            return (d[("tpu_codec_compute_duration", lbl)][1],
                    d[("tpu_codec_transfer_duration", lbl)][1],
                    d[("tpu_codec_dispatch_duration", plat)][1])

        c0, t0, w0 = read()
        out = ec.encode(x)
        c1, t1, w1 = read()
    finally:
        ec_tpu.ec_apply_fn = real
    assert out.shape == (4, 1, 65536)
    compute, transfer, wall = c1 - c0, t1 - t0, w1 - w0
    assert wall > 0.005, wall
    assert compute >= 0.8 * wall, (compute, transfer, wall)
    assert compute + transfer <= wall * 1.001
    assert (("tpu_codec_dispatch_cpu_seconds_total", plat)) in registry.counters
    assert telemetry.annotate("x", "host").__class__.__name__ == "nullcontext"


def test_critical_path_gives_each_phase_its_busy_ms(metered):
    """(i) `critical_path()` returns a `busyMs` per phase beside `ms`;
    their sum is <= the request's `wallMs` (one loop thread), and a
    phase that waited reads a long `ms` with a short `busyMs`."""

    async def main():
        async with metered():
            latency.aggregator.reset()
            seen = []
            tracer.add_hook(seen.append)
            try:
                with tracer.span(latency.ROOT_SPAN_NAME, layer="api") as root:
                    latency.mark_op("put")
                    with latency.phase_span("encode"):
                        await asyncio.sleep(0)
                        spin(0.02)
                    with latency.phase_span("meta_commit"):
                        with tracer.span("table:insert", layer="table"):
                            await asyncio.sleep(0)
                            spin(0.005)
                        await asyncio.sleep(0.03)
            finally:
                tracer.remove_hook(seen.append)
            return root, seen, latency.aggregator.snapshot()

    root, spans, snap = run(main())
    r = latency.critical_path(root, spans)
    enc, meta = r["phases"]["encode"], r["phases"]["meta_commit"]
    assert 19 <= enc["busyMs"] <= enc["ms"] + 0.5
    assert meta["ms"] >= 34 and 4.5 <= meta["busyMs"] <= 12
    assert sum(p["busyMs"] for p in r["phases"].values()) <= r["busyMs"] <= r["wallMs"]
    # the waterfall endpoint and the slow-request record carry it
    put = snap["put"]
    assert put["phases"]["meta_commit"]["busyMs"] == pytest.approx(meta["busyMs"], abs=0.01)
    # (the aggregator read the root before its own hooks' time was added)
    assert r["busyMs"] - 2.0 <= put["busyMs"] <= r["busyMs"]
    rec = flight._build_record(root, spans, 60.0)
    assert rec["busyMs"] == pytest.approx(r["busyMs"], abs=0.01)
    assert {s["name"]: s["busyMs"] for s in rec["spans"]}["table:insert"] >= 4.5
