"""rpc/peer_health.py: circuit breaker, adaptive timeouts, health-aware
read ordering, and the RpcHelper retry loop — with every state transition
and retry observable in the utils/metrics registry."""

import asyncio
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(__file__))

from test_net import make_node  # noqa: E402
from test_rpc import make_cluster, stop_cluster  # noqa: E402

from garage_tpu.net.fault import FaultPlan, FaultRule  # noqa: E402
from garage_tpu.net.message import PRIO_HIGH, Resp  # noqa: E402
from garage_tpu.rpc.peer_health import (  # noqa: E402
    CLOSED,
    HALF_OPEN,
    OPEN,
    PeerHealth,
    PeerUnavailable,
)
from garage_tpu.rpc.rpc_helper import RpcHelper  # noqa: E402
from garage_tpu.utils.metrics import registry  # noqa: E402

ME = b"\x00" * 32
PEER = b"\xaa" * 32


class FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


def make_health(**over):
    clock = FakeClock()
    h = PeerHealth(ME, clock=clock)
    for k, v in over.items():
        setattr(h, k, v)
    return h, clock


def transition_count(peer: bytes, to: str) -> float:
    return registry.counters.get(
        (
            "rpc_breaker_transition_counter",
            (("peer", peer.hex()[:16]), ("to", to)),
        ),
        0,
    )


def test_breaker_full_cycle_and_metrics():
    """closed -> open (after N consecutive transport failures) ->
    half-open (cooldown elapsed, one probe admitted) -> closed (probe
    succeeded); every transition counted in the registry."""
    h, clock = make_health(open_after=3, open_cooldown=10.0)
    t_open0 = transition_count(PEER, OPEN)
    t_closed0 = transition_count(PEER, CLOSED)

    assert h.state_of(PEER) == CLOSED
    h.record_failure(PEER)
    h.record_failure(PEER)
    assert h.state_of(PEER) == CLOSED  # not yet
    h.acquire(PEER)  # still admitted while closed
    h.record_failure(PEER)
    assert h.state_of(PEER) == OPEN
    assert transition_count(PEER, OPEN) == t_open0 + 1

    # open: calls fast-fail, and the fast-fail is counted
    ff_lbl = ("rpc_breaker_fastfail_counter", (("peer", PEER.hex()[:16]),))
    ff0 = registry.counters.get(ff_lbl, 0)
    with pytest.raises(PeerUnavailable):
        h.acquire(PEER)
    assert registry.counters[ff_lbl] == ff0 + 1

    # cooldown elapses: next acquire flips to half-open and admits ONE probe
    clock.t += 10.0
    h.acquire(PEER)
    assert h.state_of(PEER) == HALF_OPEN
    with pytest.raises(PeerUnavailable):
        h.acquire(PEER)  # second caller is fast-failed while probing

    # probe succeeds: closed again, gauge/counters updated
    h.record_success(PEER, rtt=0.01)
    assert h.state_of(PEER) == CLOSED
    assert transition_count(PEER, CLOSED) == t_closed0 + 1
    assert (
        registry.gauges[("rpc_peer_breaker_state", (("peer", PEER.hex()[:16]),))]
        == 0
    )


def test_half_open_probe_failure_reopens():
    h, clock = make_health(open_after=2, open_cooldown=5.0)
    h.record_failure(PEER)
    h.record_failure(PEER)
    assert h.state_of(PEER) == OPEN
    clock.t += 5.0
    assert h.acquire(PEER) is True  # probe admitted
    h.record_failure(PEER, probe=True)  # probe failed
    assert h.state_of(PEER) == OPEN
    # a STALE verdict (non-probe) must NOT reopen a half-open breaker or
    # free a probe slot it doesn't own
    clock.t += 5.0
    assert h.acquire(PEER) is True  # next probe in flight
    h.record_failure(PEER)  # stale failure from an old call / a ping
    assert h.state_of(PEER) == HALF_OPEN, "stale verdict must not reopen"
    with pytest.raises(PeerUnavailable):
        h.acquire(PEER)  # the probe slot is still held by the real probe
    # and the cooldown restarts from the probe failure
    with pytest.raises(PeerUnavailable):
        h.acquire(PEER)


def test_cancelled_probe_releases_slot():
    h, clock = make_health(open_after=1, open_cooldown=1.0)
    h.record_failure(PEER)
    clock.t += 1.0
    assert h.acquire(PEER) is True  # this call owns the probe slot
    h.release(PEER)  # ... cancelled, no verdict
    assert h.acquire(PEER) is True  # slot is free again for the next probe


def test_only_probe_owner_may_release():
    """acquire() returns False for ordinary (closed-state) admissions —
    RpcHelper uses that to never release a probe slot someone else holds
    (a cancelled stale call must not let a second concurrent probe at a
    half-open peer)."""
    h, clock = make_health(open_after=1, open_cooldown=1.0)
    assert h.acquire(PEER) is False  # closed: not a probe
    h.record_failure(PEER)
    clock.t += 1.0
    assert h.acquire(PEER) is True  # half-open: the one probe
    with pytest.raises(PeerUnavailable):
        h.acquire(PEER)  # second caller fast-fails while the probe runs


def test_success_while_open_closes():
    """Late evidence of life (a peering ping succeeding) closes the
    breaker without waiting for the half-open dance."""
    h, _clock = make_health(open_after=1)
    h.record_failure(PEER)
    assert h.state_of(PEER) == OPEN
    h.record_success(PEER, rtt=0.002)
    assert h.state_of(PEER) == CLOSED


def test_adaptive_timeout_from_rtt():
    h, _clock = make_health()
    # no history: the default stands
    assert h.adaptive_timeout(PEER, 30.0) == 30.0
    # fast peer: timeout collapses to the floor
    for _ in range(10):
        h.record_success(PEER, rtt=0.002)
    assert h.adaptive_timeout(PEER, 30.0) == h.timeout_floor
    # slow peer: rtt * mult + slack, never above the default
    h2, _ = make_health()
    for _ in range(50):
        h2.record_success(PEER, rtt=1.0)
    t = h2.adaptive_timeout(PEER, 30.0)
    assert h.timeout_floor < t < 30.0
    h3, _ = make_health()
    for _ in range(50):
        h3.record_success(PEER, rtt=20.0)
    assert h3.adaptive_timeout(PEER, 30.0) == 30.0


def test_timeout_widens_adaptive_window():
    """A timeout must widen the adaptive-timeout window (TCP-RTO style):
    otherwise a load spike that pushes responses past the window is
    metastable — every later call times out at the same too-small
    window and the breaker flaps forever."""
    h, _clock = make_health()
    for _ in range(10):
        h.record_success(PEER, rtt=0.002)  # fast history
    narrow = h.adaptive_timeout(PEER, 30.0)
    assert narrow == h.timeout_floor
    h.record_failure(PEER, timed_out_after=narrow)
    wider = h.adaptive_timeout(PEER, 30.0)
    assert wider > narrow
    h.record_failure(PEER, timed_out_after=wider)
    assert h.adaptive_timeout(PEER, 30.0) > wider
    # successes shrink it back down through the EWMA
    for _ in range(50):
        h.record_success(PEER, rtt=0.002)
    assert h.adaptive_timeout(PEER, 30.0) == h.timeout_floor


def test_request_order_skips_sick_peers():
    """A known-sick peer must sort after every healthy one, whatever its
    zone or rtt advantage (read path: don't spend quorum slots on nodes
    that will fast-fail)."""

    class FakePeering:
        def __init__(self, rtts):
            self.rtts = rtts

        def peer_avg_rtt(self, n):
            return self.rtts.get(n)

    me, a, b = b"\x00" * 32, b"\x01" * 32, b"\x02" * 32
    helper = RpcHelper(me, FakePeering({a: 0.001, b: 0.200}))
    assert helper.request_order([b, a, me]) == [me, a, b]
    # open a's breaker: despite being the fastest remote, it sorts last
    helper.health.open_after = 1
    helper.health.record_failure(a)
    assert helper.health.state_of(a) == OPEN
    assert helper.request_order([b, a, me]) == [me, b, a]


def test_idempotent_retry_and_counter():
    """A transient transport failure retries with backoff (idempotent
    calls only) and the retries are counted in the registry."""

    async def main():
        apps, systems = await make_cluster(2)
        try:
            async def h(from_id, req):
                return Resp("pong")

            apps[1].endpoint("t/retry").set_handler(h)
            helper = RpcHelper(apps[0].id, systems[0].peering)
            ep = apps[0].endpoint("t/retry")
            target = apps[1].id

            lbl = ("rpc_retry_counter", (("endpoint", "t/retry"),))
            r0 = registry.counters.get(lbl, 0)

            # transient fault: unreachable now, healed in ~80 ms
            apps[0].blocked_peers.add(target)

            async def heal():
                await asyncio.sleep(0.08)
                apps[0].blocked_peers.discard(target)

            heal_task = asyncio.create_task(heal())
            resp = await helper.call(
                ep, target, "ping", idempotent=True, max_attempts=6
            )
            await heal_task
            assert resp.body == "pong"
            assert registry.counters.get(lbl, 0) > r0, "retries not counted"

            # non-idempotent calls do NOT retry
            apps[0].blocked_peers.add(target)
            from garage_tpu.net.netapp import RpcError

            with pytest.raises(RpcError):
                await helper.call(ep, target, "ping")
        finally:
            await stop_cluster(apps, systems)

    asyncio.run(main())


def test_open_breaker_fast_fails_without_timeout():
    """With the circuit open, a call returns in milliseconds instead of
    burning the (default 30 s) timeout."""

    async def main():
        apps, systems = await make_cluster(2)
        try:
            async def h(from_id, req):
                return Resp("pong")

            apps[1].endpoint("t/ff").set_handler(h)
            helper = RpcHelper(apps[0].id, systems[0].peering)
            helper.health.open_after = 2
            ep = apps[0].endpoint("t/ff")
            target = apps[1].id

            apps[0].blocked_peers.add(target)
            for _ in range(2):
                with pytest.raises(Exception):
                    await helper.call(ep, target, "x")
            assert helper.health.state_of(target) == OPEN

            t0 = asyncio.get_event_loop().time()
            with pytest.raises(PeerUnavailable):
                await helper.call(ep, target, "x", timeout=30.0)
            assert asyncio.get_event_loop().time() - t0 < 0.1
        finally:
            await stop_cluster(apps, systems)

    asyncio.run(main())


def test_snapshot_shape():
    h, _clock = make_health()
    h.record_success(PEER, rtt=0.004)
    h.record_failure(PEER)
    snap = h.snapshot()
    entry = snap[PEER.hex()]
    assert entry["state"] == CLOSED
    assert entry["successes"] == 1 and entry["failures"] == 1
    assert entry["rttMsecEwma"] == 4.0
    assert 0.0 < entry["successEwma"] < 1.0



# --- a remote call on the wire: send-queue wait, silent peers, the probe ----------


def ctr(name: str, **labels) -> float:
    return registry.counters.get((name, tuple(sorted(labels.items()))), 0.0)


async def two_nodes(floor: float = 0.1, default_timeout: float = 30.0, history: bool = True):
    """Two bare nodes (no gossip) and a helper on the first.  With
    `history` the second has answered once in 1 ms, so the adaptive
    window toward it starts at `floor`; without, at `default_timeout`."""
    a, b = await make_node(), await make_node()
    await a.connect(b.bind_addr, b.id)
    helper = RpcHelper(a.id, None, default_timeout=default_timeout)
    helper.health.timeout_floor, helper.health.timeout_slack = floor, 0.0
    if history:
        helper.health.record_success(b.id, rtt=0.001)
    return a, b, helper


def trickle(b, ep: str, secs: float):
    """An endpoint on `b` whose answer streams a small chunk every 50 ms
    for `secs`: `b` keeps sending frames on the connection meanwhile."""

    async def gen():
        for _ in range(int(secs / 0.05)):
            await asyncio.sleep(0.05)
            yield b"t" * 64

    async def h(_from, req):
        return Resp("ok", stream=gen())

    b.endpoint(ep).set_handler(h)


async def drain(resp) -> None:
    async for _chunk in resp.stream:
        pass


def test_a_requests_wait_in_its_own_send_queue_is_counted_by_endpoint():
    """The connection's send loop is held 1.2 s inside a HIGH message's
    producer; a small call behind it waits there, is sent, is answered:
    its send-queue wait and the call are counted under its endpoint, and
    the streamed request once, when its stream's last frame is sealed."""

    async def main():
        a, b, _helper = await two_nodes()
        try:
            async def echo(_f, req):
                return Resp(req.body)

            async def sink(_f, req):
                async for _c in req.stream:
                    pass
                return Resp("done")

            b.endpoint("t/q/echo").set_handler(echo)
            b.endpoint("t/q/sink").set_handler(sink)

            async def slow_producer():
                await asyncio.sleep(1.2)
                yield b"x" * 100

            sink0 = ctr("rpc_calls_sent_total", endpoint="t/q/sink")
            blocker = asyncio.create_task(a.endpoint("t/q/sink").call(
                b.id, "s", prio=PRIO_HIGH, stream=slow_producer()))
            await asyncio.sleep(0.05)  # the send loop now waits in the producer
            assert ctr("rpc_calls_sent_total", endpoint="t/q/sink") == sink0
            sent0 = ctr("rpc_calls_sent_total", endpoint="t/q/echo")
            wait0 = ctr("rpc_call_send_wait_seconds_total", endpoint="t/q/echo")
            resp = await a.endpoint("t/q/echo").call(b.id, "ping", timeout=30.0)
            assert resp.body == "ping"
            assert (await blocker).body == "done"
            assert ctr("rpc_calls_sent_total", endpoint="t/q/echo") == sent0 + 1
            assert 1.0 <= ctr("rpc_call_send_wait_seconds_total", endpoint="t/q/echo") - wait0 < 2.0
            assert ctr("rpc_calls_sent_total", endpoint="t/q/sink") == sink0 + 1
        finally:
            await a.shutdown()
            await b.shutdown()

    asyncio.run(main())


@pytest.mark.parametrize("silence, history", [
    ("handler", True), ("dropped", True), ("handler_while_streaming", True), ("handler", False)])
def test_a_silent_peer_times_out_within_its_window_and_opens_the_breaker(silence, history):
    """A peer that never answers — its handler hangs, the request is lost
    on the way, or its handler hangs while it streams another answer on
    the same connection — fails every call at that call's adaptive window,
    charged to it, and `open_after` such calls open its breaker.  Each
    timeout widens the next window, as in service; a peer with no RTT
    history is given the default timeout."""

    async def main():
        a, b, helper = await two_nodes(default_timeout=0.5, history=history)
        ep = f"t/s/{silence}"
        try:
            async def hang(_f, req):
                await asyncio.sleep(3600)

            b.endpoint(ep).set_handler(hang)
            drainer = None
            if silence == "dropped":
                a.fault_plan = FaultPlan(7).set_rule(FaultRule(drop=1.0), peer=b.id)
            elif silence == "handler_while_streaming":
                trickle(b, "t/s/trickle", secs=10.0)
                drainer = asyncio.create_task(drain(await a.endpoint("t/s/trickle").call(b.id, "go")))
            to0 = ctr("rpc_timeout_counter", endpoint=ep)
            loop = asyncio.get_running_loop()
            windows = []
            for i in range(helper.health.open_after):
                window = helper.health.adaptive_timeout(b.id, helper.default_timeout)
                windows.append(window)
                t0 = loop.time()
                with pytest.raises(asyncio.TimeoutError):
                    await helper.call(a.endpoint(ep), b.id, "x")
                dt = loop.time() - t0
                assert window - 0.02 <= dt < window + 0.3, (i, window, dt)
                assert helper.health.peers[b.id].consecutive_failures == i + 1
            assert windows[0] == (0.1 if history else 0.5)
            # each timeout widened the next window, up to the default
            assert windows == sorted(windows) and windows[-1] == 0.5
            assert helper.health.state_of(b.id) == OPEN
            assert ctr("rpc_timeout_counter", endpoint=ep) == to0 + helper.health.open_after
            with pytest.raises(PeerUnavailable):
                await helper.call(a.endpoint(ep), b.id, "x")
            if drainer is not None:
                drainer.cancel()
        finally:
            await a.shutdown()
            await b.shutdown()

    asyncio.run(main())


def test_the_half_open_probe_has_the_full_timeout():
    """After the cooldown the probe is let through with the helper's full
    timeout, not the adaptive window: a probe that fails re-opens the
    breaker; an answer slower than the window closes it."""

    async def main():
        a, b, helper = await two_nodes(default_timeout=2.0)
        helper.health.open_cooldown = 0.2
        try:
            mode = {"hang": True}

            async def h(_f, req):
                if mode["hang"]:
                    await asyncio.sleep(3600)
                await asyncio.sleep(0.6)
                return Resp("pong")

            b.endpoint("t/p").set_handler(h)
            for _ in range(helper.health.open_after):
                with pytest.raises(asyncio.TimeoutError):
                    await helper.call(a.endpoint("t/p"), b.id, "x")
            assert helper.health.state_of(b.id) == OPEN
            await asyncio.sleep(0.25)
            # the probe fails (at the full 2 s): open again
            t0 = asyncio.get_running_loop().time()
            with pytest.raises(asyncio.TimeoutError):
                await helper.call(a.endpoint("t/p"), b.id, "x")
            assert asyncio.get_running_loop().time() - t0 >= 1.9
            assert helper.health.state_of(b.id) == OPEN
            await asyncio.sleep(0.25)
            mode["hang"] = False
            # the window has collapsed below the answer's 0.6 s
            helper.health.peers[b.id].rtt_ewma = 0.001
            assert helper.health.adaptive_timeout(b.id, helper.default_timeout) < 0.6
            assert (await helper.call(a.endpoint("t/p"), b.id, "x")).body == "pong"
            assert helper.health.state_of(b.id) == CLOSED
        finally:
            await a.shutdown()
            await b.shutdown()

    asyncio.run(main())
