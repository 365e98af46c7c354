"""net layer: in-process multi-node mesh tests (reference src/net/test.rs
pattern: several NetApp+PeeringManager instances on localhost ports inside
one event loop), plus handshake security and stream/QoS behavior."""

import asyncio
import os

import pytest

from garage_tpu.net import NetApp, PRIO_BACKGROUND, PRIO_HIGH
from garage_tpu.net.connection import RemoteError
from garage_tpu.net.handshake import HandshakeError, gen_node_key, node_id_of
from garage_tpu.net.message import Req, Resp
from garage_tpu.net.peering import PeeringManager
from garage_tpu.net.stream import bytes_stream, read_stream_to_end

NETKEY = b"n" * 32


async def make_node(netkey=NETKEY):
    app = NetApp(netkey, gen_node_key())
    await app.listen("127.0.0.1", 0)
    return app


@pytest.fixture
def anyio_backend():
    return "asyncio"


def run(coro):
    return asyncio.run(coro)


def test_basic_call_roundtrip():
    async def main():
        a, b = await make_node(), await make_node()
        ep = b.endpoint("test/echo")
        from_ids = []

        async def handler(from_id, req):
            from_ids.append(from_id)
            return Resp({"echo": req.body, "n": req.body["n"] + 1})

        ep.set_handler(handler)
        await a.connect(b.bind_addr, b.id)
        resp = await a.endpoint("test/echo").call(b.id, {"n": 41})
        assert resp.body["n"] == 42
        assert from_ids == [a.id], "remote call must carry the caller's node id"
        # local shortcut: a node can call its own endpoints
        b_resp = await b.endpoint("test/echo").call(b.id, {"n": 1})
        assert b_resp.body["n"] == 2
        await a.shutdown()
        await b.shutdown()

    run(main())


def test_shutdown_ends_though_a_peer_redials_during_it(monkeypatch):
    """A peer whose connection `shutdown()` closes redials at once (the
    peering loop does).  A connection accepted after shutdown's sweep must
    not keep `Server.wait_closed` (3.12+: it waits for every accepted
    transport) waiting for ever: the 21-node teardown of
    tests/test_ec_cluster.py hung there in 5 of 42 loaded runs."""

    from garage_tpu.net.connection import Connection

    close = Connection.close

    async def close_as_in_a_wide_mesh(self):
        # the sweep over 20 peers' connections on a loaded loop takes long
        # enough for the first of them to notice and dial again
        await close(self)
        await asyncio.sleep(0.3)

    async def main():
        a, b = await make_node(), await make_node()
        await b.connect(a.bind_addr, a.id)
        redials = []
        b.on_disconnected = lambda _peer: redials.append(
            asyncio.ensure_future(b.connect(a.bind_addr, a.id)))
        monkeypatch.setattr(Connection, "close", close_as_in_a_wide_mesh)
        try:
            await asyncio.wait_for(a.shutdown(), 10)
            assert redials, "the peer never redialed: the race was not driven"
            assert not a.all_conns and not a.conns
        finally:
            monkeypatch.setattr(Connection, "close", close)
            b.on_disconnected = None
            await asyncio.wait(redials, timeout=10)
            await asyncio.wait_for(b.shutdown(), 10)

    run(main())


def test_remote_error_propagates():
    async def main():
        a, b = await make_node(), await make_node()

        async def handler(from_id, req):
            raise ValueError("deliberate")

        b.endpoint("test/fail").set_handler(handler)
        await a.connect(b.bind_addr, b.id)
        with pytest.raises(RemoteError, match="deliberate"):
            await a.endpoint("test/fail").call(b.id, None)
        await a.shutdown()
        await b.shutdown()

    run(main())


def test_large_body_and_stream():
    async def main():
        a, b = await make_node(), await make_node()
        blob = os.urandom(300 * 1024)  # forces multi-chunk body

        async def handler(from_id, req):
            got = await read_stream_to_end(req.stream)
            return Resp(
                {"body_len": len(req.body), "stream_len": len(got)},
                stream=bytes_stream(got[::-1]),
            )

        b.endpoint("test/stream").set_handler(handler)
        await a.connect(b.bind_addr, b.id)
        resp = await a.endpoint("test/stream").call(
            b.id, "x" * 100_000, stream=bytes_stream(blob), timeout=30
        )
        assert resp.body == {"body_len": 100_000, "stream_len": len(blob)}
        back = await read_stream_to_end(resp.stream)
        assert back == blob[::-1]
        await a.shutdown()
        await b.shutdown()

    run(main())


def test_wrong_network_key_rejected():
    async def main():
        a = await make_node(netkey=b"a" * 32)
        b = await make_node(netkey=b"b" * 32)
        with pytest.raises((HandshakeError, asyncio.IncompleteReadError, ConnectionError)):
            await a.connect(b.bind_addr, b.id)
        await a.shutdown()
        await b.shutdown()

    run(main())


def test_peer_id_pinning():
    async def main():
        a, b = await make_node(), await make_node()
        wrong_id = node_id_of(gen_node_key())
        with pytest.raises(HandshakeError, match="peer id mismatch"):
            await a.connect(b.bind_addr, wrong_id)
        await a.shutdown()
        await b.shutdown()

    run(main())


def test_reflection_attack_rejected():
    """A peer that knows only the network key and echoes our own auth
    frame back must NOT authenticate (signatures are role+identity-bound;
    an identical frame is rejected outright)."""

    async def main():
        import hashlib
        import hmac as hmac_mod
        import struct

        # the attacker uses the same primitives the node does (real
        # cryptography when installed, the stdlib fallback otherwise)
        from garage_tpu.net import handshake as hs
        from garage_tpu.net.crypto_compat import (
            ChaCha20Poly1305,
            X25519PrivateKey,
            X25519PublicKey,
        )

        netkey = NETKEY

        async def evil_server(reader, writer):
            # steps 1-2 performed honestly (attacker knows the network key)
            my_nonce = b"\x01" * 32
            eph = X25519PrivateKey.generate()
            eph_pub = eph.public_key().public_bytes_raw()
            body = hs.VERSION_TAG + my_nonce + eph_pub
            mac = hmac_mod.new(netkey, body, hashlib.sha256).digest()
            writer.write(body + mac)
            await writer.drain()
            peer_hello = await reader.readexactly(len(body) + 32)
            peer_body = peer_hello[:-32]
            peer_nonce = peer_body[len(hs.VERSION_TAG) : len(hs.VERSION_TAG) + 32]
            peer_eph = peer_body[len(hs.VERSION_TAG) + 32 :]
            shared = eph.exchange(X25519PublicKey.from_public_bytes(peer_eph))
            info = my_nonce + peer_nonce
            k_s2c = hs._hkdf(shared, netkey, info + b"s2c", 32)
            k_c2s = hs._hkdf(shared, netkey, info + b"c2s", 32)
            # step 3: receive the client's auth frame and echo it back
            hdr = await reader.readexactly(4)
            (n,) = struct.unpack("<I", hdr)
            ct = await reader.readexactly(n)
            client_auth = ChaCha20Poly1305(k_c2s).decrypt(
                b"send" + struct.pack("<Q", 0), ct, None
            )
            echo = ChaCha20Poly1305(k_s2c).encrypt(
                b"send" + struct.pack("<Q", 0), client_auth, None
            )
            writer.write(struct.pack("<I", len(echo)) + echo)
            await writer.drain()
            # let the client read the echo, then close our transport
            # (3.12's Server.wait_closed blocks on open connections)
            try:
                await asyncio.wait_for(reader.read(1), 5)
            except asyncio.TimeoutError:
                pass
            writer.close()

        server = await asyncio.start_server(evil_server, "127.0.0.1", 0)
        port = server.sockets[0].getsockname()[1]
        reader, writer = await asyncio.open_connection("127.0.0.1", port)
        with pytest.raises(HandshakeError, match="reflection|signature invalid"):
            await asyncio.wait_for(
                hs.handshake(
                    reader, writer, netkey, gen_node_key(), is_server=False
                ),
                timeout=15,
            )
        writer.close()
        server.close()

    run(main())


def test_three_node_mesh_converges():
    """a knows b, b knows c: peer-list exchange must close the mesh so a
    discovers and connects to c (reference net/test.rs:15-44)."""

    async def main():
        a, b, c = await make_node(), await make_node(), await make_node()
        pa = PeeringManager(a, [(b.id, b.bind_addr)])
        pb = PeeringManager(b, [(c.id, c.bind_addr)])
        pc = PeeringManager(c, [])
        # speed up the test: ping every 0.2s
        import garage_tpu.net.peering as peering_mod

        old = peering_mod.PING_INTERVAL
        peering_mod.PING_INTERVAL = 0.2
        try:
            for p in (pa, pb, pc):
                p.start()
            for _ in range(100):
                await asyncio.sleep(0.1)
                if (
                    set(pa.connected_peers()) == {b.id, c.id}
                    and set(pb.connected_peers()) == {a.id, c.id}
                    and set(pc.connected_peers()) == {a.id, b.id}
                ):
                    break
            assert set(pa.connected_peers()) == {b.id, c.id}, "a not fully meshed"
            assert set(pb.connected_peers()) == {a.id, c.id}, "b not fully meshed"
            assert set(pc.connected_peers()) == {a.id, b.id}, "c not fully meshed"
            assert pa.peer_avg_rtt(b.id) is not None
        finally:
            peering_mod.PING_INTERVAL = old
            for p in (pa, pb, pc):
                await p.stop()
            for n in (a, b, c):
                await n.shutdown()

    run(main())


def test_priority_qos_interleaving():
    """A HIGH-priority call issued while a huge BACKGROUND body is in
    flight must complete long before the background transfer finishes."""

    async def main():
        a, b = await make_node(), await make_node()
        order = []

        async def big_handler(from_id, req):
            order.append("big_done")
            return Resp(len(req.body))

        async def small_handler(from_id, req):
            order.append("small_done")
            return Resp("pong")

        b.endpoint("test/big").set_handler(big_handler)
        b.endpoint("test/small").set_handler(small_handler)
        await a.connect(b.bind_addr, b.id)

        big_len = 32 * 1024 * 1024  # ~2048 chunks: in flight for a while
        big = asyncio.create_task(
            a.endpoint("test/big").call(
                b.id, "z" * big_len, prio=PRIO_BACKGROUND, timeout=120
            )
        )
        await asyncio.sleep(0.01)  # let the big transfer start
        small = await a.endpoint("test/small").call(
            b.id, "ping", prio=PRIO_HIGH, timeout=10
        )
        assert small.body == "pong"
        big_resp = await big
        assert big_resp.body == big_len
        assert order[0] == "small_done", f"QoS violated: {order}"
        await a.shutdown()
        await b.shutdown()

    run(main())


def test_timeout_cancels():
    async def main():
        a, b = await make_node(), await make_node()

        async def slow(from_id, req):
            await asyncio.sleep(5)
            return Resp("late")

        b.endpoint("test/slow").set_handler(slow)
        await a.connect(b.bind_addr, b.id)
        with pytest.raises(asyncio.TimeoutError):
            await a.endpoint("test/slow").call(b.id, None, timeout=0.3)
        # connection still usable afterwards
        b.endpoint("test/ok").set_handler(lambda f, r: _resp_ok())
        resp = await a.endpoint("test/ok").call(b.id, None, timeout=5)
        assert resp.body == "ok"
        await a.shutdown()
        await b.shutdown()

    async def _resp_ok():
        return Resp("ok")

    run(main())


def test_bidirectional_concurrent_calls():
    """Both peers call each other simultaneously: request ids must not
    collide between directions (dialer odd / accepter even)."""

    async def main():
        a, b = await make_node(), await make_node()

        async def mk_handler(tag):
            async def h(from_id, req):
                await asyncio.sleep(0.05)  # force overlap
                return Resp([tag, req.body])

            return h

        a.endpoint("t/x").set_handler(await mk_handler("a"))
        b.endpoint("t/x").set_handler(await mk_handler("b"))
        await a.connect(b.bind_addr, b.id)
        results = await asyncio.gather(
            *[a.endpoint("t/x").call(b.id, i) for i in range(5)],
            *[b.endpoint("t/x").call(a.id, 100 + i) for i in range(5)],
        )
        assert [r.body for r in results[:5]] == [["b", i] for i in range(5)]
        assert [r.body for r in results[5:]] == [["a", 100 + i] for i in range(5)]
        await a.shutdown()
        await b.shutdown()

    run(main())


def test_abandoned_stream_does_not_stall_connection():
    """A caller that never reads a response stream must not wedge the recv
    loop for other multiplexed RPCs."""

    async def main():
        a, b = await make_node(), await make_node()
        blob = os.urandom(2 * 1024 * 1024)

        async def streamer(from_id, req):
            return Resp("here", stream=bytes_stream(blob))

        async def pong(from_id, req):
            return Resp("pong")

        b.endpoint("t/stream").set_handler(streamer)
        b.endpoint("t/pong").set_handler(pong)
        await a.connect(b.bind_addr, b.id)
        resp = await a.endpoint("t/stream").call(b.id, None)
        assert resp.body == "here"  # stream deliberately never consumed
        for _ in range(3):
            r = await a.endpoint("t/pong").call(b.id, None, timeout=5)
            assert r.body == "pong"
        await a.shutdown()
        await b.shutdown()

    run(main())


def test_stream_producer_failure_unblocks_peer():
    """If the sender's stream producer raises mid-transfer, the receiving
    handler must get a stream error instead of hanging forever."""

    async def main():
        a, b = await make_node(), await make_node()
        handler_result = asyncio.get_event_loop().create_future()

        async def h(from_id, req):
            try:
                await read_stream_to_end(req.stream)
                handler_result.set_result("completed")
            except BaseException as e:  # StreamError or CancelledError
                if not handler_result.done():
                    handler_result.set_result(f"error: {type(e).__name__}")
                raise
            return Resp("ok")

        b.endpoint("t/sink").set_handler(h)
        await a.connect(b.bind_addr, b.id)

        async def bad_producer():
            yield b"x" * 50_000
            await asyncio.sleep(0.3)  # let the peer's handler start reading
            raise RuntimeError("producer died")

        with pytest.raises(RuntimeError, match="producer died"):
            await a.endpoint("t/sink").call(b.id, None, stream=bad_producer(), timeout=5)
        got = await asyncio.wait_for(handler_result, 5)
        assert got.startswith("error"), f"handler saw: {got}"
        await a.shutdown()
        await b.shutdown()

    run(main())


def test_stream_flow_control_backpressure():
    """A slow stream consumer must backpressure the sender: outstanding
    bytes stay within the credit window instead of ballooning, other
    requests on the connection keep flowing, and the transfer completes."""

    async def main():
        from garage_tpu.net.connection import STREAM_WINDOW

        a, b = await make_node(), await make_node()
        produced = 0
        total = 6 * STREAM_WINDOW
        consumed = asyncio.Event()

        async def producer():
            nonlocal produced
            chunk = b"x" * 65536
            while produced < total:
                produced += len(chunk)
                yield chunk

        async def handler(from_id, req):
            # consume slowly at first, then drain
            it = req.stream.__aiter__()
            got = 0
            first = await it.__anext__()
            got += len(first)
            await asyncio.sleep(0.5)  # let the producer run ahead if it can
            # the producer must be throttled by credit, not unbounded:
            # it can be at most window + scheduler slack ahead of us
            assert produced - got <= STREAM_WINDOW + 512 * 1024, (
                f"producer ran {produced - got} bytes ahead of the consumer"
            )
            async for chunk in it:
                got += len(chunk)
            consumed.set()
            return Resp(got)

        async def ping(from_id, req):
            return Resp("pong")

        b.endpoint("t/fc").set_handler(handler)
        b.endpoint("t/ping").set_handler(ping)
        await a.connect(b.bind_addr, b.id)

        call = asyncio.create_task(
            a.endpoint("t/fc").call(b.id, None, stream=producer(), timeout=60)
        )
        # while the big stream is parked on credit, small RPCs still flow
        await asyncio.sleep(0.2)
        r = await asyncio.wait_for(
            a.endpoint("t/ping").call(b.id, None), timeout=5
        )
        assert r.body == "pong"
        resp = await call
        assert resp.body == total
        assert consumed.is_set()
        await a.shutdown()
        await b.shutdown()

    run(main())


def test_ordered_substream_serializes_responses():
    """Responses tagged with one OrderTag stream must transmit one at a
    time in seq order (reference net/message.rs:62-89): even when the
    seq-1 handler finishes while seq-0's stream is mid-flight, seq-0's
    bytes all arrive before seq-1's."""

    async def main():
        from garage_tpu.net.message import OrderTag, new_order_stream

        a, b = await make_node(), await make_node()
        events = []  # (rid_label, "first"|"last") chunk arrival order

        async def slow_stream(label, n_chunks):
            async def gen():
                for i in range(n_chunks):
                    await asyncio.sleep(0.002)
                    yield b"x" * 16384
            return gen()

        async def handler(from_id, req):
            label, delay, chunks = req.body
            await asyncio.sleep(delay)
            return Resp(label, stream=await slow_stream(label, chunks))

        b.endpoint("t/ordered").set_handler(handler)
        await a.connect(b.bind_addr, b.id)

        tags = new_order_stream()
        t0, t1 = tags.order(), tags.order()

        async def get(label, delay, chunks, tag, start_after=0.0):
            await asyncio.sleep(start_after)
            resp = await a.endpoint("t/ordered").call(
                b.id, [label, delay, chunks], timeout=30, order_tag=tag
            )
            events.append((label, "meta"))
            data = await read_stream_to_end(resp.stream)
            events.append((label, "stream_done"))
            return data

        # seq 0 streams many slow chunks; seq 1 (small) is requested
        # while seq 0 is mid-stream.  Without ordering, the round-robin
        # scheduler would interleave and finish r1 first.
        r0, r1 = await asyncio.gather(
            get("r0", 0.0, 40, t0), get("r1", 0.0, 2, t1, start_after=0.02)
        )
        assert len(r0) == 40 * 16384 and len(r1) == 2 * 16384
        done_order = [lab for lab, ev in events if ev == "stream_done"]
        assert done_order == ["r0", "r1"], (
            f"ordered sub-stream violated: {events}"
        )
        await a.shutdown()
        await b.shutdown()

    run(main())


def test_ordered_substream_preempts_later_seq():
    """If seq 0 arrives while seq 1 is already mid-stream (out-of-order
    handler completion), seq 0 must take over at the next chunk boundary
    and finish first (reference send.rs:135 front-of-stream gating)."""

    async def main():
        from garage_tpu.net.message import new_order_stream

        a, b = await make_node(), await make_node()
        events = []

        async def handler(from_id, req):
            label, delay, chunks = req.body
            await asyncio.sleep(delay)

            async def gen():
                for _ in range(chunks):
                    await asyncio.sleep(0.002)
                    yield b"y" * 16384

            return Resp(label, stream=gen())

        b.endpoint("t/preempt").set_handler(handler)
        await a.connect(b.bind_addr, b.id)
        tags = new_order_stream()
        t0, t1 = tags.order(), tags.order()

        async def get(label, delay, chunks, tag):
            resp = await a.endpoint("t/preempt").call(
                b.id, [label, delay, chunks], timeout=30, order_tag=tag
            )
            data = await read_stream_to_end(resp.stream)
            events.append(label)
            return data

        # r1's handler is instant with a LONG stream; r0's handler takes
        # 30ms (still well within r1's stream time) with a small stream
        r0, r1 = await asyncio.gather(
            get("r0", 0.03, 2, t0), get("r1", 0.0, 60, t1)
        )
        assert len(r0) == 2 * 16384 and len(r1) == 60 * 16384
        assert events == ["r0", "r1"], f"no preemption: {events}"
        await a.shutdown()
        await b.shutdown()

    run(main())


def test_ordered_substream_gap_does_not_wedge():
    """A missing middle seq must not stall later seqs even while earlier
    ones are STILL PENDING concurrently (the serializer orders among
    pending messages; it never waits for seqs that were never
    enqueued).  seq 0 streams slowly, seq 1 is never sent, seq 2 is
    issued concurrently — seq 2 must complete, after seq 0."""

    async def main():
        from garage_tpu.net.message import new_order_stream

        a, b = await make_node(), await make_node()

        async def handler(from_id, req):
            if req.body == "slow":
                async def gen():
                    for _ in range(20):
                        await asyncio.sleep(0.002)
                        yield b"z" * 16384

                return Resp("slow", stream=gen())
            return Resp(req.body * 2)

        b.endpoint("t/gap").set_handler(handler)
        await a.connect(b.bind_addr, b.id)
        tags = new_order_stream()
        t0 = tags.order()
        _skipped = tags.order()  # seq 1 never sent
        t2 = tags.order()
        done = []

        async def slow0():
            r = await a.endpoint("t/gap").call(
                b.id, "slow", timeout=30, order_tag=t0
            )
            await read_stream_to_end(r.stream)
            done.append("r0")

        async def quick2():
            await asyncio.sleep(0.01)  # issued while seq 0 is mid-stream
            r = await a.endpoint("t/gap").call(b.id, 40, timeout=10, order_tag=t2)
            assert r.body == 80
            done.append("r2")

        await asyncio.wait_for(asyncio.gather(slow0(), quick2()), timeout=15)
        assert done == ["r0", "r2"], f"gap mis-ordered or wedged: {done}"
        await a.shutdown()
        await b.shutdown()

    run(main())


def test_peer_list_self_report_updates_stale_address():
    """A peer that crashed and restarted on a NEW port must become
    dialable again: its own peer-list entry is authoritative for its
    address (third-party gossip still must not clobber a known address
    with a stale one)."""

    async def main():
        a, b = await make_node(), await make_node()
        try:
            pa = PeeringManager(a, [(b.id, ("127.0.0.1", 59999))])
            p = pa.peers[b.id]
            p.connect_failures = 6  # deep in backoff against the dead addr
            p.next_retry = 1e18

            # third-party gossip repeating the stale address: no change
            third_party = os.urandom(32)
            pa._learn([[b.id, ["127.0.0.1", 58888]]], from_id=third_party)
            assert pa.peers[b.id].addr == ("127.0.0.1", 59999)

            # b's own self-report wins and resets the dial backoff
            pa._learn([[b.id, ["127.0.0.1", 51111]]], from_id=b.id)
            assert pa.peers[b.id].addr == ("127.0.0.1", 51111)
            assert pa.peers[b.id].connect_failures == 0
            assert pa.peers[b.id].next_retry == 0.0

            # unknown peers are still learned from any reporter
            new_id = os.urandom(32)
            pa._learn([[new_id, ["127.0.0.1", 52222]]], from_id=third_party)
            assert pa.peers[new_id].addr == ("127.0.0.1", 52222)
        finally:
            await a.shutdown()
            await b.shutdown()

    run(main())


# --- the sender's framing (PR 30): frames per message, writes per turn ---------


def _net_counters():
    from garage_tpu.utils.metrics import registry

    return {
        name: registry.counters.get((f"net_{name}_total", ()), 0)
        for name in ("messages_sent", "frames_sent", "writes", "bytes_sent")
    }


def _spy_frames(monkeypatch):
    """Every frame sealed in this process, as (kind, flags, payload length)."""
    from garage_tpu.net.handshake import FramedBox

    seen = []
    send_frame = FramedBox.send_frame

    def spy(self, plaintext, starts_message=False):
        seen.append((plaintext[0], plaintext[1], len(plaintext) - 6))
        send_frame(self, plaintext, starts_message)

    monkeypatch.setattr(FramedBox, "send_frame", spy)
    return seen


async def _plain_gen(data, chunk):
    for i in range(0, len(data), chunk):
        yield data[i : i + chunk]


from garage_tpu.net.connection import (  # noqa: E402
    F_BODY as BODY_INSIDE,
    F_FIN as FIN,
    K_BODY,
    K_CREDIT,
    K_REQ_META as K_REQ,
    K_RESP_META as K_RESP,
    K_STREAM,
)

BLOB = os.urandom(256 * 1024)


@pytest.mark.parametrize(
    "body, stream_of, request_frames",
    [
        # a small call: META and BODY in one frame, each way
        pytest.param("ping", None, [(K_REQ, BODY_INSIDE)], id="small-call"),
        # an EC(8,3) piece: header, two 64 KiB frames, FIN on the second
        pytest.param(
            ["Put", 3], lambda: bytes_stream(BLOB[:131072]),
            [(K_REQ, BODY_INSIDE), (K_STREAM, 0, 65536), (K_STREAM, FIN, 65536)],
            id="piece-131072-known-length",
        ),
        # the same bytes, length unknown to the sender: the empty FIN ends it
        pytest.param(
            ["Put", 3], lambda: _plain_gen(BLOB[:131072], 65536),
            [(K_REQ, BODY_INSIDE), (K_STREAM, 0, 65536), (K_STREAM, 0, 65536),
             (K_STREAM, FIN, 0)],
            id="piece-131072-plain-generator",
        ),
        pytest.param(
            ["Put", 1], lambda: bytes_stream(BLOB[:16384]),
            [(K_REQ, BODY_INSIDE), (K_STREAM, FIN, 16384)],
            id="piece-16384",
        ),
        # one 256 KiB producer chunk (a piece file's read) is cut into 4
        pytest.param(
            None, lambda: _plain_gen(BLOB, 256 * 1024),
            [(K_REQ, BODY_INSIDE)] + [(K_STREAM, 0, 65536)] * 4 + [(K_STREAM, FIN, 0)],
            id="chunk-256k-cut-in-4",
        ),
        pytest.param(
            None, lambda: bytes_stream(BLOB[:70_000]),
            [(K_REQ, BODY_INSIDE), (K_STREAM, 0, 65536), (K_STREAM, FIN, 4464)],
            id="stream-70000-off-the-boundary",
        ),
        # a body that does not fit a frame: META, then BODY frames
        pytest.param(
            BLOB[:100_000], None,
            [(K_REQ, 0), (K_BODY, 0, 65536), (K_BODY, FIN, 100_000 + 5 - 65536)],
            id="body-larger-than-a-frame",
        ),
        pytest.param(
            None, lambda: bytes_stream(b""),
            [(K_REQ, BODY_INSIDE), (K_STREAM, FIN, 0)],
            id="empty-stream-one-empty-fin",
        ),
    ],
)
def test_frames_per_message(monkeypatch, body, stream_of, request_frames):
    """A message is cut into as few frames as its bytes need, arrives
    byte-exact, and the counters count what went on the wire."""

    async def main():
        a, b = await make_node(), await make_node()
        received = []

        async def handler(from_id, req):
            received.append((req.body, await read_stream_to_end(req.stream)))
            return Resp("ok")

        b.endpoint("t/frames").set_handler(handler)
        await a.connect(b.bind_addr, b.id)
        await a.endpoint("t/frames").call(b.id, None)  # both directions warm
        seen = _spy_frames(monkeypatch)
        before = _net_counters()
        want = await read_stream_to_end(stream_of()) if stream_of else b""
        resp = await a.endpoint("t/frames").call(
            b.id, body, stream=stream_of() if stream_of else None, timeout=10
        )
        after = _net_counters()
        assert resp.body == "ok"
        assert received[-1] == (body, want), "body or stream not byte-exact"
        expected = request_frames + [(K_RESP, BODY_INSIDE)]
        # (the receiver's credit grant, one per 256 KiB read, is no message)
        message_frames = [f for f in seen if f[0] != K_CREDIT]
        assert len(message_frames) == len(expected), f"frames {seen}"
        for got, expect in zip(message_frames, expected):
            assert got[: len(expect)] == expect, f"frames {seen}"
        assert after["frames_sent"] - before["frames_sent"] == len(seen)
        assert after["messages_sent"] - before["messages_sent"] == 2
        assert 2 <= after["writes"] - before["writes"] <= len(seen)
        assert after["bytes_sent"] - before["bytes_sent"] == sum(
            4 + 6 + n + 16 for _k, _f, n in seen)
        await a.shutdown()
        await b.shutdown()

    run(main())


def test_nothing_sealed_waits_across_a_suspension():
    """Frames are joined within a turn of the send loop, never across a
    suspension: when the producer sleeps between chunks, the receiver
    holds chunk n before chunk n+1 is produced."""

    async def main():
        a, b = await make_node(), await make_node()
        events = []

        async def producer():
            for i in range(4):
                events.append(("produced", i))
                yield bytes([i]) * 16384
                await asyncio.sleep(0.05)

        async def handler(from_id, req):
            async for chunk in req.stream:
                events.append(("got", chunk[0]))
            return Resp("ok")

        b.endpoint("t/slow").set_handler(handler)
        await a.connect(b.bind_addr, b.id)
        await a.endpoint("t/slow").call(b.id, None, stream=producer(), timeout=10)
        assert events == [(what, i) for i in range(4) for what in ("produced", "got")]
        await a.shutdown()
        await b.shutdown()

    run(main())


def test_concurrent_small_calls_share_writes():
    """Frames ready in the same turn leave in one transport write."""

    async def main():
        a, b = await make_node(), await make_node()

        async def handler(from_id, req):
            return Resp(req.body)

        b.endpoint("t/echo").set_handler(handler)
        await a.connect(b.bind_addr, b.id)
        await a.endpoint("t/echo").call(b.id, None)
        before = _net_counters()
        resps = await asyncio.gather(
            *[a.endpoint("t/echo").call(b.id, i) for i in range(8)]
        )
        after = _net_counters()
        assert [r.body for r in resps] == list(range(8))
        assert after["frames_sent"] - before["frames_sent"] == 16
        assert after["messages_sent"] - before["messages_sent"] == 16
        assert after["writes"] - before["writes"] < 16
        await a.shutdown()
        await b.shutdown()

    run(main())


def test_a_piece_write_does_not_pause_its_receiver(monkeypatch):
    """One joined write of a 128 KiB piece stays under the receiver's
    StreamReader watermark: no pause_reading / resume_reading (two
    epoll_ctl calls) per piece, as asyncio's default limit of 64 KiB had."""
    import asyncio.selector_events as se

    async def main():
        a, b = await make_node(), await make_node()

        async def handler(from_id, req):
            return Resp(len(await read_stream_to_end(req.stream)))

        b.endpoint("t/piece").set_handler(handler)
        await a.connect(b.bind_addr, b.id)
        pauses = []
        pause_reading = se._SelectorTransport.pause_reading

        def counted(self):
            pauses.append(self)
            pause_reading(self)

        monkeypatch.setattr(se._SelectorTransport, "pause_reading", counted)
        for _ in range(16):
            resp = await a.endpoint("t/piece").call(
                b.id, ["Put", 3], stream=bytes_stream(BLOB[:131072]), timeout=10
            )
            assert resp.body == 131072
        assert not pauses
        await a.shutdown()
        await b.shutdown()

    run(main())


def test_old_protocol_version_refused():
    """A peer that announces the previous wire format (16 KiB frames,
    META and BODY apart) is refused at the first hello."""

    async def main():
        import hashlib
        import hmac as hmac_mod

        from garage_tpu.net import handshake as hs

        assert hs.VERSION_TAG != b"grg_tpu2"

        async def old_node(reader, writer):
            body = b"grg_tpu2" + b"\x01" * 32 + b"\x02" * 32
            writer.write(body + hmac_mod.new(NETKEY, body, hashlib.sha256).digest())
            await writer.drain()
            try:
                await asyncio.wait_for(reader.read(), 5)  # until the dialer hangs up
            except asyncio.TimeoutError:
                pass
            writer.close()

        server = await asyncio.start_server(old_node, "127.0.0.1", 0)
        a = await make_node()
        with pytest.raises(HandshakeError, match="protocol version mismatch"):
            await a.connect(server.sockets[0].getsockname()[:2])
        await a.shutdown()
        server.close()
        await server.wait_closed()

    run(main())
