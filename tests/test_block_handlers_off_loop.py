"""The block endpoint's piece handlers and the file system.

A `Put` and a `Get` of `block/data` touch the file system only inside the
one worker-thread hop each makes (PERF.md section 6, PR 35): on a host
whose nodes share one event loop a failed stat through a data directory
costs the loop ~0.16 ms, and a fresh EC PUT made 192 of them.

(a) through the endpoint, EC(4,2) and replica mode: every file-system
    call under the data directories comes from a worker thread, one
    `to_thread` hop per handler, the stored file in the format it had;
(b) what the handlers decided on the loop before and decide in the hop
    now: the equal-or-better copy, the probe order, a file too large to
    read whole, a missing piece, an injected write fault.

Counts and threads, never times.
"""

import asyncio
import builtins
import functools
import os
import random
import threading

import pytest
import zstandard
from test_block import make_block_cluster, stop_all

from garage_tpu.block.codec.ec import EcCodec
from garage_tpu.block.manager import (
    PIECE_MAGIC,
    WHOLE_READ_MAX,
    piece_hash,
    unwrap_piece,
    wrap_piece,
)
from garage_tpu.net.fault import FaultPlan, FaultRule, InjectedDiskFault
from garage_tpu.net.message import Req
from garage_tpu.net.stream import BytesStream, bytes_stream, read_stream_to_end
from garage_tpu.utils.data import blake2sum
from garage_tpu.utils.error import Error

K, M = 4, 2
PEER = b"\x01" * 32


def run(coro, limit=30.0):
    return asyncio.run(asyncio.wait_for(coro, limit))


def codec_of(mode: str):
    return EcCodec(K, M) if mode == "ec" else None


def files_under(root) -> list[str]:
    return sorted(
        os.path.join(d, f) for d, _dirs, fs in os.walk(root) for f in fs if f != ".garage-marker"
    )


class FsCalls:
    """Every call of the wrapped functions whose first argument lies under
    `under`, with the thread that made it."""

    def __init__(self, monkeypatch, under: str):
        self.calls: list[tuple[str, int, str]] = []
        self.under = under
        for mod, name in [
            (os, "stat"), (os, "listdir"), (os, "remove"), (os, "replace"),
            (os, "makedirs"), (os.path, "exists"), (os.path, "getsize"),
            (builtins, "open"), (os, "open"),
        ]:
            as_ = "os.open" if (mod, name) == (os, "open") else name
            monkeypatch.setattr(mod, name, self._wrap(as_, getattr(mod, name)))

    def _wrap(self, name, real):
        @functools.wraps(real)
        def recorded(*a, **kw):
            first = a[0] if a else None
            if isinstance(first, (str, bytes)) and os.fsdecode(first).startswith(self.under):
                self.calls.append((name, threading.get_ident(), os.fsdecode(first)))
            return real(*a, **kw)

        return recorded

    def on(self, ident: int) -> list[tuple[str, str]]:
        return [(n, p) for n, t, p in self.calls if t == ident]


def count_hops(monkeypatch) -> list[str]:
    hops: list[str] = []
    real = asyncio.to_thread

    async def to_thread(fn, *a, **kw):
        hops.append(getattr(fn, "__name__", repr(fn)))
        return await real(fn, *a, **kw)

    monkeypatch.setattr(asyncio, "to_thread", to_thread)
    return hops


# --- (a) through the endpoint ---------------------------------------------------------


@pytest.mark.parametrize("mode", ["ec", "replica"])
def test_put_and_get_touch_the_file_system_in_one_hop_each(tmp_path, monkeypatch, mode):
    async def main():
        apps, systems, managers = await make_block_cluster(
            tmp_path, n=2, rf=2, codec=codec_of(mode))
        try:
            a, b = managers
            rng = random.Random(35)
            data = rng.randbytes(20_000)  # under the hash's own thread hop
            h = blake2sum(data)
            if mode == "ec":
                rank = 3
                piece = a.codec.encode(data)[rank]
                ph = piece_hash(piece)
                meta = {"c": False, "s": len(piece), "p": rank, "l": len(data), "ph": ph}
                payload, want = piece, PIECE_MAGIC + len(data).to_bytes(8, "big") + ph + piece
                name = f"{h.hex()}.p{rank}"
            else:
                rank, meta = 0, {"c": False, "s": len(data)}
                payload = want = data
                name = h.hex()
            d = b.data_layout.block_dir(b.data_layout.primary_dir(h), h)
            loop_thread = threading.get_ident()
            fs = FsCalls(monkeypatch, str(tmp_path / "data"))
            hops = count_hops(monkeypatch)

            await a.helper.call(
                a.endpoint, b.system.id, ["Put", h, meta],
                stream_factory=lambda: bytes_stream(payload))
            assert hops == ["_store_sync"]
            assert fs.on(loop_thread) == []
            made = {n for n, t, _p in fs.calls if t != loop_thread}
            assert {"exists", "os.open", "makedirs", "replace"} <= made  # the first block of its prefix
            # an EC piece is looked for under the one name it is written under
            looked = [p for n, _t, p in fs.calls if n == "exists" and h.hex() in p]
            assert looked == ([os.path.join(d, name)] if mode == "ec"
                              else [os.path.join(d, name + ".zst"), os.path.join(d, name)])
            # the file as it was before: name, place, bytes
            assert files_under(tmp_path / "data1") == [os.path.join(d, name)]
            with open(os.path.join(d, name), "rb") as f:
                assert f.read() == want
            if mode == "ec":
                assert want[:4] == b"GTP2" and unwrap_piece(want) == (len(data), piece)

            del hops[:], fs.calls[:]
            resp = await a.helper.call(a.endpoint, b.system.id, ["Get", h, rank])
            assert resp.body[0] == "ok" and resp.body[1] == {"c": False, "s": len(want)}
            assert await read_stream_to_end(resp.stream) == want
            assert hops == ["_read_stored_sync"]
            assert fs.on(loop_thread) == []
            assert "os.open" in {n for n, t, _p in fs.calls if t != loop_thread}
        finally:
            await stop_all(apps, systems)

    run(main())


# --- (b) what the hop decides ---------------------------------------------------------


@pytest.mark.parametrize("order", ["compressed-then-plain", "plain-then-compressed"])
def test_replica_mode_keeps_the_better_copy(tmp_path, monkeypatch, order):
    """A compressed copy stays over a plain re-put; a plain copy gives
    way to a compressed one and its file goes, in the same hop."""

    async def main():
        apps, systems, managers = await make_block_cluster(tmp_path, n=1, rf=1)
        try:
            mgr = managers[0]
            data = b"garage " * 4000
            h = blake2sum(data)
            zst = zstandard.compress(data, 1)
            first, second = (zst, True), (data, False)
            if order == "plain-then-compressed":
                first, second = second, first
            d = mgr.data_layout.block_dir(mgr.data_layout.primary_dir(h), h)
            await mgr.write_block_local(h, *first)
            hops = count_hops(monkeypatch)
            await mgr.write_block_local(h, *second)
            assert hops == ["_store_sync"]  # the removal rides the same hop
            assert files_under(tmp_path / "data0") == [os.path.join(d, h.hex() + ".zst")]
            assert mgr.find_block_file(h) == (os.path.join(d, h.hex() + ".zst"), True)
            with open(os.path.join(d, h.hex() + ".zst"), "rb") as f:
                assert f.read() == zst
            assert await mgr.read_block_local(h) == data
        finally:
            await stop_all(apps, systems)

    run(main())


@pytest.mark.parametrize("stored_as", ["plain", "zst-by-hand"])
def test_probe_order_of_an_ec_piece(tmp_path, monkeypatch, stored_as):
    """An EC piece is never written compressed: its plain name is tried
    first and found at one stat; a `.p<i>.zst` put there by hand is
    still found, served as compressed and read."""

    async def main():
        apps, systems, managers = await make_block_cluster(
            tmp_path, n=1, rf=1, codec=EcCodec(K, M))
        try:
            mgr = managers[0]
            data = random.Random(5).randbytes(8192)
            h, rank = blake2sum(data), 2
            stored = wrap_piece(len(data), mgr.codec.encode(data)[rank])
            d = mgr.data_layout.block_dir(mgr.data_layout.primary_dir(h), h)
            if stored_as == "plain":
                await mgr.write_block_local(h, stored, False, piece=rank)
                path, on_disk = os.path.join(d, f"{h.hex()}.p{rank}"), stored
            else:
                path, on_disk = os.path.join(d, f"{h.hex()}.p{rank}.zst"), zstandard.compress(stored)
                os.makedirs(d)
                with open(path, "wb") as f:
                    f.write(on_disk)
            fs = FsCalls(monkeypatch, str(tmp_path / "data0"))
            assert mgr.find_block_file(h, piece=rank) == (path, stored_as != "plain")
            stats = [p for n, _t, p in fs.calls if n == "stat"]
            assert stats == ([path] if stored_as == "plain" else [path[:-4], path])
            resp = await mgr._handle(PEER, Req(["Get", h, rank]))
            assert resp.body[1] == {"c": stored_as != "plain", "s": len(on_disk)}
            assert await read_stream_to_end(resp.stream) == on_disk
            blen, piece = await mgr._fetch_piece(mgr.system.id, h, rank, None)
            assert (blen, piece) == unwrap_piece(stored)
            if stored_as == "plain":
                # a re-put finds the piece at one stat and writes nothing
                del fs.calls[:]
                await mgr.write_block_local(h, stored, False, piece=rank)
                assert [(n, p) for n, _t, p in fs.calls if n != "stat"] == [("exists", path)]
                assert files_under(tmp_path / "data0") == [path]
        finally:
            await stop_all(apps, systems)

    run(main())


def test_a_file_above_whole_read_max_is_streamed(tmp_path, monkeypatch):
    async def main():
        apps, systems, managers = await make_block_cluster(tmp_path, n=1, rf=1)
        try:
            mgr = managers[0]
            data = random.Random(6).randbytes(WHOLE_READ_MAX + 1)
            h = blake2sum(data)
            await mgr.write_block_local(h, data, False)
            loop_thread = threading.get_ident()
            fs = FsCalls(monkeypatch, str(tmp_path / "data0"))
            hops = count_hops(monkeypatch)
            resp = await mgr._handle(PEER, Req(["Get", h]))
            # found and measured in the hop, read by the stream
            assert hops == ["_read_stored_sync"]
            assert resp.body[1] == {"c": False, "s": WHOLE_READ_MAX + 1}
            assert not isinstance(resp.stream, BytesStream)
            assert await read_stream_to_end(resp.stream) == data
            assert hops[1] == "open" and hops[-1] == "close"  # `_file_stream`'s own hops
            assert fs.on(loop_thread) == []
            # exactly at the size it is read whole
            data = data[:-1]
            h = blake2sum(data)
            await mgr.write_block_local(h, data, False)
            resp = await mgr._handle(PEER, Req(["Get", h]))
            assert isinstance(resp.stream, BytesStream) and resp.stream.total == WHOLE_READ_MAX
        finally:
            await stop_all(apps, systems)

    run(main())


@pytest.mark.parametrize("mode", ["ec", "replica"])
def test_get_of_a_missing_piece_raises_as_before(tmp_path, mode):
    async def main():
        apps, systems, managers = await make_block_cluster(
            tmp_path, n=2, rf=2, codec=codec_of(mode))
        try:
            a, b = managers
            h = blake2sum(b"never stored")
            with pytest.raises(Error) as e:
                await b._handle(PEER, Req(["Get", h, 1]))
            assert str(e.value) == f"block {h.hex()[:16]} piece 1 not found"
            with pytest.raises(Exception) as e:  # and over the wire, as a remote error
                await a.helper.call(a.endpoint, b.system.id, ["Get", h, 1])
            assert f"block {h.hex()[:16]} piece 1 not found" in str(e.value)
            with pytest.raises(Error, match="piece not local"):
                await b._fetch_piece(b.system.id, h, 1, None)
            assert await b.read_block_local(h) is None
        finally:
            await stop_all(apps, systems)

    run(main())


def test_an_injected_write_fault_fails_the_put_before_any_file(tmp_path, monkeypatch):
    async def main():
        apps, systems, managers = await make_block_cluster(
            tmp_path, n=1, rf=1, codec=EcCodec(K, M))
        try:
            mgr = managers[0]
            mgr.fault_plan = FaultPlan(7).set_rule(FaultRule(disk_write_fail=1.0))
            data = random.Random(8).randbytes(4096)
            h = blake2sum(data)
            piece = mgr.codec.encode(data)[0]
            fs = FsCalls(monkeypatch, str(tmp_path / "data0"))
            hops = count_hops(monkeypatch)
            with pytest.raises(InjectedDiskFault):
                await mgr._handle(PEER, Req(
                    ["Put", h, {"c": False, "s": len(piece), "p": 0, "l": len(data)}],
                    stream=bytes_stream(piece)))
            assert hops == [] and fs.calls == []
            assert files_under(tmp_path / "data0") == []
            assert h not in mgr.resync._written  # nothing vouches for a piece that is not there
            mgr.fault_plan = None
            await mgr._handle(PEER, Req(
                ["Put", h, {"c": False, "s": len(piece), "p": 0, "l": len(data)}],
                stream=bytes_stream(piece)))
            assert len(files_under(tmp_path / "data0")) == 1 and h in mgr.resync._written
        finally:
            await stop_all(apps, systems)

    run(main())
