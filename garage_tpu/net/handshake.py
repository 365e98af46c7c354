"""Connection handshake: mutual authentication + session encryption.

Same guarantees as the reference's kuska secret-handshake + box
(src/net/client.rs:55-74, server.rs:69-88) with a Noise-style construction
from the `cryptography` package primitives (NOT a port):

  1. Both sides exchange: version tag, 32-byte nonce, X25519 ephemeral
     public key, and an HMAC(network_key) over those — only holders of the
     cluster's shared network key produce a valid hello (the version tag
     gates incompatible protocol versions up front, reference
     netapp.rs:33-40).
  2. Session keys = HKDF(x25519_shared, salt=network_key, info=nonces):
     one ChaCha20-Poly1305 key per direction; forward secrecy from the
     ephemeral DH.
  3. Over the encrypted channel, each side sends its static ed25519 public
     key (= node id) and a signature over (role tag || its own static key
     || the handshake transcript), proving node identity.  Binding the
     signer's role and static key into the signed message (as the
     reference's secret-handshake does) prevents reflection: a peer that
     only knows the network key cannot echo our own auth frame back as its
     identity proof — the role tag differs per side, and an identical
     frame is rejected outright.  The client may pin an expected peer id.

Frames after the handshake: [u32 len][ChaCha20-Poly1305 ciphertext], nonce
= 4-byte direction tag + 8-byte counter.  `FramedBox.send_frame` seals a
frame and appends it to the box's one pending list; the list leaves in
one transport write (`flush`).  The nonce counter makes the order of
sealing the order on the wire, so nothing else writes to the transport.
"""

from __future__ import annotations

import asyncio
import hashlib
import hmac as hmac_mod
import os
import struct
from dataclasses import dataclass

from ..utils.metrics import registry
from .crypto_compat import (
    HAVE_REAL_CRYPTO,
    ChaCha20Poly1305,
    Ed25519PrivateKey,
    Ed25519PublicKey,
    X25519PrivateKey,
    X25519PublicKey,
)

# protocol version gate (2: stream flow control; 3: frames of up to
# 64 KiB, META and BODY in one frame, FIN on a stream's last data frame —
# connection.py).  A node of another version is refused at the first
# hello: a cluster restarts together.  The insecure stdlib fallback
# transport (crypto_compat.py) announces a DIFFERENT tag, so a fallback
# node and a real-crypto node refuse each other at the first hello
# instead of silently downgrading the cluster's transport security.
VERSION_TAG = b"grg_tpu3" if HAVE_REAL_CRYPTO else b"grg_tpuG"
# the largest sealed frame a peer may send: connection.py's 64 KiB
# payload + its 6-byte header + the 16-byte AEAD tag
MAX_FRAME = 64 * 1024 + 6 + 16
# frames sealed in one turn of the sender are joined into one transport
# write, which leaves at the latest when this much is pending
WRITE_JOIN = 128 * 1024
# the connection's StreamReader limit (netapp.py): a reader pauses its
# transport above TWICE its limit and resumes it below the limit, two
# epoll_ctl calls — at asyncio's default of 64 KiB every joined write of a
# 128 KiB piece did that to its receiver
READ_LIMIT = 2 * WRITE_JOIN

_U32 = struct.Struct("<I")
_U64 = struct.Struct("<Q")


class HandshakeError(Exception):
    pass


@dataclass
class SessionKeys:
    send_key: bytes
    recv_key: bytes
    peer_id: bytes  # peer's ed25519 public key bytes


def _hkdf(key_material: bytes, salt: bytes, info: bytes, n: int) -> bytes:
    prk = hmac_mod.new(salt, key_material, hashlib.sha256).digest()
    out, t, i = b"", b"", 1
    while len(out) < n:
        t = hmac_mod.new(prk, t + info + bytes([i]), hashlib.sha256).digest()
        out += t
        i += 1
    return out[:n]


def gen_node_key() -> bytes:
    """Generate an ed25519 private key, returned as 32 raw bytes."""
    return Ed25519PrivateKey.generate().private_bytes_raw()


def node_id_of(privkey_raw: bytes) -> bytes:
    return (
        Ed25519PrivateKey.from_private_bytes(privkey_raw)
        .public_key()
        .public_bytes_raw()
    )


class FramedBox:
    """Length-prefixed AEAD framing over an asyncio stream pair.

    Sending is two steps.  `send_frame` seals a frame and appends it to
    the pending list; `flush` joins the list into ONE transport write.
    The caller flushes when it has nothing more to send in this turn (and
    must when `pending >= WRITE_JOIN`); so that a caller which suspends
    with frames pending cannot hold them back, the first frame of a list
    schedules a flush with `loop.call_soon`: it runs in the loop's next
    iteration, which comes only once the sending task has yielded.
    Nothing sealed waits longer than that one iteration."""

    def __init__(self, reader, writer, keys: SessionKeys):
        self.reader = reader
        self.writer = writer
        self.peer_id = keys.peer_id
        self._send = ChaCha20Poly1305(keys.send_key)
        self._recv = ChaCha20Poly1305(keys.recv_key)
        self._send_ctr = 0
        self._recv_ctr = 0
        self._out: list[bytes] = []  # [len, ciphertext, len, ciphertext, ...]
        self.pending = 0  # bytes in _out
        self._messages = 0  # messages begun among the frames in _out
        self._soon: asyncio.Handle | None = None

    def send_frame(self, plaintext: bytes, starts_message: bool = False) -> None:
        nonce = b"send" + _U64.pack(self._send_ctr)
        self._send_ctr += 1
        ct = self._send.encrypt(nonce, plaintext, None)
        out = self._out
        if not out:
            self._soon = asyncio.get_running_loop().call_soon(self.flush)
        out.append(_U32.pack(len(ct)))
        out.append(ct)
        self.pending += 4 + len(ct)
        if starts_message:
            self._messages += 1

    def flush(self) -> None:
        """Hand every pending frame to the transport in one write."""
        out = self._out
        if not out:
            return
        # graft-lint: allow-cancel(a call_soon Handle, not a task: a cancelled handle is skipped, nothing is left running; a no-op when this IS the scheduled call)
        self._soon.cancel()
        self.writer.write(b"".join(out))
        incr = registry.incr
        incr("net_writes_total")
        incr("net_frames_sent_total", by=len(out) >> 1)
        incr("net_bytes_sent_total", by=self.pending)
        if self._messages:
            incr("net_messages_sent_total", by=self._messages)
            self._messages = 0
        out.clear()
        self.pending = 0

    async def drain(self) -> None:
        self.flush()
        await self.writer.drain()

    def close(self) -> None:
        """Flush what is pending (the transport sends it before it
        closes) and close the transport."""
        try:
            self.flush()
        finally:
            self.writer.close()

    async def recv_frame(self) -> bytes:
        hdr = await self.reader.readexactly(4)
        (n,) = _U32.unpack(hdr)
        if n > MAX_FRAME:
            raise HandshakeError(f"oversized frame {n}")
        ct = await self.reader.readexactly(n)
        nonce = b"send" + _U64.pack(self._recv_ctr)
        self._recv_ctr += 1
        return self._recv.decrypt(nonce, ct, None)


async def handshake(
    reader,
    writer,
    network_key: bytes,
    node_privkey_raw: bytes,
    is_server: bool,
    expected_peer_id: bytes | None = None,
) -> FramedBox:
    """Run the 3-step handshake; returns the encrypted framed channel."""
    my_nonce = os.urandom(32)
    eph = X25519PrivateKey.generate()
    eph_pub = eph.public_key().public_bytes_raw()

    hello_body = VERSION_TAG + my_nonce + eph_pub
    mac = hmac_mod.new(network_key, hello_body, hashlib.sha256).digest()
    writer.write(hello_body + mac)
    await writer.drain()

    peer_hello = await reader.readexactly(len(hello_body) + 32)
    peer_body, peer_mac = peer_hello[:-32], peer_hello[-32:]
    if not hmac_mod.compare_digest(
        peer_mac, hmac_mod.new(network_key, peer_body, hashlib.sha256).digest()
    ):
        raise HandshakeError("peer does not know the network key")
    if peer_body[: len(VERSION_TAG)] != VERSION_TAG:
        raise HandshakeError(
            f"protocol version mismatch: {peer_body[:len(VERSION_TAG)]!r}"
        )
    peer_nonce = peer_body[len(VERSION_TAG) : len(VERSION_TAG) + 32]
    peer_eph = peer_body[len(VERSION_TAG) + 32 :]

    shared = eph.exchange(X25519PublicKey.from_public_bytes(peer_eph))
    # deterministic transcript ordering: server material first
    if is_server:
        info = my_nonce + peer_nonce
        k_server, k_client = (
            _hkdf(shared, network_key, info + b"s2c", 32),
            _hkdf(shared, network_key, info + b"c2s", 32),
        )
        send_key, recv_key = k_server, k_client
    else:
        info = peer_nonce + my_nonce
        k_server, k_client = (
            _hkdf(shared, network_key, info + b"s2c", 32),
            _hkdf(shared, network_key, info + b"c2s", 32),
        )
        send_key, recv_key = k_client, k_server

    keys = SessionKeys(send_key=send_key, recv_key=recv_key, peer_id=b"")
    box = FramedBox(reader, writer, keys)

    # step 3: prove static identity over the encrypted channel
    sk = Ed25519PrivateKey.from_private_bytes(node_privkey_raw)
    my_id = sk.public_key().public_bytes_raw()
    transcript = info + eph_pub + peer_eph if is_server else info + peer_eph + eph_pub
    my_role, peer_role = (b"server", b"client") if is_server else (b"client", b"server")
    sig = sk.sign(b"garage-tpu-auth" + my_role + my_id + transcript)
    my_auth = my_id + sig
    box.send_frame(my_auth)
    await box.drain()

    peer_auth = await box.recv_frame()
    if hmac_mod.compare_digest(peer_auth, my_auth):
        raise HandshakeError("peer echoed our own auth frame (reflection)")
    peer_id, peer_sig = peer_auth[:32], peer_auth[32:]
    try:
        Ed25519PublicKey.from_public_bytes(peer_id).verify(
            peer_sig, b"garage-tpu-auth" + peer_role + peer_id + transcript
        )
    except Exception as e:
        raise HandshakeError(f"peer identity signature invalid: {e}") from e
    if expected_peer_id is not None and peer_id != expected_peer_id:
        raise HandshakeError(
            f"peer id mismatch: expected {expected_peer_id.hex()[:16]}, "
            f"got {peer_id.hex()[:16]}"
        )
    keys.peer_id = peer_id
    box.peer_id = peer_id
    return box
