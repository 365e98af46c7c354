"""Erasure codec: GF(2^8) Cauchy Reed-Solomon, batched on TPU.

A block becomes k data shards + m parity shards; any k of the k+m pieces
reconstruct it.  Shard size is padded to a multiple of 64 bytes so the
fused encode dispatch and scrub can BLAKE3-hash shards on-device
(ops/ec_tpu.py `ec_encode_hash_fn`, ops/hash_tpu.py).

The scalar API is the numpy LUT reference codec.  The batched API goes to
the XLA bit-plane kernel (ops/ec_tpu.py) where `_on_device` says so; it
groups reconstructions by erasure pattern so thousands of blocks repair
in a handful of device dispatches.
"""

from __future__ import annotations

import numpy as np

from ...ops import gf
from ...utils.metrics import registry
from .base import BlockCodec

SHARD_ALIGN = 64  # blake3 batch hashing wants multiples of 64 bytes
# decode and reconstruct batches shorter than this stay on the host; the
# fused encode has no such floor.  Inherited from before the program ran
# on a chip and not measured there: ROADMAP D1 decides it.
TPU_BATCH_MIN = 8


def _count(op: str, path: str, blocks: int, nbytes: int) -> None:
    """Codec-layer view of the offload decision: which path (tpu batch vs
    numpy scalar) actually served how many blocks/bytes.  A production
    node silently degraded to the scalar path shows up as a rising
    `path="numpy"` share instead of staying invisible."""
    lbl = (("op", op), ("path", path))
    registry.incr("block_codec_blocks_total", lbl, blocks)
    registry.incr("block_codec_bytes_total", lbl, nbytes)


class EcCodec(BlockCodec):
    def __init__(self, k: int, m: int, tpu_enable: bool = True, platform=None):
        self.k, self.m = k, m
        self.n_pieces = k + m
        self.min_pieces = k
        self._parity_mat = gf.cauchy_parity_matrix(k, m)
        self._tpu = None
        if tpu_enable:
            # `tpu.enable = true` is a promise: a device codec that cannot
            # be built fails the boot instead of serving from numpy
            from ...ops.ec_tpu import EcTpu

            self._tpu = EcTpu(k, m, platform=platform)

    def piece_len(self, block_len: int) -> int:
        s = (block_len + self.k - 1) // self.k
        return (s + SHARD_ALIGN - 1) // SHARD_ALIGN * SHARD_ALIGN

    def _split(self, block: bytes) -> np.ndarray:
        s = self.piece_len(len(block))
        if len(block) == self.k * s:
            # aligned block (the common case: block_size is a multiple of
            # k * 64): a zero-copy read-only view — the foreground encode
            # loop must not memcpy every block while holding the GIL
            return np.frombuffer(block, dtype=np.uint8).reshape(self.k, s)
        buf = np.zeros(self.k * s, dtype=np.uint8)
        buf[: len(block)] = np.frombuffer(block, dtype=np.uint8)
        return buf.reshape(self.k, s)

    # --- scalar API ----------------------------------------------------------

    def encode(self, block: bytes) -> list[bytes]:
        # padded split bytes (k*s), same unit the tpu path and both
        # reconstruct paths count — the tpu-vs-numpy byte shares compare
        _count("encode", "numpy", 1, self.k * self.piece_len(len(block)))
        data = self._split(block)  # (k, s)
        parity = gf.apply_matrix(self._parity_mat, data)
        return [bytes(data[i]) for i in range(self.k)] + [
            bytes(parity[i]) for i in range(self.m)
        ]

    def decode(self, pieces: dict[int, bytes], block_len: int) -> bytes:
        data_idx = [i for i in range(self.k) if i in pieces]
        if len(data_idx) == self.k:
            # systematic fast path: the k data shards ARE the plaintext —
            # counted under its own path label so the GET pipeline's
            # systematic share (systematic / (systematic+reconstruct)
            # within op="decode") is computable (ROADMAP item 1a feeds
            # on exactly this number)
            _count(
                "decode", "systematic", 1, self.k * self.piece_len(block_len)
            )
            return b"".join(pieces[i] for i in range(self.k))[:block_len]
        # degraded GET: some data shard is missing, a real decode runs.
        # Counted as op="decode" (the GET-path view) IN ADDITION to the
        # op="reconstruct" count inside reconstruct_pieces — that label
        # is shared with the background repair plane, so without this
        # one the GET decode share would be unrecoverable
        _count("decode", "reconstruct", 1, self.k * self.piece_len(block_len))
        missing = [i for i in range(self.k) if i not in pieces]
        rec = self.reconstruct_pieces(pieces, missing, block_len)
        full = {**pieces, **rec}
        return b"".join(full[i] for i in range(self.k))[:block_len]

    def reconstruct_pieces(
        self, pieces: dict[int, bytes], want: list[int], block_len: int
    ) -> dict[int, bytes]:
        present = sorted(pieces.keys())
        if len(present) < self.k:
            raise ValueError(
                f"need {self.k} pieces to reconstruct, have {len(present)}"
            )
        use = present[: self.k]
        s = self.piece_len(block_len)
        _count("reconstruct", "numpy", 1, self.k * s)
        shards = np.stack(
            [np.frombuffer(pieces[i], dtype=np.uint8) for i in use]
        )  # (k, s)
        assert shards.shape[-1] == s, (shards.shape, s)
        rmat = gf.reconstruction_matrix(self.k, self.m, use, want)
        rec = gf.apply_matrix(rmat, shards)
        return {w: bytes(rec[j]) for j, w in enumerate(want)}

    # --- batched API: the codec batcher's and the repair plane's backend -------

    def _prefer_xla(self) -> bool:
        """auto-impl policy: the XLA path only on a real device backend.
        On a host backend the einsum body emulates the bit-plane matmul
        in software and the native LUT codec is the faster of the two,
        so `auto` keeps the work on the host there.  Whether the device
        wins at a given batch length is not measured on the chip
        (ROADMAP D1)."""
        if self._tpu is None:
            return False
        from ...ops.telemetry import is_host_platform, resolved_platform

        # the ONE shared definition of "host backend" (lint rule
        # backend-gate): scattered string compares are how silent
        # fallbacks breed
        return not is_host_platform(resolved_platform(self._tpu.platform))

    def _on_device(self, op: str, n: int, impl: str = "auto") -> bool:
        """Does the device serve this batched call?  The one place the
        codec decides host or device.  `op` is "encode" (the fused
        encode+hash), "decode" (degraded reads) or "reconstruct" (the
        repair plane); `n` the batch length; `impl` the batcher's
        `[block] batch_impl` ("xla" forces the device kernel, "host" the
        native codec, "auto" asks `_prefer_xla()`).  The repair plane has
        no `impl`: with a device codec built, its batches go to it on any
        backend.  The outcomes are inherited, not measured on the chip;
        ROADMAP D1 replaces them with something the code observes."""
        if self._tpu is None:
            return False
        if op != "reconstruct" and not (
            impl == "xla" or (impl == "auto" and self._prefer_xla())
        ):
            return False
        return op == "encode" or n >= TPU_BATCH_MIN

    def encode_batch_hashed(
        self, blocks: list[bytes], impl: str = "auto"
    ) -> list[tuple[list[bytes], list[bytes] | None]]:
        """ONE coalesced encode dispatch per shard-size group:
        `[(pieces, piece_hashes | None)] ` aligned with `blocks`.

        This is the codec batcher's backend (block/codec_batch.py).
        On the device (`_on_device`): the fused encode+BLAKE3 kernel,
        batch axis padded to its power-of-two bucket; otherwise the
        native C codec + native BLAKE3.  Piece hashes cover all k+m
        pieces in piece order; None when no batched hasher is available
        (callers fall back to per-piece host hashing on the receiving
        node)."""
        if not self._on_device("encode", len(blocks), impl):
            return self._encode_hashed_host(blocks)
        out: list[tuple[list[bytes], list[bytes] | None] | None] = [None] * len(blocks)
        groups: dict[int, list[int]] = {}
        for idx, b in enumerate(blocks):
            groups.setdefault(self.piece_len(len(b)), []).append(idx)
        for s, idxs in groups.items():
            data = np.stack([self._split(blocks[i]) for i in idxs])  # (B,k,s)
            _count("encode", "tpu", len(idxs), data.nbytes)
            parity, hashes = self._tpu.encode_and_hash(data)
            for j, i in enumerate(idxs):
                pieces = [bytes(data[j, x]) for x in range(self.k)] + [
                    bytes(parity[j, x]) for x in range(self.m)
                ]
                hs = (
                    None
                    if hashes is None
                    else [bytes(hashes[j, x]) for x in range(self.n_pieces)]
                )
                out[i] = (pieces, hs)
        return out  # type: ignore[return-value]

    def _encode_hashed_host(
        self, blocks: list[bytes]
    ) -> list[tuple[list[bytes], list[bytes] | None]]:
        """Host backend of the coalesced dispatch: a straight per-block
        loop over the native C codec + native BLAKE3.  Deliberately NO
        batch stacking here — every heavy step (GF matmul, hashing) is a
        ctypes call that RELEASES the GIL, while numpy stack/transpose
        megacopies would hold it and stall the event loop from inside
        the "off-loop" worker thread (measured: a 64-block stacked
        dispatch held the GIL for tens of ms and made the batcher a
        pessimization on CPU).  The coalescing win on the host backend
        is one thread hop + one telemetry record per BATCH, with the
        loop left free the whole time."""
        from ... import _native
        from ...ops import telemetry

        nbytes = sum(self.k * self.piece_len(len(b)) for b in blocks)
        _count("encode", "numpy", len(blocks), nbytes)
        out: list[tuple[list[bytes], list[bytes] | None]] = []
        with telemetry.dispatch(
            "ec_encode_host", "host", len(blocks), nbytes
        ) as rec:
            # the host path never pads (no fixed-shape executable), so
            # its pad-waste is an honest 0 — keeping the kernel in the
            # X-ray's pad table instead of absent
            rec.pad(len(blocks), len(blocks))
            with rec.compute():
                for block in blocks:
                    data = self._split(block)  # zero-copy view when aligned
                    parity = gf.apply_matrix(self._parity_mat, data)
                    pieces = [bytes(data[i]) for i in range(self.k)] + [
                        bytes(parity[i]) for i in range(self.m)
                    ]
                    hashes: list[bytes] | None = []
                    for p in pieces:
                        h = _native.blake3(p)
                        if h is None:  # native lib absent: receiver hashes
                            hashes = None
                            break
                        hashes.append(h)
                    out.append((pieces, hashes))
        return out

    def note_systematic_read(self, block_len: int) -> None:
        """The streamed systematic GET (block/manager.py) joins the k
        data shards OUTSIDE the codec — piece i goes to the caller while
        piece i+1 is still in flight, so `decode()` never runs.  It
        reports here instead, keeping the `op="decode"` systematic/
        reconstruct split honest (the ROADMAP 1a share)."""
        _count("decode", "systematic", 1, self.k * self.piece_len(block_len))

    def decode_batch(
        self, items: list[tuple[dict[int, bytes], int]], impl: str = "auto"
    ) -> list[bytes]:
        """ONE coalesced reconstruction dispatch per erasure-pattern/
        shard-size group: `[plaintext]` aligned with `items` — the codec
        batcher's decode-lane backend (degraded-mode GETs under load
        share a device dispatch instead of N single-block ones).

        On the host this stays a per-block loop over the native LUT
        codec (NO batch stacking — the numpy megacopies would hold the
        GIL inside the worker thread, the PR 9 trap).  Items whose k data
        shards all arrived are systematic joins either way and never
        touch the device."""
        if not self._on_device("decode", len(items), impl):
            return self._decode_batch_host(items)
        out: list[bytes | None] = [None] * len(items)
        degraded: list[int] = []
        for idx, (pieces, block_len) in enumerate(items):
            if all(i in pieces for i in range(self.k)):
                # systematic: zero decode, plain host join
                out[idx] = self.decode(pieces, block_len)
            else:
                degraded.append(idx)
        batches = [
            (
                items[i][0],
                [r for r in range(self.k) if r not in items[i][0]],
                items[i][1],
            )
            for i in degraded
        ]
        if batches:
            _count(
                "decode", "reconstruct", len(batches),
                sum(self.k * self.piece_len(n) for _p, _w, n in batches),
            )
        for i, rec in zip(degraded, self._reconstruct_device(batches)):
            pieces, block_len = items[i]
            full = {**pieces, **rec}
            out[i] = b"".join(full[r] for r in range(self.k))[:block_len]
        return out  # type: ignore[return-value]

    def _decode_batch_host(
        self, items: list[tuple[dict[int, bytes], int]]
    ) -> list[bytes]:
        """Host backend of the coalesced decode: a per-block loop over
        the scalar decode (native LUT reconstruction inside), ONE thread
        hop + one telemetry record per batch — the `_encode_hashed_host`
        pattern."""
        from ...ops import telemetry

        nbytes = sum(self.k * self.piece_len(n) for _p, n in items)
        with telemetry.dispatch(
            "ec_decode_host", "host", len(items), nbytes
        ) as rec:
            rec.pad(len(items), len(items))
            with rec.compute():
                return [self.decode(p, n) for p, n in items]

    def reconstruct_batch(self, batches):
        for idx, (pieces, _w, _n) in enumerate(batches):
            if len(pieces) < self.k:
                raise ValueError(
                    f"batch entry {idx}: need {self.k} pieces to "
                    f"reconstruct, have {len(pieces)}"
                )
        if not self._on_device("reconstruct", len(batches)):
            return [self.reconstruct_pieces(p, w, n) for p, w, n in batches]
        return self._reconstruct_device(batches)

    def _reconstruct_device(
        self, batches: list[tuple[dict[int, bytes], list[int], int]]
    ) -> list[dict[int, bytes]]:
        """`[(pieces, want, block_len)] -> [{rank: piece}]` on the device:
        one dispatch per (erasure pattern, want, shard size) group, one
        compiled kernel per shard shape overall."""
        out: list[dict[int, bytes] | None] = [None] * len(batches)
        groups: dict[tuple, list[int]] = {}
        for idx, (pieces, want, block_len) in enumerate(batches):
            present = tuple(sorted(pieces.keys())[: self.k])
            key = (present, tuple(sorted(want)), self.piece_len(block_len))
            groups.setdefault(key, []).append(idx)
        for (present, want, _s), idxs in groups.items():
            shards = np.stack(
                [
                    np.stack(
                        [
                            np.frombuffer(batches[i][0][p], dtype=np.uint8)
                            for p in present
                        ]
                    )
                    for i in idxs
                ]
            )  # (B, k, s)
            _count("reconstruct", "tpu", len(idxs), shards.nbytes)
            rec = self._tpu.reconstruct(shards, list(present), list(want))
            for j, i in enumerate(idxs):
                out[i] = {w: bytes(rec[j, x]) for x, w in enumerate(want)}
        return out  # type: ignore[return-value]
