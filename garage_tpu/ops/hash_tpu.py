"""Batched BLAKE3 in JAX — the TPU scrub/integrity offload.

Hashes B equal-length inputs in one XLA dispatch.  Supported lengths: any
multiple of 64 bytes up to one chunk (<=1024), or a power-of-two number of
full 1024-byte chunks — exactly the shard sizes the EC codec produces
(shards are padded to these sizes by the block layer).  Output is bit-exact
official BLAKE3 (oracle: blake3_ref.py; vectors in tests/test_blake3.py).

Structure (all uint32, wrap-around arithmetic is native):
  - the 7-round compression runs on state rows (..., 4) with the standard
    column/diagonal vectorization (rotate rows between half-rounds);
  - a `lax.scan` chains the 16 blocks of each chunk, vmapped over B x chunks;
  - chunk CVs reduce pairwise (PARENT compressions) log2(n) times;
  - ROOT flag applied on the final compression.

Elementwise VPU work, not MXU — the win is batching thousands of shard
hashes into one dispatch next to the EC matmuls so scrub never touches the
host per block.
"""

from __future__ import annotations

import numpy as np

from ..utils.compile_cache import instrumented_cache
from . import telemetry
from .blake3_ref import CHUNK_END, CHUNK_START, IV, MSG_PERMUTATION, PARENT, ROOT
from .bucketing import bucket_batch, pad_to_bucket

BLOCK_LEN = 64
CHUNK_LEN = 1024


def _build(n_chunks: int):
    """Jitted hasher; the per-chunk block count (and the 64-byte full last
    block) are derived from the input shape at trace time."""
    last_block_len = BLOCK_LEN
    import jax
    import jax.numpy as jnp
    from jax import lax

    iv = jnp.array(IV, dtype=jnp.uint32)
    perm = jnp.array(MSG_PERMUTATION, dtype=jnp.int32)

    def rotr(x, n):
        return (x >> n) | (x << (32 - n))

    def ghalf(a, b, c, d, mx, r1, r2):
        a = a + b + mx
        d = rotr(d ^ a, r1)
        c = c + d
        b = rotr(b ^ c, r2)
        return a, b, c, d

    def compress(cv, m, counter, block_len, flags):
        # cv (..., 8), m (..., 16) -> full 16-word output (..., 16)
        ctr_lo = jnp.uint32(counter & 0xFFFFFFFF) if isinstance(counter, int) else counter.astype(jnp.uint32)
        ctr_hi = jnp.uint32(0)
        tail = jnp.stack(
            jnp.broadcast_arrays(
                ctr_lo, ctr_hi, jnp.uint32(block_len), jnp.uint32(flags)
            ),
            axis=-1,
        )
        tail = jnp.broadcast_to(tail.astype(jnp.uint32), cv.shape[:-1] + (4,))
        state = jnp.concatenate(
            [cv, jnp.broadcast_to(iv[:4], cv.shape[:-1] + (4,)), tail],
            axis=-1,
        )
        a, b, c, d = (state[..., i * 4 : (i + 1) * 4] for i in range(4))
        for r in range(7):
            mx = m[..., 0:8:2]
            my = m[..., 1:8:2]
            a, b, c, d = ghalf(a, b, c, d, mx, 16, 12)
            a, b, c, d = ghalf(a, b, c, d, my, 8, 7)
            # diagonalize
            b = jnp.roll(b, -1, axis=-1)
            c = jnp.roll(c, -2, axis=-1)
            d = jnp.roll(d, -3, axis=-1)
            mx = m[..., 8:16:2]
            my = m[..., 9:16:2]
            a, b, c, d = ghalf(a, b, c, d, mx, 16, 12)
            a, b, c, d = ghalf(a, b, c, d, my, 8, 7)
            b = jnp.roll(b, 1, axis=-1)
            c = jnp.roll(c, 2, axis=-1)
            d = jnp.roll(d, 3, axis=-1)
            if r < 6:
                m = m[..., perm]
        lo = jnp.concatenate([a, b], axis=-1) ^ jnp.concatenate([c, d], axis=-1)
        hi = jnp.concatenate([c, d], axis=-1) ^ cv
        return jnp.concatenate([lo, hi], axis=-1)

    def hash_batch(x):
        # x: (B, L) uint8
        b = x.shape[0]
        # -> little-endian uint32 words (B, n_chunks, blocks, 16)
        w = x.reshape(b, n_chunks, -1, 16, 4).astype(jnp.uint32)
        words = w[..., 0] | (w[..., 1] << 8) | (w[..., 2] << 16) | (w[..., 3] << 24)
        n_blocks = words.shape[2]
        chunk_ctr = jnp.broadcast_to(
            jnp.arange(n_chunks, dtype=jnp.uint32)[None, :], (b, n_chunks)
        )
        single_chunk = n_chunks == 1

        def step(cv, inp):
            blk, flags, block_len = inp
            out = compress(cv, blk, chunk_ctr, block_len, flags)
            return out[..., :8], None

        flags_per_block = []
        lens_per_block = []
        for i in range(n_blocks):
            f = 0
            if i == 0:
                f |= CHUNK_START
            if i == n_blocks - 1:
                f |= CHUNK_END
                if single_chunk:
                    f |= ROOT
                lens_per_block.append(last_block_len)
            else:
                lens_per_block.append(BLOCK_LEN)
            flags_per_block.append(f)

        cv0 = jnp.broadcast_to(iv, (b, n_chunks, 8))
        blocks_seq = jnp.moveaxis(words, 2, 0)  # (n_blocks, B, n_chunks, 16)
        flags_seq = jnp.array(flags_per_block, dtype=jnp.uint32)
        lens_seq = jnp.array(lens_per_block, dtype=jnp.uint32)

        if single_chunk:
            # chain all but the last block, then one final compression whose
            # full 16-word output is the root
            cv_prev = cv0
            if n_blocks > 1:
                cv_prev, _ = lax.scan(
                    step,
                    cv0,
                    (
                        blocks_seq[:-1],
                        flags_seq[:-1, None, None],
                        lens_seq[:-1, None, None],
                    ),
                )
            out = compress(
                cv_prev,
                blocks_seq[-1],
                chunk_ctr,
                jnp.uint32(last_block_len),
                jnp.uint32(flags_per_block[-1]),
            )
            root_words = out[:, 0, :8]
        else:
            # chain all 16 blocks of every chunk, then tree-reduce the CVs
            cvs, _ = lax.scan(
                step,
                cv0,
                (blocks_seq, flags_seq[:, None, None], lens_seq[:, None, None]),
            )
            n = n_chunks
            while n > 1:
                left = cvs[:, 0:n:2, :]
                right = cvs[:, 1:n:2, :]
                m = jnp.concatenate([left, right], axis=-1)  # (B, n/2, 16)
                n //= 2
                flags = PARENT | (ROOT if n == 1 else 0)
                out = compress(
                    jnp.broadcast_to(iv, m.shape[:-1] + (8,)),
                    m,
                    jnp.uint32(0),
                    jnp.uint32(BLOCK_LEN),
                    jnp.uint32(flags),
                )
                cvs = out[..., :8]
            root_words = cvs[:, 0, :]

        # -> bytes (B, 32) little-endian
        rw = root_words  # (B, 8) uint32
        out_bytes = jnp.stack(
            [(rw >> (8 * i)) & 0xFF for i in range(4)], axis=-1
        ).astype(jnp.uint8)
        return out_bytes.reshape(b, 32)

    return jax.jit(hash_batch)


@instrumented_cache("blake3_hasher")
def _hasher_for_len(length: int):
    if length % BLOCK_LEN != 0 or length == 0:
        raise ValueError("batched blake3 requires a positive multiple of 64 bytes")
    if length <= CHUNK_LEN:
        n_chunks = 1
    else:
        if length % CHUNK_LEN != 0:
            raise ValueError("multi-chunk batched blake3 requires multiple of 1024")
        n_chunks = length // CHUNK_LEN
        if n_chunks & (n_chunks - 1):
            raise ValueError("chunk count must be a power of two")
    return _build(n_chunks)


def blake3_batch(x: np.ndarray) -> np.ndarray:
    """x: (B, L) uint8 -> (B, 32) uint8 official BLAKE3 digests.

    The batch axis is padded to its power-of-two bucket (scrub hands
    this whatever group sizes the piece inventory produced — unbucketed,
    every distinct group size would compile a fresh executable); pad
    rows hash independently and are sliced off.  SYNCHRONOUS: the
    np.asarray is a device round-trip — async callers must dispatch via
    asyncio.to_thread (lint rule `host-sync`, the scrub path does)."""
    b = x.shape[0]
    fn = _hasher_for_len(x.shape[1])
    bucket = bucket_batch(b)
    with telemetry.dispatch(
        "blake3_hash", telemetry.resolved_platform(), b, x.nbytes
    ) as rec:
        rec.pad(b, bucket)
        with rec.transfer("pad"):
            xp = pad_to_bucket(np.asarray(x), bucket)
        with rec.compute():
            # graft-lint: allow-donation(callers retain and re-read the host batch; the hasher also serves fused pipelines with long-lived inputs)
            out_dev = telemetry.wait_ready(fn(xp))
        with rec.transfer("download"):
            return np.asarray(out_dev)[:b]


def blake3_batch_fn(length: int):
    """The jitted device function for fused pipelines (bench / graft entry)."""
    return _hasher_for_len(length)
