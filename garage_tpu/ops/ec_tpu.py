"""TPU erasure codec: GF(2^8) coding as bit-plane matmuls on the MXU.

Design (TPU-first, no reference analog — the reference's replication has no
erasure coding; this implements the BASELINE.json north star):

GF(2^8) multiplication by a constant is GF(2)-linear on the operand's bits
(gf.gf_const_bitmatrix), so a full (r x q) GF coding matrix expands to an
(8r x 8q) 0/1 matrix B, and coding becomes

    out_bits[b, i, s] = ( B @ in_bits )[b, i, s]  mod 2

i.e. ONE dense matmul over the bit-unpacked shards, batched over blocks —
exactly the shape the MXU wants.  XOR becomes addition because we only
need the low bit of the integer accumulation.

Two data paths share that math:

1. `gf_bitmatmul` — pure-XLA einsum.  Portable (CPU/TPU), but XLA
   materializes the bit-unpacked operand in HBM: bf16 bit-planes are a 16x
   traffic blowup over the uint8 shards, capping throughput far below the
   HBM roofline.  The host-backend path, and the body for shard lengths
   that are not a multiple of 128 (decided by shape, never by failure).

2. `gf_bitmatmul_pallas` — fused Pallas kernel: each grid step DMAs a
   (q, TS) uint8 shard tile into VMEM, unpacks to bit-planes *in VMEM*,
   runs the (8r x 8q) @ (8q x TS) product on the MXU (int8 x int8 ->
   int32), takes the low bit, and re-packs bits
   to bytes with a second tiny matmul, so HBM sees only the uint8 shards
   in and the uint8 parity out (1 + r/q of input bytes — the roofline).
   Bit-packing via matmul keeps every intermediate 2-D (Mosaic-friendly):
   pack matrix P[i, 8i+t] = 2^t, with t=7 encoded as int8 -128 and
   recovered by the wrapping int32 -> uint8 cast.

The coding matrix is a traced argument: encode, decode and every repair
erasure-pattern reuse ONE compiled kernel per data shape, so batched
resync (10k blocks / dispatch) never recompiles.  Checked bit-for-bit
against the numpy LUT reference in tests/test_ec.py.
"""

from __future__ import annotations

import numpy as np

from ..utils.compile_cache import instrumented_cache, record_cache_event
from . import gf, telemetry
from .bucketing import bucket_batch, pad_for_mesh, pad_to_bucket

__all__ = [
    "bucket_batch", "pad_for_mesh", "pad_to_bucket",  # re-exported
    "gf_bitmatmul", "gf_bitmatmul_pallas", "ec_apply_fn",
    "ec_apply_fn_mesh", "ec_encode_hash_fn", "blake3_supported_len",
    "EcTpu",
]


def _jax():
    import jax  # deferred so CPU-only code paths never pay the import

    return jax


def gf_bitmatmul(bitmat, x):
    """Pure-XLA bit-plane coding body (host backends, odd shard lengths).

    bitmat: (8r, 8q) 0/1 bf16;  x: (B, q, S) uint8  ->  (B, r, S) uint8.
    """
    import jax.numpy as jnp

    b, q, s = x.shape
    shifts = jnp.arange(8, dtype=jnp.uint8)
    bits = (x[:, :, None, :] >> shifts[None, None, :, None]) & 1  # (B,q,8,S)
    bits = bits.reshape(b, q * 8, s).astype(jnp.bfloat16)
    acc = jnp.einsum(
        "ij,bjs->bis", bitmat, bits, preferred_element_type=jnp.float32
    )
    out_bits = acc.astype(jnp.int32) & 1  # exact: acc <= 8q < 2^24
    r = bitmat.shape[0] // 8
    out_bits = out_bits.reshape(b, r, 8, s).astype(jnp.uint8)
    weights = (jnp.uint8(1) << shifts)[None, None, :, None]
    return (out_bits * weights).sum(axis=2, dtype=jnp.uint8)


# --- fused Pallas kernel -----------------------------------------------------

def _pick_tile(s: int) -> int:
    """Largest lane-tile (multiple of 128, at most 8192) dividing S: bigger
    tiles amortize per-grid-step overhead against the VMEM budget."""
    for ts in (8192, 4096, 2048, 1024, 512, 256, 128):
        if s % ts == 0:
            return ts
    return 0  # S not a multiple of 128: caller must use the einsum path


def _plane_major_cols(bitmat, q: int):
    """Permute (8r, 8q) standard-layout columns (8j+a) to plane-major (a*q+j)
    so the kernel can build its RHS by concatenating 8 shift-planes."""
    r8 = bitmat.shape[0]
    return bitmat.reshape(r8, q, 8).transpose(0, 2, 1).reshape(r8, 8 * q)


def _pack_matrix(r: int) -> np.ndarray:
    """(r, 8r) int8 bit-pack matrix: P[i, 8i+t] = 2^t, t=7 as -128 (two's
    complement; the wrapping int32 -> uint8 cast restores bit 7)."""
    p = np.zeros((r, 8 * r), dtype=np.int8)
    for i in range(r):
        for t in range(8):
            p[i, 8 * i + t] = -128 if t == 7 else (1 << t)
    return p


def gf_bitmatmul_pallas(bitmat, x, *, interpret: bool = False):
    """Fused unpack -> MXU matmul -> pack kernel.

    bitmat: (8r, 8q) 0/1 integer array (standard gf.bitmatrix_of layout);
    x: (B, q, S) uint8 with S a multiple of 128  ->  (B, r, S) uint8.
    """
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, q, s = x.shape
    r8, q8 = bitmat.shape
    assert q8 == 8 * q, (bitmat.shape, x.shape)
    r = r8 // 8
    ts = _pick_tile(s)
    assert ts, f"shard size {s} not a multiple of 128; use the einsum path"

    w = _plane_major_cols(bitmat, q).astype(jnp.int8)
    pack = jnp.asarray(_pack_matrix(r), dtype=jnp.int8)

    def kernel(w_ref, p_ref, x_ref, o_ref):
        xi = x_ref[0].astype(jnp.int32)  # (q, TS)
        bits = jnp.concatenate(
            [(xi >> t) & 1 for t in range(8)], axis=0
        ).astype(jnp.int8)  # (8q, TS), plane-major rows
        acc = jax.lax.dot_general(
            w_ref[:], bits,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32,
        )  # (8r, TS)
        obits = (acc & 1).astype(jnp.int8)
        packed = jax.lax.dot_general(
            p_ref[:], obits,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32,
        )  # (r, TS), values in [-128, 127]
        o_ref[0] = packed.astype(jnp.uint8)  # wrapping cast restores bit 7

    return pl.pallas_call(
        kernel,
        grid=(b, s // ts),
        in_specs=[
            pl.BlockSpec((r8, q8), lambda i, j: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((r, r8), lambda i, j: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, q, ts), lambda i, j: (i, 0, j), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec(
            (1, r, ts), lambda i, j: (i, 0, j), memory_space=pltpu.VMEM
        ),
        out_shape=jax.ShapeDtypeStruct((b, r, s), jnp.uint8),
        interpret=interpret,
    )(w, pack, x)


# --- dispatch ---------------------------------------------------------------

def _ec_body(plat: str, impl: str | None):
    """Unjitted coding body for (resolved platform, impl).  impl: None =
    auto (Pallas on TPU, einsum elsewhere)."""
    import jax.numpy as jnp

    if impl is None:
        impl = "einsum" if telemetry.is_host_platform(plat) else "pallas_int8"

    if impl == "einsum":
        def body(bitmat, x):
            return gf_bitmatmul(bitmat.astype(jnp.bfloat16), x)
    elif impl == "pallas_int8":
        # interpreter mode for CPU tests
        interp = telemetry.is_host_platform(plat)

        def body(bitmat, x):
            if _pick_tile(x.shape[-1]) == 0:
                return gf_bitmatmul(bitmat.astype(jnp.bfloat16), x)
            return gf_bitmatmul_pallas(bitmat, x, interpret=interp)
    else:
        raise ValueError(f"unknown impl {impl!r}")
    # the jitted program's name in a device trace (`jit_ec_apply`), not
    # one `jit_body` for every program of this module
    body.__name__ = "ec_apply"
    return body


def _donate_kwargs(plat: str) -> dict:
    """donate_argnums for the consume-once shard input: the fused
    foreground encode reads the data shards exactly once per dispatch,
    so on device backends the input buffer is donated to the output,
    removing a full HBM copy per dispatch (SNIPPETS pjit exemplar
    pattern).  CPU XLA cannot honor donation and warns per compile —
    skip it there.  Only the fused encode+hash path donates: the generic
    `ec_apply_fn` is also driven with long-lived device arrays
    that a donation would invalidate."""
    return (
        {} if telemetry.is_host_platform(plat) else {"donate_argnums": (1,)}
    )


@instrumented_cache("ec_apply")
def ec_apply_fn(platform: str | None = None, impl: str | None = None):
    """Jitted `fn(bitmat_uint8, x_uint8) -> out_uint8`, cached per
    (platform, impl).  impl: None = auto (Pallas on TPU, einsum elsewhere),
    or one of "einsum" / "pallas_int8"."""
    jax = _jax()

    plat = platform or jax.default_backend()
    body = _ec_body(plat, impl)
    kwargs = {"backend": platform} if platform else {}
    return jax.jit(body, **kwargs)


@instrumented_cache("ec_apply_mesh")
def ec_apply_fn_mesh(
    platform: str | None, impl: str | None, n_devices: int, axis: str = "blocks"
):
    """(jitted_fn, mesh): the coding body shard_map-ed over an n-device 1-D
    mesh — block batch split across devices, coding matrix replicated, no
    collectives (embarrassingly parallel).  `shard_map` (not GSPMD
    auto-partitioning) because the Pallas kernel is opaque to GSPMD: each
    device runs its own pallas_call on its local batch slice.

    This is the pod-level repair fan-out path (BASELINE.md staged config
    row 5): `EcCodec.{encode,reconstruct}_batch` route here whenever >1
    device is visible, so `block/manager.bulk_reconstruct` — the real
    storage-side repair driver — scales across a v5e pod with no changes."""
    jax = _jax()
    from jax.sharding import PartitionSpec as P

    from ..parallel.mesh import make_mesh

    mesh = make_mesh(n_devices, axis=axis)
    plat = platform or jax.default_backend()
    body = _ec_body(plat, impl)
    # check_vma=False: the pallas_call's out_shape carries no varying-
    # mesh-axes annotation, and the body has no collective to check
    fn = jax.shard_map(
        body, mesh=mesh, in_specs=(P(), P(axis)), out_specs=P(axis),
        check_vma=False,
    )
    fn.__name__ = f"ec_apply_mesh{n_devices}"
    return jax.jit(fn), mesh


def blake3_supported_len(s: int) -> bool:
    """Shard lengths the batched BLAKE3 kernel accepts (ops/hash_tpu.py):
    any multiple of 64 up to one chunk, or a power-of-two number of full
    1024-byte chunks.  Shard-size classes outside this set fall back to
    host-side piece hashing."""
    if s <= 0 or s % 64:
        return False
    if s <= 1024:
        return True
    return s % 1024 == 0 and (s // 1024).bit_count() == 1


def _encode_hash_body(plat: str, impl: str | None, s: int):
    """Unjitted fused body: `(bitmat, x (B,k,S)) -> (parity (B,m,S),
    hashes (B,k+m,32))`."""
    import jax.numpy as jnp

    from .hash_tpu import blake3_batch_fn

    ec_body = _ec_body(plat, impl)
    hash_fn = blake3_batch_fn(s)

    def body(bitmat, x):
        b, k, _s = x.shape
        parity = ec_body(bitmat, x)
        shards = jnp.concatenate([x, parity], axis=1)  # (B, k+m, S)
        n = shards.shape[1]
        hashes = hash_fn(shards.reshape(b * n, s)).reshape(b, n, 32)
        return parity, hashes

    body.__name__ = f"ec_encode_hash_s{s}"
    return body


@instrumented_cache("ec_encode_hash")
def ec_encode_hash_fn(platform: str | None, impl: str | None, s: int):
    """Jitted fused foreground-encode dispatch: `fn(bitmat, x (B,k,S))
    -> (parity (B,m,S), hashes (B,k+m,32))` — the EC coding matmul AND
    the BLAKE3 of every data+parity shard in ONE device dispatch, so
    the per-piece integrity hashes (block/manager.py wrap_piece) ride
    the encode instead of costing k+m host hashes per block.  The shard
    input is donated on device backends (consume-once)."""
    jax = _jax()

    plat = platform or jax.default_backend()
    kwargs = {"backend": platform} if platform else {}
    return jax.jit(
        _encode_hash_body(plat, impl, s), **kwargs, **_donate_kwargs(plat)
    )


class EcTpu:
    """Batched EC(k, m) encode/reconstruct on the XLA backend.

    Host API takes/returns numpy uint8 arrays shaped (B, shards, S); the
    BlockCodec layer (garage_tpu/block/codec/ec.py) handles bytes<->array
    marshalling and dispatch batching.  The platform decides the body
    once (Pallas on a device backend, einsum on a host backend); a
    dispatch that fails raises — nothing retries it on a slower path.
    """

    def __init__(
        self, k: int, m: int, platform: str | None = None,
        n_devices: int | None = None,
    ):
        self.k, self.m = k, m
        self.platform = platform
        self._impl: str | None = None  # None = by platform; tests pin one
        # Pod-level fan-out: shard the block batch over every visible device
        # (v5e-8 = 8-chip mesh) whenever there is more than one and the
        # batch is big enough to feed them.  n_devices pins the mesh width.
        self._n_dev = n_devices
        self._enc_bitmat = self._to_dev(gf.bitmatrix_of(gf.cauchy_parity_matrix(k, m)))
        self._recon_cache: dict[tuple[tuple[int, ...], tuple[int, ...]], object] = {}

    def _mesh_width(self) -> int:
        if self._n_dev is not None:
            return self._n_dev
        jax = _jax()
        devs = jax.devices(self.platform) if self.platform else jax.devices()
        return len(devs)

    def _to_dev(self, bitmat_np: np.ndarray):
        import jax.numpy as jnp

        arr = jnp.asarray(bitmat_np, dtype=jnp.uint8)
        if self.platform:
            jax = _jax()
            arr = jax.device_put(arr, jax.devices(self.platform)[0])
        return arr

    def _apply(self, bitmat, x: np.ndarray, kernel: str) -> np.ndarray:
        with telemetry.dispatch(
            kernel, telemetry.resolved_platform(self.platform),
            x.shape[0], x.nbytes,
        ) as rec:
            return self._apply_inner(bitmat, x, kernel, rec)

    def _apply_inner(
        self, bitmat, x: np.ndarray, kernel: str = "ec",
        rec: telemetry.DispatchRecord | None = None,
    ) -> np.ndarray:
        n = self._mesh_width()
        # auto-detected meshes only engage once every device gets >=2
        # blocks; an explicitly pinned width engages as soon as padding
        # wastes less than half the mesh
        min_batch = 2 * n if self._n_dev is None else n
        if n > 1 and x.shape[0] >= min_batch:
            out = self._apply_mesh(bitmat, x, n, rec)
            telemetry.mesh_engaged(
                kernel, telemetry.resolved_platform(self.platform), n
            )
            return out
        b = x.shape[0]
        bucket = bucket_batch(b)
        record_cache_event("ec_dispatch_bucket", bucket == b)
        if rec is None:
            # detached record: still counts pads/phases, but no wall is
            # attributed at exit (only `_apply` owns the dispatch timer)
            rec = telemetry.DispatchRecord(kernel, "")
        rec.pad(b, bucket)
        fn = ec_apply_fn(self.platform, self._impl)
        with rec.transfer("pad"):
            xp = pad_to_bucket(x, bucket)
        with rec.compute():
            # graft-lint: allow-donation(ec_apply_fn also drives long-lived device arrays; donation would invalidate them)
            out_dev = telemetry.wait_ready(fn(bitmat, xp))
        with rec.transfer("download"):
            out = np.asarray(out_dev)
        return out[:b]

    def _apply_mesh(
        self, bitmat, x: np.ndarray, n: int,
        rec: telemetry.DispatchRecord | None = None,
    ) -> np.ndarray:
        """Shard the block batch over the n-device mesh: the batch axis
        is padded to its power-of-two bucket AND to a multiple of n with
        zero blocks (one compiled executable per bucket instead of one
        per planner round size), then the result is sliced back."""
        jax = _jax()
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        b = x.shape[0]
        if rec is None:
            # detached record (see _apply_inner)
            rec = telemetry.DispatchRecord("ec", "")
        with rec.transfer("pad"):
            xp = pad_for_mesh(x, n)
        rec.pad(b, xp.shape[0])
        fn, mesh = ec_apply_fn_mesh(self.platform, self._impl, n)
        with rec.transfer("pad"):
            xd = jax.device_put(
                jnp.asarray(xp), NamedSharding(mesh, P("blocks"))
            )
        with rec.compute():
            # graft-lint: allow-donation(the mesh program shares its jit with callers that keep the sharded input)
            out_dev = telemetry.wait_ready(fn(bitmat, xd))
        with rec.transfer("download"):
            out = np.asarray(out_dev)
        return out[:b]

    def encode(self, data: np.ndarray) -> np.ndarray:
        """(B, k, S) data shards -> (B, m, S) parity shards."""
        assert data.ndim == 3 and data.shape[1] == self.k and data.dtype == np.uint8
        return self._apply(self._enc_bitmat, data, "ec_encode")

    def encode_and_hash(
        self, data: np.ndarray
    ) -> tuple[np.ndarray, np.ndarray | None]:
        """Foreground fused dispatch: (B, k, S) data shards ->
        (parity (B, m, S), BLAKE3 hashes (B, k+m, 32) or None).

        The batch axis is padded to its power-of-two bucket
        (`bucket_batch`) so ONE compiled executable serves every ragged
        batch the codec batcher coalesces; pad rows are sliced off.
        Hashes are None only when the shard length is outside the batched
        BLAKE3 kernel's supported set (decided by shape) — callers then
        hash host-side (or let the receiving node hash).  A dispatch that
        fails raises."""
        assert data.ndim == 3 and data.shape[1] == self.k and data.dtype == np.uint8
        b, _k, s = data.shape
        if not blake3_supported_len(s):
            return self.encode(data), None
        bucket = bucket_batch(b)
        record_cache_event("ec_batch_bucket", bucket == b)
        plat = telemetry.resolved_platform(self.platform)
        fn = ec_encode_hash_fn(self.platform, self._impl, s)
        with telemetry.dispatch("ec_encode_hash", plat, b, data.nbytes) as rec:
            rec.pad(b, bucket)
            # the shard input is DONATED on device backends: JAX donates
            # the transient device copy of this host batch, never the
            # host buffer itself
            with rec.transfer("pad"):
                x = pad_to_bucket(np.asarray(data), bucket)
            with rec.compute():
                parity, hashes = telemetry.wait_ready(
                    fn(self._enc_bitmat, x)
                )
            with rec.transfer("download"):
                parity, hashes = np.asarray(parity), np.asarray(hashes)
        return parity[:b], hashes[:b]

    def reconstruct(
        self, shards: np.ndarray, present: list[int], want: list[int]
    ) -> np.ndarray:
        """shards: (B, >=k, S) surviving shards ordered as `present`.
        Returns (B, len(want), S).  One compiled kernel serves every
        erasure pattern (the pattern only changes the small traced matrix)."""
        key = (tuple(present[: self.k]), tuple(want))
        bitmat = self._recon_cache.get(key)
        record_cache_event("ec_recon_matrix", bitmat is not None)
        if bitmat is None:
            rmat = gf.reconstruction_matrix(self.k, self.m, list(key[0]), list(want))
            bitmat = self._to_dev(gf.bitmatrix_of(rmat))
            self._recon_cache[key] = bitmat
        return self._apply(bitmat, shards[:, : self.k, :], "ec_reconstruct")
