"""Operations and bytes the codec work NEEDS, and the chip's least time for it.

The work is counted at the codec seam (`block_codec_blocks_total{op,
path="tpu"}`: blocks the device path was handed), not in a kernel, so the
share reads the same whatever kernel does the work and whatever it is
called.  Per block of k data shards of S bytes, as the algorithm needs it
(pad rows of a batch bucket, bit-plane unpacking and any recomputation
are the implementation's, and count as nothing):

  encode+hash        read k*S; write m*S parity and 32 bytes of BLAKE3 for
                     each of the k+m shards; the GF(2^8) product as the
                     (8m x 8k) bit-matrix times 8k bit-planes of S bits:
                     2 * 8m * 8k * S int8 operations
  reconstruct r      read k*S, write r*S; 2 * 8r * 8k * S operations

BLAKE3's own arithmetic (about 7 rounds x 8 G x 14 32-bit operations per
64 bytes) is on the vector unit, not the int8 MXU peak, and is left out
of `ops`: the share is then a lower bound where hashing binds, and the
roof that binds here is HBM in any case.
"""

from __future__ import annotations

import json
import os

PEAKS_FILE = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "peaks.json")


def peaks_for(device_kind: str) -> dict:
    with open(PEAKS_FILE) as f:
        table = json.load(f)["by_device_kind"]
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in {PEAKS_FILE}: add it with its source")
    return table[device_kind]


def encode_hash_work(k: int, m: int, s: int) -> tuple[float, float]:
    """(int8 ops, HBM bytes) of one block's fused encode + piece hashes."""
    return 2.0 * (8 * m) * (8 * k) * s, float(k * s + m * s + 32 * (k + m))


def reconstruct_work(k: int, r: int, s: int) -> tuple[float, float]:
    """(int8 ops, HBM bytes) of one block's reconstruction of r shards."""
    return 2.0 * (8 * r) * (8 * k) * s, float(k * s + r * s)


def least_seconds(ops: float, nbytes: float, peaks: dict) -> tuple[float, str]:
    """The least time the chip could take, and the roof that sets it."""
    t_ops, t_mem = ops / peaks["int8_ops_per_s"], nbytes / peaks["hbm_bytes_per_s"]
    return (t_ops, "int8") if t_ops > t_mem else (t_mem, "hbm")


def codec_least_seconds(encoded: float, reconstructed: float, k: int, m: int, s: int,
                        peaks: dict) -> tuple[float, str]:
    """Least device time for `encoded` blocks through encode+hash and
    `reconstructed` blocks rebuilt one shard each."""
    eo, eb = encode_hash_work(k, m, s)
    ro, rb = reconstruct_work(k, 1, s)
    return least_seconds(encoded * eo + reconstructed * ro, encoded * eb + reconstructed * rb, peaks)
