"""Batch-axis shape bucketing — the fixed-shape dispatch discipline.

XLA compiles one executable per input shape.  Every foreground/repair
surface that batches ragged work (the codec batcher's linger window,
the repair planner's urgency-coalesced rounds, scrub's equal-length
hash groups) therefore pads its batch axis through THESE helpers before
dispatching, so the compile cache stays bounded at log2(max_batch)
entries per shard shape instead of growing with every distinct
concurrency level the node ever sees.  Pad blocks are zeros and their
outputs are sliced off host-side (GF coding and BLAKE3 treat batch rows
independently — nothing leaks between tenants).

graft-lint's `recompile-hazard` family recognizes these helpers by name
(`bucket_batch` / `pad_to_bucket` / `pad_to_multiple`): a compiled
dispatch whose arguments never flowed through one is flagged as an
unbucketed dispatch.  Keep new pad paths routed through here — an
inline ``np.concatenate`` pad is invisible to the lint.
"""

from __future__ import annotations

import numpy as np


def bucket_batch(b: int) -> int:
    """Round a block-batch size up to its power-of-two shape class.

    The foreground codec batcher coalesces RAGGED batches (whatever
    arrived during the linger window), and XLA compiles one executable
    per input shape: unbucketed batch sizes would compile a fresh kernel
    for every distinct concurrency level the node ever sees.  Padding
    the batch axis to a power of two bounds the compile cache at
    log2(max_batch) entries per shard shape."""
    if b <= 1:
        return 1
    return 1 << (b - 1).bit_length()


def pad_to_bucket(x: np.ndarray, b_padded: int) -> np.ndarray:
    """Zero-pad the leading (batch) axis up to `b_padded` rows.  The
    caller slices the corresponding output rows back off."""
    if x.shape[0] == b_padded:
        return x
    return np.concatenate(
        [x, np.zeros((b_padded - x.shape[0], *x.shape[1:]), np.uint8)]
    )


def pad_to_multiple(x: np.ndarray, n: int) -> np.ndarray:
    """Zero-pad the leading axis up to a multiple of `n` (mesh width):
    explicit shardings require the batch to divide the device count."""
    pad = (-x.shape[0]) % n
    if not pad:
        return x
    return np.concatenate(
        [np.asarray(x), np.zeros((pad, *x.shape[1:]), np.uint8)]
    )


def pad_for_mesh(x: np.ndarray, n: int) -> np.ndarray:
    """The mesh-dispatch pad (`EcTpu._apply_mesh`):
    power-of-two bucket first (bounded compile cache — one executable
    per bucket class, not one per planner round size), then up to a
    multiple of the n-device mesh."""
    return pad_to_multiple(pad_to_bucket(x, bucket_batch(x.shape[0])), n)
