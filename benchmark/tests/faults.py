"""Ways to break the timed path underneath the harness.

Each is a `sabotage(garages)` for `harness.cell.run_cell`, applied once the
cluster is up; `undo()` puts the program back.  Used by `test_control.py`
(tiny, on the CPU) and `chip_control.py` (the cells' own sizes, on the chip).

The CONTROL is the first: the system states no numeric precision, so the
control breaks a guarantee the configuration states — the code.  A device
path that computes one parity shard fewer (the step that would tempt a
later PR: EC(k, m-1) is a third less device work and every healthy GET
still reads back) must come out as not correct.
"""

from __future__ import annotations

import numpy as np

_undo: list = []


def undo() -> None:
    while _undo:
        obj, name, old = _undo.pop()
        setattr(obj, name, old)


def _patch(obj, name, new) -> None:
    _undo.append((obj, name, getattr(obj, name)))
    setattr(obj, name, new)


def control_parity_shard_dropped(_garages) -> None:
    """The device's last parity shard comes back as zeros: EC(k, m-1) sold
    as EC(k, m).  No healthy GET reads parity, so only a comparison of the
    stored pieces with the reference sees it."""
    from garage_tpu.ops.ec_tpu import EcTpu

    real = EcTpu.encode_and_hash

    def cheap(self, data):
        parity, hashes = real(self, data)
        parity = np.array(parity)
        parity[:, -1, :] = 0
        return parity, hashes

    _patch(EcTpu, "encode_and_hash", cheap)


def fault_answer_altered(_garages) -> None:
    """A frontend flips one byte of what it streams out of a block."""
    from garage_tpu.block.manager import BlockRead

    real = BlockRead.chunks

    async def altered(self):
        first = True
        async for chunk in real(self):
            if first and chunk:
                chunk = bytes([chunk[0] ^ 0x01]) + bytes(chunk[1:])
                first = False
            yield chunk

    _patch(BlockRead, "chunks", altered)


def fault_rebuilt_piece_altered(_garages) -> None:
    """The device's reconstruction comes back with one byte flipped."""
    from garage_tpu.ops.ec_tpu import EcTpu

    real = EcTpu.reconstruct

    def wrong(self, shards, present, want):
        out = np.array(real(self, shards, present, want))
        out[:, 0, 0] ^= 0x01
        return out

    _patch(EcTpu, "reconstruct", wrong)


def fault_pieces_acknowledged_and_dropped(garages) -> None:
    """Two storage nodes acknowledge every piece and store none: a PUT is
    then acknowledged below the write quorum the configuration states."""
    async def drop(_hash32, _stored, _compressed, piece=0):
        return None

    for g in garages[-2:]:
        _patch(g.block_manager, "write_block_local", drop)


def requests_refused(_garages) -> None:
    """Every seventh request to a frontend is answered 500, as the program
    answers the requests in flight when its host stands still for some
    seconds (PERF.md, Findings).  NOT a fault of what is stored or said: the
    one sabotage here that has to leave `correct` true, with the refusals
    counted in `failed`."""
    from garage_tpu.api.s3.api_server import S3ApiServer

    real = S3ApiServer._handle
    seen = [0]

    async def refusing(self, request):
        seen[0] += 1
        if seen[0] % 7 == 0:
            raise RuntimeError("refused by the test: could not reach quorum")
        return await real(self, request)

    _patch(S3ApiServer, "_handle", refusing)


BY_NAME = {f.__name__: f for f in (
    control_parity_shard_dropped, fault_answer_altered,
    fault_rebuilt_piece_altered, fault_pieces_acknowledged_and_dropped, requests_refused)}
