"""The plain reference agrees with the program's own oracles at a small size
(`ops/gf.py`, `ops/blake3_ref.py`, `block/manager.py wrap_piece`) and with
BLAKE3's published vector.  The reference itself imports none of them."""

import numpy as np
import pytest

from harness import reference as R


def test_reference_imports_nothing_of_the_program():
    src = open(R.__file__).read()
    assert "garage_tpu" not in src.split('"""', 2)[2]
    assert "import jax" not in src


def test_blake3_published_vector_and_the_programs_reference():
    from garage_tpu.ops.blake3_ref import blake3

    assert R.blake3_rows(np.zeros((1, 0), np.uint8))[0].tobytes().hex() == (
        "af1349b9f5f9a1a6a0404dea36dcc9499bcb25c9adc112b7cc9a93cae41f3262")
    rng = np.random.default_rng(0)
    for length in (1, 64, 65, 1024, 1025, 3072, 5000, 16384):
        rows = rng.integers(0, 256, size=(3, length), dtype=np.uint8)
        got = R.blake3_rows(rows)
        for i in range(3):
            assert got[i].tobytes() == blake3(rows[i].tobytes()), length


@pytest.mark.parametrize("k,m", [(8, 3), (4, 2)])
def test_gf_agrees_with_ops_gf(k, m):
    from garage_tpu.ops import gf

    rng = np.random.default_rng(k)
    assert np.array_equal(R.generator(k, m)[k:], gf.cauchy_parity_matrix(k, m))
    block = rng.bytes(k * 640 - 17)  # padded split
    pieces = R.encode_pieces(block, k, m)
    assert np.array_equal(pieces[:k], gf.split_block(block, k)) or pieces.shape[1] % 64 == 0
    data = R.split_block(block, k)
    assert np.array_equal(pieces[k:], gf.encode_blocks_ref(data, k, m))
    lost = [1, k]
    present = {i: pieces[i] for i in range(k + m) if i not in lost}
    assert np.array_equal(R.reconstruct(present, lost, k, m), pieces[lost])
    rmat = gf.reconstruction_matrix(k, m, sorted(present)[:k], lost)
    assert np.array_equal(R.reconstruct(present, lost, k, m),
                          gf.apply_matrix_ref(rmat, np.stack([present[i] for i in sorted(present)[:k]])))


def test_piece_file_is_what_the_block_manager_writes():
    from garage_tpu.block.manager import wrap_piece
    from garage_tpu.utils.data import blake2sum

    block = np.random.default_rng(3).bytes(8 * 2048)
    files = R.expected_piece_files([block], 8, 3)[0]
    assert sorted(files) == list(range(11))
    for rank, stored in files.items():
        assert stored == wrap_piece(len(block), stored[44:], None), rank
    assert R.block_hash(block) == blake2sum(block)
    assert R.piece_len(65536, 4) == 16384 and R.piece_len(1 << 20, 8) == 131072
