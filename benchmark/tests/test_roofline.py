"""The ops and bytes functions against numbers worked by hand."""

import pytest

from harness import roofline

V5E = {"int8_ops_per_s": 393e12, "hbm_bytes_per_s": 819e9}


def test_encode_hash_ec83_one_mib_block():
    ops, nbytes = roofline.encode_hash_work(8, 3, 131072)
    # (24 x 64) bit-matrix times 64 bit-planes of 131072 bits, 2 ops per MAC
    assert ops == 2 * 24 * 64 * 131072 == 402_653_184
    # read 8 shards, write 3 parity shards and 11 hashes of 32 bytes
    assert nbytes == 8 * 131072 + 3 * 131072 + 11 * 32 == 1_442_144
    secs, roof = roofline.least_seconds(ops, nbytes, V5E)
    assert roof == "hbm"
    assert secs == pytest.approx(1_442_144 / 819e9)  # 1.76 us; the int8 roof would be 1.02 us


def test_encode_hash_ec42_one_64k_block():
    ops, nbytes = roofline.encode_hash_work(4, 2, 16384)
    assert ops == 2 * 16 * 32 * 16384 == 16_777_216
    assert nbytes == 4 * 16384 + 2 * 16384 + 6 * 32 == 98_496


def test_reconstruct_one_shard():
    ops, nbytes = roofline.reconstruct_work(8, 1, 131072)
    assert ops == 2 * 8 * 64 * 131072 == 134_217_728
    assert nbytes == 9 * 131072


def test_codec_least_seconds_adds_both_kinds():
    least, roof = roofline.codec_least_seconds(10, 5, 8, 3, 131072, V5E)
    assert roof == "hbm"
    assert least == pytest.approx((10 * 1_442_144 + 5 * 9 * 131072) / 819e9)


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        roofline.peaks_for("TPU v9 imaginary")
    assert roofline.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
