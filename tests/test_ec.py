"""Erasure-codec correctness: GF math, reference codec round-trips, and the
TPU bit-plane kernel checked bit-for-bit against the numpy reference."""

import numpy as np
import pytest

from garage_tpu.ops import gf


def test_gf_field_laws():
    rng = np.random.default_rng(0)
    for _ in range(200):
        a, b, c = (int(x) for x in rng.integers(1, 256, 3))
        assert gf.gf_mul(a, gf.gf_inv(a)) == 1
        assert gf.gf_mul(a, b) == gf.gf_mul(b, a)
        assert gf.gf_mul(a, gf.gf_mul(b, c)) == gf.gf_mul(gf.gf_mul(a, b), c)
        # distributivity over XOR (field addition)
        assert gf.gf_mul(a, b ^ c) == gf.gf_mul(a, b) ^ gf.gf_mul(a, c)
    assert gf.gf_mul(0, 37) == 0
    assert gf.GF_MUL_TABLE[3, 7] == gf.gf_mul(3, 7)


def test_matrix_inverse():
    rng = np.random.default_rng(1)
    m = gf.cauchy_parity_matrix(4, 4)[:4, :4]
    inv = gf.gf_invert_matrix(m)
    prod = gf.gf_matmul(m, inv)
    assert np.array_equal(prod, np.eye(4, dtype=np.uint8))


@pytest.mark.parametrize("k,m", [(2, 1), (4, 2), (8, 3), (16, 4)])
def test_reference_codec_roundtrip(k, m):
    rng = np.random.default_rng(k * 31 + m)
    B, S = 3, 64
    data = rng.integers(0, 256, (B, k, S), dtype=np.uint8)
    parity = gf.encode_blocks_ref(data, k, m)
    shards = np.concatenate([data, parity], axis=1)  # (B, k+m, S)

    # lose up to m arbitrary shards, reconstruct them from any k survivors
    for trial in range(5):
        lost = sorted(rng.choice(k + m, size=m, replace=False).tolist())
        present = [i for i in range(k + m) if i not in lost]
        rec = gf.reconstruct_blocks_ref(shards[:, present, :], k, m, present, lost)
        assert np.array_equal(rec, shards[:, lost, :]), f"trial {trial} lost={lost}"


def test_bitmatrix_equals_gf_mul():
    rng = np.random.default_rng(2)
    for c in [0, 1, 2, 3, 0x1D, 255]:
        m = gf.gf_const_bitmatrix(c)
        for v in rng.integers(0, 256, 16):
            bits_in = np.array([(int(v) >> a) & 1 for a in range(8)])
            bits_out = m @ bits_in % 2
            got = sum(int(bits_out[b]) << b for b in range(8))
            assert got == gf.gf_mul(c, int(v))


@pytest.mark.parametrize("k,m", [(4, 2), (8, 3)])
def test_tpu_kernel_matches_reference(k, m):
    from garage_tpu.ops.ec_tpu import EcTpu

    rng = np.random.default_rng(7)
    B, S = 4, 256
    data = rng.integers(0, 256, (B, k, S), dtype=np.uint8)
    codec = EcTpu(k, m)

    parity = codec.encode(data)
    parity_ref = gf.encode_blocks_ref(data, k, m)
    assert np.array_equal(parity, parity_ref), "TPU encode != reference"

    shards = np.concatenate([data, parity], axis=1)
    lost = list(range(m))  # lose the first m data shards
    present = [i for i in range(k + m) if i not in lost]
    rec = codec.reconstruct(shards[:, present, :], present, lost)
    assert np.array_equal(rec, shards[:, lost, :]), "TPU reconstruct != truth"

    # a second erasure pattern reuses the same compiled kernel
    lost2 = [k, k + 1]  # parity shards lost: nothing to reconstruct for data,
    present2 = [i for i in range(k + m) if i not in lost2]
    rec2 = codec.reconstruct(shards[:, present2, :], present2, lost2)
    assert np.array_equal(rec2, shards[:, lost2, :])


def test_pallas_kernel_matches_reference():
    """The fused unpack->MXU->pack Pallas kernel (interpret mode on CPU)
    must be bit-identical to the LUT reference for encode and repair."""
    import jax.numpy as jnp

    from garage_tpu.ops.ec_tpu import gf_bitmatmul_pallas

    k, m = 8, 3
    rng = np.random.default_rng(11)
    B, S = 3, 384  # S a non-power-of-two multiple of 128: exercises tiling
    data = rng.integers(0, 256, (B, k, S), dtype=np.uint8)
    cmat = gf.cauchy_parity_matrix(k, m)
    bitmat = jnp.asarray(gf.bitmatrix_of(cmat), jnp.uint8)
    got = np.asarray(
        gf_bitmatmul_pallas(bitmat, jnp.asarray(data), interpret=True)
    )
    assert np.array_equal(got, gf.apply_matrix_ref(cmat, data))

    # repair: arbitrary erasure pattern through the same kernel
    shards = np.concatenate([data, got], axis=1)
    lost = [1, 5, k + 2]
    present = [i for i in range(k + m) if i not in lost]
    rmat = gf.reconstruction_matrix(k, m, present, lost)
    rec = np.asarray(
        gf_bitmatmul_pallas(
            jnp.asarray(gf.bitmatrix_of(rmat), jnp.uint8),
            jnp.asarray(shards[:, present[:k], :]),
            interpret=True,
        )
    )
    assert np.array_equal(rec, shards[:, lost, :])


def test_pallas_unaligned_shard_falls_back():
    """Shard sizes that aren't a lane multiple route to the einsum path."""
    from garage_tpu.ops.ec_tpu import ec_apply_fn

    import jax.numpy as jnp

    k, m = 4, 2
    rng = np.random.default_rng(12)
    data = rng.integers(0, 256, (2, k, 100), dtype=np.uint8)  # 100 % 128 != 0
    cmat = gf.cauchy_parity_matrix(k, m)
    bitmat = jnp.asarray(gf.bitmatrix_of(cmat), jnp.uint8)
    got = np.asarray(ec_apply_fn(None, "pallas_int8")(bitmat, jnp.asarray(data)))
    assert np.array_equal(got, gf.apply_matrix_ref(cmat, data))


def test_split_block_padding():
    blk = b"hello world, this is a block"
    arr = gf.split_block(blk, 4)
    assert arr.shape[0] == 4
    assert bytes(arr.reshape(-1)[: len(blk)]) == blk


def test_native_matches_reference():
    """The C++ host codec and BLAKE3 must be bit-identical to the oracles
    (skipped when no toolchain is available)."""
    from garage_tpu import _native

    if not _native.available():
        pytest.skip("native extension not built (no g++?)")
    rng = np.random.default_rng(5)
    mat = gf.cauchy_parity_matrix(8, 3)
    shards = rng.integers(0, 256, (8, 5000), dtype=np.uint8)
    assert np.array_equal(
        _native.gf8_apply(mat, shards), gf.apply_matrix_ref(mat, shards)
    )
    from garage_tpu.ops.blake3_ref import blake3 as py_blake3

    for n in [0, 1, 64, 1023, 1024, 1025, 4096, 5000, 100000]:
        d = bytes(rng.integers(0, 256, n, dtype=np.uint8))
        assert _native.blake3(d) == py_blake3(d), f"len {n}"
    batch = rng.integers(0, 256, (7, 2048), dtype=np.uint8)
    got = _native.blake3_batch(batch)
    for i in range(7):
        assert bytes(got[i]) == py_blake3(bytes(batch[i]))


def test_pallas_kernel_lowers_for_tpu():
    """AOT cross-lowering for the TPU platform (jax.export) must succeed
    for encode + repair matrix shapes — catches Mosaic lowering
    regressions without TPU hardware."""
    import jax
    import jax.numpy as jnp

    # `jax.export` as an attribute is deprecated-then-removed on newer
    # jax; the submodule import works on every version that has it
    from jax import export as jax_export

    from garage_tpu.ops.ec_tpu import gf_bitmatmul_pallas

    k, m = 8, 3
    enc = jnp.asarray(gf.bitmatrix_of(gf.cauchy_parity_matrix(k, m)), jnp.uint8)
    rmat = gf.reconstruction_matrix(k, m, list(range(m, k + m))[:k], list(range(m)))
    rec = jnp.asarray(gf.bitmatrix_of(rmat), jnp.uint8)
    x = jnp.zeros((4, k, 16384), jnp.uint8)
    for bm in (enc, rec):
        exported = jax_export.export(
            jax.jit(gf_bitmatmul_pallas), platforms=["tpu"]
        )(bm, x)
        assert exported.out_avals[0].shape == (4, bm.shape[0] // 8, 16384)


# --- the device path raises; nothing retries it on a slower path -------------


class _Boom(RuntimeError):
    pass


def _raising_factory(*_a, **_kw):
    def fn(*_args):
        raise _Boom("device dispatch failed")

    return fn


def _ec_and_data(b=8, s=256):
    from garage_tpu.ops.ec_tpu import EcTpu

    rng = np.random.default_rng(7)
    return EcTpu(2, 1, n_devices=1), rng.integers(0, 256, (b, 2, s), dtype=np.uint8)


def test_failed_apply_dispatch_raises(monkeypatch):
    """A failing coding dispatch used to be retried on the einsum body;
    now the failure reaches the caller."""
    from garage_tpu.ops import ec_tpu

    ec, data = _ec_and_data()
    monkeypatch.setattr(ec_tpu, "ec_apply_fn", _raising_factory)
    with pytest.raises(_Boom):
        ec.encode(data)
    with pytest.raises(_Boom):
        ec.reconstruct(data, [0, 1], [2])


def test_failed_fused_dispatch_raises(monkeypatch):
    """The fused encode+hash used to warn and return (parity, None) from
    a slower path; now the failure reaches the caller."""
    from garage_tpu.ops import ec_tpu

    ec, data = _ec_and_data()
    monkeypatch.setattr(ec_tpu, "ec_encode_hash_fn", _raising_factory)
    with pytest.raises(_Boom):
        ec.encode_and_hash(data)


def test_pinned_impl_is_the_impl_that_runs(monkeypatch):
    """`_impl` is a pin, not the head of a retry ladder: exactly one body
    is asked for, and it is the pinned one."""
    from garage_tpu.ops import ec_tpu

    asked = []
    real = ec_tpu.ec_apply_fn

    def spy(platform=None, impl=None):
        asked.append(impl)
        return real(platform, impl)

    ec, data = _ec_and_data()
    ec._impl = "pallas_int8"
    monkeypatch.setattr(ec_tpu, "ec_apply_fn", spy)
    got = ec.encode(data)
    assert asked == ["pallas_int8"]
    want = np.stack([gf.apply_matrix_ref(gf.cauchy_parity_matrix(2, 1), d) for d in data])
    assert np.array_equal(got, want)


def test_codec_boot_raises_when_device_codec_cannot_be_built(monkeypatch):
    """`tpu.enable = true` with an EcTpu that cannot be built fails the
    boot instead of logging and serving from numpy."""
    from garage_tpu.block.codec.ec import EcCodec
    from garage_tpu.ops import ec_tpu

    def broken(*_a, **_kw):
        raise _Boom("no backend")

    monkeypatch.setattr(ec_tpu, "EcTpu", broken)
    with pytest.raises(_Boom):
        EcCodec(2, 1, tpu_enable=True)
    assert EcCodec(2, 1, tpu_enable=False)._tpu is None


def test_resolved_platform_lets_backend_errors_out(monkeypatch):
    """A backend that cannot be named is an error, not the "unknown"
    platform (which selected the interpret-mode host path)."""
    import jax

    from garage_tpu.ops import telemetry

    def broken():
        raise _Boom("backend init failed")

    monkeypatch.setattr(jax, "default_backend", broken)
    with pytest.raises(_Boom):
        telemetry.resolved_platform(None)
    assert telemetry.resolved_platform("tpu") == "tpu"
    assert not telemetry.is_host_platform("unknown")
    assert telemetry.is_host_platform("cpu")


def test_scrub_hasher_is_chosen_by_platform_not_by_failure():
    """On a host backend `_prefer_xla` is false (scrub and the batcher's
    `auto` use the native host paths); nothing probes the device path
    and falls back."""
    from garage_tpu.block.codec.ec import EcCodec

    assert EcCodec(2, 1)._prefer_xla() is False
    assert EcCodec(2, 1, tpu_enable=False)._prefer_xla() is False


# --- the persistent compile cache can be placed from outside ------------------


@pytest.fixture
def cache_module(monkeypatch):
    """utils.compile_cache with the host-backend gate opened (the suite
    runs on the CPU) and every jax.config value it touches restored."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    from garage_tpu.ops import telemetry
    from garage_tpu.utils import compile_cache as cc

    names = (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_entry_size_bytes",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_enable_xla_caches",
    )
    saved = {n: getattr(jax.config, n) for n in names}
    monkeypatch.setattr(telemetry, "is_host_platform", lambda _p: False)
    monkeypatch.setattr(cc, "_enabled", False)
    yield cc
    for n, v in saved.items():
        jax.config.update(n, v)
    compilation_cache.reset_cache()
    from garage_tpu.utils.metrics import registry

    registry.unregister_gauge("xla_persistent_cache_entries", ())


def test_compile_cache_defaults_to_the_checkout(cache_module, monkeypatch, tmp_path):
    import os

    import jax

    import garage_tpu

    # the default is a fixed path in the checkout ...
    root = os.path.dirname(os.path.dirname(os.path.abspath(garage_tpu.__file__)))
    assert cache_module.DEFAULT_CACHE_DIR == os.path.join(root, ".xla_cache")
    # ... used (and set in code) only where the variable is not set
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(cache_module, "DEFAULT_CACHE_DIR", str(tmp_path / ".xla_cache"))
    assert cache_module.enable_persistent_cache() == str(tmp_path / ".xla_cache")
    assert jax.config.jax_compilation_cache_dir == str(tmp_path / ".xla_cache")
    assert (tmp_path / ".xla_cache").is_dir()


def test_compile_cache_env_dir_is_not_overridden_in_code(cache_module, monkeypatch, tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, jax reads it itself (at import)
    and the program sets no directory in code."""
    import jax

    placed = str(tmp_path / "placed")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", placed)
    jax.config.update("jax_compilation_cache_dir", placed)  # what jax's import did
    updates = []
    real_update = jax.config.update

    def spy(name, value):
        updates.append(name)
        return real_update(name, value)

    monkeypatch.setattr(jax.config, "update", spy)
    assert cache_module.enable_persistent_cache() == placed
    assert "jax_compilation_cache_dir" not in updates
    assert "jax_persistent_cache_min_compile_time_secs" in updates
    assert jax.config.jax_compilation_cache_dir == placed


def test_compile_cache_is_off_on_a_host_backend():
    from garage_tpu.utils.compile_cache import enable_persistent_cache

    assert enable_persistent_cache() == ""
