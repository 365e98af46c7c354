"""The main path's kernels compiled for the chip, without the chip.

The TPU compiler is installed in the sandbox and compiles for a chip that
is described (`v5e:2x2`, device kind "TPU v5 lite") and not attached —
on-chip-measurement guide, section 2.  These compiles refuse what the
interpreter-mode CPU tests cannot see: a misaligned tile, too much VMEM,
a program that does not fit the chip's 16 GB, a kernel shard_map cannot
partition.  A compile that passes is NOT a chip run: it says nothing
about results or times (`chip_smoke.py` is the chip run).

Everything here runs in the test's own process (one process may hold
libtpu), the topology is described inside a fixture — never at import —
and all chip compiles live in this one file, so one xdist worker gets them.
"""

import numpy as np
import pytest

K, M = 8, 3
S = (1 << 20) // K  # 1 MiB blocks -> 131072-byte shards
B = 64  # block.batch_max_blocks: the served batch
HBM_BYTES = 16 * 1024**3  # one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache off around them."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", old)
    compilation_cache.reset_cache()


def _spec(shape, sharding):
    import jax

    return jax.ShapeDtypeStruct(shape, np.uint8, sharding=sharding)


def _pallas_body():
    from garage_tpu.ops.ec_tpu import _ec_body

    # the body the served path picks on a "tpu" platform (not interpreted)
    return _ec_body("tpu", None)


def test_int8_pallas_encode_compiles(one_chip, no_persistent_cache):
    import jax

    compiled = jax.jit(_pallas_body()).lower(
        _spec((8 * M, 8 * K), one_chip), _spec((B, K, S), one_chip)
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_pallas_reconstruct_r1_compiles(one_chip, no_persistent_cache):
    import jax

    compiled = jax.jit(_pallas_body()).lower(
        _spec((8 * 1, 8 * K), one_chip), _spec((B, K, S), one_chip)
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_fused_encode_hash_fits_the_chip(one_chip, no_persistent_cache):
    """The body of `ec_encode_hash_fn` at the served batch: compiles, keeps
    its kernel, and its arguments + outputs + temporaries fit 16 GB."""
    import jax

    from garage_tpu.ops.ec_tpu import _encode_hash_body

    body = _encode_hash_body("tpu", None, S)
    compiled = jax.jit(body, donate_argnums=(1,)).lower(
        _spec((8 * M, 8 * K), one_chip), _spec((B, K, S), one_chip)
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    total = (
        mem.temp_size_in_bytes
        + mem.argument_size_in_bytes
        + mem.output_size_in_bytes
    )
    assert 0 < total < HBM_BYTES, mem


def test_blake3_batch_compiles(one_chip, no_persistent_cache):
    from garage_tpu.ops.hash_tpu import blake3_batch_fn

    compiled = blake3_batch_fn(S).lower(
        _spec((B * (K + M), S), one_chip)
    ).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < HBM_BYTES


def test_shard_map_body_on_four_chips(topo, no_persistent_cache, monkeypatch):
    """`ec_apply_fn_mesh`'s program over the 4 described chips: the block
    batch split four ways, the kernel kept, no collective put in."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from garage_tpu.ops import ec_tpu
    from garage_tpu.parallel import mesh as mesh_mod

    # steer the program's own factory onto the described chips: its mesh
    # comes from jax.devices(), which here are the CPU's
    monkeypatch.setattr(
        mesh_mod, "make_mesh",
        lambda n, axis="blocks": Mesh(np.array(topo.devices[:n]), (axis,)),
    )
    try:
        fn, mesh = ec_tpu.ec_apply_fn_mesh("tpu", None, 4)
    finally:
        ec_tpu.ec_apply_fn_mesh.cache_clear()
    compiled = fn.lower(
        _spec((8 * M, 8 * K), NamedSharding(mesh, P())),
        _spec((B, K, S), NamedSharding(mesh, P("blocks"))),
    ).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    for collective in ("all-reduce", "all-gather", "all-to-all",
                       "collective-permute", "reduce-scatter"):
        assert collective not in text, collective
    # each chip holds B/4 rows of the output
    out = compiled.output_shardings
    assert out.shard_shape((B, M, S)) == (B // 4, M, S)
