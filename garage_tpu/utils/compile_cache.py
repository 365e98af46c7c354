"""Persistent XLA compilation cache + the in-process jit-cache counters.

A cold process pays the Pallas/Mosaic and BLAKE3 compiles (seconds each,
tens of seconds for every batch bucket of the served path) before its
first dispatch.  `enable_persistent_cache()` turns on JAX's persistent
compilation cache so a later process on the same chip loads the compiled
executables instead:

- where `JAX_COMPILATION_CACHE_DIR` is set, JAX already reads it and this
  module sets no directory in code — the cache can be placed from outside;
- otherwise the cache lives at `<checkout>/.xla_cache` (a fixed path: the
  path is part of the cache key, so a directory that moves never hits).
  It is generated, not source, and `.gitignore` lists it.

Entries are keyed by jax version + backend fingerprint + HLO, so a stale
entry is a miss, never a wrong result.  The daemon calls this where it
builds its codec (`model/garage.py`, when `tpu.enable`), and so does
`chip_smoke.py`.

The cache is only enabled on device backends: CPU compiles are cheap, and
the test suite's CPU entries would only bloat the directory.
`enable_persistent_cache` returns "" when the process resolves to a host
backend.
"""

from __future__ import annotations

import functools
import os
import time

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
DEFAULT_CACHE_DIR = os.path.join(_REPO_ROOT, ".xla_cache")

_enabled = False


def record_cache_event(cache: str, hit: bool) -> None:
    """Count a compile-cache lookup in the metrics registry
    (`tpu_compile_cache_{hit,miss}_total{cache=...}`): a miss storm is
    visible on /metrics."""
    from .metrics import registry

    registry.incr(
        "tpu_compile_cache_hit_total" if hit else "tpu_compile_cache_miss_total",
        (("cache", cache),),
    )


def record_compile_secs(cache: str, secs: float) -> None:
    """One compile event's wall seconds into `tpu_compile_duration{cache}`
    (histogram count = compile events, sum = total lowering seconds —
    the Codec X-ray's compile budget, doc/monitoring.md §"Codec X-ray").
    A cache HIT must never reach here: hits record no compile time, and
    tests/test_codec_xray.py asserts exactly that."""
    from .metrics import registry

    registry.observe("tpu_compile_duration", (("cache", cache),), secs)


def instrumented_cache(cache_name: str):
    """lru_cache-style memoizer that counts hits/misses per family AND
    times the miss path as a compile event.

    Used for the in-process jit/trace caches (ec kernels, blake3
    hashers): a process that keeps missing these is recompiling —
    measurable both as a count (miss storm) and as wall seconds lost."""

    def deco(fn):
        memo: dict = {}

        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            key = (args, tuple(sorted(kwargs.items())))
            hit = key in memo
            record_cache_event(cache_name, hit)
            if not hit:
                t0 = time.perf_counter()
                memo[key] = fn(*args, **kwargs)
                record_compile_secs(cache_name, time.perf_counter() - t0)
            return memo[key]

        wrapper.cache_clear = memo.clear  # type: ignore[attr-defined]
        return wrapper

    return deco


def enable_persistent_cache() -> str:
    """Idempotently enable the persistent compilation cache.

    jax.config is live, so this takes effect for every compile after
    the call.  Returns the cache dir in use, or "" on a host backend
    (see module docstring).
    """
    global _enabled
    import jax

    from ..ops.telemetry import is_host_platform

    if is_host_platform(jax.default_backend()):
        return ""
    # placed from outside: jax reads the variable itself, at import
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    path = placed or DEFAULT_CACHE_DIR
    if _enabled:
        return path
    os.makedirs(path, exist_ok=True)
    if not placed:
        jax.config.update("jax_compilation_cache_dir", path)
    # scrape-time view of the persistent cache: how many compiled
    # executables this chip's earlier processes left behind
    from .metrics import registry

    registry.register_gauge(
        "xla_persistent_cache_entries", (),
        lambda: sum(1 for f in os.listdir(path) if not f.startswith(".")),
    )
    # cache EVERYTHING: the default thresholds skip small/fast compiles,
    # and the served path has dozens of them (one per batch bucket)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_enable_xla_caches", "all")
    _enabled = True
    return path
