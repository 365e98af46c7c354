#!/usr/bin/env python3
"""Record a small device trace on the chip and print what is in it.

    chiprun --chips 1 -- python benchmark/tests/record_trace.py

Takes a `jax.profiler` trace around a few fused encode+hash and
reconstruct dispatches of a bare `EcTpu`, copies the `.xplane.pb` to
`chiprun_out/`, and prints every plane, line and the first events with
their stats: the look by hand that `harness/trace.py` was written from.
`tests/data/small.xplane.pb` is such a recording; `tests/test_trace.py`
reduces it.
"""

from __future__ import annotations

import glob
import os
import shutil
import sys
import time

sys.path.insert(0, os.getcwd())

import numpy as np  # noqa: E402


def main() -> int:
    import jax

    from garage_tpu.ops.ec_tpu import EcTpu
    from garage_tpu.utils.compile_cache import enable_persistent_cache

    dev = jax.devices()[0]
    print("device", dev.platform, dev.device_kind, len(jax.devices()), flush=True)
    if dev.platform == "cpu" and "--anywhere" not in sys.argv:
        print("no accelerator")
        return 2
    enable_persistent_cache()
    out_dir = os.path.join(os.getcwd(), "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    rng = np.random.default_rng(0)
    work = [(8, 3, 131072, 2), (4, 2, 16384, 1)]
    ecs = {}
    for k, m, s, b in work:  # compile outside the trace
        ecs[(k, m)] = EcTpu(k, m)
        t0 = time.perf_counter()
        ecs[(k, m)].encode_and_hash(np.zeros((b, k, s), np.uint8))
        ecs[(k, m)].reconstruct(np.zeros((b, k, s), np.uint8), list(range(1, k + 1)), [0])
        print("warm", k, m, s, b, round(time.perf_counter() - t0, 2), flush=True)
    # host-clock time of the served batch, for scale
    x = rng.integers(0, 256, size=(64, 8, 131072), dtype=np.uint8)
    ecs[(8, 3)].encode_and_hash(x)
    t0 = time.perf_counter()
    for _ in range(3):
        ecs[(8, 3)].encode_and_hash(x)
    print("encode_hash b64 host-clock s per dispatch", (time.perf_counter() - t0) / 3, flush=True)

    tdir = os.path.join(out_dir, "trace_small")
    shutil.rmtree(tdir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    opts.enable_hlo_proto = False
    t_start = time.perf_counter()
    jax.profiler.start_trace(tdir, profiler_options=opts)
    t_on = time.perf_counter()
    for k, m, s, b in work:
        d = rng.integers(0, 256, size=(b, k, s), dtype=np.uint8)
        ecs[(k, m)].encode_and_hash(d)
        time.sleep(0.05)
        ecs[(k, m)].reconstruct(d, list(range(1, k + 1)), [0])
        time.sleep(0.05)
    t_off = time.perf_counter()
    jax.profiler.stop_trace()
    print("trace start/stop cost s", t_on - t_start, time.perf_counter() - t_off,
          "window", t_off - t_on, flush=True)
    path = glob.glob(tdir + "/plugins/profile/*/*.xplane.pb")[0]
    print("xplane bytes", os.path.getsize(path))
    shutil.copy(path, os.path.join(out_dir, "small.xplane.pb"))
    shutil.rmtree(tdir, ignore_errors=True)

    from jax.profiler import ProfileData

    pd = ProfileData.from_file(os.path.join(out_dir, "small.xplane.pb"))
    for plane in pd.planes:
        lines = list(plane.lines)
        print("PLANE", repr(plane.name), len(lines), "lines")
        for line in lines:
            evs = list(line.events)
            if not evs:
                continue
            first = min(e.start_ns for e in evs)
            print("  LINE", repr(line.name), len(evs), "events, first start", first)
            for e in evs[:6]:
                print("      ", repr(e.name), e.start_ns - first, e.duration_ns,
                      [(a, str(b)[:60]) for a, b in list(e.stats)[:8]])
    # the recording kept beside the reduction: reconstruct dispatches only (a
    # fused encode+hash has thousands of ops, and their names are megabytes)
    sdir = os.path.join(out_dir, "trace_recon")
    shutil.rmtree(sdir, ignore_errors=True)
    jax.profiler.start_trace(sdir, profiler_options=opts)
    for _ in range(2):
        for k, m, s, b in work:
            d = rng.integers(0, 256, size=(b, k, s), dtype=np.uint8)
            ecs[(k, m)].reconstruct(d, list(range(1, k + 1)), [0])
            time.sleep(0.02)
    jax.profiler.stop_trace()
    spath = glob.glob(sdir + "/plugins/profile/*/*.xplane.pb")[0]
    print("reconstruct-only xplane bytes", os.path.getsize(spath))
    shutil.copy(spath, os.path.join(out_dir, "recon.xplane.pb"))
    shutil.rmtree(sdir, ignore_errors=True)
    print("memory_stats", {k: v for k, v in (dev.memory_stats() or {}).items() if "bytes" in k})
    return 0


if __name__ == "__main__":
    sys.exit(main())
