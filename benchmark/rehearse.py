#!/usr/bin/env python3
"""Rehearsals that cost no chip time.  Nothing printed here is a measurement.

    JAX_PLATFORMS=cpu python3 benchmark/rehearse.py [--workload <name>] [--trace 0|1]
        every cell (or one) end to end at a tiny size on the CPU: the same
        harness, generator, checks and result line as `run.py`, with the
        blocks cut to 1/64, a few objects, a 4 s window and — in the
        rehearsal's own node config — `[block] batch_impl = "xla"`, so that
        the device code path is the one driven (on a CPU `auto` picks the
        native host codec).  It prints which metrics a chip run would
        report and whether the checks passed, never a value of a time, a
        rate or a share: a CPU says nothing about those.

    JAX_PLATFORMS=cpu python3 benchmark/rehearse.py --compile
        the fused encode+hash and reconstruct programs of every cell's
        warm-up list, compiled for a DESCRIBED v5e (nothing attached), as
        `tests/test_chip_compile.py` does for the served batch: what the
        chip's compiler refuses here costs no chip time.  A compile that
        passes is not a chip run.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
import tempfile
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
sys.path.insert(0, BENCH_DIR)
sys.path.insert(0, ROOT)

SCALE = 64


def shrink(cell: dict, tmp: str) -> dict:
    """The cell at a size a CPU can hold: same shape of traffic, 1/64 of the bytes."""
    cfg, t = dict(cell["config"]), dict(cell["traffic"])
    cfg["block_size"] //= SCALE
    # above the 3072-byte inline threshold: an inline object has no blocks
    t["object_bytes"] = max(4096, int(t["object_bytes"]) // SCALE)
    if t.get("preload_objects"):
        t["preload_objects"] = min(int(t["preload_objects"]), 3 * int(t["clients"]))
    t["body_pool_per_client"] = 6
    t["readback_objects"], t["piece_check_blocks"] = 8, 16
    path = os.path.join(tmp, os.path.basename(cell["traffic_file"]))
    with open(path, "w") as f:
        json.dump(t, f)
    return {**cell, "config": cfg, "traffic": t, "traffic_file": path}


def rehearse(name: str, traced: bool, seconds: float, sabotage=None, bench: dict | None = None) -> dict:
    import jax

    from harness import cluster, loader
    from harness.cell import run_cell

    cluster.TAG = "[rehearsal on a CPU: not a measurement]"

    dev = jax.devices()[0]
    with tempfile.TemporaryDirectory(prefix="bench_rehearsal_") as tmp:
        cell = shrink(loader.cell(name, bench=bench), tmp)
        rehearsal = {
            "extra_top": f"block_size = {cell['config']['block_size']}",
            "extra_sections": '\n[block]\nbatch_impl = "xla"\n',
        }
        device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(jax.devices())}
        return asyncio.run(run_cell(
            cell, 7, seconds, traced, time.perf_counter(), device, tmp,
            rehearsal=rehearsal, sabotage=sabotage))


def compile_for_described_chip() -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from garage_tpu.ops.ec_tpu import _ec_body, _encode_hash_body
    from harness import cluster, loader, traffic

    jax.config.update("jax_enable_compilation_cache", False)
    one = SingleDeviceSharding(
        topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices[0])

    def spec(shape):
        return jax.ShapeDtypeStruct(shape, np.uint8, sharding=one)

    done = set()
    for wl in loader.benchmark()["workloads"]:
        cell = loader.cell(wl["name"])
        cfg = cell["config"]
        shapes = cluster.warm_shapes(cfg, traffic.validate(cell["traffic"]))
        k, m, s = cfg["k"], cfg["m"], shapes["shard_bytes"]
        for kind, buckets in (("encode_hash", shapes["encode_hash"]), ("reconstruct", shapes["reconstruct"])):
            for b in buckets:
                if (kind, k, m, s, b) in done:
                    continue
                done.add((kind, k, m, s, b))
                t0 = time.perf_counter()
                if kind == "encode_hash":
                    fn = jax.jit(_encode_hash_body("tpu", None, s), donate_argnums=(1,))
                    c = fn.lower(spec((8 * m, 8 * k)), spec((b, k, s))).compile()
                else:
                    c = jax.jit(_ec_body("tpu", None)).lower(spec((8, 8 * k)), spec((b, k, s))).compile()
                assert "tpu_custom_call" in c.as_text(), (kind, k, m, s, b)
                print(f"[rehearsal] compiled for a described v5e: {kind} ({b}, {k}, {s}) "
                      f"in {time.perf_counter() - t0:.1f} s here, temporaries "
                      f"{c.memory_analysis().temp_size_in_bytes} B", flush=True)
    print("[rehearsal] a compile that passes is not a chip run")
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1)
    ap.add_argument("--seconds", type=float, default=4.0)
    ap.add_argument("--compile", action="store_true")
    args = ap.parse_args()
    if args.compile:
        return compile_for_described_chip()
    from harness import loader

    names = [args.workload] if args.workload else [w["name"] for w in loader.benchmark()["workloads"]]
    bad = 0
    for name in names:
        r = rehearse(name, bool(args.trace), args.seconds)
        print(f"[rehearsal] {name}: REHEARSAL on {r['device']['platform']}, not a chip run: "
              f"correct={r['correct']} attempted={r['attempted']} failed={r['failed']} "
              f"metrics a chip run would report: {sorted(r['metrics'])}", flush=True)
        bad += not r["correct"]
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
