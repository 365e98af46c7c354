"""The system under test, brought up in this process.

Copies of `chip_smoke.py`'s `start_cluster`, `warm_buckets`, `HostStrain`,
`PersistentCacheEvents`, `piece_files` and `ctr` (PR 25), made
general over the configuration file: a later PR may change the smoke, not
the yardstick.  Mapping M1 of ROADMAP 2b: every storage node of the
configuration in the one process that holds the chip, on one event loop,
each node built the way `cli/main.py run_server` builds one (TOML ->
`read_config` -> `Garage` -> `start` -> `AdminRpcHandler` ->
`spawn_workers`), each with its own `EcTpu`, sharing the jitted programs.
"""

from __future__ import annotations

import asyncio
import io
import json
import os
import sys
import time

import numpy as np

from .check import pieces_under, read_file  # noqa: F401 — read_file is re-exported


TAG = "[bench]"  # rehearse.py relabels its lines: nothing a CPU prints is a measurement


def say(tag: str, **kv) -> None:
    """An earlier line of the run, on standard output."""
    print(f"{TAG} {tag} " + json.dumps(kv, sort_keys=True, default=str), flush=True)


# --- the program's registry ------------------------------------------------------

def ctr(name: str, **labels) -> float:
    """Sum of the registry's `name` counters whose labels include `labels`."""
    from garage_tpu.utils.metrics import registry

    want = set(labels.items())
    return sum(v for (n, lbl), v in registry.counters.items() if n == name and want <= set(lbl))


class HostStrain:
    """What the shared event loop and the RPC plane went through: the worst
    loop stall seen by a 20 ms ticker, RPC calls that timed out, peer
    breakers that opened, and the CPU seconds per second of the loop's
    thread and of the whole process.  All nodes share ONE loop here, so one
    node's work delays every other node's answers.  Made and asked on the
    loop's thread."""

    def __init__(self):
        self.max_lag = 0.0
        self.long_stalls: list[tuple[float, float]] = []  # (seconds into the phase, stall seconds)
        self._t_phase = time.perf_counter()
        self._cpu = (time.thread_time(), time.process_time())
        self._task = asyncio.get_running_loop().create_task(self._tick())
        self._base = self._counters()

    async def _tick(self) -> None:
        while True:
            t0 = time.perf_counter()
            await asyncio.sleep(0.02)
            lag = time.perf_counter() - t0 - 0.02
            self.max_lag = max(self.max_lag, lag)
            if lag > 0.5:
                self.long_stalls.append((round(t0 - self._t_phase, 2), round(lag, 2)))

    @staticmethod
    def _counters() -> dict:
        return {
            "rpc_timeouts": ctr("rpc_timeout_counter"),
            "breaker_opens": ctr("rpc_breaker_transition_counter", to="open"),
        }

    def phase(self) -> dict:
        """Strain since the last call."""
        now = self._counters()
        out = {k: now[k] - self._base[k] for k in now}
        out["loop_stall_max_ms"] = self.max_lag * 1000.0
        out["stalls_over_500ms"] = self.long_stalls[:20]
        cpu, secs = (time.thread_time(), time.process_time()), time.perf_counter() - self._t_phase
        out["loop_thread_cpu_share"] = (cpu[0] - self._cpu[0]) / secs
        out["process_cpu_share"] = (cpu[1] - self._cpu[1]) / secs
        self._base, self.max_lag, self.long_stalls, self._cpu = now, 0.0, [], cpu
        self._t_phase = time.perf_counter()
        return out

    async def stop(self) -> None:
        self._task.cancel()
        await asyncio.gather(self._task, return_exceptions=True)


class PersistentCacheEvents:
    """JAX's own persistent-compilation-cache hit/miss events."""

    def __init__(self):
        import jax.monitoring

        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._on)

    def _on(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


# --- warm-up ------------------------------------------------------------------------

def pow2_up_to(n: int) -> list[int]:
    out, b = [], 1
    while b < n:
        out.append(b)
        b *= 2
    return out + [b]


def warm_shapes(cfg: dict, t: dict) -> dict:
    """The batch buckets THIS cell's traffic can dispatch, and no others.

    Fused encode+hash: the batcher flushes at most `batch_max_blocks` (64,
    the program's default) and no more blocks than the clients can have in
    flight.  Reconstruct (one lost rank): a repair round gathers at most
    `repair.bytes_in_flight` (128 MiB, default) of surviving shards, and
    its erasure-pattern groups can be of any size below that."""
    k = cfg["k"]
    block = min(int(t["object_bytes"]), cfg["block_size"])
    s = -(-(-(-block // k)) // 64) * 64
    blocks_per_object = -(-int(t["object_bytes"]) // cfg["block_size"])
    in_flight = blocks_per_object * max(
        int(t["clients"]) if t["mix"].get("PUT") else 0,
        int(t["preload_concurrency"]) if t["preload_objects"] else 0,
    )
    shapes = {"shard_bytes": s, "encode_hash": pow2_up_to(min(64, in_flight)) if in_flight else [],
              "reconstruct": []}
    if t.get("fault"):
        shapes["reconstruct"] = pow2_up_to(max(1, (128 << 20) // (k * s)))
    return shapes


def warm_buckets(cfg: dict, shapes: dict) -> dict:
    """Run each shape once on a bare EcTpu (a compile on a cold cache, a
    load on a warm one), after the program's native library has been built
    or loaded; seconds per step."""
    from garage_tpu import _native
    from garage_tpu.ops.ec_tpu import EcTpu

    k, m, s = cfg["k"], cfg["m"], shapes["shard_bytes"]
    ec = EcTpu(k, m)
    # the program builds its native host library (C++, ~6 s, on whatever
    # thread asks first — the shared loop, mid-request) on first use in a
    # checkout or on a new kind of host; built here it is set-up
    t0 = time.perf_counter()
    _native.lib()
    secs = {"native_library": round(time.perf_counter() - t0, 3)}
    for b in shapes["encode_hash"]:
        t0 = time.perf_counter()
        ec.encode_and_hash(np.zeros((b, k, s), dtype=np.uint8))
        secs[f"encode_hash_b{b}"] = round(time.perf_counter() - t0, 3)
    for b in shapes["reconstruct"]:
        t0 = time.perf_counter()
        ec.reconstruct(np.zeros((b, k, s), dtype=np.uint8), list(range(1, k + 1)), [0])
        secs[f"reconstruct_b{b}"] = round(time.perf_counter() - t0, 3)
    return secs


# --- the cluster ----------------------------------------------------------------------

NODE_TOML = """\
metadata_dir = "{root}/n{i}/meta"
data_dir = "{root}/n{i}/data"
db_engine = "{db_engine}"
replication_mode = "{replication_mode}"
rpc_bind_addr = "127.0.0.1:0"
rpc_secret = "{secret}"
{extra_top}
[tpu]
enable = true

[admin]
canary_enabled = false
event_loop_watchdog_threshold_msec = 0
slo_latency_p99_target_msec = {slo_ms}
{extra_sections}
"""


async def start_cluster(cfg: dict, root: str, extra_top: str = "", extra_sections: str = ""):
    """`cfg["storage_nodes"]` nodes, one per zone, full mesh, layout applied,
    workers spawned, and every node's layout sync tracker covering the
    version on every node before any traffic (the boot-time table-sync
    round otherwise starves RPCs past the adaptive timeout).  The canary
    and the per-node event-loop watchdog are off, as in the smoke: the
    nodes share one loop.  The one value stated besides is the deployment's
    SLO latency target (the configuration file says why).  `extra_*` is for the CPU rehearsal only."""
    from garage_tpu.cli.admin_rpc import AdminRpcHandler
    from garage_tpu.model.garage import Garage
    from garage_tpu.rpc.layout.types import NodeRole
    from garage_tpu.utils.config import read_config

    n_nodes = cfg["storage_nodes"]
    garages = []
    for i in range(n_nodes):
        os.makedirs(f"{root}/n{i}")
        path = f"{root}/n{i}/garage.toml"
        with open(path, "w") as f:
            f.write(NODE_TOML.format(
                root=root, i=i, secret="c5" * 32, db_engine=cfg["db_engine"],
                replication_mode=cfg["replication_mode"],
                slo_ms=float(cfg["slo"]["latency_p99_target_msec"]),
                extra_top=extra_top, extra_sections=extra_sections,
            ))
        garages.append(Garage(read_config(path)))
    try:
        for g in garages:
            await g.start()
            AdminRpcHandler(g)
        for i, gi in enumerate(garages):
            for gj in garages[i + 1:]:
                await gj.netapp.connect(gi.netapp.bind_addr, gi.node_id)
        for _ in range(400):
            await asyncio.sleep(0.05)
            if all(len(g.system.peering.connected_peers()) == n_nodes - 1 for g in garages):
                break
        else:
            raise RuntimeError("full mesh did not close")
        lm = garages[0].layout_manager
        for i, g in enumerate(garages):
            lm.stage_role(g.node_id, NodeRole(zone=f"dc{i}", capacity=10**12))
        lm.apply_staged()
        for _ in range(400):
            await asyncio.sleep(0.05)
            if all(g.layout_manager.digest() == lm.digest() for g in garages):
                break
        else:
            raise RuntimeError("layout did not converge")
        for g in garages:
            g.spawn_workers()
        t0 = time.perf_counter()

        def synced(g) -> bool:
            h = g.layout_manager.history
            return all(h.sync.get(o.node_id) >= h.current().version for o in garages)

        while not all(synced(g) for g in garages):
            if time.perf_counter() - t0 > 600:
                raise RuntimeError("layout transition did not close in 600 s")
            await asyncio.sleep(0.25)
        say("layout_synced", secs=round(time.perf_counter() - t0, 2))
    except BaseException:
        await stop_cluster(garages, [])
        raise
    return garages


async def start_frontends(cfg: dict, garages: list, bucket: str):
    """S3 servers on the configuration's frontend nodes, an access key and
    the bucket.  Returns (servers, endpoints, key id, secret)."""
    from garage_tpu.api.s3.api_server import S3ApiServer

    from .s3client import S3Client

    servers = []
    for idx in cfg["frontends"]:
        srv = S3ApiServer(garages[idx])
        await srv.start("127.0.0.1", 0)
        servers.append(srv)
    g0 = garages[cfg["frontends"][0]]
    key = await g0.helper.create_key("benchmark")
    key.params().allow_create_bucket.update(True)
    await g0.key_table.insert(key)
    endpoints = [f"http://127.0.0.1:{s.runner.addresses[0][1]}" for s in servers]
    cl = S3Client(endpoints[0], key.key_id, key.secret())
    try:
        st, _h, body = await cl.request("PUT", f"/{bucket}")
        if st != 200:
            raise RuntimeError(f"create bucket: {st} {body[:200]!r}")
    finally:
        await cl.close()
    return servers, endpoints, key.key_id, key.secret()


async def _bounded(coro, what: str, secs: float) -> None:
    """Await `coro` for at most `secs`; a stop that does not end is reported
    with where it was waiting, cancelled, and left behind (the process ends
    with the run; the data directories are what the check reads)."""
    task = asyncio.ensure_future(coro)
    done, _pending = await asyncio.wait({task}, timeout=secs)
    if done:
        if task.exception() is not None:
            print(f"[bench] {what}: {task.exception()!r}", file=sys.stderr, flush=True)
        return
    buf = io.StringIO()
    task.print_stack(file=buf)
    print(f"[bench] {what} did not end in {secs} s; it waits at:\n{buf.getvalue()}",
          file=sys.stderr, flush=True)
    task.cancel()
    await asyncio.wait({task}, timeout=2)


async def stop_cluster(garages: list, servers: list) -> None:
    for i, s in enumerate(servers):
        await _bounded(s.stop(), f"stop of S3 server {i}", 15)
    for i, g in enumerate(garages):
        await _bounded(g.stop(), f"stop of node {i}", 15)


def piece_files(garage) -> dict[tuple[bytes, int], str]:
    """(block hash, piece index) -> path, for every piece in a node's data dir."""
    return {key: path for dd in garage.config.data_dir for key, path in pieces_under(dd.path)}
