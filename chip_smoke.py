#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that garage-tpu still starts on the chip.

One process, one chip: an EC(8,3) cluster of 11 storage nodes is brought
up in THIS process (each node the way `cli/main.py run_server` builds
one), two of them serve S3 over HTTP, and seeded objects are PUT, read
back, read degraded and repaired — every byte compared, and the
program's own counters read afterwards to prove the codec ran on the
chip and not on a host path.

    python chip_smoke.py                 the driver's run: needs one TPU chip
    python chip_smoke.py --chips 4       ONLY the 4-device mesh path + what it
                                         is compared with (builder-run)
    JAX_PLATFORMS=cpu python chip_smoke.py --rehearse
                                         same phases, tiny, on the CPU; skips
                                         the platform check and the `tpu`
                                         labels and never prints the ok line

Without an accelerator (and without --rehearse) it prints what JAX found
and exits 2.  Every phase failure is an exception: non-zero exit, no ok
line.  The last stdout line of a passing chip run is exactly
`{"ok": true, "device": {"platform": ..., "kind": ..., "count": N}}`.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import shutil
import sys
import tempfile
import time

import numpy as np

K, M = 8, 3
N_NODES = K + M
S3_A, S3_B = 0, 5  # node indices of the two S3 frontends
GET_CONCURRENCY = 3


def say(tag: str, **kv) -> None:
    print(f"[smoke] {tag} " + json.dumps(kv, sort_keys=True), flush=True)


class SmokeFailure(Exception):
    pass


def need(cond, msg) -> None:
    """A wrong byte or a phase that cannot go on: the run ends here."""
    if not cond:
        raise SmokeFailure(msg)


_problems: list[str] = []


def expect(cond, msg: str) -> None:
    """A counter that attributes the work to the wrong path: the run goes
    on (one chip call then shows every phase) and fails at the end."""
    if not cond:
        _problems.append(msg)
        say("PROBLEM", what=msg)


# --- counters ----------------------------------------------------------------

def ctr(name: str, **labels) -> float:
    """Sum of the registry's `name` counters whose labels include `labels`."""
    from garage_tpu.utils.metrics import registry

    want = set(labels.items())
    return sum(
        v for (n, lbl), v in registry.counters.items()
        if n == name and want <= set(lbl)
    )


def codec_blocks(op: str) -> dict:
    """`block_codec_blocks_total{op}` so far, by the path that served them."""
    return {p: ctr("block_codec_blocks_total", op=op, path=p) for p in ("tpu", "numpy")}


def since(before: dict, now: dict) -> dict:
    return {k: now[k] - before[k] for k in before}


class HostStrain:
    """What the shared event loop and the RPC plane went through in a
    phase: the worst loop stall seen by a 20 ms ticker, RPC calls that
    timed out, peer breakers that opened.  All 11 nodes share ONE loop
    here, so one node's work delays every other node's answers."""

    def __init__(self):
        self.max_lag = 0.0
        self._task = asyncio.get_running_loop().create_task(self._tick())
        self._base = self._counters()

    async def _tick(self) -> None:
        while True:
            t0 = time.perf_counter()
            await asyncio.sleep(0.02)
            self.max_lag = max(self.max_lag, time.perf_counter() - t0 - 0.02)

    @staticmethod
    def _counters() -> dict:
        return {
            "rpc_timeouts": ctr("rpc_timeout_counter"),
            "breaker_opens": ctr("rpc_breaker_transition_counter", to="open"),
        }

    def phase(self) -> dict:
        """Strain since the last call."""
        now = self._counters()
        out = {**since(self._base, now), "max_loop_stall_secs": round(self.max_lag, 3)}
        self._base, self.max_lag = now, 0.0
        return out

    async def stop(self) -> None:
        self._task.cancel()
        await asyncio.gather(self._task, return_exceptions=True)


def decode_lane_flushes() -> dict:
    """Decode-lane dispatches so far, by flush reason."""
    return {
        f: ctr("block_codec_batch_decode_dispatch_total", flush=f)
        for f in ("full", "linger")
    }


def device_dispatches(platform: str) -> dict:
    """kernel -> [dispatches, host-clock seconds inside them] on `platform`."""
    from garage_tpu.utils.metrics import registry

    return {
        dict(lbl)["kernel"]: [int(cnt), round(total, 2)]
        for (name, lbl), (cnt, total, _b) in sorted(registry.durations.items())
        if name == "tpu_codec_dispatch_duration" and dict(lbl).get("platform") == platform
    }


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


# --- oracles -----------------------------------------------------------------

def host_blake3_rows(rows: np.ndarray) -> np.ndarray:
    from garage_tpu import _native
    from garage_tpu.ops.blake3_ref import blake3

    got = _native.blake3_batch(np.ascontiguousarray(rows))
    if got is not None:
        return np.asarray(got)
    return np.stack(
        [np.frombuffer(blake3(bytes(r)), dtype=np.uint8) for r in rows]
    )


def check_kernel_against_oracle(s: int, rng, on_chip: bool) -> None:
    """One fused encode+hash dispatch and one reconstruct dispatch of a
    bare EcTpu, compared with the numpy LUT oracle and the host BLAKE3 —
    BEFORE the cluster exists, so a wrong kernel is not met as a failed
    GET."""
    import jax

    from garage_tpu.ops import gf
    from garage_tpu.ops.ec_tpu import EcTpu, ec_encode_hash_fn

    ec = EcTpu(K, M)
    b = 8
    data = rng.integers(0, 256, size=(b, K, s), dtype=np.uint8)
    parity, hashes = ec.encode_and_hash(data)
    need(hashes is not None, "fused encode+hash returned no hashes")
    need(parity.shape == (b, M, s) and hashes.shape == (b, K + M, 32), "fused dispatch returned wrong shapes")
    pmat = gf.cauchy_parity_matrix(K, M)
    for i in range(b):
        want = gf.apply_matrix_ref(pmat, data[i])
        need(np.array_equal(parity[i], want), f"parity of block {i} differs from the numpy oracle")
    shards = np.concatenate([data, parity], axis=1)
    want_h = host_blake3_rows(shards.reshape(b * (K + M), s)).reshape(b, K + M, 32)
    need(np.array_equal(hashes, want_h), "device BLAKE3 differs from the host BLAKE3")
    # reconstruct: lose data shard 2 and parity 9, rebuild shard 2
    present = [i for i in range(K + M) if i not in (2, 9)][:K]
    rec = ec.reconstruct(shards[:, present, :], present, [2])
    need(np.array_equal(rec[:, 0, :], data[:, 2, :]), "reconstruct differs from the lost shard")
    rmat = gf.reconstruction_matrix(K, M, present, [2])
    need(np.array_equal(rec[0], gf.apply_matrix_ref(rmat, shards[0, present, :])), "reconstruct differs from the numpy oracle")
    text = ec_encode_hash_fn(None, None, s).lower(
        jax.ShapeDtypeStruct((8 * M, 8 * K), np.uint8),
        jax.ShapeDtypeStruct((b, K, s), np.uint8),
    ).as_text()
    has_kernel = "tpu_custom_call" in text
    if on_chip:
        need(has_kernel, "lowered fused encode holds no tpu_custom_call")
    say("kernel_check", blocks=b, shard_bytes=s, parity="oracle-exact",
        hashes="host-blake3-exact", reconstruct="oracle-exact",
        tpu_custom_call=has_kernel)


def warm_buckets(s: int, max_blocks: int, repair_blocks: int) -> dict:
    """Compile every batch bucket the served path can dispatch, so no PUT
    or GET pays a compile inside its latency: fused encode+hash for every
    bucket the batcher can flush (1..max_blocks: on a device backend
    `encode_batch_hashed` sends batches of any size to the device),
    reconstruct (r=1) for every bucket up to the repair round's
    (erasure-pattern groups can be any size; one bulk repair round is
    the whole inventory)."""
    from garage_tpu.ops.ec_tpu import EcTpu

    ec = EcTpu(K, M)
    secs = {}
    b = 1
    while b <= max(max_blocks, repair_blocks):
        x = np.zeros((b, K, s), dtype=np.uint8)
        if b <= max_blocks:
            t0 = time.perf_counter()
            ec.encode_and_hash(x)
            secs[f"encode_hash_b{b}"] = round(time.perf_counter() - t0, 3)
        t0 = time.perf_counter()
        ec.reconstruct(x, list(range(1, K + 1)), [0])
        secs[f"reconstruct_b{b}"] = round(time.perf_counter() - t0, 3)
        b *= 2
    return secs


# --- the four-chip phase ------------------------------------------------------

def four_chip_phase(seed: int, s: int, n_blocks: int, rehearse: bool) -> None:
    """EcTpu encode + reconstruct through the 4-device shard_map mesh,
    against the single-device result and the numpy oracle.  Nothing else."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from garage_tpu.ops import gf
    from garage_tpu.ops.ec_tpu import EcTpu, ec_apply_fn_mesh

    n = 4
    rng = np.random.default_rng(seed)
    data = rng.integers(0, 256, size=(n_blocks, K, s), dtype=np.uint8)
    mesh_ec = EcTpu(K, M, n_devices=n)
    one_ec = EcTpu(K, M, n_devices=1)
    # on the chip the body is chosen by platform (the Pallas kernel); a
    # CPU rehearsal pins it, so that the kernel (interpreted) is what
    # shard_map wraps there too
    impl = mesh_ec._impl = one_ec._impl = "pallas_int8" if rehearse else None
    before = ctr("tpu_mesh_engaged_total", devices=str(n))

    # the sharding of the mesh program's own output, on device
    fn, mesh = ec_apply_fn_mesh(None, impl, n)
    xd = jax.device_put(jnp.asarray(data), NamedSharding(mesh, P("blocks")))
    out_dev = fn(mesh_ec._enc_bitmat, xd)
    devs = {sh.device for sh in out_dev.addressable_shards}
    rows = {sh.data.shape[0] for sh in out_dev.addressable_shards}
    need(len(devs) == n, f"output spans {len(devs)} devices, not {n}")
    need(rows == {n_blocks // n}, f"rows per device {rows}, want {n_blocks // n}")
    parity_dev = np.asarray(out_dev)

    t0 = time.perf_counter()
    parity_mesh = mesh_ec.encode(data)
    t_mesh = time.perf_counter() - t0
    parity_one = one_ec.encode(data)
    need(np.array_equal(parity_mesh, parity_one), "mesh encode != single-device encode")
    need(np.array_equal(parity_mesh, parity_dev), "EcTpu's mesh encode != the mesh program's own output")
    pmat = gf.cauchy_parity_matrix(K, M)
    for i in range(0, n_blocks, max(1, n_blocks // 16)):
        need(np.array_equal(parity_mesh[i], gf.apply_matrix(pmat, data[i])),
             f"mesh parity of block {i} differs from the numpy oracle")

    shards = np.concatenate([data, parity_mesh], axis=1)
    present = [i for i in range(K + M) if i not in (1, 6, 10)]
    want = [1, 6]
    rec_mesh = mesh_ec.reconstruct(shards[:, present, :], present, want)
    rec_one = one_ec.reconstruct(shards[:, present, :], present, want)
    need(np.array_equal(rec_mesh, rec_one), "mesh reconstruct != single-device reconstruct")
    need(np.array_equal(rec_mesh, data[:, want, :]), "mesh reconstruct != the lost shards")
    rmat = gf.reconstruction_matrix(K, M, present, want)
    need(np.array_equal(rec_mesh[0], gf.apply_matrix(rmat, shards[0, present, :])), "mesh reconstruct differs from the numpy oracle")

    engaged = ctr("tpu_mesh_engaged_total", devices=str(n)) - before
    need(engaged >= 2, f"tpu_mesh_engaged_total rose by {engaged}, want >= 2")
    say("four_chip", blocks=n_blocks, shard_bytes=s, devices=len(devs),
        rows_per_device=n_blocks // n, mesh_engaged=engaged,
        encode="single-device-exact, oracle-exact",
        reconstruct="single-device-exact, oracle-exact",
        first_mesh_encode_secs=round(t_mesh, 3))


# --- the cluster ---------------------------------------------------------------

NODE_TOML = """\
metadata_dir = "{root}/n{i}/meta"
data_dir = "{root}/n{i}/data"
db_engine = "sqlite"
replication_mode = "ec:{k}:{m}"
rpc_bind_addr = "127.0.0.1:0"
rpc_secret = "{secret}"
{extra_top}
[tpu]
enable = true

[admin]
canary_enabled = false
event_loop_watchdog_threshold_msec = 0
{extra_sections}
"""


async def start_cluster(root: str, rehearse_block_size: int | None):
    """11 nodes, each built as run_server builds one: a parsed config ->
    Garage(config) -> start() -> spawn_workers(); full mesh + layout as
    tests/test_ec_cluster.py does it."""
    from garage_tpu.cli.admin_rpc import AdminRpcHandler
    from garage_tpu.model.garage import Garage
    from garage_tpu.rpc.layout.types import NodeRole
    from garage_tpu.utils.config import read_config

    extra_top, extra_sections = "", ""
    if rehearse_block_size is not None:
        # rehearsal only: a small block, and the device code path forced
        # (on a CPU `auto` would pick the native host codec)
        extra_top = f"block_size = {rehearse_block_size}"
        extra_sections = '\n[block]\nbatch_impl = "xla"\n'
    garages = []
    for i in range(N_NODES):
        os.makedirs(f"{root}/n{i}")
        path = f"{root}/n{i}/garage.toml"
        with open(path, "w") as f:
            f.write(NODE_TOML.format(
                root=root, i=i, k=K, m=M, secret="c5" * 32,
                extra_top=extra_top, extra_sections=extra_sections,
            ))
        garages.append(Garage(read_config(path)))
    for g in garages:
        await g.start()
        AdminRpcHandler(g)
    for i, gi in enumerate(garages):
        for gj in garages[i + 1:]:
            await gj.netapp.connect(gi.netapp.bind_addr, gi.node_id)
    for _ in range(200):
        await asyncio.sleep(0.05)
        if all(len(g.system.peering.connected_peers()) == N_NODES - 1 for g in garages):
            break
    else:
        raise RuntimeError("full mesh did not close")
    lm = garages[0].layout_manager
    for i, g in enumerate(garages):
        lm.stage_role(g.node_id, NodeRole(zone=f"dc{i}", capacity=10**12))
    lm.apply_staged()
    for _ in range(200):
        await asyncio.sleep(0.05)
        if all(g.layout_manager.digest() == lm.digest() for g in garages):
            break
    else:
        raise RuntimeError("layout did not converge")
    for g in garages:
        g.spawn_workers()
    # a new layout version starts every table's anti-entropy round against
    # every peer (and resync's); a node marks the version synced once all
    # of them ran clean.  Traffic starts when every node's sync tracker
    # covers the version on every node — the `cluster transition` view
    t0 = time.perf_counter()
    def synced(g) -> bool:
        h = g.layout_manager.history
        return all(h.sync.get(o.node_id) >= h.current().version for o in garages)

    while not all(synced(g) for g in garages):
        if time.perf_counter() - t0 > 600:
            raise RuntimeError("layout transition did not close in 600 s")
        await asyncio.sleep(0.25)
    say("layout_synced", secs=round(time.perf_counter() - t0, 2))
    return garages


def piece_files(garage) -> dict[tuple[bytes, int], str]:
    """(block hash, piece index) -> path, for every piece in a node's data dir."""
    out = {}
    for dd in garage.config.data_dir:
        for dirpath, _dirs, files in os.walk(dd.path):
            for fn in files:
                stem, dot, ext = fn.partition(".p")
                if dot and len(stem) == 64 and ext.isdigit():
                    out[(bytes.fromhex(stem), int(ext))] = os.path.join(dirpath, fn)
    return out


def read_file(path: str) -> bytes:
    with open(path, "rb") as f:
        return f.read()


async def cluster_phase(args, rng, block_size: int) -> dict:
    from garage_tpu.api.s3.api_server import S3ApiServer
    from garage_tpu.api.s3.client import S3Client
    from garage_tpu.block.manager import stored_piece_parts
    from garage_tpu.ops import gf

    root = tempfile.mkdtemp(prefix="garage_chip_smoke_")
    garages, servers, clients, strain = [], [], [], None
    t_phase = time.perf_counter()
    try:
        garages = await start_cluster(root, block_size if args.rehearse else None)
        need(all(g.config.block_size == block_size for g in garages), "a node did not take the block size")
        need(all(g.block_manager.codec._tpu is not None for g in garages), "a node built no EcTpu")
        for idx in (S3_A, S3_B):
            srv = S3ApiServer(garages[idx])
            await srv.start("127.0.0.1", 0)
            servers.append(srv)
        key = await garages[S3_A].helper.create_key("chip-smoke")
        key.params().allow_create_bucket.update(True)
        await garages[S3_A].key_table.insert(key)
        ca, cb = (
            S3Client(f"http://127.0.0.1:{s.runner.addresses[0][1]}", key.key_id, key.secret())
            for s in servers
        )
        clients += [ca, cb]
        await ca.create_bucket("smoke")
        strain = HostStrain()
        say("cluster_up", nodes=N_NODES, mode=f"ec:{K}:{M}", block_size=block_size,
            s3_nodes=[S3_A, S3_B], secs=round(time.perf_counter() - t_phase, 2))

        # --- load: distinct seeded bodies (one body reused dedupes to one hash)
        obj_blocks, n_objs, mp_blocks, mp_parts = args.obj_blocks, args.objects, args.mp_blocks, 4
        bodies = {
            f"obj-{i:03d}": rng.bytes(obj_blocks * block_size) for i in range(n_objs)
        }
        mp_body = rng.bytes(mp_blocks * block_size)
        n_blocks = n_objs * obj_blocks + mp_blocks
        enc0 = codec_blocks("encode")
        t0 = time.perf_counter()
        await asyncio.gather(*[ca.put_object("smoke", k, v) for k, v in bodies.items()])
        # one multipart upload through the OTHER frontend, parts concurrent
        uid = await cb.create_multipart_upload("smoke", "multipart.bin")
        part = len(mp_body) // mp_parts
        etags = await asyncio.gather(*[
            cb.upload_part("smoke", "multipart.bin", uid, n + 1, mp_body[n * part:(n + 1) * part])
            for n in range(mp_parts)
        ])
        await cb.complete_multipart_upload(
            "smoke", "multipart.bin", uid, [(n + 1, e) for n, e in enumerate(etags)]
        )
        t_put = time.perf_counter() - t0
        enc = since(enc0, codec_blocks("encode"))
        total_bytes = sum(map(len, bodies.values())) + len(mp_body)
        say("put", objects=n_objs + 1, blocks=n_blocks, bytes=total_bytes,
            host_clock_secs=round(t_put, 2), encode_blocks_tpu=enc["tpu"],
            encode_blocks_host=enc["numpy"],
            tpu_share=round(enc["tpu"] / max(1.0, enc["tpu"] + enc["numpy"]), 4),
            host=strain.phase())
        expect(enc["tpu"] + enc["numpy"] == n_blocks, f"encode counters {enc} do not add up to {n_blocks}")
        expect(enc["tpu"] >= n_blocks / 2,
               f"only {enc['tpu']} of {n_blocks} blocks were encoded on the device path")

        # --- read back every acknowledged PUT through the other frontend
        everything = {**bodies, "multipart.bin": mp_body}

        # GETs run GET_CONCURRENCY at a time.  A GET prefetches 8 blocks and
        # a peer answers one GET's piece requests in order, so a response
        # header waits behind (8 x concurrent GETs) piece streams.  At 25
        # concurrent GETs this in-process cluster (11 nodes, one event loop)
        # kept headers waiting past the peer-health plane's adaptive timeout
        # (1 s floor): 1100-1635 RPC timeouts and 14-20 breaker opens in a
        # healthy read-back, and two runs in three lost a GET to open
        # breakers.  At 3 there are none, and the read-back is faster.
        gate = asyncio.Semaphore(GET_CONCURRENCY)

        async def get_exact(names, client_of) -> int:
            async def one(k):
                async with gate:
                    got = await client_of(k).get_object("smoke", k)
                need(got == everything[k], f"{k}: GET differs from what was PUT")
                return len(got)
            return sum(await asyncio.gather(*[one(k) for k in names]))

        t0 = time.perf_counter()
        n = await get_exact(everything, lambda k: ca if k == "multipart.bin" else cb)
        say("read_back", bytes=n, exact=True, via="the other S3 node",
            host_clock_secs=round(time.perf_counter() - t0, 2), host=strain.phase())

        # --- every piece of every block lands (PUT acks at quorum; the
        # leftover sends finish in the background)
        for _ in range(600):
            counts = [len(piece_files(g)) for g in garages]
            if all(c == n_blocks for c in counts):
                break
            await asyncio.sleep(0.1)
        else:
            raise RuntimeError(f"pieces per node never reached {n_blocks}: {counts}")

        # the node to lose: not a frontend, and one that holds DATA shards
        # (a node's rank is the same for every partition of this layout;
        # losing a parity rank would leave every GET systematic)
        victim_idx = next(
            i for i, g in enumerate(garages)
            if i not in (S3_A, S3_B) and all(pi < K for (_h, pi) in piece_files(g))
        )
        victim = garages[victim_idx]
        lost = {hp: read_file(p) for hp, p in piece_files(victim).items()}
        # the piece hashes the fused dispatch shipped equal the host BLAKE3
        sample = sorted(lost)[:: max(1, len(lost) // 16)]
        for hp in sample:
            _blen, phash, piece = stored_piece_parts(lost[hp])
            want = host_blake3_rows(np.frombuffer(piece, dtype=np.uint8)[None, :])[0]
            need(phash == bytes(want), "stored piece hash differs from the host BLAKE3")

        # --- degraded read: the victim's pieces are taken away
        for p in piece_files(victim).values():
            os.remove(p)
        need(all(pi < K for (_h, pi) in lost), "the victim holds a parity rank")
        # the inventory walks above ran on the loop; let the ticker book that
        # stall before the meter is reset, so it is not charged to the reads
        await asyncio.sleep(0.05)
        strain.phase()
        # Every object is read again, through the frontend that has NOT
        # read it before (its read cache cannot answer), in two passes:
        # the first with the batcher as configured, the second with the
        # live knob `codec-batch-linger-msec` raised on the frontends, so
        # that concurrent degraded GETs share a device dispatch
        # (`decode_batch` sends batches under TPU_BATCH_MIN to the host)
        names = sorted(bodies)
        passes = [
            ("as configured", None, names[: len(names) // 2]),
            ("linger raised", "1000.0", names[len(names) // 2:] + ["multipart.bin"]),
        ]
        frontends = [garages[S3_A], garages[S3_B]]
        for label, linger, keys in passes:
            saved = [g.bg_vars.get("codec-batch-linger-msec") for g in frontends]
            if linger is not None:
                for g in frontends:
                    g.bg_vars.set("codec-batch-linger-msec", linger)
            dec0 = ctr("block_codec_blocks_total", op="decode", path="reconstruct")
            rec0 = codec_blocks("reconstruct")
            lane0 = decode_lane_flushes()
            t0 = time.perf_counter()
            n = await get_exact(keys, lambda k: cb if k == "multipart.bin" else ca)
            for g, v in zip(frontends, saved):
                g.bg_vars.set("codec-batch-linger-msec", v)
            n_deg = n // block_size  # the victim held a data shard of every block
            dec = ctr("block_codec_blocks_total", op="decode", path="reconstruct") - dec0
            rec = since(rec0, codec_blocks("reconstruct"))
            lane = since(lane0, decode_lane_flushes())
            say("degraded_read", batcher=label, linger_msec=float(linger or saved[0]),
                bytes=n, exact=True, node_lost=victim_idx, blocks=n_deg, blocks_decoded=dec,
                reconstruct_blocks_tpu=rec["tpu"], reconstruct_blocks_host=rec["numpy"],
                decode_lane_dispatches=lane, host_clock_secs=round(time.perf_counter() - t0, 2),
                host=strain.phase())
            # (not all n_deg: the victim's resync worker heals pieces meanwhile)
            expect(dec > 0, f"no degraded decode in {n_deg} blocks missing a data shard")
            if linger is not None and not args.rehearse:
                # not in a rehearsal: blocks trickle out of the CPU-emulated
                # cluster too slowly to coalesce reliably
                expect(rec["tpu"] > 0, "no degraded GET was reconstructed on the device path")

        # --- repair: rebuild the lost pieces on the victim
        rec0 = codec_blocks("reconstruct")
        t0 = time.perf_counter()
        hashes = sorted({h for (h, _pi) in lost})
        # the victim's own resync worker heals in the background what reads
        # and its queue point it at; take that away again, so that the
        # batched repair is what rebuilds every piece (no await between
        # this and bulk_reconstruct's own inventory)
        healed = piece_files(victim)
        for p in healed.values():
            os.remove(p)
        rebuilt = await victim.block_manager.bulk_reconstruct(hashes)
        rec = since(rec0, codec_blocks("reconstruct"))
        need(rebuilt == len(lost), f"rebuilt {rebuilt} of {len(lost)} lost pieces")
        expect(rec["tpu"] > 0, "repair did not reconstruct on the device path")
        now = piece_files(victim)
        need(set(now) == set(lost), "the victim's piece inventory differs from before the loss")
        for hp, p in now.items():
            need(read_file(p) == lost[hp], "a rebuilt piece file differs from the one lost")
        others = [piece_files(g) for i, g in enumerate(garages) if i != victim_idx]
        for (h, rank) in sample:
            have = {}
            for pf in others:
                for (h2, pi), p in pf.items():
                    if h2 == h:
                        have[pi] = stored_piece_parts(read_file(p))[2]
            present = sorted(have)[:K]
            shards = np.stack([np.frombuffer(have[i], dtype=np.uint8) for i in present])
            want = gf.apply_matrix(gf.reconstruction_matrix(K, M, present, [rank]), shards)[0]
            _blen, phash, piece = stored_piece_parts(read_file(now[(h, rank)]))
            need(piece == bytes(want), "rebuilt piece differs from the numpy oracle")
            need(phash == bytes(host_blake3_rows(want[None, :])[0]), "rebuilt piece's stored hash differs from the host BLAKE3")
        say("repair", pieces_rebuilt=rebuilt, healed_by_resync_before=len(healed), exact="all files equal the lost ones",
            oracle_checked=len(sample), reconstruct_blocks_tpu=rec["tpu"],
            reconstruct_blocks_host=rec["numpy"],
            host_clock_secs=round(time.perf_counter() - t0, 2), host=strain.phase())
        return {"blocks": n_blocks, "bytes": total_bytes}
    finally:
        if strain is not None:
            if sys.exc_info()[0] is not None:
                say("host_at_failure", **strain.phase())
            await strain.stop()
        for c in clients:
            await c.close()
        for s in servers:
            await s.stop()
        for g in garages:
            await g.stop()
        shutil.rmtree(root, ignore_errors=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=25)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny CPU rehearsal: no platform check, no ok line")
    args = ap.parse_args()

    import jax

    from garage_tpu.utils.compile_cache import enable_persistent_cache

    devs = jax.devices()
    platform, kind, count = devs[0].platform, devs[0].device_kind, len(devs)
    on_chip = platform == "tpu"
    if not on_chip and not args.rehearse:
        print(f"[smoke] no accelerator: jax {jax.__version__} found "
              f"{count} x {platform} ({kind})", flush=True)
        return 2
    if count < args.chips:
        print(f"[smoke] --chips {args.chips} needs {args.chips} devices, found {count}", flush=True)
        return 2
    events = PersistentCacheEvents()
    cache_dir = enable_persistent_cache()
    say("device", platform=platform, kind=kind, count=count, jax=jax.__version__,
        compile_cache_dir=cache_dir or "(off on a host backend)", rehearse=args.rehearse)

    rng = np.random.default_rng(args.seed)
    if args.rehearse:
        block_size, args.objects, args.obj_blocks, args.mp_blocks = 16384, 8, 4, 16
        four_blocks = 16
    else:
        block_size, args.objects, args.obj_blocks, args.mp_blocks = 1 << 20, 24, 8, 64
        four_blocks = 256
    s = block_size // K
    t_start = time.perf_counter()

    if args.chips == 4:
        four_chip_phase(args.seed, s, four_blocks, args.rehearse)
    else:
        from garage_tpu.ops import telemetry

        check_kernel_against_oracle(s, rng, on_chip)
        t0 = time.perf_counter()
        warm = warm_buckets(s, 64, args.objects * args.obj_blocks + args.mp_blocks)
        say("warm_up", setup_secs=round(time.perf_counter() - t0, 2), per_bucket=warm,
            persistent_cache_hits=events.hits, persistent_cache_misses=events.misses)
        misses_before = events.misses
        totals = asyncio.run(cluster_phase(args, rng, block_size))
        seen = telemetry.platforms_seen()
        fused = ctr("tpu_codec_dispatch_total", kernel="ec_encode_hash", platform=platform)
        recon = ctr("tpu_codec_dispatch_total", kernel="ec_reconstruct", platform=platform)
        say("counters", platforms_seen=seen,
            device_dispatches=device_dispatches(platform),
            dispatch_host_encode=ctr("tpu_codec_dispatch_total", kernel="ec_encode_host"),
            dispatch_host_decode=ctr("tpu_codec_dispatch_total", kernel="ec_decode_host"),
            dispatch_errors=ctr("tpu_codec_dispatch_duration_errors"),
            compiles_after_warm_up=events.misses - misses_before, **totals)
        expect(platform in seen, f"{platform} never served a dispatch: {seen}")
        expect(fused > 0, f"no ec_encode_hash dispatch on {platform}")
        expect(recon > 0, f"no ec_reconstruct dispatch on {platform}")
        expect(ctr("tpu_codec_dispatch_duration_errors") == 0, "a codec dispatch raised")

    from garage_tpu.utils.metrics import registry

    cm = registry.family_merge("tpu_compile_duration")
    say("compile", events=int(cm[0]) if cm else 0,
        secs_in_compile_events=round(cm[1], 2) if cm else 0.0,
        persistent_cache_hits=events.hits, persistent_cache_misses=events.misses,
        cache_hit=events.hits > 0 and events.misses == 0,
        total_secs=round(time.perf_counter() - t_start, 2))
    if _problems:
        say("FAILED", problems=_problems)
        return 1
    if args.rehearse:
        say("rehearsal_done", note="a rehearsal is not a chip run: no ok line")
        return 0
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": kind, "count": count}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
