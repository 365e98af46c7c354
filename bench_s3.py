#!/usr/bin/env python3
"""S3 PUT/GET latency benchmark: erasure-coded vs replicated block store.

BASELINE.md north star: "S3 PUT p99 <= 1.2x of 3-replica mode" at the
north-star geometry — EC(8,3), 1 MiB objects (VERDICT Missing #3 wanted
exactly this configuration measured, not the ec:2:1/64 KiB proxy this
bench used to run).  Boots a 3-node replication-"3" cluster and an
11-node EC(8,3) cluster (k+m = 11 pieces need 11 storage nodes), drives
identical PUT+GET workloads through the real S3 HTTP API, and reports
client-side wall-time percentiles.

    python bench_s3.py [--objects 200] [--size 1048576] \
        [--artifact BENCH_s3_geometry.json]

Prints ONE JSON line: {"metric": "s3_put_p99_ec_over_replica", ...};
--artifact also writes it to a committed JSON file so the driver can read
the EC-vs-replica PUT p99 ratio without scraping stdout.
Runs on CPU (numpy codec) — the ratio isolates protocol overhead, which is
what the target bounds.
"""

import argparse
import asyncio
import json
import os
import sys
import tempfile

# a host-codec latency benchmark: keep jax on the CPU
os.environ.setdefault("JAX_PLATFORMS", "cpu")

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "tests"))


async def boot_bench_cluster(tmp_path, mode: str, n: int = 3, block_size: int = 65536):
    """n-node cluster + S3 server on node0 + an authorized client."""
    from test_ec_cluster import make_ec_cluster

    from garage_tpu.api.s3.api_server import S3ApiServer
    from garage_tpu.api.s3.client import S3Client

    garages = await make_ec_cluster(tmp_path, n=n, mode=mode, block_size=block_size)
    s3 = S3ApiServer(garages[0])
    await s3.start("127.0.0.1", 0)
    ep = f"http://127.0.0.1:{s3.runner.addresses[0][1]}"
    key = await garages[0].helper.create_key("bench")
    key.params().allow_create_bucket.update(True)
    await garages[0].key_table.insert(key)
    client = S3Client(ep, key.key_id, key.secret())
    return garages, s3, client


def _pct(xs: list[float], q: float) -> float:
    xs = sorted(xs)
    return xs[min(len(xs) - 1, int(q * len(xs)))]


def _meta_summary(garages) -> dict:
    """Quorum shape of the metadata plane vs the block stripe (ISSUE
    15): the artifact datum proving table quorums stay O(1) in stripe
    width — `table_nodes` is the meta-ring fan, `block_nodes` the
    stripe fan of the same partition."""
    from garage_tpu.table.replication import partition_first_hash

    rep = garages[0].object_table.replication
    h = garages[0].layout_manager.history
    fh = partition_first_hash(0)
    rf = rep.effective_rf() if hasattr(rep, "effective_rf") else None
    return {
        "rf": rf,
        "read_q": rep.read_quorum(),
        "write_q": rep.write_quorum(),
        "table_nodes": len(rep.read_nodes(fh)),
        "block_nodes": len(h.read_nodes_of(fh)),
    }


def _coalesce_counts() -> dict:
    """Cumulative insert-coalescer counters (table/coalesce.py) —
    sampled before/after the measured mix, the delta shows how many
    table RPCs the linger window saved."""
    from garage_tpu.utils.metrics import registry as reg

    merged = reg.family_merge("table_coalesce_batch_entries")
    return {
        "dispatches": int(merged[0]) if merged else 0,
        "entries": int(merged[1]) if merged else 0,
        "coalesced_entries": int(
            reg.counter_family_sum("table_coalesce_coalesced_total")
        ),
    }


def _coalesce_delta(before: dict, after: dict) -> dict:
    out = {k: after[k] - before[k] for k in before}
    out["avg_batch"] = (
        round(out["entries"] / out["dispatches"], 2)
        if out["dispatches"]
        else None
    )
    return out


def _phase_share(phases: dict | None, phase: str) -> float | None:
    """criticalPathShare: this phase's fraction of the ATTRIBUTED time."""
    if not phases:
        return None
    st = (phases.get("phases") or {}).get(phase)
    return st["share"] if st else None


def _phase_client_share(
    phases: dict | None, phase: str, client_p50_s: float | None
) -> float | None:
    """Fraction of the CLIENT-side GET p50 spent in this phase
    (phase p50 / client wall p50).  The gated index_read datum uses
    this, not criticalPathShare: once the hot-block cache serves the
    data plane in ~zero time, the critical-path denominator collapses
    to metadata+auth and the share saturates no matter how fast
    index_read gets.  The client ratio measures what the user feels,
    and — numerator and denominator carrying the same box-load noise —
    is stable across runs (0.42–0.43 over three banking runs vs
    0.54 before the meta ring)."""
    if not phases or not client_p50_s:
        return None
    st = (phases.get("phases") or {}).get(phase)
    if not st:
        return None
    return round(st["p50_ms"] / (client_p50_s * 1000.0), 4)


def _phase_summary(snap: dict | None) -> dict | None:
    """Compact per-phase stats for the artifact from a latency-X-ray
    snapshot op entry (utils/latency.py): the future pipeline PR must be
    able to prove exactly which phase it shortened."""
    if not snap:
        return None
    return {
        "coverage": snap["coverage"],
        "overlap_efficiency": snap["overlapEfficiency"],
        "wall_p50_ms": snap["wallMs"]["p50"],
        "wall_p99_ms": snap["wallMs"]["p99"],
        "phases": {
            ph: {"p50_ms": st["p50"], "p99_ms": st["p99"],
                 "share": st["criticalPathShare"]}
            for ph, st in snap["phases"].items()
        },
    }


async def run_cluster(
    tmp_path, mode: str, n_objects: int, size: int, n_nodes: int = 3,
    block_size: int = 65536, concurrency: int = 1,
) -> dict:
    import time

    from test_ec_cluster import stop_cluster

    from garage_tpu.utils import latency as latency_mod

    garages, s3, client = await boot_bench_cluster(
        tmp_path, mode, n=n_nodes, block_size=block_size
    )
    try:
        await client.create_bucket("bench")
        body = os.urandom(size)
        # warmup: worker spin-up / allocator effects must not pollute p99
        for i in range(10):
            await client.put_object("bench", f"warm{i}", body)
        # the server-side phase waterfall for THIS workload only
        latency_mod.aggregator.reset()
        co0 = _coalesce_counts()
        # exact client-side wall times: the server-side latency histograms
        # (utils/metrics.py) use log2 buckets, which quantize a p99 ratio
        # to powers of two — too coarse to check a 1.2x bound honestly
        put_times, get_times = [], []

        async def put_worker(w: int) -> None:
            # closed-loop concurrent clients sharing one connection pool:
            # each drives its slice of the keyspace back-to-back
            for i in range(w, n_objects, concurrency):
                t0 = time.perf_counter()
                await client.put_object("bench", f"o{i:05d}", body)
                put_times.append(time.perf_counter() - t0)

        await asyncio.gather(*[put_worker(w) for w in range(concurrency)])
        for i in range(0, n_objects, 4):
            t0 = time.perf_counter()
            await client.get_object("bench", f"o{i:05d}")
            get_times.append(time.perf_counter() - t0)
        return {
            "put_p50": _pct(put_times, 0.5),
            "put_p99": _pct(put_times, 0.99),
            "get_p99": _pct(get_times, 0.99),
            "phases": _phase_summary(
                latency_mod.aggregator.snapshot().get("put")
            ),
            # metadata-plane shape + coalescer work (ISSUE 15)
            "meta": {
                **_meta_summary(garages),
                "coalesce": _coalesce_delta(co0, _coalesce_counts()),
            },
        }
    finally:
        await stop_cluster(garages, [s3], [client])


async def run_bigget(tmp_path, size: int, depths: list[int]) -> dict:
    """Multi-block GET wall time vs prefetch depth (VERDICT r2 #6: a
    100 MiB GET must stream blocks back-to-back, not one round-trip per
    block).  Depth 1 reproduces the old one-ahead pipeline."""
    import time

    from test_ec_cluster import stop_cluster

    from garage_tpu.api.s3 import objects as objects_mod

    # replication "1": each block lives on exactly one node, so ~2/3 of
    # the fetches are REAL network round-trips from the serving node —
    # with "3" every block is local and there is nothing to pipeline
    garages, s3, client = await boot_bench_cluster(tmp_path, "1")
    old_depth = objects_mod.GET_PREFETCH_DEPTH
    try:
        await client.create_bucket("bench")
        await client.put_object("bench", "big", os.urandom(size))
        # simulate same-region inter-node RTT (reference benches with
        # mknet 100ms geo RTT; 2ms keeps the run short while making
        # per-block round-trips the bottleneck they are in production)
        from garage_tpu.net.fault import FaultPlan, FaultRule

        for g in garages:
            g.netapp.fault_plan = FaultPlan(0).set_rule(
                FaultRule(latency_ms=2.0)
            )
        out = {}
        for d in depths:
            objects_mod.GET_PREFETCH_DEPTH = d
            times = []
            for _ in range(3):
                t0 = time.perf_counter()
                got = await client.get_object("bench", "big")
                times.append(time.perf_counter() - t0)
                assert len(got) == size
            out[d] = min(times)
        return out
    finally:
        objects_mod.GET_PREFETCH_DEPTH = old_depth
        await stop_cluster(garages, [s3], [client])


async def run_read_heavy_cluster(
    tmp_path, mode: str, n_nodes: int, n_objects: int, n_reads: int,
    size: int, zipf_s: float, block_size: int, concurrency: int = 4,
) -> dict:
    """GET-dominant (90/10) zipfian workload against one cluster mode.
    Returns client-side GET/PUT percentiles, the server-side GET phase
    waterfall, and (for the EC run) what the traffic observatory saw —
    including top-K precision vs the ground-truth hot set the bench
    itself generated."""
    import random
    import time
    from collections import Counter

    from test_ec_cluster import stop_cluster

    from garage_tpu.rpc import traffic as traffic_mod
    from garage_tpu.utils import latency as latency_mod
    from garage_tpu.utils.metrics import registry

    def _read_path_counts() -> dict:
        """Cumulative read-pipeline counters (ISSUE 13): sampled before/
        after the measured mix, the delta shows what served the GETs —
        cache hits vs systematic streams vs reconstruction decodes, and
        how often hedges fired."""

        def _c(name, labels=()):
            return registry.counters.get((name, labels), 0)

        return {
            "cache_hits": _c("block_cache_hits_total"),
            "cache_misses": _c("block_cache_misses_total"),
            "decode_systematic": _c(
                "block_codec_blocks_total",
                (("op", "decode"), ("path", "systematic")),
            ),
            "decode_reconstruct": _c(
                "block_codec_blocks_total",
                (("op", "decode"), ("path", "reconstruct")),
            ),
            "hedges": {
                oc: _c("block_read_hedges_total", (("outcome", oc),))
                for oc in ("won", "lost", "failed")
            },
        }

    garages, s3, client = await boot_bench_cluster(
        tmp_path, mode, n=n_nodes, block_size=block_size
    )
    # the overload plane has its own bench (--overload); here it would
    # rewrite the workload mid-measurement (an in-process 11-node
    # cluster easily burns the default latency SLO, the ladder steps to
    # shed-write, and the 90/10 mix 503s).  Pin its signals calm — the
    # read path is what's being measured.
    for g in garages:
        if g.shedder is not None:
            g.shedder.signals = lambda consume=True: (0.0, 0.0)
        g.overload.set_shed_tier(None)
    try:
        await client.create_bucket("bench")
        body = os.urandom(size)

        async def populate(w: int) -> None:
            for i in range(w, n_objects, 8):
                await client.put_object("bench", f"o{i:05d}", body)

        await asyncio.gather(*[populate(w) for w in range(8)])

        # ground-truth zipfian access sequence, GET-dominant with a 10%
        # PUT refresh mix (same popularity law for both)
        rng = random.Random(20260804)
        weights = [1.0 / (i + 1) ** zipf_s for i in range(n_objects)]
        seq = rng.choices(range(n_objects), weights, k=n_reads)
        true_gets = Counter(i for n, i in enumerate(seq) if n % 10 != 0)

        latency_mod.aggregator.reset()
        traffic_mod.observatory.reset()
        rp0 = _read_path_counts()
        co0 = _coalesce_counts()
        get_times: list[float] = []
        put_times: list[float] = []

        async def worker(w: int) -> None:
            for n in range(w, len(seq), concurrency):
                i = seq[n]
                t0 = time.perf_counter()
                if n % 10 == 0:
                    await client.put_object("bench", f"o{i:05d}", body)
                    put_times.append(time.perf_counter() - t0)
                else:
                    await client.get_object("bench", f"o{i:05d}")
                    get_times.append(time.perf_counter() - t0)

        await asyncio.gather(*[worker(w) for w in range(concurrency)])
        await asyncio.sleep(0.05)  # trailing in-process records land

        rp1 = _read_path_counts()
        read_path = {
            k: rp1[k] - rp0[k]
            for k in (
                "cache_hits", "cache_misses",
                "decode_systematic", "decode_reconstruct",
            )
        }
        read_path["hedges"] = {
            oc: rp1["hedges"][oc] - rp0["hedges"][oc]
            for oc in rp1["hedges"]
        }
        snap = traffic_mod.observatory.snapshot()
        got = [
            o["key"] for o in snap["hotObjects"]
            if o["bucket"] == "bench"
        ][:10]
        want = {f"o{i:05d}" for i, _ in true_gets.most_common(10)}
        return {
            "get_p50": _pct(get_times, 0.5),
            "get_p99": _pct(get_times, 0.99),
            "put_p99": _pct(put_times, 0.99) if put_times else None,
            "read_path": read_path,
            "phases": _phase_summary(
                latency_mod.aggregator.snapshot().get("get")
            ),
            # metadata-plane shape + coalescer work (ISSUE 15)
            "meta": {
                **_meta_summary(garages),
                "coalesce": _coalesce_delta(co0, _coalesce_counts()),
            },
            "observatory": {
                "topk_precision": round(len(set(got) & want) / 10, 2),
                "top_objects": snap["hotObjects"][:5],
                "zipf_estimate": snap["zipfS"],
                "read_fraction": snap["readFraction"],
                "hot_bucket": (
                    snap["hotBuckets"][0]["bucket"]
                    if snap["hotBuckets"] else None
                ),
            },
        }
    finally:
        await stop_cluster(garages, [s3], [client])


async def run_overload(
    tmp_path, k: int, m: int, duration: float, slo_ms: float
) -> dict:
    """Overload mode (ISSUE 8 gate): 4x offered load against an
    11-node EC(k,m) cluster with the admission controller + shedding
    ladder live.  Measures what the overload-control plane promises:
    the lowest offered tier sheds with 503 SlowDown, admitted
    interactive p99 stays within the declared SLO, the ladder engages
    and recovers, and the canary stays live throughout.  The scenario
    itself lives in tests/overload_burst.py, shared with the slow
    acceptance test so the two harnesses cannot drift."""
    from overload_burst import (
        MAX_IN_FLIGHT,
        N_INTERACTIVE,
        N_LISTERS,
        N_WRITERS,
        p99_ms,
        run_overload_burst,
    )
    from test_ec_cluster import stop_cluster

    garages, s3, booted_client = await boot_bench_cluster(
        tmp_path, f"ec:{k}:{m}", n=k + m, block_size=65536
    )
    g0 = garages[0]
    ep = booted_client.endpoint
    clients = [booted_client]
    try:
        res = await run_overload_burst(g0, ep, duration=duration)
        clients += res["clients"]
        stats, canary = res["stats"], res["canary"]

        def tier_out(kind):
            s = stats[kind]
            offered = s["ok"] + s["shed"]
            return {
                "ok": s["ok"],
                "shed": s["shed"],
                "shed_fraction": (
                    round(s["shed"] / offered, 4) if offered else None
                ),
                "p99_ms": (
                    round(p99_ms(s["times"]), 2) if s["times"] else None
                ),
            }

        admitted_p99 = p99_ms(stats["interactive"]["times"])
        return {
            "offered_concurrency": N_INTERACTIVE + N_WRITERS + N_LISTERS,
            "max_in_flight": MAX_IN_FLIGHT,
            "duration_s": duration,
            "slo_ms": slo_ms,
            "admitted_p99_ms": (
                round(admitted_p99, 2) if admitted_p99 else None
            ),
            "tiers": {t: tier_out(t) for t in stats},
            "shed_fraction_lowest": tier_out("list")["shed_fraction"],
            "ladder_max_level": res["max_level"],
            "ladder_final_level": g0.shedder.level,
            "ladder_steps_up": g0.shedder.steps_up,
            "ladder_steps_down": g0.shedder.steps_down,
            "canary_probes": canary.probes,
            "canary_failed": canary.failed,
        }
    finally:
        await stop_cluster(garages, [s3], clients)


async def run_tenants(
    tmp_path, n_nodes: int, duration: float, key_rate: float,
) -> dict:
    """Tenant-observatory mode (ISSUE 20): the BEFORE number for ROADMAP
    item 5 (cluster-wide per-tenant budget enforcement).  Boots an
    n-node cluster with an S3 frontend on EVERY node, three well-behaved
    tenants in distinct SLO classes plus one abusive tenant, and a small
    per-node admission budget (`key_rate` tokens/s per key, burst =
    rate).  The abuser drives all n frontends flat-out; because
    admission is per NODE, every frontend grants it a full budget — the
    headline is its cluster-wide admitted consumption as a multiple of
    the single-node budget (~= n until enforcement goes cluster-wide).
    The tenant observatory must see all of it: share attribution, joined
    sheds, per-class burn, and the fairness rollup's hog verdict."""
    import time

    from test_ec_cluster import make_ec_cluster, stop_cluster

    from garage_tpu.api.s3.api_server import S3ApiServer
    from garage_tpu.api.s3.client import S3Client, S3Error
    from garage_tpu.rpc import tenant as tenant_mod
    from garage_tpu.utils.config import TenantClassConfig

    garages = await make_ec_cluster(
        tmp_path, n=n_nodes, mode="ec:2:1", block_size=65536
    )
    servers, clients = [], []
    try:
        # SLO classes BEFORE any S3 traffic so every row lands in its
        # class (config is read live; the observatory's class_resolver
        # closes over node configs)
        keys = {}
        for name in ("premium", "standard", "batch", "abuser"):
            key = await garages[0].helper.create_key(name)
            key.params().allow_create_bucket.update(True)
            await garages[0].key_table.insert(key)
            keys[name] = key
        classes = {
            "premium": TenantClassConfig(
                availability_target=99.99, latency_target_msec=250.0,
                keys=[keys["premium"].key_id],
            ),
            "standard": TenantClassConfig(
                availability_target=99.9, latency_target_msec=1000.0,
                keys=[keys["standard"].key_id],
            ),
            # the abuser rides the cheapest class alongside a
            # well-behaved batch tenant
            "batch": TenantClassConfig(
                availability_target=99.0, latency_target_msec=5000.0,
                keys=[keys["batch"].key_id, keys["abuser"].key_id],
            ),
        }
        for g in garages:
            g.config.tenants = classes
            # the ladder would shed whole tiers and swamp the per-key
            # signal this mode measures; pin it calm (same pattern as
            # --read-heavy) — the token buckets stay live
            if g.shedder is not None:
                g.shedder.signals = lambda consume=True: (0.0, 0.0)
            g.overload.set_shed_tier(None)
            # the per-bucket bucket must not be the binding constraint
            g.config.overload.bucket_rate = 100000.0
            g.config.overload.bucket_burst = 200000.0

        # an S3 frontend on EVERY node — spreading across frontends is
        # exactly the leak being measured
        eps = []
        for g in garages:
            s3 = S3ApiServer(g)
            await s3.start("127.0.0.1", 0)
            servers.append(s3)
            eps.append(f"http://127.0.0.1:{s3.runner.addresses[0][1]}")

        def mk_clients(name):
            k = keys[name]
            cs = [S3Client(ep, k.key_id, k.secret()) for ep in eps]
            clients.extend(cs)
            return cs

        tenants = {name: mk_clients(name) for name in keys}
        body = os.urandom(1024)  # inline-sized: metadata-plane ops
        for name, cs in tenants.items():
            await cs[0].create_bucket(f"t-{name}")
            await cs[0].put_object(f"t-{name}", "seed", body)

        # setup done on the default (generous) budget; now clamp the
        # per-key budget.  Knobs are read live and TokenBucket._refill
        # clamps existing levels down to the new burst on first touch.
        for g in garages:
            g.config.overload.key_rate = key_rate
            g.config.overload.key_burst = key_rate

        snap0 = tenant_mod.observatory.snapshot(top_n=64)
        ops0 = {t["id"]: t["ops"] for t in snap0["tenants"]}
        stats = {
            name: {"ok": 0, "shed": 0}
            for name in ("premium", "standard", "batch", "abuser")
        }
        stop_at = time.monotonic() + duration

        async def drive(name, client, pace: float | None, seq=None):
            i = 0
            while time.monotonic() < stop_at:
                i += 1
                try:
                    if seq is None and i % 2:
                        await client.get_object(f"t-{name}", "seed")
                    else:
                        await client.put_object(
                            f"t-{name}",
                            f"o{next(seq) if seq is not None else i:06d}",
                            body,
                        )
                    stats[name]["ok"] += 1
                except S3Error as e:
                    if e.status == 503 and e.code == "SlowDown":
                        stats[name]["shed"] += 1
                        await asyncio.sleep(0.02)
                    else:
                        raise
                if pace:
                    await asyncio.sleep(pace)

        import itertools

        abuse_seq = itertools.count()
        tasks = [
            # well-behaved: paced GET/PUT mix against node0 only, well
            # under the per-node budget
            asyncio.create_task(drive(name, tenants[name][0], 0.25, None))
            for name in ("premium", "standard", "batch")
        ] + [
            # abusive: 2 closed-loop writers against EVERY frontend
            asyncio.create_task(
                drive("abuser", tenants["abuser"][node], None, abuse_seq)
            )
            for node in range(n_nodes)
            for _ in range(2)
        ]
        await asyncio.gather(*tasks)
        await asyncio.sleep(0.05)  # trailing in-process records land

        # what the observatory saw (the module singleton is shared by
        # the in-process nodes, so its totals count each request once)
        snap = tenant_mod.observatory.snapshot(top_n=64)
        rows = {t["id"]: t for t in snap["tenants"]}

        def obs(name):
            r = rows.get(keys[name].key_id) or {}
            d_ops = r.get("ops", 0) - ops0.get(keys[name].key_id, 0)
            return {
                "ops": d_ops,
                "sheds": r.get("shed", 0),
                "class": r.get("class"),
                "burn": (r.get("burn") or {}).get("worst"),
            }

        total_run_ops = sum(
            t["ops"] - ops0.get(t["id"], 0) for t in snap["tenants"]
        )
        abuse_obs = obs("abuser")
        abuse_share = (
            round(abuse_obs["ops"] / total_run_ops, 4)
            if total_run_ops else None
        )

        # the fairness rollup as any node would serve it (shares and
        # ratios are scale-invariant, so the in-process digest overlap
        # does not distort them)
        for _ in range(2):
            for g in garages:
                await g.system.status_exchange_once()
            await asyncio.sleep(0.05)
        resp = tenant_mod.tenants_response(garages[0])

        budget = key_rate * duration + key_rate  # rate x window + burst
        admitted = stats["abuser"]["ok"]
        return {
            "n_frontends": n_nodes,
            "duration_s": duration,
            "key_rate": key_rate,
            "single_node_budget_ops": round(budget, 1),
            "consumption_multiple": round(admitted / budget, 3),
            "classes_tracked": len(classes),
            "abusive": {
                "admitted_ops": admitted,
                "sheds_client": stats["abuser"]["shed"],
                "sheds_observed": abuse_obs["sheds"],
                "observed_share": abuse_share,
                "class": abuse_obs["class"],
                "burn": abuse_obs["burn"],
            },
            "tenants": {
                name: {**stats[name], "observatory": obs(name)}
                for name in stats
            },
            "fairness": resp["cluster"]["fairness"],
            "hog": resp["cluster"].get("hog"),
        }
    finally:
        await stop_cluster(garages, servers, clients)


async def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--objects", type=int, default=200)
    ap.add_argument("--size", type=int, default=1024 * 1024)
    ap.add_argument("--ec", default="ec:8:3", help="EC geometry under test")
    ap.add_argument(
        "--block-size", type=int, default=1024 * 1024,
        help="cluster block size (north star: 1 MiB)",
    )
    ap.add_argument(
        "--artifact", help="also write the JSON result to this path"
    )
    ap.add_argument("--bigget", action="store_true")
    ap.add_argument("--big-size", type=int, default=100 * 1024 * 1024)
    ap.add_argument(
        "--overload", action="store_true",
        help="overload-control gate: 4x burst against the EC cluster "
        "with admission + shedding live (ISSUE 8)",
    )
    ap.add_argument("--duration", type=float, default=8.0,
                    help="overload mode: burst length in seconds")
    ap.add_argument(
        "--slo-ms", type=float, default=2500.0,
        help="overload mode: declared latency SLO for admitted traffic",
    )
    ap.add_argument(
        "--tenants", action="store_true",
        help="tenant-observatory gate (ISSUE 20): N tenants in distinct "
        "SLO classes, one abusive, frontends on every node — banks the "
        "abusive tenant's cluster-wide consumption multiple vs its "
        "single-node admission budget (ROADMAP item 5 before-number)",
    )
    ap.add_argument("--tenant-nodes", type=int, default=3,
                    help="tenants mode: cluster size (= S3 frontends)")
    ap.add_argument(
        "--key-rate", type=float, default=12.0,
        help="tenants mode: per-key admission tokens/s on each node "
        "(burst = rate); the single-node budget the abuser multiplies",
    )
    ap.add_argument(
        "--concurrency",
        help="sweep mode (ROADMAP item 1 prerequisite): comma-separated "
        "concurrent-client counts, e.g. 1,16,64 — runs the EC-vs-replica "
        "geometry at each level and records per-phase stats per level",
    )
    ap.add_argument(
        "--read-heavy", action="store_true",
        help="ISSUE 12: GET-dominant (90/10) zipfian workload — banks "
        "the EC-vs-replica GET p99 baseline (+ phase shares + "
        "observatory top-K) the read-path PR must beat",
    )
    ap.add_argument("--reads", type=int, default=240,
                    help="read-heavy mode: total mixed requests")
    ap.add_argument("--zipf-s", type=float, default=1.2,
                    help="read-heavy mode: key-popularity zipf exponent")
    args = ap.parse_args()

    if args.bigget:
        import pathlib

        with tempfile.TemporaryDirectory() as d:
            res = await run_bigget(pathlib.Path(d), args.big_size, [1, 8])
        speedup = res[1] / res[8] if res.get(8) else None
        print(
            json.dumps(
                {
                    "metric": "s3_get_100mib_prefetch_speedup",
                    "value": round(speedup, 3) if speedup else None,
                    "unit": "x (depth8 vs depth1)",
                    "vs_baseline": round(speedup, 3) if speedup else None,
                    "detail": {
                        "size": args.big_size,
                        "get_s_depth1": round(res[1], 3),
                        "get_s_depth8": round(res[8], 3),
                        "mib_per_s_depth8": round(
                            args.big_size / res[8] / 2**20, 1
                        ),
                    },
                }
            )
        )
        return

    import pathlib
    import re

    m = re.fullmatch(r"ec:(\d+):(\d+)", args.ec)
    if not m:
        raise SystemExit(f"bad --ec {args.ec!r}, want ec:k:m")
    k, mm = int(m.group(1)), int(m.group(2))

    if args.read_heavy:
        with tempfile.TemporaryDirectory() as d1:
            rep = await run_read_heavy_cluster(
                pathlib.Path(d1), "3", 3, args.objects, args.reads,
                args.size, args.zipf_s, args.block_size,
            )
        with tempfile.TemporaryDirectory() as d2:
            ec = await run_read_heavy_cluster(
                pathlib.Path(d2), args.ec, k + mm, args.objects,
                args.reads, args.size, args.zipf_s, args.block_size,
            )
        ratio = (
            ec["get_p99"] / rep["get_p99"]
            if rep["get_p99"] and ec["get_p99"]
            else None
        )

        def _rms(res: dict) -> dict:
            return {
                k_: round(v * 1000, 2) if v else None
                for k_, v in res.items()
                if k_ in ("get_p50", "get_p99", "put_p99")
            }

        result = {
            "metric": "s3_get_p99_ec_over_replica",
            # the committed BEFORE number for ROADMAP item 1: the
            # read-path PR targets <= 2.0 and will add the ceiling floor
            "value": round(ratio, 3) if ratio else None,
            "unit": "ratio (read-heavy zipfian, 90% GET)",
            "vs_baseline": round(2.0 / ratio, 3) if ratio else None,
            "detail": {
                "geometry": args.ec,
                "replica_nodes": 3,
                "ec_nodes": k + mm,
                "objects": args.objects,
                "reads": args.reads,
                "size": args.size,
                "block_size": args.block_size,
                "zipf_s": args.zipf_s,
                "read_fraction": 0.9,
                "replica_ms": _rms(rep),
                "ec_ms": _rms(ec),
                # what served the GETs (ISSUE 13): cache hits vs
                # systematic streams vs reconstruction, + hedge outcomes
                "read_path": {
                    "replica": rep["read_path"],
                    "ec": ec["read_path"],
                },
                "phases": {"replica": rep["phases"], "ec": ec["phases"]},
                # metadata plane (ISSUE 15): quorum node counts + the
                # index_read share of the EC GET waterfall — the datum
                # the meta-ring decoupling had to push down (~0.80
                # before), floor-gated by script/bench_diff.py
                "meta": {
                    **ec["meta"],
                    # share of the EC GET client p50 spent reading
                    # metadata (the gated datum; was 0.54 before the
                    # meta ring: index_read p50 102 ms of 190 ms)
                    "index_read_share": _phase_client_share(
                        ec["phases"], "index_read", ec["get_p50"]
                    ),
                    # continuity with the pre-meta-ring artifact's
                    # criticalPathShare (~0.82 banked): saturates on a
                    # cache-served read path, see _phase_wall_share
                    "index_read_critical_path_share": _phase_share(
                        ec["phases"], "index_read"
                    ),
                    "index_read_p50_ms": (
                        (ec["phases"].get("phases") or {}).get(
                            "index_read", {}
                        ).get("p50_ms")
                        if ec["phases"]
                        else None
                    ),
                },
                # what the observatory reported for the EC run — the
                # precision datum doubles as an end-to-end check that
                # the measurement plane sees the workload it will tune
                "observatory": ec["observatory"],
            },
        }
        line = json.dumps(result)
        print(line)
        if args.artifact:
            with open(args.artifact, "w") as f:
                f.write(line + "\n")
        return

    if args.tenants:
        with tempfile.TemporaryDirectory() as d:
            detail = await run_tenants(
                pathlib.Path(d), args.tenant_nodes, args.duration,
                args.key_rate,
            )
        mult = detail["consumption_multiple"]
        result = {
            "metric": "s3_tenant_cluster_consumption_multiple",
            # > 1.0 = the abusive tenant consumed more than its intended
            # budget by spreading across frontends (per-node admission
            # cannot see it); ~n_frontends is the worst case.  This is
            # the BEFORE number ROADMAP item 5's enforcement PR must
            # push back toward 1.0.
            "value": mult,
            "unit": f"x single-node budget ({detail['n_frontends']} frontends)",
            "vs_baseline": (
                round(mult / detail["n_frontends"], 3) if mult else None
            ),
            "detail": detail,
        }
        line = json.dumps(result)
        print(line)
        if args.artifact:
            with open(args.artifact, "w") as f:
                f.write(line + "\n")
        return

    if args.overload:
        with tempfile.TemporaryDirectory() as d:
            detail = await run_overload(
                pathlib.Path(d), k, mm, args.duration, args.slo_ms
            )
        p99 = detail["admitted_p99_ms"]
        result = {
            "metric": "s3_overload_graceful_degradation",
            # <= 1.0 means admitted interactive p99 held the declared
            # SLO while the burst was being shed
            "value": round(p99 / args.slo_ms, 3) if p99 else None,
            "unit": "admitted p99 / declared SLO",
            "vs_baseline": round(args.slo_ms / p99, 3) if p99 else None,
            "detail": {"geometry": args.ec, **detail},
        }
        line = json.dumps(result)
        print(line)
        if args.artifact:
            with open(args.artifact, "w") as f:
                f.write(line + "\n")
        return

    def _ms_of(res: dict) -> dict:
        return {
            k_: round(v * 1000, 2) if v else None
            for k_, v in res.items()
            if k_ in ("put_p50", "put_p99", "get_p99")
        }

    async def one_level(concurrency: int) -> dict:
        with tempfile.TemporaryDirectory() as d1:
            rep = await run_cluster(
                pathlib.Path(d1), "3", args.objects, args.size,
                n_nodes=3, block_size=args.block_size,
                concurrency=concurrency,
            )
        with tempfile.TemporaryDirectory() as d2:
            # EC(k,m) stores k+m distinct pieces per block -> k+m
            # storage nodes
            ec = await run_cluster(
                pathlib.Path(d2), args.ec, args.objects, args.size,
                n_nodes=k + mm, block_size=args.block_size,
                concurrency=concurrency,
            )
        ratio = (
            ec["put_p99"] / rep["put_p99"]
            if rep["put_p99"] and ec["put_p99"]
            else None
        )
        return {
            "ratio": round(ratio, 3) if ratio else None,
            "replica_ms": _ms_of(rep),
            "ec_ms": _ms_of(ec),
            "replica_phases": rep["phases"],
            "ec_phases": ec["phases"],
            # metadata plane (ISSUE 15): quorum node counts + the
            # meta_commit share of the EC PUT waterfall + what the
            # insert coalescer saved at this concurrency level
            "meta": {
                **ec["meta"],
                "meta_commit_share": _phase_share(
                    ec["phases"], "meta_commit"
                ),
            },
        }

    base_detail = {
        "geometry": args.ec,
        "replica_nodes": 3,
        "ec_nodes": k + mm,
        "objects": args.objects,
        "size": args.size,
        "block_size": args.block_size,
    }
    if args.concurrency:
        levels = [int(c) for c in args.concurrency.split(",") if c.strip()]
        per_level = {}
        for c in levels:
            per_level[str(c)] = await one_level(c)
        # headline: the HIGHEST concurrency level — that is where ROADMAP
        # item 1's <= 1.5x target is declared
        top = per_level[str(max(levels))]
        ratio = top["ratio"]
        result = {
            "metric": "s3_put_p99_ec_over_replica_sweep",
            "value": ratio,
            "unit": f"ratio @ {max(levels)} clients",
            "vs_baseline": round(1.5 / ratio, 3) if ratio else None,
            # headline meta shape = the HIGHEST concurrency level's
            # (same cluster geometry at every level; the coalescer
            # numbers are where the levels differ)
            "detail": {
                **base_detail,
                "meta": top["meta"],
                "levels": per_level,
            },
        }
    else:
        lvl = await one_level(1)
        result = {
            "metric": "s3_put_p99_ec_over_replica",
            "value": lvl["ratio"],
            "unit": "ratio",
            "vs_baseline": round(1.2 / lvl["ratio"], 3) if lvl["ratio"] else None,
            "detail": {
                **base_detail,
                "meta": lvl["meta"],
                "replica_ms": lvl["replica_ms"],
                "ec_ms": lvl["ec_ms"],
                # per-phase attribution (utils/latency.py): where the EC
                # PUT's extra milliseconds go — the datum the pipeline PR
                # must shorten, and prove it did
                "phases": {
                    "replica": lvl["replica_phases"],
                    "ec": lvl["ec_phases"],
                },
            },
        }
    line = json.dumps(result)
    print(line)
    if args.artifact:
        with open(args.artifact, "w") as f:
            f.write(line + "\n")


if __name__ == "__main__":
    asyncio.run(main())
