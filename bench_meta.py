"""Metadata-plane benchmark (VERDICT r2 Missing #6): the reference's
metadata story is memory-mapped LMDB (src/db/lmdb_adapter.rs); ours is
pure-Python engines (sqlite, append-only log).  This prints the measured
numbers so that trade-off is quantified, not assumed.

Measures, per durable engine:
  - db-layer single-op insert/get ops/sec and batched-tx insert ops/sec
  - end-to-end S3 metadata ops/sec on a single-node daemon: PUT of
    INLINE objects (< 3072 B bodies never touch the block store, so a
    PUT is a pure metadata quorum write) and ListObjectsV2 keys/sec

Output: one JSON line
({"metric", "value", "unit", "vs_baseline", ...detail}).  The headline
metric is end-to-end inline-PUT ops/sec on the default engine (sqlite);
vs_baseline is against META_BASELINE_OPS (no published reference number
exists for this workload — the baseline is the round-3 measurement on
this box, so the ratio guards regressions).

Usage: python bench_meta.py [--quick]
"""

import asyncio
import json
import os
import shutil
import sys
import tempfile
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

# round-3 sqlite end-to-end inline-PUT ops/s measured on the 1-CPU bench
# box (337-499 across 150-2000 objects, converging ~370); vs_baseline =
# measured/this, so < 1.0 flags a metadata-plane regression
META_BASELINE_OPS = 330.0

N_DB_OPS = 5000
N_S3_PUTS = 600
N_LIST_KEYS = 600


def bench_db_engine(engine: str, n: int, fsync=True) -> dict:
    from garage_tpu.db import open_db

    d = tempfile.mkdtemp(prefix=f"benchmeta-{engine}-")
    try:
        db = open_db(os.path.join(d, "db"), engine=engine, fsync=fsync)
        tree = db.open_tree("bench")
        val = b"v" * 128  # typical small table entry

        t0 = time.perf_counter()
        for i in range(n):
            tree.insert(b"k%08d" % i, val)
        insert_s = time.perf_counter() - t0

        t0 = time.perf_counter()
        for i in range(n):
            assert tree.get(b"k%08d" % i) is not None
        get_s = time.perf_counter() - t0

        def batch(tx):
            for i in range(n):
                tx.insert(tree, b"b%08d" % i, val)

        t0 = time.perf_counter()
        db.transaction(batch)
        tx_insert_s = time.perf_counter() - t0

        t0 = time.perf_counter()
        cnt = sum(1 for _ in tree.iter_range())
        scan_s = time.perf_counter() - t0
        db.close()
        return {
            "insert_ops": round(n / insert_s),
            "get_ops": round(n / get_s),
            "tx_insert_ops": round(n / tx_insert_s),
            "scan_keys_per_s": round(cnt / scan_s),
        }
    finally:
        shutil.rmtree(d, ignore_errors=True)


async def bench_s3_meta(engine: str, n_puts: int, n_list: int) -> dict:
    """Single-node daemon; inline PUTs are metadata-only writes."""
    from garage_tpu.api.s3.api_server import S3ApiServer
    from garage_tpu.api.s3.client import S3Client
    from garage_tpu.model.garage import Garage
    from garage_tpu.rpc.layout.types import NodeRole
    from garage_tpu.utils.config import config_from_dict

    d = tempfile.mkdtemp(prefix=f"benchmeta-s3-{engine}-")
    try:
        cfg = config_from_dict(
            {
                "metadata_dir": os.path.join(d, "meta"),
                "data_dir": os.path.join(d, "data"),
                "db_engine": engine,
                "replication_mode": "1",
                "rpc_bind_addr": "127.0.0.1:0",
                "rpc_secret": "ab" * 32,
                "tpu": {"enable": False},
                "s3_api": {"api_bind_addr": None},
            }
        )
        g = Garage(cfg)
        await g.start()
        lm = g.layout_manager
        lm.stage_role(g.node_id, NodeRole(zone="dc0", capacity=10**12))
        lm.apply_staged()
        g.spawn_workers()
        key = await g.helper.create_key("bench")
        key.params().allow_create_bucket.update(True)
        await g.key_table.insert(key)
        s3 = S3ApiServer(g)
        await s3.start("127.0.0.1", 0)
        port = s3.runner.addresses[0][1]
        client = S3Client(f"http://127.0.0.1:{port}", key.key_id, key.secret())
        await client.create_bucket("bench")

        body = b"m" * 512  # inline (< 3072): pure metadata write
        t0 = time.perf_counter()
        for i in range(n_puts):
            await client.put_object("bench", f"obj-{i:06d}", body)
        put_s = time.perf_counter() - t0

        # make sure the listing has n_list keys to walk
        for i in range(n_puts, n_list):
            await client.put_object("bench", f"obj-{i:06d}", body)

        t0 = time.perf_counter()
        listed = 0
        token = None
        while True:
            resp = await client.list_objects_v2(
                "bench", **({"continuation_token": token} if token else {})
            )
            listed += len(resp["keys"])
            token = resp.get("next_token")
            if not token:
                break
        list_s = time.perf_counter() - t0

        await client.close()
        await s3.stop()
        await g.stop()
        return {
            "inline_put_ops": round(n_puts / put_s),
            "list_keys_per_s": round(listed / list_s),
            "listed": listed,
        }
    finally:
        shutil.rmtree(d, ignore_errors=True)


def main() -> None:
    quick = "--quick" in sys.argv
    n_db = 1000 if quick else N_DB_OPS
    n_puts = 150 if quick else N_S3_PUTS
    n_list = 150 if quick else N_LIST_KEYS

    from garage_tpu import _native

    engines = ["sqlite", "log"]
    if _native.available():
        engines.append("native")
    detail = {}
    for engine in engines:
        detail[engine] = bench_db_engine(engine, n_db)
        detail[engine].update(
            asyncio.run(bench_s3_meta(engine, n_puts, n_list))
        )
    # Relaxed-durability apples-to-apples (bounded-window semantics):
    # native group commit (C++ flusher, window ~ one fdatasync) vs sqlite
    # WAL + synchronous=NORMAL (sync at checkpoints).  The reference's
    # default posture (metadata_fsync = false on LMDB) is this class.
    if "native" in engines:
        detail["native"]["group_insert_ops"] = bench_db_engine(
            "native", n_db, fsync="group"
        )["insert_ops"]
    detail["sqlite"]["normal_insert_ops"] = bench_db_engine(
        "sqlite", n_db, fsync=False
    )["insert_ops"]

    headline = detail["sqlite"]["inline_put_ops"]
    print(
        json.dumps(
            {
                "metric": "meta_inline_put",
                "value": headline,
                "unit": "ops/s",
                "vs_baseline": round(headline / META_BASELINE_OPS, 3),
                "engines": detail,
            }
        )
    )


if __name__ == "__main__":
    main()
