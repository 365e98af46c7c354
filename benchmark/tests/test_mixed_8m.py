"""The `ec83-mixed-8m` cell (PR 34) is files and entries: they load and
validate, the loader holds the mix to the configuration's data scale, and
a CPU rehearsal of the cell at a tiny size reads `correct`.

The rehearsal boots a cluster (~1 min): `python -m pytest benchmark/tests/test_mixed_8m.py`.
"""

import json
import os
import shutil

import pytest

import rehearse
from harness import cluster, layers, loader, traffic

CELL, CONFIG, MIX = "ec83-mixed-8m", "ec83-11node-512m", "mixed-8m"
NEW_METRICS = {"get_pieces_per_block", "get_hedged_piece_share_pct",
               "get_decoded_block_share_pct", "read_cache_hit_pct"}


def test_the_cells_three_files_load_and_validate():
    bench = loader.benchmark()
    cell = loader.cell(CELL)
    wl, cfg, t = cell["workload"], cell["config"], traffic.validate(cell["traffic"])
    assert (wl["config"], wl["traffic"], wl["chips"]) == (CONFIG, MIX, 1)
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert len(entry["source"]) <= 200 and len(wl["why"]) <= 200
    assert set(entry["reduced"]) == set(cfg["reduced"]) == {"machines", "slo", "objects"}

    # the traffic ISSUE 34 names, letter for letter
    assert (t["clients"], t["object_bytes"], t["preload_objects"], t["preload_concurrency"]) == (6, 8388608, 64, 4)
    assert t["mix"] == {"GET": 9, "STAT": 6, "PUT": 3, "DELETE": 2} and t["key_choice"] == "uniform_own"
    assert (t["body_pool_per_client"], t["readback_objects"], t["piece_check_blocks"]) == (12, 16, 64)
    assert (t["request_timeout_s"], t["preroll_s"], t["stagger_s"], t["trace_s"]) == (60, 8.0, 4.0, 20.0)
    assert "fault" not in t

    # ec83-11node's cluster, code, SLO and kept defaults key for key; its guarantees and two more
    base = loader.load_json(os.path.join(loader.BENCH_DIR, "configs", "ec83-11node.json"))
    for key in ("replication_mode", "k", "m", "storage_nodes", "zones", "block_size", "shard_bytes",
                "inline_threshold_bytes", "db_engine", "frontends", "code", "slo", "machines",
                "program_defaults_kept"):
        assert cfg[key] == base[key], key
    assert cfg["guarantees"].items() >= base["guarantees"].items()
    assert set(cfg["guarantees"]) - set(base["guarantees"]) == {"reads_beside_writes", "delete"}
    assert cfg["objects"] == t["preload_objects"] == 64
    assert cfg["blocks"] == cfg["objects"] * cfg["object_bytes"] // cfg["block_size"] == 512
    assert cfg["data"]["data_bytes"] == 4 * cfg["data"]["read_cache_bytes_per_node"]

    # ec83-put-8m's shard length and its seven fused buckets, nothing else to compile
    put = loader.cell("ec83-put-8m")
    assert cluster.warm_shapes(cfg, t) == cluster.warm_shapes(put["config"], traffic.validate(put["traffic"]))

    reports = {m["name"] for m in cell["end_to_end"]}
    assert reports == {"goodput_mb_s", "put_p95_ms", "get_p95_ms", "setup_s"}
    got = {m["name"] for m in cell["per_layer"]}
    assert NEW_METRICS <= got
    assert {"batch_blocks_per_dispatch", "device_block_share_pct", "dispatch_host_ms",
            "codec_roofline_pct", "resync_settled_share_pct", "device_idle_pct"} <= got
    # keyed to the accepted cells since this PR, and the repair plane's to its own cell
    assert not got & {"resync_loop_ms_per_entry", "resync_noop_share_pct", "repair_scan_s", "ladder_steps_up"}
    for name in ("resync_loop_ms_per_entry", "resync_noop_share_pct"):
        m = next(m for m in bench["per_layer"] if m["name"] == name)
        assert m["workloads"] == ["ec83-put-8m", "ec42-small-mixed", "ec83-node-loss"]


def test_the_new_readers_read_the_programs_counters_and_nothing_where_there_are_none():
    specs = {m["name"]: m for m in loader.cell(CELL)["per_layer"]}

    def snap(counters: dict) -> dict:
        return {"counters": {(key[0], tuple(key[1:])): v for key, v in counters.items()}, "durations": {}}

    before = snap({})
    after = snap({
        ("block_read_pieces_total", ("rank", "data"), ("why", "first")): 800.0,
        ("block_read_pieces_total", ("rank", "parity"), ("why", "hedge")): 16.0,
        ("block_read_pieces_total", ("rank", "parity"), ("why", "failover")): 4.0,
        ("block_read_blocks_total", ("served", "systematic")): 95.0,
        ("block_read_blocks_total", ("served", "decoded")): 5.0,
        ("block_read_blocks_total", ("served", "cache")): 25.0,
        ("block_cache_hits_total",): 25.0,
        ("block_cache_misses_total",): 100.0,
    })
    ctx = {"client_ops": {}, "platform": "tpu"}
    assert layers.read(specs["get_pieces_per_block"], before, after, ctx) == pytest.approx(8.2)
    assert layers.read(specs["get_hedged_piece_share_pct"], before, after, ctx) == pytest.approx(100 * 20 / 820)
    assert layers.read(specs["get_decoded_block_share_pct"], before, after, ctx) == pytest.approx(4.0)
    assert layers.read(specs["read_cache_hit_pct"], before, after, ctx) == pytest.approx(20.0)
    # a program that counts none of it (the parent of PR 34): nothing, never 0
    for name in NEW_METRICS:
        assert layers.read(specs[name], before, before, ctx) is None


def test_the_loader_refuses_the_mix_on_a_configuration_of_another_data_scale(tmp_path):
    bench_dir = tmp_path / "benchmark"
    for sub in ("configs", "traffic", "layers"):
        shutil.copytree(os.path.join(loader.BENCH_DIR, sub), bench_dir / sub)
    bench = loader.benchmark()
    # the node-loss deployment states 40 objects
    bench["workloads"].append({"name": "mixed-on-1lost", "config": "ec83-11node-1lost",
                               "traffic": MIX, "chips": 1, "why": "x"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    with pytest.raises(ValueError, match="mixed-8m preloads 64 objects, ec83-11node-1lost states 40"):
        loader.cell("mixed-on-1lost", root=str(tmp_path), bench_dir=str(bench_dir))
    # and takes it on its own
    assert loader.cell(CELL, root=str(tmp_path), bench_dir=str(bench_dir))["config"]["objects"] == 64


def test_a_cpu_rehearsal_of_the_cell_reads_correct():
    r = rehearse.rehearse(CELL, traced=True, seconds=4.0)
    assert r["correct"] is True, r["checks"]
    assert all(c["ok"] for c in r["checks"].values())
    assert r["failed"] == 0 and r["attempted"] > 0
    # every counter metric of the cell finds something to read (the trace metrics need a device)
    assert NEW_METRICS <= set(r["metrics"])
    assert {"batch_blocks_per_dispatch", "device_block_share_pct", "resync_settled_share_pct",
            "s3_get_front_ms", "s3_put_front_ms", "loop_ms_per_request"} <= set(r["metrics"])
    assert r["metrics"]["get_pieces_per_block"]["value"] >= 8.0
