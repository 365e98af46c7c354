"""The `ec83-mixed-10m-c20` cell is files and entries: they load and
validate, the traffic is warp's `mixed` at its own 20 clients and 10 MiB
objects, the loader holds the mix to the configuration's data scale, the
send-wait reader reads the program's counters and nothing where there
are none, and a CPU rehearsal of the cell at a tiny size reads
`correct`.

The rehearsal boots a cluster (~1.5 min): `python -m pytest benchmark/tests/test_mixed_10m_c20.py`.
"""

import json
import os
import shutil

import pytest

import rehearse
from harness import cluster, layers, loader, traffic

CELL, CONFIG, MIX = "ec83-mixed-10m-c20", "ec83-11node-warp", "mixed-10m-c20"
NEW_METRICS = {"rpc_send_wait_ms"}
GET_PREFETCH_DEPTH = 8  # garage_tpu/api/s3/objects.py, read there by the rehearsal test below


def test_the_cells_three_files_load_and_validate():
    bench = loader.benchmark()
    cell = loader.cell(CELL)
    wl, cfg, t = cell["workload"], cell["config"], traffic.validate(cell["traffic"])
    assert (wl["config"], wl["traffic"], wl["chips"]) == (CONFIG, MIX, 1)
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert len(entry["source"]) <= 200 and len(wl["why"]) <= 200 and len(entry["why"]) <= 200
    assert set(entry["reduced"]) == set(cfg["reduced"]) == {"machines", "slo", "objects"}
    # one public benchmark, two deployments: their sources differ
    other = next(c for c in bench["configs"] if c["name"] == "ec83-11node-512m")
    assert entry["source"] != other["source"]

    # the traffic the cell names, letter for letter
    assert (t["clients"], t["object_bytes"], t["preload_objects"], t["preload_concurrency"]) == (20, 10485760, 80, 4)
    assert t["mix"] == {"GET": 9, "STAT": 6, "PUT": 3, "DELETE": 2} and t["key_choice"] == "uniform_own"
    assert (t["body_pool_per_client"], t["readback_objects"], t["piece_check_blocks"]) == (6, 16, 64)
    assert (t["request_timeout_s"], t["preroll_s"], t["stagger_s"], t["trace_s"]) == (60, 10.0, 5.0, 20.0)
    assert "fault" not in t and "rate" not in t
    assert t["preload_objects"] == 4 * t["clients"]

    # ec83-11node-512m's cluster, code, SLO, kept defaults and guarantees key for key, and one more
    base = loader.load_json(os.path.join(loader.BENCH_DIR, "configs", "ec83-11node-512m.json"))
    for key in ("replication_mode", "k", "m", "storage_nodes", "zones", "block_size", "shard_bytes",
                "inline_threshold_bytes", "db_engine", "frontends", "code", "slo", "machines",
                "program_defaults_kept"):
        assert cfg[key] == base[key], key
    assert cfg["guarantees"].items() >= base["guarantees"].items()
    assert set(cfg["guarantees"]) - set(base["guarantees"]) == {"live_peers"}
    assert cfg["objects"] == t["preload_objects"] == 80 and cfg["object_bytes"] == t["object_bytes"]
    assert cfg["blocks"] == cfg["objects"] * cfg["object_bytes"] // cfg["block_size"] == 800
    d = cfg["data"]
    assert d["data_bytes"] == cfg["blocks"] * cfg["block_size"]
    assert d["piece_bytes_all_nodes"] == cfg["blocks"] * (cfg["k"] + cfg["m"]) * cfg["shard_bytes"]
    assert d["piece_bytes_per_node"] == d["piece_bytes_all_nodes"] // cfg["storage_nodes"] == 100 << 20
    assert d["data_bytes"] / d["read_cache_bytes_per_node"] == 6.25
    # an object is longer than the GET's prefetch window
    assert cfg["object_bytes"] // cfg["block_size"] > GET_PREFETCH_DEPTH

    # ec83-put-8m's shard length and its seven fused buckets, nothing else to compile
    put = loader.cell("ec83-put-8m")
    assert cluster.warm_shapes(cfg, t) == cluster.warm_shapes(put["config"], traffic.validate(put["traffic"]))

    # ~45 PUTs a window make the PUT p95 the third-largest sample: too wide a spread to
    # be admitted, so the cell reports goodput and the GET tail
    assert {m["name"] for m in cell["end_to_end"]} == {"goodput_mb_s", "get_p95_ms", "setup_s"}
    got = {m["name"] for m in cell["per_layer"]}
    assert NEW_METRICS <= got
    assert "dispatch_host_ms" not in got  # it moves the PUT tail, which the cell does not report
    assert {"batch_blocks_per_dispatch", "device_block_share_pct", "codec_roofline_pct",
            "resync_settled_share_pct", "get_pieces_per_block", "get_hedged_piece_share_pct",
            "get_decoded_block_share_pct", "read_cache_hit_pct", "rpc_timeouts_per_kop",
            "requests_failed_per_kop", "device_idle_pct", "ladder_steps_up"} <= got
    # not the resync entries the node-loss cell reads as null, nor the repair plane's
    assert not got & {"resync_loop_ms_per_entry", "resync_noop_share_pct", "repair_scan_s"}
    for name in NEW_METRICS:
        m = next(m for m in bench["per_layer"] if m["name"] == name)
        assert m["workloads"] == [CELL, "ec83-mixed-8m"] and m["layer"] == "RPC plane"
    assert NEW_METRICS <= {m["name"] for m in loader.cell("ec83-mixed-8m")["per_layer"]}


def test_the_send_wait_reader_reads_the_programs_counters_and_nothing_where_there_are_none():
    specs = {m["name"]: m for m in loader.cell(CELL)["per_layer"]}

    def snap(counters: dict) -> dict:
        return {"counters": {(key[0], tuple(key[1:])): v for key, v in counters.items()}, "durations": {}}

    before = snap({})
    after = snap({
        ("rpc_calls_sent_total", ("endpoint", "block/data")): 3000.0,
        ("rpc_calls_sent_total", ("endpoint", "table/object")): 1000.0,
        ("rpc_call_send_wait_seconds_total", ("endpoint", "block/data")): 30.0,
        ("rpc_call_send_wait_seconds_total", ("endpoint", "table/object")): 2.0,
    })
    ctx = {"client_ops": {"all": 500, "failed": 0}, "platform": "tpu"}
    assert layers.read(specs["rpc_send_wait_ms"], before, after, ctx) == pytest.approx(8.0)
    # calls sent that never waited: 0, a reading
    quiet = snap({("rpc_calls_sent_total", ("endpoint", "block/data")): 10.0})
    assert layers.read(specs["rpc_send_wait_ms"], before, quiet, ctx) == 0.0
    # a program that counts none of it (the parent): nothing, never 0
    for name in NEW_METRICS:
        assert layers.read(specs[name], before, before, ctx) is None


def test_the_loader_refuses_the_mix_on_a_configuration_of_another_data_scale(tmp_path):
    bench_dir = tmp_path / "benchmark"
    for sub in ("configs", "traffic", "layers"):
        shutil.copytree(os.path.join(loader.BENCH_DIR, sub), bench_dir / sub)
    bench = loader.benchmark()
    # the 512 MiB deployment states 64 objects
    bench["workloads"].append({"name": "c20-on-512m", "config": "ec83-11node-512m",
                               "traffic": MIX, "chips": 1, "why": "x"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    with pytest.raises(ValueError, match="mixed-10m-c20 preloads 80 objects, ec83-11node-512m states 64"):
        loader.cell("c20-on-512m", root=str(tmp_path), bench_dir=str(bench_dir))
    # and takes it on its own
    assert loader.cell(CELL, root=str(tmp_path), bench_dir=str(bench_dir))["config"]["objects"] == 80


def test_a_cpu_rehearsal_of_the_cell_reads_correct(tmp_path):
    from garage_tpu.api.s3.objects import GET_PREFETCH_DEPTH as depth

    assert depth == GET_PREFETCH_DEPTH
    cell = rehearse.shrink(loader.cell(CELL), str(tmp_path))
    # the tiny size keeps the cell's shape: 20 clients, objects longer than the window
    assert cell["traffic"]["clients"] == 20
    assert cell["traffic"]["object_bytes"] // cell["config"]["block_size"] > depth

    r = rehearse.rehearse(CELL, traced=True, seconds=4.0)
    assert r["correct"] is True, r["checks"]
    assert all(c["ok"] for c in r["checks"].values())
    assert r["failed"] == 0 and r["attempted"] > 0
    # every counter metric of the cell finds something to read (the trace metrics need a device)
    assert NEW_METRICS <= set(r["metrics"])
    assert {"get_pieces_per_block", "read_cache_hit_pct", "batch_blocks_per_dispatch",
            "device_block_share_pct", "s3_get_front_ms", "rpc_timeouts_per_kop"} <= set(r["metrics"])
    assert "ladder_steps_up" in r["metrics"]
