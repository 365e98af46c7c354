"""The `ec83-node-loss-mixed-c20` cell is files and entries: they load and
validate, the traffic is warp's `mixed` at its own 20 clients and 10 MiB
objects with `node-loss`'s fault, the configuration is
`ec83-11node-warp`'s key for key with `ec83-11node-1lost`'s failure, the
three repair readers read the program's counters and nothing where there
are none, and a CPU rehearsal of the cell at a tiny size reads `correct`
with stripes restored.

The rehearsal boots a cluster (~2 min): `python -m pytest benchmark/tests/test_node_loss_mixed_c20.py`.
"""

import os

import pytest

import rehearse
from harness import cluster, layers, loader, traffic

CELL, CONFIG, MIX = "ec83-node-loss-mixed-c20", "ec83-11node-warp-1lost", "mixed-10m-c20-loss"
NEW_METRICS = {"repair_done_pct", "repair_gather_ms_per_stripe", "repair_to_resync_pct"}


def test_the_cells_three_files_load_and_validate():
    bench = loader.benchmark()
    cell = loader.cell(CELL)
    wl, cfg, t = cell["workload"], cell["config"], traffic.validate(cell["traffic"])
    assert (wl["config"], wl["traffic"], wl["chips"]) == (CONFIG, MIX, 1)
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert len(entry["source"]) <= 200 and len(wl["why"]) <= 200 and len(entry["why"]) <= 200
    assert "warp" in entry["source"] and "BASELINE.json configs[3]" in entry["source"]
    assert set(entry["reduced"]) == set(cfg["reduced"]) == {"machines", "slo", "objects"}
    assert entry["source"] not in {c["source"] for c in bench["configs"] if c["name"] != CONFIG}

    # ec83-11node-warp's cluster, code, SLO and kept defaults key for key; its guarantees
    # and ec83-11node-1lost's two; 1lost's failure at this data scale
    base = loader.load_json(os.path.join(loader.BENCH_DIR, "configs", "ec83-11node-warp.json"))
    lost = loader.load_json(os.path.join(loader.BENCH_DIR, "configs", "ec83-11node-1lost.json"))
    for key in ("replication_mode", "k", "m", "storage_nodes", "zones", "block_size", "shard_bytes",
                "inline_threshold_bytes", "db_engine", "frontends", "code", "slo", "machines",
                "program_defaults_kept", "objects", "object_bytes", "blocks", "data"):
        assert cfg[key] == base[key], key
    g = cfg["guarantees"]
    assert g.items() >= base["guarantees"].items()
    assert {k: g[k] for k in ("reads_while_degraded", "repair")} == \
        {k: lost["guarantees"][k] for k in ("reads_while_degraded", "repair")}
    assert set(g) - set(base["guarantees"]) == {"reads_while_degraded", "repair"}
    # skipping a stripe deleted mid-round saves work; no comparison holds the program to it
    assert "outcome=deleted" in cfg["repair_efficiency"]
    assert (cfg["fault"]["lost_nodes"], cfg["fault"]["which"]) == (1, lost["fault"]["which"])
    assert cfg["fault"]["pieces_lost"] == cfg["blocks"] == 800
    assert {"traffic", "storage_nodes", "frontends"} <= set(cfg["assumed"])

    # the whole reconstruct ladder is warmed beside put-8m's seven fused buckets
    shapes = cluster.warm_shapes(cfg, t)
    put = loader.cell("ec83-put-8m")
    assert shapes["encode_hash"] == cluster.warm_shapes(put["config"], traffic.validate(put["traffic"]))["encode_hash"]
    assert shapes["reconstruct"] == [1, 2, 4, 8, 16, 32, 64, 128]

    # get_p95_ms spreads too widely here for its bound, so the cell reports goodput and
    # set-up alone, and the metrics that move the GET tail are not keyed to it
    assert {m["name"] for m in cell["end_to_end"]} == {"goodput_mb_s", "setup_s"}
    got = {m["name"] for m in cell["per_layer"]}
    assert NEW_METRICS <= got
    assert {"device_block_share_pct", "codec_roofline_pct", "batch_blocks_per_dispatch",
            "repair_pieces_per_s", "repair_scan_s", "repair_survey_loop_ms_per_stripe",
            "repair_loop_ms_per_piece", "ladder_steps_up", "resync_settled_share_pct"} <= got
    assert "dispatch_host_ms" not in got  # it moves the PUT tail, which the cell does not report
    for name in NEW_METRICS:
        m = next(m for m in bench["per_layer"] if m["name"] == name)
        assert m["workloads"] == ["ec83-node-loss", CELL]
        assert (m["layer"], m["moves"], m["source"]) == ("repair plane", "goodput_mb_s", "program_counter")
        assert name in {x["name"] for x in loader.cell("ec83-node-loss")["per_layer"]}


def test_the_traffic_is_warps_mixed_at_20_clients_with_the_node_loss():
    t = traffic.validate(loader.cell(CELL)["traffic"])
    healthy = loader.load_json(os.path.join(loader.BENCH_DIR, "traffic", "mixed-10m-c20.json"))
    node_loss = loader.load_json(os.path.join(loader.BENCH_DIR, "traffic", "node-loss.json"))
    for key, v in healthy.items():
        if key not in ("what", "why_these"):
            assert t[key] == v, key
    assert (t["clients"], t["object_bytes"], t["preload_objects"], t["preload_concurrency"]) == (20, 10485760, 80, 4)
    assert t["mix"] == {"GET": 9, "STAT": 6, "PUT": 3, "DELETE": 2} and t["key_choice"] == "uniform_own"
    assert (t["body_pool_per_client"], t["preroll_s"], t["stagger_s"], t["trace_s"]) == (6, 10.0, 5.0, 20.0)
    assert t["fault"] == node_loss["fault"]
    assert (t["fault"]["kind"], t["fault"]["repair_tranquility"]) == ("node_loss", 0)
    assert "rate" not in t


def test_the_repair_readers_read_the_programs_counters_and_nothing_where_there_are_none():
    specs = {m["name"]: m for m in loader.cell(CELL)["per_layer"]}

    def snap(counters: dict) -> dict:
        return {"counters": {(key[0], tuple(key[1:])): v for key, v in counters.items()}, "durations": {}}

    before = snap({})
    after = snap({
        ("repair_plan_planned_total",): 800.0,
        ("repair_plan_blocks_total",): 600.0,
        ("repair_round_stripes_total", ("outcome", "rebuilt")): 600.0,
        ("repair_round_stripes_total", ("outcome", "to_resync")): 40.0,
        ("repair_round_stripes_total", ("outcome", "deleted")): 50.0,
        ("repair_round_stripes_total", ("outcome", "present")): 110.0,
        ("repair_round_seconds_total", ("phase", "gather")): 32.0,
        ("repair_round_gathered_total",): 640.0,
        ("repair_round_seconds_total", ("phase", "verify")): 3.0,
    })
    ctx = {"client_ops": {"all": 300, "failed": 0}, "platform": "tpu"}
    assert layers.read(specs["repair_done_pct"], before, after, ctx) == pytest.approx(75.0)
    # the gather's wall time over the stripes whose gathers ran
    assert layers.read(specs["repair_gather_ms_per_stripe"], before, after, ctx) == pytest.approx(50.0)
    assert layers.read(specs["repair_to_resync_pct"], before, after, ctx) == pytest.approx(5.0)
    # a program that counts none of it (the parent): nothing, never 0
    for name in NEW_METRICS:
        assert layers.read(specs[name], before, before, ctx) is None


def test_a_cpu_rehearsal_of_the_cell_reads_correct_with_stripes_restored():
    r = rehearse.rehearse(CELL, traced=True, seconds=25.0)
    assert r["correct"] is True, r["checks"]
    assert all(c["ok"] for c in r["checks"].values())
    assert r["checks"]["stripes_restored"]["value"] >= 1
    assert r["checks"]["restored_pieces_wrong"]["value"] == 0
    assert r["attempted"] > 0
    # the counter metrics of the repair plane find something to read (the trace metrics need a device)
    assert NEW_METRICS <= set(r["metrics"])
    assert {"repair_pieces_per_s", "device_block_share_pct", "ladder_steps_up"} <= set(r["metrics"])
