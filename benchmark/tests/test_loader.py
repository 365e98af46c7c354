"""A later PR adds a configuration, a traffic mix, a per-layer metric and a
cell as files and entries only: the loader finds them by name, and no code
under `harness/` knows any of the names `BENCHMARK.json` holds."""

import json
import os
import shutil

import pytest

from harness import layers, loader, traffic


def test_every_entry_of_benchmark_json_has_its_files():
    bench = loader.benchmark()
    for wl in bench["workloads"]:
        cell = loader.cell(wl["name"])
        assert cell["config"]["name"] == wl["config"]
        traffic.validate(cell["traffic"])
        assert {m["name"] for m in cell["end_to_end"]} >= {"setup_s", "goodput_mb_s"}
        assert cell["per_layer"], wl["name"]
        reported = {m["name"] for m in cell["end_to_end"]}
        assert all(m["moves"] in reported for m in cell["per_layer"]), wl["name"]
    for m in bench["per_layer"]:
        spec = loader.load_json(os.path.join(loader.BENCH_DIR, "layers", m["name"] + ".json"))
        for key in ("name", "layer", "unit", "better", "source", "moves"):
            assert spec[key] == m[key], (m["name"], key)
        assert spec["read"]["kind"] in ("ratio", "strain", "trace", "compiles")


def test_no_name_of_a_cell_config_or_metric_in_the_harness_code():
    bench = loader.benchmark()
    names = [x["name"] for key in ("configs", "workloads", "per_layer") for x in bench[key]]
    names += [w["traffic"] for w in bench["workloads"]]
    harness = os.path.join(loader.BENCH_DIR, "harness")
    for fn in sorted(os.listdir(harness)) + ["../run.py"]:
        if not fn.endswith(".py"):
            continue
        src = open(os.path.join(harness, fn)).read()
        for n in names:
            if n in ("device_idle_pct", "codec_roofline_pct", "loop_stall_max_ms", "compiles_in_window"):
                continue  # fields the trace/strain readers produce under the metric's own name
            assert f'"{n}"' not in src and f"'{n}'" not in src, (fn, n)


def test_a_new_cell_is_files_and_entries_only(tmp_path):
    root = tmp_path
    bench_dir = root / "benchmark"
    for sub in ("configs", "traffic", "layers"):
        shutil.copytree(os.path.join(loader.BENCH_DIR, sub), bench_dir / sub)
    bench = loader.benchmark()
    cfg = loader.load_json(os.path.join(loader.BENCH_DIR, "configs", "ec42-6node.json"))
    cfg.update(name="ec21-3node", replication_mode="ec:2:1", k=2, m=1, storage_nodes=3, frontends=[0, 1],
               objects=64)
    (bench_dir / "configs" / "ec21-3node.json").write_text(json.dumps(cfg))
    (bench_dir / "traffic" / "get-heavy.json").write_text(json.dumps(
        {"clients": 4, "object_bytes": 65536, "preload_objects": 64, "mix": {"GET": 19, "PUT": 1}}))
    (bench_dir / "layers" / "hedges_per_get.json").write_text(json.dumps({
        "name": "hedges_per_get", "layer": "block manager", "unit": "1/get", "better": "lower",
        "source": "program_counter", "moves": "get_p95_ms",
        "read": {"kind": "ratio", "num": [{"counter": "block_read_hedges_total"}],
                 "den": [{"client_ops": "GET"}]}}))
    bench["configs"].append({"name": "ec21-3node", "source": "x", "reduced": [], "why": "y",
                             "file": "benchmark/configs/ec21-3node.json"})
    bench["workloads"].append({"name": "ec21-get-heavy", "config": "ec21-3node",
                               "traffic": "get-heavy", "chips": 1, "why": "z"})
    bench["per_layer"].append({"name": "hedges_per_get", "unit": "1/get", "better": "lower",
                               "source": "program_counter", "layer": "block manager",
                               "moves": "get_p95_ms"})
    for m in bench["end_to_end"]:
        if m["name"] == "get_p95_ms":
            m["workloads"].append("ec21-get-heavy")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = loader.cell("ec21-get-heavy", root=str(root), bench_dir=str(bench_dir))
    assert cell["config"]["k"] == 2
    assert traffic.validate(cell["traffic"])["mix"] == {"GET": 19, "PUT": 1}
    got = {m["name"] for m in cell["per_layer"]}
    # the new metric, every keyless metric that moves something the cell reports,
    # and none of those keyed to other cells
    assert "hedges_per_get" in got and "device_idle_pct" in got and "s3_get_front_ms" in got
    assert "codec_roofline_pct" not in got and "put_fanout_ms" not in got
    spec = next(m for m in cell["per_layer"] if m["name"] == "hedges_per_get")
    before = {"counters": {("block_read_hedges_total", (("outcome", "won"),)): 2.0}, "durations": {}}
    after = {"counters": {("block_read_hedges_total", (("outcome", "won"),)): 12.0}, "durations": {}}
    assert layers.read(spec, before, after, {"client_ops": {"GET": 40}, "platform": "tpu"}) == 0.25
    # a reader that finds nothing to read returns nothing
    assert layers.read(spec, before, after, {"client_ops": {}, "platform": "tpu"}) is None
    # a mix that preloads another number of objects than the configuration states is refused
    cfg["objects"] = 65
    (bench_dir / "configs" / "ec21-3node.json").write_text(json.dumps(cfg))
    with pytest.raises(ValueError, match="states 65"):
        loader.cell("ec21-get-heavy", root=str(root), bench_dir=str(bench_dir))
