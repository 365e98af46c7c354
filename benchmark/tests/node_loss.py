"""`ec83-node-loss` as ISSUE 26 specified it: out of `BENCHMARK.json` until
the repair plan restores something inside a window (PERF.md, Open questions),
kept here so that the harness's fault, checks and repair readers stay
rehearsed.  A later `benchmark` PR moves these entries into `BENCHMARK.json`."""

from harness import loader

NAME = "ec83-node-loss"


def bench_with_node_loss() -> dict:
    bench = loader.benchmark()
    bench["workloads"].append({
        "name": NAME, "config": "ec83-11node", "traffic": "node-loss", "chips": 1,
        "why": "40 x 8 MiB preloaded, a storage node's 320 pieces removed at window start, repair "
               "plan at tranquility 0 under 3 closed-loop GET clients"})
    bench["end_to_end"].append({"name": "repair_blocks_s", "unit": "blocks/s", "better": "higher",
                                "bound": 0.25, "source": "host_clock", "workloads": [NAME]})
    for m in bench["end_to_end"]:
        if m["name"] == "get_p95_ms":
            m["workloads"].append(NAME)
    for name in ("repair_host_decode_pct", "repair_blocks_per_round"):
        spec = loader.load_json(f"{loader.BENCH_DIR}/layers/{name}.json")
        bench["per_layer"].append({k: spec[k] for k in ("name", "unit", "better", "source", "layer", "moves")})
    for m in bench["per_layer"]:
        if m["name"] in ("device_block_share_pct", "codec_roofline_pct"):
            m["workloads"].append(NAME)
    return bench
