"""Find a cell's files by the names in `BENCHMARK.json`.

    workload  -> its entry, its configuration's file, `traffic/<traffic>.json`
    metric    -> `layers/<name>.json`

Nothing here knows a cell, a configuration or a metric by name: a later
PR adds them as files and entries, and edits no file that is there.
"""

from __future__ import annotations

import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: str = ROOT) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def cell(name: str, root: str = ROOT, bench_dir: str = BENCH_DIR, bench: dict | None = None) -> dict:
    """Everything `run.py` needs for the workload `name` (`bench`: a
    BENCHMARK.json already read, or one a test has added entries to)."""
    bench = bench or benchmark(root)
    wl = next((w for w in bench["workloads"] if w["name"] == name), None)
    if wl is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json: "
                       f"{[w['name'] for w in bench['workloads']]}")
    cfg_entry = next(c for c in bench["configs"] if c["name"] == wl["config"])
    cfg = load_json(os.path.join(root, cfg_entry["file"]))
    traffic_file = os.path.join(bench_dir, "traffic", wl["traffic"] + ".json")
    traffic = load_json(traffic_file)
    # the data scale is the configuration's to state: a mix that preloads holds it to that
    if "objects" in cfg and traffic.get("preload_objects", 0) not in (0, cfg["objects"]):
        raise ValueError(f"{wl['traffic']} preloads {traffic['preload_objects']} objects, "
                         f"{wl['config']} states {cfg['objects']}")
    e2e = [m for m in bench["end_to_end"] if "workloads" not in m or name in m["workloads"]]
    reported = {m["name"] for m in e2e}
    per_layer = []
    for m in bench["per_layer"]:
        if (name in m["workloads"]) if "workloads" in m else (m["moves"] in reported):
            per_layer.append({**m, **load_json(os.path.join(bench_dir, "layers", m["name"] + ".json"))})
    return {"workload": wl, "config": cfg, "traffic_file": traffic_file,
            "traffic": traffic, "end_to_end": e2e, "per_layer": per_layer}
