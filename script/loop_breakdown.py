#!/usr/bin/env python3
"""Where the shared event loop's time went in one benchmark window.

    python3 script/loop_breakdown.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>
    JAX_PLATFORMS=cpu python3 script/loop_breakdown.py --rehearse --workload <cell> [--trace 0|1]

Runs `benchmark/run.py` (or, with `--rehearse`, `benchmark/rehearse.py`:
tiny, on the CPU, no number of which is a measurement) unchanged, and
prints one more `[loop]` line from the registry snapshots the harness
already takes at the window's edges: the event-loop meter's counters
(utils/flight.py LoopMeter) as window deltas — busy, wait, CPU, steps,
the bracket's calibrated cost, the spans finished — the busy time by
layer, the ten largest `span` labels, beside `worker:resync:*` the
queue entries disposed of by outcome, how many of them the arrival
settled unexamined, and the loop's ms per entry, the
repair plane's labels with its scan and rounds, and the connections'
loops with the messages, frames and transport writes they sent.  The
per-layer metrics of `BENCHMARK.json` read the same counters, in traced
runs only; this reads them in an untraced run too, the one the profiler
does not bend.
"""

from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_DIR = os.path.join(ROOT, "benchmark")
sys.path.insert(0, BENCH_DIR)
sys.path.insert(0, ROOT)

from harness import layers  # noqa: E402 — imports nothing of the program or JAX

BUSY = "event_loop_busy_seconds_total"


def loop_line(before: dict, after: dict, requests: int, seconds: float) -> dict:
    def d(name: str) -> float:
        return layers.delta({"counter": name}, before, after, {})

    by_layer: dict[str, float] = {}
    by_span: dict[str, float] = {}
    for key, v in after["counters"].items():
        if key[0] != BUSY:
            continue
        dv = v - before["counters"].get(key, 0.0)
        lbl = dict(key[1])
        by_layer[lbl["layer"]] = by_layer.get(lbl["layer"], 0.0) + dv
        by_span[f'{lbl["layer"]}/{lbl["span"]}'] = dv
    busy, wait = d(BUSY), d("event_loop_wait_seconds_total")

    def dur(name: str, idx: int) -> float:
        # device dispatches only: the host codec's kernels end in `_host`
        return sum(
            v[idx] - before["durations"].get(key, (0, 0.0))[idx]
            for key, v in after["durations"].items()
            if key[0] == name and not dict(key[1])["kernel"].endswith("_host")
        )

    n_disp = dur("tpu_codec_dispatch_duration", 0)
    per_disp = 1000.0 / max(n_disp, 1)
    cpu_disp = sum(
        v - before["counters"].get(key, 0.0)
        for key, v in after["counters"].items()
        if key[0] == "tpu_codec_dispatch_cpu_seconds_total"
        and not dict(key[1])["kernel"].endswith("_host")
    )
    per_req = 1000.0 / max(requests, 1)
    # the resync workers: queue entries disposed of, by outcome, what one
    # costs the loop (block_resync_entries_total; absent before PR 28) and
    # how many the arrival settled unexamined (absent before PR 33)
    resync_s = sum(v for k, v in by_span.items() if k.startswith("background/worker:resync:"))
    entries = {
        dict(key[1])["outcome"]: v - before["counters"].get(key, 0.0)
        for key, v in after["counters"].items()
        if key[0] == "block_resync_entries_total"
    }
    n_entries = sum(entries.values())
    n_settled = d("block_resync_settled_total")
    # the repair plane (PR 29): the scan's and the rounds' share of the loop
    repair_s = {name: by_span.get("background/" + name, 0.0)
                for name in ("repair:survey", "repair:inv", "repair:queue", "worker:repair_plan")}
    surveyed, pieces = d("repair_plan_surveyed_total"), d("repair_plan_blocks_total")
    # the RPC plane's sender (PR 30): frames a message is cut into, frames a write carries
    msgs, frames, writes = d("net_messages_sent_total"), d("net_frames_sent_total"), d("net_writes_total")
    return {
        "window_s": seconds, "requests": requests,
        "busy_s": busy, "wait_s": wait, "busy_plus_wait_s": busy + wait,
        "cpu_s": d("event_loop_cpu_seconds_total"),
        "steps": d("event_loop_steps_total"),
        "meter_s": d("event_loop_meter_seconds_total"),
        "spans_finished": after.get("spans_finished", 0) - before.get("spans_finished", 0),
        "loop_ms_per_request": busy * per_req,
        "layer_s": dict(sorted(by_layer.items(), key=lambda kv: -kv[1])),
        "layer_ms_per_request": {k: v * per_req for k, v in by_layer.items()},
        "top_spans_s": dict(sorted(by_span.items(), key=lambda kv: -kv[1])[:10]),
        "resync": {
            "loop_s": resync_s,
            "entries": dict(sorted(entries.items(), key=lambda kv: -kv[1])),
            "settled": n_settled,
            "settled_share_pct": 100.0 * n_settled / n_entries if n_entries else None,
            "loop_ms_per_entry": 1000.0 * resync_s / n_entries if n_entries else None,
        },
        "repair": {
            "loop_s": repair_s, "surveyed": surveyed, "pieces": pieces,
            "rounds": d("repair_plan_rounds_total"), "scan_s": d("repair_plan_scan_seconds"),
            "survey_loop_ms_per_stripe":
                1000.0 * (repair_s["repair:survey"] + repair_s["repair:inv"]) / surveyed if surveyed else None,
            "loop_ms_per_piece": 1000.0 * repair_s["worker:repair_plan"] / pieces if pieces else None,
            "ladder_steps_up": layers.delta(
                {"counter": "overload_ladder_steps_total", "labels": {"direction": "up"}}, before, after, {}),
        },
        "net": {
            "loop_s": {name: by_span.get("rpc/" + name, 0.0) for name in ("net:send", "net:recv", "net:io")},
            "messages": msgs, "frames": frames, "writes": writes, "bytes": d("net_bytes_sent_total"),
            "frames_per_message": frames / msgs if msgs else None,
            "frames_per_write": frames / writes if writes else None,
        },
        # the device dispatch, ms each: wall = wait + copies + what is left,
        # of which the thread was on the CPU for cpu_ms (all phases together)
        "dispatch": {
            "n": n_disp,
            "wall_ms": dur("tpu_codec_dispatch_duration", 1) * per_disp,
            "device_wait_ms": dur("tpu_codec_compute_duration", 1) * per_disp,
            "copies_ms": dur("tpu_codec_transfer_duration", 1) * per_disp,
            "cpu_ms": cpu_disp * per_disp,
        },
    }


def main() -> int:
    rehearse = "--rehearse" in sys.argv
    if rehearse:
        sys.argv.remove("--rehearse")
    from harness import cell as cell_mod
    from harness import cluster

    from garage_tpu.utils import latency

    snapshot, judge = layers.snapshot, cell_mod._judge

    def snapshot_with_spans() -> dict:
        snap = snapshot()
        snap["spans_finished"] = latency.aggregator._calls  # every finished span passes this hook
        return snap

    def judge_and_say(cell, seed, seconds, traced, device, root, w):
        result = judge(cell, seed, seconds, traced, device, root, w)
        ops = cell_mod.summarize(w["win"]["records"], seconds)["ops"]
        cluster.say("loop", **loop_line(
            w["before"], w["after"], sum(o["n"] for o in ops.values()), w["t_close"] - w["t_win"]))
        return result

    layers.snapshot = snapshot_with_spans
    cell_mod._judge = judge_and_say
    if rehearse:
        import rehearse as entry
    else:
        import run as entry
    return entry.main()


if __name__ == "__main__":
    sys.exit(main())
