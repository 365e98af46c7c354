#!/usr/bin/env python3
"""Where the shared event loop's time went in one benchmark window.

    python3 script/loop_breakdown.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>
    JAX_PLATFORMS=cpu python3 script/loop_breakdown.py --rehearse --workload <cell> [--trace 0|1]
    python3 script/loop_breakdown.py ... --hold set_up+20 --for 5

Runs `benchmark/run.py` (or, with `--rehearse`, `benchmark/rehearse.py`:
tiny, on the CPU, no number of which is a measurement) unchanged, and
prints one more `[loop]` line from the registry snapshots the harness
already takes at the window's edges: the event-loop meter's counters
(utils/flight.py LoopMeter) as window deltas — busy, wait, CPU, steps,
the bracket's calibrated cost, the spans finished — the busy time by
layer, the ten largest `span` labels, beside `worker:resync:*` the
queue entries disposed of by outcome, how many of them the arrival
settled unexamined, and the loop's ms per entry, the
repair plane's labels with its scan and rounds (a round's stripes by
outcome, the stripes gathered, its seconds by phase), the connections'
loops with the messages, frames and transport writes they sent, and a
`get` entry: how the blocks that GETs streamed were served (read cache,
systematic, decoded), the pieces asked per block by rank and by why,
the hedges by outcome, how often the prefetch window had a block landed
when the streamer turned to it, the DELETE cascade's label, and the
span tree's on-loop ms per request by op (what a request's own spans,
its handlers on the other nodes among them, worked while it was open:
the connections' loops and whatever runs after the answer are not in
it), and an `rpc` entry: calls that timed out, quorum errors and retries
by endpoint, breaker transitions and fast-fails, per endpoint the
calls' p50, p99 and largest bucket, and the ms a request waited in its
own node's send queue, and a `table` entry: per table, the best-effort
background copies to non-quorum holders — messages a PUT, rows a
message, rows dropped — beside the rows a PUT carried inside its piece
`Put`s instead, the table's quorum and background calls sent, and the
copies' and the carried rows' own loop time, and a `standstill` entry: the
process's standstills by cause (the meter's clock: `host`,
`interpreter`; its long steps: `loop_held`), each episode's start in
seconds after the window opened on `perf_counter_ns` (the clock of the
`window` line's `stalls_over_500ms` and of the `.xplane.pb`), the long
steps' largest holders, RPC timeouts and 5xx answers in standstill by
endpoint and method beside all of them, the clock's mean lateness and
its thread's own CPU, and a `gc` entry: the cyclic collector's
collections and seconds by generation (utils/gc_policy.py), its own
statistics and counts, the objects frozen and tracked at the close,
each full collection's place in the window, and the long steps and
standstill episodes that overlapped one.  The
per-layer metrics of `BENCHMARK.json` read the same counters, in traced
runs only; this reads them in an untraced run too, the one the profiler
does not bend.

`--hold <line>+<s> --for <s>` stops this process with SIGSTOP for `--for`
seconds, `<s>` seconds after the run says `<line>`, from a child process
(the load generator, its other child, goes on), as
`benchmark/tests/held_up.py` stops `run.py`: a machine that stands still.
"""

from __future__ import annotations

import gc
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_DIR = os.path.join(ROOT, "benchmark")
sys.path.insert(0, BENCH_DIR)
sys.path.insert(0, ROOT)

from harness import layers  # noqa: E402 — imports nothing of the program or JAX

BUSY = "event_loop_busy_seconds_total"


def by_label(name: str, label: str, before: dict, after: dict, **where) -> dict[str, float]:
    """Window deltas of the counter family `name` (of its series whose
    labels include `where`), summed by one label's value."""
    out: dict[str, float] = {}
    for key, v in after["counters"].items():
        lbl = dict(key[1])
        if key[0] == name and where.items() <= lbl.items():
            val = lbl.get(label, "")
            out[val] = out.get(val, 0.0) + v - before["counters"].get(key, 0.0)
    return out


def rpc_entry(before: dict, after: dict, bounds: list[float]) -> dict:
    """The RPC plane's failures over the window: calls that timed out by
    endpoint, quorum errors, breaker transitions and fast-fails, retries,
    and per endpoint the calls' count with the duration histogram's p50,
    p99 and largest occupied bucket (upper bounds, ms; `inf` = past the
    last bound) — how near the small calls come to the adaptive timeout's
    1 s floor."""
    out = {
        "timeouts": by_label("rpc_timeout_counter", "endpoint", before, after),
        "errors": by_label("rpc_error_counter", "endpoint", before, after),
        "quorum_errors": by_label("rpc_quorum_error_counter", "endpoint", before, after),
        "retries": by_label("rpc_retry_counter", "endpoint", before, after),
        "stagger_launches": by_label("rpc_stagger_launch_counter", "endpoint", before, after),
        "breaker_to": by_label("rpc_breaker_transition_counter", "to", before, after),
        "breaker_fastfails": layers.delta({"counter": "rpc_breaker_fastfail_counter"}, before, after, {}),
    }
    out = {k: ({kk: vv for kk, vv in v.items() if vv} if isinstance(v, dict) else v) for k, v in out.items()}
    # the request's wait in its own node's send queue (empty where the
    # program does not count it)
    sent = by_label("rpc_calls_sent_total", "endpoint", before, after)
    wait_s = by_label("rpc_call_send_wait_seconds_total", "endpoint", before, after)
    out["send_wait_by_endpoint"] = {
        ep: {"calls": n, "ms_per_call": 1000.0 * wait_s.get(ep, 0.0) / n}
        for ep, n in sorted(sent.items(), key=lambda kv: -wait_s.get(kv[0], 0.0)) if n}
    ms = [round(b * 1000.0, 2) for b in bounds] + ["inf"]
    durations = {}
    for ep, buckets in after.get("rpc_buckets", {}).items():
        was = before.get("rpc_buckets", {}).get(ep, [0] * len(buckets))
        d = [a - b for a, b in zip(buckets, was)]
        n = sum(d)
        if not n:
            continue

        def quantile(q: float):
            acc = 0
            for i, c in enumerate(d):
                acc += c
                if acc >= q * n:
                    return ms[i]

        durations[ep] = {"n": n, "p50_le_ms": quantile(0.5), "p99_le_ms": quantile(0.99),
                         "max_le_ms": ms[max(i for i, c in enumerate(d) if c)],
                         "over_512ms": sum(c for b, c in zip(ms, d) if b == "inf" or b > 512.0)}
    out["duration_by_endpoint"] = durations
    return out


def table_entry(before: dict, after: dict, by_span: dict, puts: int) -> dict:
    """The tables' best-effort rows to non-quorum holders: per table, the
    copy messages (table/background.py), the rows they carried and the
    rows dropped (the last two None where the program does not count
    them), beside the rows that rode another message instead — a fresh
    PUT's block_ref row in its piece `Put` (None where the program does
    not carry rows) — and every remote call of the table's endpoint,
    quorum writes and reads included."""
    msgs = by_label("table_background_replicate_total", "table_name", before, after)
    rows = by_label("table_background_rows_total", "table_name", before, after)
    dropped = by_label("table_background_dropped_total", "table_name", before, after)
    carried = by_label("table_rows_carried_total", "table_name", before, after)
    calls = by_label("rpc_calls_sent_total", "endpoint", before, after)

    def per_put(n):
        return n / puts if puts and n is not None else None

    return {
        "background": {
            t: {"messages": msgs.get(t, 0.0), "messages_per_put": per_put(msgs.get(t, 0.0)),
                "rows_carried_per_put": per_put(carried.get(t)),
                "rows": rows.get(t), "rows_per_message": rows[t] / msgs[t] if msgs.get(t) and t in rows else None,
                "dropped": dropped.get(t),
                "endpoint_calls_per_put": per_put(calls.get("table/" + t, 0.0))}
            for t in sorted(set(msgs) | set(carried)) if msgs.get(t) or carried.get(t)},
        "background_loop_s": by_span.get("table/table:replicate_background"),
        "carried_loop_s": by_span.get("table/table:carried_rows"),
    }


def get_entry(before: dict, after: dict, by_span: dict, gets: int, deletes: int, op_busy_ms: dict) -> dict:
    """The healthy read path and the DELETE cascade (PR 34); a family the
    program does not count reads as nothing, never as 0."""
    def d(name: str) -> float:
        return layers.delta({"counter": name}, before, after, {})

    served = by_label("block_read_blocks_total", "served", before, after)
    by_why = by_label("block_read_pieces_total", "why", before, after)
    pieces = sum(by_why.values())
    fetched = served.get("systematic", 0.0) + served.get("decoded", 0.0)
    hedged = by_why.get("hedge", 0.0) + by_why.get("failover", 0.0)
    hits, misses = d("block_cache_hits_total"), d("block_cache_misses_total")
    landed = {"duration_sum": "s3_get_prefetch_landed"}, {"duration_count": "s3_get_prefetch_landed"}
    n_landed, n_turned = (layers.delta(t, before, after, {}) for t in landed)
    cascade_s = by_span.get("table/table:delete_cascade", 0.0)
    return {
        "gets": gets,
        "blocks_served": served or None,
        "pieces": {"by_rank": by_label("block_read_pieces_total", "rank", before, after),
                   "by_why": by_why} if pieces else None,
        "pieces_per_block": pieces / fetched if fetched else None,
        "hedged_share_pct": 100.0 * hedged / pieces if pieces else None,
        "hedges_by_outcome": by_label("block_read_hedges_total", "outcome", before, after),
        "decode_blocks_by_path": by_label("block_codec_blocks_total", "path", before, after, op="decode"),
        "cache": {"hits": hits, "misses": misses,
                  "hit_pct": 100.0 * hits / (hits + misses) if hits + misses else None},
        "prefetch_landed_share_pct": 100.0 * n_landed / n_turned if n_turned else None,
        "block_get_loop_ms_per_get": 1000.0 * by_span.get("block/block:get", 0.0) / gets if gets else None,
        # after the 204: the hooks' label, and the two insert-queue workers
        # whose only producers those hooks are
        "delete": {"deletes": deletes, "blocks_unreferenced": d("block_rc_zeroed_total"),
                   "cascade_loop_s": cascade_s,
                   "cascade_loop_ms_per_delete": 1000.0 * cascade_s / deletes if deletes else None,
                   "queue_workers_loop_s": {name: by_span.get("background/worker:queue:" + name, 0.0)
                                            for name in ("version", "block_ref")}},
        "span_tree_busy_ms_by_op": op_busy_ms,
    }


def standstill_entry(before: dict, after: dict) -> dict:
    """The process's standstills over the window (utils/flight.py
    LoopMeter): episodes by cause and each one's place in the window, the
    long steps' holders, the failures that overlapped one beside all of
    them; empty counts where the program has no clock."""
    def d(name: str, **labels) -> float:
        return layers.delta({"counter": name, "labels": labels}, before, after, {})

    t_open, t_close = before.get("t_ns"), after.get("t_ns")
    none = {"counters": {}, "durations": {}}
    held_s = by_label("event_loop_long_step_seconds_total", "span", before, after)
    held_n = by_label("event_loop_long_steps_total", "span", before, after)
    refused: dict[str, float] = {}
    for key, v in after["counters"].items():
        if key[0] == "api_s3_refused_total" and v - before["counters"].get(key, 0.0):
            lbl = dict(key[1])
            refused[f'{lbl["method"]} {lbl["cause"]} {lbl["standstill"]}'] = v - before["counters"].get(key, 0.0)
    wakes = d("process_clock_wakes_total")
    cpu = after.get("clock_cpu_ns"), before.get("clock_cpu_ns")
    return {
        "by_cause": {
            **{cause: {"n": d("process_standstills_total", cause=cause),
                       "s": d("process_standstill_seconds_total", cause=cause)}
               for cause in ("host", "interpreter")},
            "loop_held": {"n": sum(held_n.values()), "s": sum(held_s.values())},
        },
        # (seconds after the window opened, seconds, cause, the holder of a
        # loop_held step): the ring's episodes up to the close, those of
        # the preload and the pre-roll at negative seconds
        "episodes": [
            (round((s - t_open) / 1e9, 3), round((e - s) / 1e9, 3), cause, span)
            for s, e, cause, span in after.get("episodes", ()) if t_open is not None and s < t_close],
        "long_steps_top": {span: {"n": held_n[span], "s": held_s.get(span, 0.0)}
                           for span in sorted(held_n, key=lambda k: -held_s.get(k, 0.0))[:10] if held_n[span]},
        "rpc_timeouts": d("rpc_timeout_counter"),
        "rpc_timeouts_in_standstill": {
            "by_cause": {k: v for k, v in by_label("rpc_timeouts_in_standstill_total", "cause", before, after).items() if v},
            "by_endpoint": {k: v for k, v in by_label("rpc_timeouts_in_standstill_total", "endpoint", before, after).items() if v}},
        # every 5xx of the S3 frontends: "<method> <cause> <standstill>"
        "refused": refused,
        # the process's totals at the close: set-up, preload and pre-roll too
        "since_start": {
            "rpc_timeouts": layers.delta({"counter": "rpc_timeout_counter"}, none, after, {}),
            "rpc_timeouts_in_standstill": layers.delta(
                {"counter": "rpc_timeouts_in_standstill_total"}, none, after, {}),
            "refused_by_standstill": by_label("api_s3_refused_total", "standstill", none, after),
        },
        "clock_wakes": wakes,
        "clock_late_ms": 1000.0 * d("process_clock_late_seconds_total") / wakes if wakes else None,
        "clock_cpu_s": (cpu[0] - cpu[1]) / 1e9 if None not in cpu else None,
    }


def gc_entry(before: dict, after: dict, requests: int) -> dict:
    """The cyclic garbage collector over the window: collections and
    seconds by generation and ms per request (the program's hook, every
    thread; empty where the program does not count them), the
    collector's own statistics (`gc.get_stats()` deltas, read here, so
    a program without the hook has them too), the young generations'
    counts and the objects frozen and tracked at the close, each full
    collection as (seconds after the window opened, seconds), and the
    meter's episodes that overlapped one."""
    n = by_label("process_gc_collections_total", "generation", before, after)
    secs = by_label("process_gc_seconds_total", "generation", before, after)
    t_open, t_close = before.get("t_ns"), after.get("t_ns")
    full = [(s, e) for s, e in after.get("gc_full", ()) if t_open is not None and t_open <= s < t_close]
    return {
        "collections": n,
        "seconds": secs,
        "ms_per_collection": {g: 1000.0 * secs.get(g, 0.0) / k for g, k in n.items() if k},
        "ms_per_request": 1000.0 * sum(secs.values()) / requests if n and requests else None,
        "stats": [{k: a[k] - b.get(k, 0) for k in a}
                  for a, b in zip(after.get("gc_stats", ()), before.get("gc_stats", ()))],
        "count_at_close": after.get("gc_count"),
        "frozen_at_close": after.get("gc_frozen"),
        "tracked_at_close": after.get("gc_tracked"),
        "full_in_window": [(round((s - t_open) / 1e9, 3), round((e - s) / 1e9, 3)) for s, e in full],
        # (seconds after the window opened, seconds, cause, holder) of each
        # episode of the meter's ring that overlapped a full collection
        "episodes_over_a_full_collection": [
            (round((s - t_open) / 1e9, 3), round((e - s) / 1e9, 3), cause, span)
            for s, e, cause, span in after.get("episodes", ())
            if t_open is not None and t_open <= s < t_close and any(s < fe and fs < e for fs, fe in full)],
    }


def loop_line(before: dict, after: dict, ops: dict, seconds: float, bounds: list[float]) -> dict:
    def d(name: str) -> float:
        return layers.delta({"counter": name}, before, after, {})

    requests = sum(ops.values())

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
            "planned": d("repair_plan_planned_total"),
            "gathered": d("repair_round_gathered_total"),
            "stripes_by_outcome": by_label("repair_round_stripes_total", "outcome", before, after),
            "round_s_by_phase": by_label("repair_round_seconds_total", "phase", before, after),
            "ladder_steps_up": layers.delta(
                {"counter": "overload_ladder_steps_total", "labels": {"direction": "up"}}, before, after, {}),
        },
        "get": get_entry(before, after, by_span, ops.get("GET", 0), ops.get("DELETE", 0),
                         after.get("op_busy_ms", {})),
        "rpc": rpc_entry(before, after, bounds),
        "table": table_entry(before, after, by_span, ops.get("PUT", 0)),
        "standstill": standstill_entry(before, after),
        "gc": gc_entry(before, after, requests),
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


# stops process `pid` for `secs`, `after` seconds from now
_HOLD = (
    "import os, signal, sys, time\n"
    "pid, after, secs = int(sys.argv[1]), float(sys.argv[2]), float(sys.argv[3])\n"
    "time.sleep(after)\n"
    "os.kill(pid, signal.SIGSTOP)\n"
    "print(f'[hold] stopped {secs} s', file=sys.stderr, flush=True)\n"
    "time.sleep(secs)\n"
    "os.kill(pid, signal.SIGCONT)\n"
)


def _pop_option(name: str) -> str | None:
    if name not in sys.argv:
        return None
    i = sys.argv.index(name)
    value = sys.argv[i + 1]
    del sys.argv[i:i + 2]
    return value


def main() -> int:
    rehearse = "--rehearse" in sys.argv
    if rehearse:
        sys.argv.remove("--rehearse")
    hold, hold_for = _pop_option("--hold"), float(_pop_option("--for") or 5.0)
    from harness import cell as cell_mod
    from harness import cluster

    from garage_tpu.utils import flight, latency
    from garage_tpu.utils.metrics import BUCKETS, registry

    try:
        from garage_tpu.utils.gc_policy import gc_policy
    except ImportError:  # a program without the collector's policy
        gc_policy = None

    snapshot, judge, say = layers.snapshot, cell_mod._judge, cell_mod.say
    holds = {}
    for spec in (hold.split(",") if hold else []):
        line, _, after = spec.partition("+")
        holds[line] = float(after or 0)

    def say_and_hold(tag: str, **kv) -> None:
        say(tag, **kv)
        if tag in holds:
            after = holds.pop(tag)
            subprocess.Popen([sys.executable, "-c", _HOLD, str(os.getpid()), str(after), str(hold_for)],
                             start_new_session=True)
            say("hold", after_line=tag, after_s=after, stop_s=hold_for)

    def snapshot_with_spans() -> dict:
        snap = snapshot()
        # the window's edges on the clock of the meter's episodes, the ring,
        # and the standstill clock's own CPU (read from here: the program
        # pays for no extra clock read)
        snap["t_ns"] = time.perf_counter_ns()
        snap["episodes"] = list(getattr(flight.loop_meter, "episodes", ()))
        clock = getattr(flight.loop_meter, "clock", None)
        snap["clock_cpu_ns"] = (time.clock_gettime_ns(time.pthread_getcpuclockid(clock.ident))
                                if clock is not None and clock.is_alive() else None)
        snap["spans_finished"] = latency.aggregator._calls  # every finished span passes this hook
        # the collector: its statistics, the young generations' counts, the
        # frozen heap, and the program's ring of full collections
        snap["gc_stats"] = gc.get_stats()
        snap["gc_count"] = gc.get_count()
        snap["gc_frozen"] = gc.get_freeze_count()
        snap["gc_tracked"] = len(gc.get_objects())
        snap["gc_full"] = list(gc_policy.full) if gc_policy is not None else []
        # the RPC clients' duration histograms, bucket counts and all
        snap["rpc_buckets"] = {
            dict(key[1])["endpoint"]: list(v[2])
            for key, v in list(registry.durations.items()) if key[0] == "rpc_request_duration"}
        # the aggregator keeps each op's newest 256 analyses: at the closing
        # snapshot the window's, and where an op has fewer, the pre-roll's
        # and the preload's before them
        snap["op_busy_ms"] = {
            op: {"n": len(dq), "mean": sum(r["busyMs"] for r in dq) / len(dq)}
            for op, dq in latency.aggregator.recent.items() if dq}
        return snap

    def judge_and_say(cell, seed, seconds, traced, device, root, w):
        result = judge(cell, seed, seconds, traced, device, root, w)
        ops = cell_mod.summarize(w["win"]["records"], seconds)["ops"]
        cluster.say("loop", **loop_line(
            w["before"], w["after"], {op: o["n"] for op, o in ops.items()},
            w["t_close"] - w["t_win"], BUCKETS))
        return result

    layers.snapshot = snapshot_with_spans
    cell_mod._judge = judge_and_say
    cell_mod.say = say_and_hold
    if rehearse:
        import rehearse as entry
    else:
        import run as entry
    return entry.main()


if __name__ == "__main__":
    sys.exit(main())
