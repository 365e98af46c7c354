"""One run of one cell: set-up, the measured window, the check, the result.

`run.py` calls `run_cell` after it has found the chip.  The parent process
(this one) holds the chip and the cluster; the load generator is a child
that never imports JAX (`loadgen.py`).  Order of a run:

  set-up    child started (it makes the bodies while we go on), warm-up of
            this cell's batch buckets, cluster boot and layout sync,
            frontends, preload through S3, pieces landed, the victim
            chosen, the clients' pre-roll                  -> `setup_s`
  window    registry snapshot, clients run for --seconds, [the node loss
            and the repair plan at its start], [profiler and loop sampler
            on for the first `trace_s` seconds], snapshot at the close;
            requests sent inside the window are waited for after it
  after     device memory peak read, read-back through the other
            frontend, cluster stopped, piece files against the reference
"""

from __future__ import annotations

import asyncio
import faulthandler
import glob
import json
import math
import os
import shutil
import statistics
import sys
import tempfile
import threading
import time

from . import check, cluster, layers, roofline, traffic
from . import trace as trace_mod
from .cluster import say
from .sampler import PERIOD_S, LoopSampler

HARNESS_DIR = os.path.dirname(os.path.abspath(__file__))


class Child:
    """The load generator's process and its line protocol."""

    def __init__(self, proc):
        self.proc = proc

    @classmethod
    async def start(cls, traffic_file: str, seed: int) -> "Child":
        env = {k: v for k, v in os.environ.items() if k != "BENCH_RUN"}
        proc = await asyncio.create_subprocess_exec(
            sys.executable, os.path.join(HARNESS_DIR, "loadgen.py"), traffic_file, str(seed),
            stdin=asyncio.subprocess.PIPE, stdout=asyncio.subprocess.PIPE, env=env,
            limit=1 << 28,
        )
        return cls(proc)

    async def read(self, timeout: float) -> dict:
        line = await asyncio.wait_for(self.proc.stdout.readline(), timeout)
        if not line:
            raise RuntimeError(f"the load generator ended (exit {self.proc.returncode})")
        return json.loads(line)

    async def ask(self, cmd: str, timeout: float, **kw) -> dict:
        self.proc.stdin.write((json.dumps({"cmd": cmd, **kw}) + "\n").encode())
        await self.proc.stdin.drain()
        return await self.read(timeout)

    async def stop(self) -> None:
        if self.proc.returncode is None:
            try:
                self.proc.stdin.write(b'{"cmd": "quit"}\n')
                await self.proc.stdin.drain()
                await asyncio.wait_for(self.proc.wait(), 10)
            except (asyncio.TimeoutError, ConnectionError, OSError):
                self.proc.kill()
                await self.proc.wait()


def percentile(values: list[float], q: float) -> float:
    """Nearest-rank percentile of all values (the tail of ALL requests)."""
    vs = sorted(values)
    return vs[min(len(vs) - 1, max(0, math.ceil(q * len(vs) - 1e-9) - 1))]


def summarize(records: list, seconds: float) -> dict:
    """Per-op counts and latencies of the requests SENT inside the window
    (a failed request's latency counts as the longest of its op), and the
    goodput: payload bytes of the requests that COMPLETED inside it,
    whenever they were sent (the pre-roll's stragglers count, the window's
    own do not: a rate over all the work and all the time of the window)."""
    ops: dict[str, dict] = {}
    good_bytes = 0
    for op, start, lat, ok, nbytes in records:
        if ok and 0.0 <= start + lat <= seconds:
            good_bytes += nbytes
        if not 0.0 <= start < seconds:
            continue
        o = ops.setdefault(op, {"n": 0, "failed": 0, "lat": [], "late": 0})
        o["n"] += 1
        o["lat"].append((lat, ok))
        if not ok:
            o["failed"] += 1
        elif start + lat > seconds:
            o["late"] += 1
    for o in ops.values():
        longest = max(l for l, _ok in o["lat"])
        o["lat_ms"] = [(l if ok else longest) * 1000.0 for l, ok in o["lat"]]
        del o["lat"]
    return {"ops": ops, "goodput_mb_s": good_bytes / 1e6 / seconds}


def in_step(records: list, seconds: float) -> dict:
    """Whether the closed loop's clients ran in step: the coefficient of
    variation of the gaps between successive completions inside the window
    (about 1 where completions come at random, well above where they come in
    bursts), and for a window of few requests each one's start and latency."""
    done = sorted(start + lat for _op, start, lat, _ok, _n in records if 0.0 <= start + lat <= seconds)
    gaps = [b - a for a, b in zip(done, done[1:])]
    out = {}
    if len(gaps) > 1 and statistics.mean(gaps) > 0:
        out["completion_gap_cv"] = round(statistics.pstdev(gaps) / statistics.mean(gaps), 3)
    if len(records) <= 128:
        out["start_latency_s"] = sorted((round(start, 2), round(lat, 2)) for _op, start, lat, _ok, _n in records)
    return out


async def wait_pieces(garages, want_per_node: int, timeout: float = 120.0) -> float:
    """Until every node holds `want_per_node` piece files (a PUT is
    acknowledged at the write quorum; the leftover sends finish behind it)."""
    t0 = time.perf_counter()
    while True:
        counts = [len(cluster.piece_files(g)) for g in garages]
        if all(c >= want_per_node for c in counts):
            return time.perf_counter() - t0
        if time.perf_counter() - t0 > timeout:
            raise RuntimeError(f"pieces per node never reached {want_per_node}: {counts}")
        await asyncio.sleep(0.25)


class NodeLoss:
    """The fault of a `"fault": {"kind": "node_loss"}` traffic file: at the
    window's start the victim's piece files are removed, `repair-tranquility`
    is set on it as the traffic file says (the operator's `worker set`) and
    `Garage.launch_repair_plan(fresh=True)` — the entry of `cli repair plan
    launch` — is called on it."""

    def __init__(self, cfg: dict, fault: dict, garages: list):
        self.fault = fault
        k = cfg["k"]
        # not a frontend, and one that holds DATA shards (a node's rank is the
        # same for every partition of this layout; losing a parity rank would
        # leave every GET systematic)
        held = [cluster.piece_files(g) for g in garages]
        self.idx = next(
            i for i, pf in enumerate(held)
            if i not in cfg["frontends"] and pf and all(rank < k for (_h, rank) in pf)
        )
        self.victim = garages[self.idx]
        self.lost = {hp: cluster.read_file(p) for hp, p in held[self.idx].items()}
        self.t_loss = self.t_plan_end = None
        self.planner = None
        self.progress: list[tuple] = []

    def strike(self) -> None:
        for p in cluster.piece_files(self.victim).values():
            os.remove(p)
        self.t_loss = time.perf_counter()
        self.victim.bg_vars.set("repair-tranquility", str(self.fault["repair_tranquility"]))
        self.planner = self.victim.launch_repair_plan(fresh=True)

    async def watch(self) -> None:
        """Note the plan's progress (seconds since the loss, rounds, pieces
        rebuilt, the tranquility in force) until it has finished."""
        seen = None
        while not self.planner.finished:
            now = (self.planner.plan.rounds, self.planner.plan.repaired,
                   self.victim.bg_vars.get("repair-tranquility"))
            if now != seen:
                seen = now
                self.progress.append((round(time.perf_counter() - self.t_loss, 2), *now))
            await asyncio.sleep(0.05)
        self.t_plan_end = time.perf_counter()

    def restored(self) -> dict[tuple[bytes, int], bytes]:
        """Lost pieces whose file is back, with the bytes now on disk."""
        now = cluster.piece_files(self.victim)
        return {hp: cluster.read_file(now[hp]) for hp in self.lost if hp in now}


async def run_cell(cell: dict, seed: int, seconds: float, traced: bool, t_process: float,
                   device: dict, scratch: str, rehearsal: dict | None = None,
                   sabotage=None) -> dict:
    """Returns the result object of the run (`run.py` prints it).
    `rehearsal`: {"extra_top", "extra_sections"} for the CPU rehearsal's
    node config.  `sabotage(garages)`: the tests' hook, called once the
    cluster is up, to break the timed path underneath."""
    root = tempfile.mkdtemp(prefix="garage_bench_")
    try:
        w = await _serve(cell, seed, seconds, traced, t_process, scratch, root, rehearsal, sabotage)
        return _judge(cell, seed, seconds, traced, device, root, w)
    finally:
        faulthandler.cancel_dump_traceback_later()
        shutil.rmtree(root, ignore_errors=True)
        shutil.rmtree(os.path.join(scratch, "trace"), ignore_errors=True)


async def _serve(cell, seed, seconds, traced, t_process, scratch, root, rehearsal, sabotage) -> dict:
    """Set-up, the window and the read-back; the cluster is stopped on the
    way out.  Returns what `_judge` needs."""
    import jax

    cfg, t = cell["config"], traffic.validate(cell["traffic"])
    events = cluster.PersistentCacheEvents()
    shapes = cluster.warm_shapes(cfg, t)
    child = await Child.start(cell["traffic_file"], seed)
    garages, servers, strain, sampler, fault = [], [], None, None, None
    try:
        # warm-up BEFORE the boot, not beside it: a thread loading programs
        # and eleven nodes syncing their layout fight over the interpreter
        # lock, and both take twice as long (my chip runs, PR 26)
        t0 = time.perf_counter()
        warm = cluster.warm_buckets(cfg, shapes)
        t_warm = time.perf_counter() - t0
        garages = await cluster.start_cluster(cfg, root, **(rehearsal or {}))
        t_boot = time.perf_counter() - t0 - t_warm
        if sabotage is not None:
            sabotage(garages)
        servers, endpoints, key_id, secret = await cluster.start_frontends(cfg, garages, t["bucket"])
        ready = await child.read(300)
        await child.ask("connect", 30, endpoints=endpoints, key_id=key_id, secret=secret)
        say("set_up", reached_harness_at_secs=round(t0 - t_process, 2), warm_secs=round(t_warm, 2),
            boot_and_sync_secs=round(t_boot, 2), warm_per_bucket=warm, shapes=shapes,
            bodies_secs=round(ready["bodies_secs"], 2), bodies=ready["bodies"],
            cache_hits=events.hits, cache_misses=events.misses)
        pre = None
        n_pre = int(t["preload_objects"])
        if n_pre:
            pre = await child.ask("preload", 900)
            blocks = (n_pre - pre["not_stored"]) * -(-int(t["object_bytes"]) // cfg["block_size"])
            say("preload", **{k: v for k, v in pre.items() if k != "cmd"}, blocks=blocks)
            say("pieces_landed", secs=round(await wait_pieces(garages, blocks), 2))
        if t.get("fault"):
            fault = NodeLoss(cfg, t["fault"], garages)
            say("victim", node=fault.idx, pieces=len(fault.lost))
        strain = cluster.HostStrain()

        # the clients start now and run through the traffic file's pre-roll;
        # the window opens `preroll_s` from here, on the child's clock and ours
        preroll = float(t["preroll_s"])
        t_open = time.perf_counter() + preroll
        window_task = asyncio.ensure_future(child.ask("window", preroll + seconds + 200, seconds=seconds))
        tdir = os.path.join(scratch, "trace")
        t_trace_call = None
        if traced:
            await asyncio.sleep(max(0.0, t_open - 0.5 - time.perf_counter()))
            shutil.rmtree(tdir, ignore_errors=True)
            sampler = LoopSampler(threading.get_ident())
            sampler.start()
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            opts.enable_hlo_proto = False
            t_trace_call = time.perf_counter()
            jax.profiler.start_trace(tdir, profiler_options=opts)
        await asyncio.sleep(max(0.0, t_open - time.perf_counter()))
        strain_preroll = strain.phase()
        misses_before = events.misses
        before = layers.snapshot()
        t_win = time.perf_counter()
        watch_task = None
        if fault is not None:
            fault.strike()
            watch_task = asyncio.ensure_future(fault.watch())
        span = None
        if traced:
            # the traced span is the window's first `trace_s` seconds: a whole
            # window of a small-object mix is millions of device-op events
            await asyncio.sleep(max(0.0, t_win + min(seconds, float(t["trace_s"])) - time.perf_counter()))
            span = {"t_end": time.perf_counter(), "after": layers.snapshot()}
            # off the loop: the window goes on
            await asyncio.to_thread(jax.profiler.stop_trace)
            sampler.stop()
        await asyncio.sleep(max(0.0, t_win + seconds - time.perf_counter()))
        t_close = time.perf_counter()
        after = layers.snapshot()
        strain_w = strain.phase()
        compiles = events.misses - misses_before
        restored = fault.restored() if fault is not None else {}
        win = await window_task
        if watch_task is not None:
            if fault.t_plan_end is None:
                watch_task.cancel()
            await asyncio.gather(watch_task, return_exceptions=True)
        peak = int((jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use", 0))
        # from here a run that stops moving says where and ends (exit 1, no result)
        faulthandler.dump_traceback_later(240, exit=True)
        ver = await child.ask("verify", 200)
        # the strain of the drain and the read-back: what the check itself put on the loop
        say("verify", **{k: v for k, v in ver.items() if k != "cmd"}, host=strain.phase())
    finally:
        if strain is not None:
            await strain.stop()
        if sampler is not None:
            sampler.stop()
        await child.stop()
        await cluster.stop_cluster(garages, servers)
    return {
        "cfg": cfg, "t": t, "shapes": shapes, "win": win, "ver": ver, "fault": fault,
        "restored": restored, "pre": pre, "n_pre": n_pre,
        "setup_s": t_win - t_process, "t_win": t_win, "t_close": t_close,
        "t_trace_call": t_trace_call, "tdir": tdir, "sampler": sampler, "span": span,
        "before": before, "after": after, "strain": strain_w, "strain_preroll": strain_preroll,
        "compiles": compiles, "peak": peak,
    }


def _judge(cell, seed, seconds, traced, device, root, w) -> dict:
    """The window's numbers, the bytes on disk against the reference, the
    metrics and the verdict."""
    cfg, t, win, fault = w["cfg"], w["t"], w["win"], w["fault"]
    summary = summarize(win["records"], seconds)
    ops = summary["ops"]
    n_req = sum(o["n"] for o in ops.values())
    n_failed = sum(o["failed"] for o in ops.values())
    # failed requests of the pre-roll and the window by what came of them
    # (`loadgen.outcome`), and the preload's beside them
    came = {kind: win["n"][kind] + (w["pre"]["n"][kind] if w["pre"] else 0) for kind in win["n"]}
    say("window", seconds=seconds, requests=n_req, failed=n_failed,
        sent_in_preroll=sum(1 for r in win["records"] if r[1] < 0),
        failed_in_preroll_and_window=win["n"], why_failed=win["why"],
        per_op={op: {"n": o["n"], "failed": o["failed"], "late": o["late"],
                     "p50_ms": statistics.median(o["lat_ms"]),
                     "p95_ms": percentile(o["lat_ms"], 0.95), "max_ms": max(o["lat_ms"])}
                for op, o in ops.items()},
        inflight_at_close=win["inflight_at_close"], drain_secs=round(win["drain_secs"], 2),
        generator_cpu_share=round(win["generator_cpu_share"], 3),
        bodies_made_in_window=win["bodies_made_in_window"],
        host=w["strain"], host_in_preload_and_preroll=w["strain_preroll"],
        in_step=in_step(win["records"], seconds),
        compiles_in_window=w["compiles"])

    t_chk = time.perf_counter()
    gone = set(win["deleted"]) | set(win["unknown"])
    stored = [(key, bid) for key, bid, _front in win["acked"] if key not in gone]
    stored += [(traffic.preload_key(i), i) for i in range(w["n_pre"])
               if traffic.preload_key(i) not in gone]
    # a refused request (a 5xx: `loadgen.outcome`) is failed, not wrong: it
    # counts in `failed`, the tails and the goodput, not here
    numbers = {
        "answers_wrong": (came["wrong"], 0),
        "requests_unanswered": (came["unanswered"], 0),
        "readback_wrong": (w["ver"]["wrong"], 0),
    }
    at_least = {"requests_answered": (n_req - n_failed, 1)}
    if stored:
        disk = check.check_stored(cfg, t, seed, stored, root)
        numbers["pieces_wrong"] = (disk["pieces_wrong"], 0)
        numbers["blocks_under_quorum"] = (disk["blocks_under_quorum"], 0)
        at_least["blocks_checked"] = (disk["blocks_checked"], 1)
    e2e = {"goodput_mb_s": summary["goodput_mb_s"], "setup_s": w["setup_s"]}
    for op, name in (("PUT", "put_p95_ms"), ("GET", "get_p95_ms")):
        if op in ops:
            e2e[name] = percentile(ops[op]["lat_ms"], 0.95)
    if fault is not None:
        rank = {h: r for (h, r) in fault.lost}
        want = check.expected_victim_files(cfg, t, seed, w["n_pre"], lambda h: rank[h])
        numbers["lost_pieces_unlike_reference"] = (
            sum(fault.lost[hp] != want.get(hp) for hp in fault.lost), 0)
        numbers["restored_pieces_wrong"] = (
            sum(data != want.get(hp) for hp, data in w["restored"].items()), 0)
        good = sum(data == want.get(hp) for hp, data in w["restored"].items())
        # from the loss to the plan's end or the window's close, whichever is first
        secs = min(fault.t_plan_end or w["t_close"], w["t_close"]) - fault.t_loss
        e2e["repair_blocks_s"] = good / secs
        at_least["stripes_restored"] = (good, 1)
        say("repair", stripes_restored=good, of=len(fault.lost), secs=secs,
            plan_finished_in_window=fault.t_plan_end is not None and fault.t_plan_end <= w["t_close"],
            plan_repaired=fault.planner.plan.repaired, plan_rounds=fault.planner.plan.rounds,
            progress_secs_rounds_pieces_tranquility=fault.progress[:40])
    say("check", secs=round(time.perf_counter() - t_chk, 2))

    result = {"attempted": n_req, "failed": n_failed,
              "refused": {"preload_and_traffic": came["refused"],
                          "readback_asked_again": w["ver"]["refused_and_asked_again"]}}
    device = dict(device, memory_peak_bytes=w["peak"])
    if not traced:
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in cell["end_to_end"] if m["name"] in e2e}
    else:
        t_red = time.perf_counter()
        tr = reduce_trace(w, device)
        tr["reduce_secs"] = time.perf_counter() - t_red
        device.update(busy_s=tr["busy_s"], window_s=tr["window_s"])
        result["breakdown"] = {"device_ops": tr["device_ops"], "idle_gaps": tr["idle_gaps"]}
        ctx = {
            "platform": device["platform"], "strain": w["strain"], "trace": tr,
            "compiles_in_window": w["compiles"],
            "client_ops": {"all": n_req, "failed": n_failed, **{op: o["n"] for op, o in ops.items()}},
        }
        metrics = {}
        for m in cell["per_layer"]:
            v = layers.read(m, w["before"], w["after"], ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        say("traced", end_to_end_in_this_run=e2e, roof=tr["roof"], codec_least_s=tr["codec_least_s"],
            executions=tr["executions"], xplane_bytes=tr["xplane_bytes"], traced_s=tr["window_s"],
            device_op_events=tr.get("device_op_events"), reduce_secs=round(tr["reduce_secs"], 2),
            breaker_opens=w["strain"]["breaker_opens"])
    correct, shown = check.verdict(numbers, at_least)
    return {"correct": correct, **result, "metrics": metrics, "device": device, "checks": shown}


def reduce_trace(w: dict, device: dict) -> dict:
    """The device's busy time over the traced span (from the window's start),
    the codec roofline share over it, and the idle time by host activity."""
    t_call, t_win, t_close = w["t_trace_call"], w["t_win"], w["span"]["t_end"]
    paths = glob.glob(os.path.join(w["tdir"], "plugins", "profile", "*", "*.xplane.pb"))
    if not paths:
        raise RuntimeError(f"the profiler left no .xplane.pb under {w['tdir']}")
    planes = trace_mod.read_xplane(paths[0])
    if not planes and device["platform"] == "cpu":
        # a CPU rehearsal: no device plane to reduce, so no trace metric
        return {"busy_s": 0.0, "window_s": t_close - t_win, "device_ops": [], "idle_gaps": [],
                "roof": None, "codec_least_s": None, "executions": 0, "xplane_bytes": 0}
    # the trace's clock starts with the profiling session
    lo, hi = (t_win - t_call) * 1e9, (t_close - t_call) * 1e9
    tr = trace_mod.reduce_planes(planes, lo, hi)
    tr["xplane_bytes"] = os.path.getsize(paths[0])
    tr["device_op_events"] = sum(len(p["ops"]) for p in planes)
    samples = [((ts - t_call) * 1e9, label) for ts, label in w["sampler"].samples]
    tr["idle_gaps"] = trace_mod.idle_by_host_activity(
        tr.pop("busy_intervals_ns"), lo, hi, samples, PERIOD_S)
    tr["device_idle_pct"] = 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
    ctx = {"platform": device["platform"], "client_ops": {}}
    seam = "block_codec_blocks_total"
    enc, rec = (
        layers.delta({"counter": seam, "labels": {"op": op, "path": "tpu"}},
                     w["before"], w["span"]["after"], ctx)
        for op in ("encode", "reconstruct"))
    cfg = w["cfg"]
    least, roof = roofline.codec_least_seconds(
        enc, rec, cfg["k"], cfg["m"], w["shapes"]["shard_bytes"], roofline.peaks_for(device["kind"]))
    tr["codec_least_s"], tr["roof"] = least, roof
    if tr["busy_s"] > 0 and least > 0:
        tr["codec_roofline_pct"] = 100.0 * least / tr["busy_s"]
    return tr
