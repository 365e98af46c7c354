"""The event-loop meter's standstill clock (utils/flight.py LoopMeter): a
process the host stopped told from a callback that held the loop, and the
two failure paths — an RPC timeout, a 5xx of the S3 frontend — that ask
whether one overlapped them.  A child process stops this one with SIGSTOP,
as a machine that stands still does."""

from __future__ import annotations

import asyncio
import collections
import os
import subprocess
import sys
import time

import pytest

sys.path.insert(0, os.path.dirname(__file__))

from test_net import make_node  # noqa: E402

from garage_tpu.api.s3.api_server import S3ApiServer  # noqa: E402
from garage_tpu.api.s3.client import S3Client, S3Error  # noqa: E402
from garage_tpu.model.garage import Garage  # noqa: E402
from garage_tpu.net.message import Resp  # noqa: E402
from garage_tpu.rpc.layout.types import NodeRole  # noqa: E402
from garage_tpu.utils import flight, latency  # noqa: E402
from garage_tpu.utils.config import config_from_dict  # noqa: E402
from garage_tpu.utils.metrics import registry  # noqa: E402
from garage_tpu.utils.tracing import tracer  # noqa: E402

# waits for a line on stdin, then stops its parent `after` seconds later for
# `secs`; stdin closed with no line (a test that failed first) stops nothing
_HOLD = (
    "import os, signal, sys, time\n"
    "after, secs = float(sys.argv[1]), float(sys.argv[2])\n"
    "if not sys.stdin.readline():\n"
    "    sys.exit(0)\n"
    "time.sleep(after)\n"
    "os.kill(os.getppid(), signal.SIGSTOP)\n"
    "time.sleep(secs)\n"
    "os.kill(os.getppid(), signal.SIGCONT)\n"
)


class Stopper:
    """A child that stops this process for `secs`, `after` seconds after `go()`."""

    def __init__(self, secs: float, after: float = 0.05):
        self.proc = subprocess.Popen(
            [sys.executable, "-c", _HOLD, str(after), str(secs)], stdin=subprocess.PIPE)

    def go(self) -> None:
        self.proc.stdin.write(b"go\n")
        self.proc.stdin.flush()

    def wait(self) -> None:
        assert self.proc.wait(timeout=10) == 0


def run(coro, limit: float = 30.0):
    return asyncio.run(asyncio.wait_for(coro, limit))


def ctr(name: str, **labels) -> float:
    want = set(labels.items())
    return sum(v for (n, lbl), v in list(registry.counters.items()) if n == name and want <= set(lbl))


def host_s() -> float:
    return ctr("process_standstill_seconds_total", cause="host")


class metered:
    """Spans on and the meter (with its clock) installed on the test's loop."""

    async def __aenter__(self) -> flight.LoopMeter:
        latency.enable()
        flight.loop_meter.install()
        return flight.loop_meter

    async def __aexit__(self, *exc):
        flight.loop_meter.remove()
        latency.disable()


def test_the_clock_is_one_thread_while_the_meter_is_installed_and_feeds_no_ladder_input():
    """The first `install()` starts the clock, a second shares it, the
    last `remove()` stops it; it wakes ~50 times a second, its families
    read 0 (not absent) from the start, and it writes neither
    `event_loop_blocked_total` nor `event_loop_lag_seconds`."""
    m = flight.loop_meter

    async def main():
        blocked0 = ctr("event_loop_blocked_total")
        lag0 = registry.durations.get(("event_loop_lag_seconds", ()), (0,))[0]
        wakes0 = ctr("process_clock_wakes_total")
        m.install()
        clock = m.clock
        m.install()
        assert m.clock is clock and clock.is_alive()
        assert ("process_standstill_seconds_total", (("cause", "host"),)) in registry.counters
        assert ("process_standstills_total", (("cause", "interpreter"),)) in registry.counters
        await asyncio.sleep(0.5)
        wakes = ctr("process_clock_wakes_total") - wakes0
        m.remove()
        assert m.clock is clock and clock.is_alive()
        m.remove()
        assert m.clock is None and not clock.is_alive()
        return (wakes, ctr("event_loop_blocked_total") - blocked0,
                registry.durations.get(("event_loop_lag_seconds", ()), (0,))[0] - lag0)

    wakes, blocked, lag = run(main())
    assert 10 <= wakes <= 26, wakes
    assert blocked == 0 and lag == 0


def test_a_stopped_process_reads_one_host_episode_and_no_loop_held_time_beyond_it():
    """A callback spins 1.9 s of wall; 0.1 s into it the process is
    stopped 1.5 s.  The clock reads one `host` episode of 1.3–1.7 s, and
    the long step is charged only what it held beyond the stop."""

    async def main():
        async with metered() as m:
            stopper = Stopper(1.5, after=0.1)
            await asyncio.sleep(0.3)  # the child is up and reading
            t_start, held0, host0 = time.perf_counter_ns(), ctr(
                "event_loop_long_step_seconds_total", span="t:across"), host_s()
            with tracer.span("t:across", layer="block"):
                await asyncio.sleep(0)  # one step, under the span from start to end
                stopper.go()
                t0 = time.perf_counter()
                while time.perf_counter() - t0 < 1.9:
                    pass
                await asyncio.sleep(0)
            await asyncio.sleep(0.1)  # the clock has woken since
            stopper.wait()
            host = [e for e in m.episodes if e[2] == "host" and e[0] >= t_start]
            return (host, ctr("event_loop_long_step_seconds_total", span="t:across") - held0,
                    host_s() - host0, m.stood_still_since(t_start))

    host, held, host_secs, answer = run(main())
    assert len([e for e in host if 1.3 <= (e[1] - e[0]) / 1e9 <= 1.7]) == 1, host
    assert host_secs >= 1.3
    # 1.9 s of wall less the 1.5 s stop: the program held the loop ~0.4 s
    assert 0.15 <= held <= 0.6, held
    assert answer == "host"


def test_a_callback_that_spins_holds_the_loop_under_its_span():
    """A callback spins 300 ms in Python: one long step under its span,
    a `loop_held` episode with the span's name, and no host time."""

    async def main():
        async with metered() as m:
            await asyncio.sleep(0)
            t_start = time.perf_counter_ns()
            n0 = ctr("event_loop_long_steps_total", layer="codec", span="t:hold")
            s0 = ctr("event_loop_long_step_seconds_total", span="t:hold")
            with tracer.span("t:hold", layer="codec"):
                await asyncio.sleep(0)
                t0 = time.perf_counter()
                while time.perf_counter() - t0 < 0.3:
                    pass
                await asyncio.sleep(0)
            t_end = time.perf_counter_ns()
            return (ctr("event_loop_long_steps_total", layer="codec", span="t:hold") - n0,
                    ctr("event_loop_long_step_seconds_total", span="t:hold") - s0,
                    [e for e in m.episodes if e[1] > t_start and e[0] < t_end],
                    m.stood_still_since(t_start))

    n, secs, episodes, answer = run(main())
    assert n == 1
    assert 0.29 <= secs <= 0.45, secs
    assert [(e[2], e[3]) for e in episodes] == [("loop_held", "t:hold")]
    assert answer == "loop_held"


def test_a_standstill_the_clock_has_not_recorded_is_answered_from_its_last_wake():
    """The race of the first timers after a stop: the clock's last wake is
    over 100 ms old when the query runs, so the query itself reads the
    process's CPU — little of it since that wake is the host's, much of
    it a thread that held the lock.  A fresh wake and an empty ring read
    `none`, a ring entry that ended before `t0` too."""
    m = flight.LoopMeter(collections.defaultdict(float))
    assert m.stood_still_since(0) == "none"  # no clock: nothing to say
    now, cpu = time.perf_counter_ns(), time.process_time_ns()
    m.clock_at = (now, cpu)
    assert m.stood_still_since(now - 10**9) == "none"
    m.clock_at = (now - 1_500_000_000, cpu)
    assert m.stood_still_since(now - 2 * 10**9) == "host"
    m.clock_at = (now - 1_500_000_000, cpu - 1_000_000_000)
    assert m.stood_still_since(now - 2 * 10**9) == "interpreter"
    m.clock_at = (time.perf_counter_ns(), time.process_time_ns())
    m.episodes.append((now - 3 * 10**9, now - 2 * 10**9, "host", None))
    m.episodes.append((now - 10**9, now - 8 * 10**8, "loop_held", "t:x"))
    assert m.stood_still_since(now - int(1.5e9)) == "loop_held"
    assert m.stood_still_since(now - int(2.5e9)) == "host"
    assert m.stood_still_since(now - int(0.5e9)) == "none"
    # and a long step that ends before the clock has woken is not charged the stop
    holder = type("H", (), {"layer": "block", "name": "t:w"})()
    m.clock_at = (now - 1_500_000_000, time.process_time_ns())
    m.long_step(now - 1_600_000_000, time.perf_counter_ns(), holder)
    key = ("event_loop_long_step_seconds_total", (("layer", "block"), ("span", "t:w")))
    assert m.counters[("event_loop_long_steps_total", key[1])] == 1
    assert m.counters[key] < 0.2


async def _hanging_peer(ep: str):
    a, b = await make_node(), await make_node()
    await a.connect(b.bind_addr, b.id)

    async def hang(_frm, _req):
        await asyncio.sleep(5.0)
        return Resp("late")

    b.endpoint(ep).set_handler(hang)
    return a, b


def test_timeouts_across_a_stop_are_counted_in_standstill_even_the_first_to_fire():
    """Eight calls with a 1 s pinned timeout are in flight when the
    process is stopped 1.5 s: on wake-up every timer has run out, and
    every timeout — the first to fire among them, before the clock has
    woken — is counted in `rpc_timeouts_in_standstill_total{cause=host}`."""
    ep = "t/standstill/across"

    async def main():
        async with metered():
            a, b = await _hanging_peer(ep)
            try:
                to0, in0 = ctr("rpc_timeout_counter", endpoint=ep), ctr(
                    "rpc_timeouts_in_standstill_total", endpoint=ep, cause="host")
                stopper = Stopper(1.5)
                await asyncio.sleep(0.3)  # the child is up and reading
                calls = [asyncio.create_task(a.endpoint(ep).call(b.id, i, timeout=1.0)) for i in range(8)]
                await asyncio.sleep(0.05)
                stopper.go()
                got = await asyncio.gather(*calls, return_exceptions=True)
                stopper.wait()
                return (got, ctr("rpc_timeout_counter", endpoint=ep) - to0,
                        ctr("rpc_timeouts_in_standstill_total", endpoint=ep, cause="host") - in0,
                        ctr("rpc_timeouts_in_standstill_total", endpoint=ep) - in0)
            finally:
                await a.shutdown()
                await b.shutdown()

    got, timeouts, in_host, in_any = run(main())
    assert all(isinstance(g, asyncio.TimeoutError) for g in got), got
    assert timeouts == 8
    assert in_host == in_any == 8


def test_a_timeout_with_no_standstill_reads_none():
    """Control: a peer whose handler sleeps 2 s, a 1 s timeout, nothing
    stopped: the timeout is counted, and not in standstill."""
    ep = "t/standstill/control"

    async def main():
        async with metered():
            a, b = await _hanging_peer(ep)
            try:
                to0, in0 = ctr("rpc_timeout_counter", endpoint=ep), ctr(
                    "rpc_timeouts_in_standstill_total", endpoint=ep)
                with pytest.raises(asyncio.TimeoutError):
                    await a.endpoint(ep).call(b.id, "x", timeout=1.0)
                return (ctr("rpc_timeout_counter", endpoint=ep) - to0,
                        ctr("rpc_timeouts_in_standstill_total", endpoint=ep) - in0)
            finally:
                await a.shutdown()
                await b.shutdown()

    assert run(main()) == (1, 0)


async def _cluster(tmp_path, n: int = 3) -> list[Garage]:
    """EC(2,1) on three in-process nodes, the watchdog off as in the
    benchmark's configurations, no background workers."""
    garages = [Garage(config_from_dict({
        "metadata_dir": str(tmp_path / f"n{i}" / "meta"),
        "data_dir": str(tmp_path / f"n{i}" / "data"),
        "db_engine": "memory", "replication_mode": "ec:2:1",
        "rpc_bind_addr": "127.0.0.1:0", "rpc_secret": "ee" * 32, "block_size": 8192,
        "tpu": {"enable": False}, "s3_api": {"api_bind_addr": None},
        "admin": {"event_loop_watchdog_threshold_msec": 0, "canary_enabled": False},
    })) for i in range(n)]
    for g in garages:
        await g.start()
    for i, gi in enumerate(garages):
        for gj in garages[i + 1:]:
            await gj.netapp.connect(gi.netapp.bind_addr, gi.node_id)
    lm = garages[0].layout_manager
    for i, g in enumerate(garages):
        lm.stage_role(g.node_id, NodeRole(zone=f"dc{i}", capacity=10**12))
    lm.apply_staged()
    for _ in range(100):
        await asyncio.sleep(0.05)
        if all(g.layout_manager.digest() == lm.digest() for g in garages):
            break
    return garages


def test_a_get_refused_across_a_stop_is_counted_as_the_hosts(tmp_path):
    """The object table's holders are slow; the process is stopped 1.5 s
    while a GET waits on them: the GET is answered 500 (its quorum read
    timed out), counted in `api_s3_refused_total{standstill=host}`, and
    `api_s3_error_counter` counts it as it always did.  With the
    watchdog off, the overload ladder's inputs do not move."""

    async def main():
        garages = await _cluster(tmp_path)
        server = S3ApiServer(garages[0])
        await server.start("127.0.0.1", 0)
        g0 = garages[0]
        key = await g0.helper.create_key("standstill")
        key.params().allow_create_bucket.update(True)
        await g0.key_table.insert(key)
        client = S3Client(f"http://127.0.0.1:{server.runner.addresses[0][1]}", key.key_id, key.secret())
        try:
            stopper = Stopper(1.5)
            await client.create_bucket("stood")
            await client.put_object("stood", "k", b"s" * 5000)

            for g in garages[1:]:
                ep = g.netapp.endpoints["table/object"]

                async def slow(frm, req, orig=ep.handler):
                    if stopper.proc.stdin and not stopper.proc.stdin.closed:
                        stopper.go()
                        stopper.proc.stdin.close()
                    await asyncio.sleep(5.0)
                    return await orig(frm, req)

                ep.set_handler(slow)
            lag0 = registry.durations.get(("event_loop_lag_seconds", ()), (0,))[0]
            before = {k: v for k, v in registry.counters.items()
                      if k[0] in ("api_s3_refused_total", "api_s3_error_counter", "event_loop_blocked_total")}
            with pytest.raises(S3Error) as e:
                await client.get_object("stood", "k")
            stopper.wait()
            after = {k: v - before.get(k, 0) for k, v in registry.counters.items()
                     if k[0] in ("api_s3_refused_total", "api_s3_error_counter", "event_loop_blocked_total")
                     and v != before.get(k, 0)}
            lag1 = registry.durations.get(("event_loop_lag_seconds", ()), (0,))[0]
            return e.value.status, after, lag1 - lag0
        finally:
            await client.close()
            await server.stop()
            for g in garages:
                await g.stop()

    status, moved, lag_samples = run(main(), limit=60.0)
    assert status == 500
    assert moved == {
        ("api_s3_error_counter", (("method", "GET"), ("code", "500"))): 1,
        ("api_s3_refused_total", (("cause", "quorum"), ("method", "GET"), ("standstill", "host"))): 1,
    }, moved
    assert lag_samples == 0
