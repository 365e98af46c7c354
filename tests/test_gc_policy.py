"""The process's policy for the cyclic garbage collector
(utils/gc_policy.py): a node's start freezes what lives in the process,
the last node's stop gives it back, and every collection is counted."""

from __future__ import annotations

import asyncio
import gc
import sys
import threading
import weakref

import pytest

from garage_tpu.model.garage import Garage
from garage_tpu.utils.config import config_from_dict
from garage_tpu.utils.gc_policy import gc_policy
from garage_tpu.utils.metrics import registry


def _node(tmp_path, i: int) -> Garage:
    """One in-process node: memory engine, no S3 frontend, no device, and
    no thread of its own (the loop meter's clock is off)."""
    return Garage(config_from_dict({
        "metadata_dir": str(tmp_path / f"n{i}" / "meta"),
        "data_dir": str(tmp_path / f"n{i}" / "data"),
        "db_engine": "memory", "replication_mode": "ec:2:1",
        "rpc_bind_addr": "127.0.0.1:0", "rpc_secret": "ee" * 32, "block_size": 8192,
        "tpu": {"enable": False}, "s3_api": {"api_bind_addr": None},
        "admin": {"event_loop_watchdog_threshold_msec": 0, "canary_enabled": False,
                  "latency_xray": False},
    }))


@pytest.fixture
def unfrozen() -> int:
    """No node runs in this process: the freeze count a full collection
    leaves (the interpreter keeps a few of its own objects there)."""
    assert gc_policy.refs == 0
    gc.collect()
    return gc.get_freeze_count()


class _Cycle:
    pass


def _counted(name: str, generation: str) -> float:
    return registry.counters.get((name, (("generation", generation),)), 0.0)


def test_a_node_start_freezes_the_heap(tmp_path, unfrozen):
    async def main():
        g = _node(tmp_path, 0)
        await g.start()
        try:
            return gc.get_freeze_count(), registry.gauges[("process_gc_frozen_objects", ())]
        finally:
            await g.stop()

    frozen, gauge = asyncio.run(main())
    # the whole heap: the interpreter's modules, this test's, the node's;
    # a frozen object that dies leaves the count, so the gauge (read at
    # the freeze) is never below it
    assert frozen > unfrozen + 10_000
    assert gauge >= frozen


def test_a_cycle_made_after_start_is_still_collected(tmp_path, unfrozen):
    async def main():
        g = _node(tmp_path, 0)
        await g.start()
        try:
            c = _Cycle()
            c.me = c
            ref = weakref.ref(c)
            del c
            assert ref() is not None
            gc.collect()
            return ref() is None
        finally:
            await g.stop()

    assert asyncio.run(main())


def test_the_last_node_to_stop_unfreezes(tmp_path, unfrozen):
    async def main():
        a, b = _node(tmp_path, 0), _node(tmp_path, 1)
        await a.start()
        await b.start()
        both = gc.get_freeze_count()
        await a.stop()
        after_first = gc.get_freeze_count()
        await b.stop()
        return both, after_first, gc.get_freeze_count(), gc_policy.refs

    both, after_first, after_second, refs = asyncio.run(main())
    # the first node's own objects die as it stops; the rest stays frozen
    assert both >= after_first > unfrozen + 10_000
    assert after_second == 0 and refs == 0
    gc.collect()
    assert gc.get_freeze_count() == unfrozen


def test_start_stop_rounds_leave_nothing_behind(tmp_path, unfrozen):
    """Each round's cluster is given back and collected: the heap frozen
    at a start, and the objects tracked after a stop, do not grow."""

    async def one_round(i: int) -> int:
        g = _node(tmp_path, i)
        await g.start()
        frozen = gc.get_freeze_count()
        await g.stop()
        return frozen

    rounds = []
    for i in range(5):
        frozen = asyncio.run(one_round(i))
        gc.collect()
        rounds.append((frozen, len(gc.get_objects()), gc.get_freeze_count()))
    first_frozen, first_tracked, _ = rounds[0]
    for frozen, tracked, left in rounds[1:]:
        assert frozen <= first_frozen
        assert tracked <= first_tracked
        assert left == unfrozen


def test_collections_are_counted_and_the_hook_goes_with_the_last_node(tmp_path, unfrozen):
    async def main():
        g = _node(tmp_path, 0)
        await g.start()
        try:
            hooked = gc_policy.hook in gc.callbacks
            n0 = _counted("process_gc_collections_total", "2")
            s0 = _counted("process_gc_seconds_total", "2")
            gc.collect()
            return (hooked, _counted("process_gc_collections_total", "2") - n0,
                    _counted("process_gc_seconds_total", "2") - s0)
        finally:
            await g.stop()

    hooked, n, s = asyncio.run(main())
    assert hooked and n == 1 and s > 0
    assert gc_policy.hook is None
    assert not any(getattr(cb, "__qualname__", "").startswith("GcPolicy.") for cb in gc.callbacks)
    n0 = _counted("process_gc_collections_total", "2")
    gc.collect()
    assert _counted("process_gc_collections_total", "2") == n0


def test_collections_on_many_threads_are_each_counted_once(tmp_path, unfrozen):
    """A collection runs on whichever thread allocates: with eight
    threads making cycles and the interpreter switching between them as
    often as it can, the hook's counts match the collector's own."""

    def counts():
        gc.disable()  # no collection between the two readings
        try:
            return ([s["collections"] for s in gc.get_stats()],
                    [_counted("process_gc_collections_total", g) for g in ("0", "1", "2")])
        finally:
            gc.enable()

    def churn():
        for _ in range(20_000):
            c = _Cycle()
            c.me = c

    async def main():
        g = _node(tmp_path, 0)
        await g.start()
        try:
            stats0, hook0 = counts()
            old = sys.getswitchinterval()
            sys.setswitchinterval(1e-5)
            try:
                threads = [threading.Thread(target=churn) for _ in range(8)]
                for t in threads:
                    t.start()
                for t in threads:
                    t.join(timeout=60)
            finally:
                sys.setswitchinterval(old)
            stats1, hook1 = counts()
            return (any(t.is_alive() for t in threads),
                    [b - a for a, b in zip(stats0, stats1)], [b - a for a, b in zip(hook0, hook1)])
        finally:
            await g.stop()

    alive, by_collector, by_hook = asyncio.run(main())
    assert not alive
    assert by_collector[0] > 20
    assert by_hook == by_collector
