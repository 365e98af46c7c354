"""The process's policy for the cyclic garbage collector.

A full (generation-2) collection walks every object the collector
tracks, and the process holds many that live as long as it does: JAX,
the nodes, their connections and tables.  While it walks, no thread of
the process runs Python, so on the one event loop the nodes share it is
a step that holds everything up, a tenth of a second and more on a
large heap.

So once a node is up, the objects that already live in the process move
to the collector's permanent generation (`gc.freeze()`), and a full
collection scans only what was made since; the process gives them back
(`gc.unfreeze()`) when its last node stops.  `install()` runs from
`Garage.start()` and `remove()` from `Garage.stop()`, refcounted and
process-wide as `flight.loop_meter` is.  Each install collects first,
so nothing that is garbage at that moment is frozen; after the first
node a collect scans only what the last boot made.  There is no setting:
every cycle made after a node's start is still collected, the
collector's thresholds are its own, and nothing here runs on a request's
path.

A `gc.callbacks` hook, in place from the first install to the last
remove, counts every collection, on whichever thread ran it:
  process_gc_collections_total{generation}
  process_gc_seconds_total{generation}   wall seconds, start to stop
and the gauge `process_gc_frozen_objects` is `gc.get_freeze_count()`
after the last freeze (0 once the last node stops).  The last `RING`
full collections are kept in `full` as (start_ns, end_ns) on
`perf_counter_ns`, the clock of the loop meter's episodes.
"""

from __future__ import annotations

import collections
import gc
import time

from .metrics import registry

GENERATIONS = ("0", "1", "2")


class GcPolicy:
    RING = 64

    def __init__(self):
        self.refs = 0
        self.hook = None
        self.full: collections.deque = collections.deque(maxlen=self.RING)

    def install(self) -> None:
        """A node is up: collect, then freeze what is left."""
        if self.refs == 0:
            self.hook = self._make_hook()
            gc.callbacks.append(self.hook)
        self.refs += 1
        gc.collect()
        gc.freeze()
        registry.set_gauge("process_gc_frozen_objects", (), float(gc.get_freeze_count()))

    def remove(self) -> None:
        """A node stopped: the last one gives the frozen objects back."""
        if self.refs == 0:
            return
        self.refs -= 1
        if self.refs == 0:
            gc.unfreeze()
            gc.callbacks.remove(self.hook)
            self.hook = None
            registry.set_gauge("process_gc_frozen_objects", (), 0.0)

    def _make_hook(self):
        c, full, perf = registry.counters, self.full, time.perf_counter_ns
        n_key = [("process_gc_collections_total", (("generation", g),)) for g in GENERATIONS]
        s_key = [("process_gc_seconds_total", (("generation", g),)) for g in GENERATIONS]
        # every key the hook writes exists before it runs: a collection on
        # another thread never grows the registry under an iteration
        for key in n_key + s_key:
            c[key] += 0
        t0 = 0

        def hook(phase: str, info: dict) -> None:
            nonlocal t0
            if phase == "start":
                t0 = perf()
                return
            t1 = perf()
            g = info["generation"]
            c[n_key[g]] += 1
            c[s_key[g]] += (t1 - t0) * 1e-9
            if g == 2:
                full.append((t0, t1))

        return hook


# the process-wide policy: one collector per process
gc_policy = GcPolicy()
