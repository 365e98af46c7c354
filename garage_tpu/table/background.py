"""BackgroundCopies: a table's best-effort copies to its non-quorum
holders, one message per destination at a time.

`TableStripeSyncedReplication` (block_ref) quorums over the meta ring,
while every holder of the block's stripe keeps the rows: after each
quorum write the holders beyond the ring get a best-effort
``["U", values]`` copy (`Table.replicate_background`), each row to its
own holders.  A fresh PUT's row sends none: it rides the piece `Put`
each of those holders receives anyway (api/s3/objects.py put_one,
`Table.apply_carried`), and only a holder that gets no piece of the
block still gets a copy.  What does leave here is every other row: a
DELETE's tombstones, the insert queue's, repair's.  Nobody waits for
these copies and anti-entropy delivers whatever they miss, so they have
no deadline; what they must not do is cost a whole call per block and
holder on a loop that every node shares.

Per destination node at most ONE call is in flight:

  - rows for a destination with no call in flight leave at once, in a
    call of their own (under light load nothing waits);
  - rows that arrive while a call is in flight wait in that
    destination's pending list, and when the call ends — answered or
    failed — they all leave in the next call, at most `max_rows()` to a
    message (`[meta] coalesce_max_entries`, the cap a coalesced quorum
    write has).  The batch grows with the round trip that the
    destination's queue imposes, with no linger and no timer;
  - a destination's pending list holds at most PENDING_MESSAGES
    messages' worth of rows; past that the oldest are dropped and
    counted, the fate a copy whose call fails has too;
  - a failed call drops only its own rows; a destination that hangs
    holds up only its own rows.

The calls run under the plain label `table:replicate_background`: they
outlive the request whose quorum write queued their rows.

Metric families (doc/monitoring.md):

  table_background_replicate_total{table_name}  messages sent, one per
                                                destination and call
  table_background_rows_total{table_name}       rows those messages carried
  table_background_dropped_total{table_name}    rows dropped at the bound
"""

from __future__ import annotations

import asyncio
import logging
from typing import Callable, Iterable

from ..net.message import PRIO_BACKGROUND
from ..utils.aio import reap, spawn_supervised
from ..utils.metrics import registry
from ..utils.tracing import loop_label

logger = logging.getLogger("garage.table.background")

# a destination's pending rows are bounded at this many full messages
PENDING_MESSAGES = 4


class BackgroundCopies:
    """One per Table.  `send` never waits; `close()` (Garage.stop(),
    through Table.close()) reaps the calls in flight and drops what is
    pending."""

    def __init__(self, helper, endpoint, table_name: str, max_rows: Callable[[], int]):
        self.helper = helper
        self.endpoint = endpoint
        self.max_rows = max_rows
        self.pending: dict[bytes, list[bytes]] = {}
        self.in_flight: dict[bytes, asyncio.Task] = {}
        self._closed = False
        self._lbl = (("table_name", table_name),)

    def send(self, nodes: Iterable[bytes], values: list[bytes]) -> None:
        """Queue `values` for every node of `nodes`."""
        if self._closed:
            return
        for node in nodes:
            if node in self.in_flight:
                self._hold(node, values)
            else:
                self._launch(node, list(values))

    def _hold(self, node: bytes, rows: list[bytes]) -> None:
        q = self.pending.setdefault(node, [])
        q.extend(rows)
        over = len(q) - PENDING_MESSAGES * self.max_rows()
        if over > 0:
            del q[:over]
            registry.incr("table_background_dropped_total", self._lbl, by=over)

    def _launch(self, node: bytes, rows: list[bytes]) -> None:
        cap = self.max_rows()
        if len(rows) > cap:
            self._hold(node, rows[cap:])
            rows = rows[:cap]
        registry.incr("table_background_replicate_total", self._lbl)
        registry.incr("table_background_rows_total", self._lbl, by=len(rows))
        with loop_label("table:replicate_background", "table"):
            self.in_flight[node] = spawn_supervised(
                self._call(node, rows), name=f"table-background:{self.endpoint.path}"
            )

    async def _call(self, node: bytes, rows: list[bytes]) -> None:
        try:
            await self.helper.call(self.endpoint, node, ["U", rows], prio=PRIO_BACKGROUND)
        except Exception as e:  # noqa: BLE001 — best effort: anti-entropy is the backstop
            logger.debug("background copy to %s failed: %r", node.hex()[:16], e)
        finally:
            self.in_flight.pop(node, None)
            rows = self.pending.pop(node, None)
            if rows and not self._closed:
                self._launch(node, rows)

    async def close(self) -> None:
        self._closed = True
        self.pending.clear()
        if self.in_flight:
            await reap(list(self.in_flight.values()), log=logger, what="table background copy")
            self.in_flight.clear()
