"""Span tracing with OTLP/HTTP export (reference: OpenTelemetry spans
around every RPC/API/table op, exported via OTLP when `admin.trace_sink`
is configured — src/garage/tracing_setup.rs:13-37, src/rpc/rpc_helper.rs:172-217).

Design: a contextvar carries the current span, so `with span("name"):`
nests correctly across asyncio task boundaries (contextvars propagate
into tasks).  Finished spans buffer in memory and a background flusher
POSTs them as OTLP/HTTP JSON (`/v1/traces`) to the sink.  When no sink is
configured the API is a near-zero-cost no-op — the hot paths stay hot.

Span ids follow W3C sizes: 16-byte trace id, 8-byte span id.

Cross-node propagation (Dapper-style): `tracer.inject()` serializes the
current span as a compact binary traceparent — 16-byte trace id + 8-byte
parent span id + 1 flag byte (0x01 = sampled), the W3C traceparent
fields without the hex framing — which the RPC layer carries inside the
request frame (`net/connection.py` meta key "tp").  The receiving node
calls `tracer.extract()` and opens its handler span with
`remote_parent=...`, so one S3 PUT against a multi-node cluster yields
ONE trace whose `rpc-handle:*` spans on remote nodes share the root
trace id.  Hot paths guard with `if tracer.enabled` and fall back to the
shared `NOOP_SPAN`, so a disabled tracer allocates no Span objects, no
attr dicts, and no traceparent bytes.

Clock: `start_ns`/`end_ns` are `time.perf_counter_ns()` — the clock the
event-loop meter (utils/flight.py LoopMeter) and a profiler session are
placed on, and one that never steps — and `wall_ns()` adds the ONE
wall-clock offset read at import for the OTLP export and the flight
recorder's timestamps.

Loop time: every span carries a `layer` from the closed `LAYERS` set and
collects `busy_ns`, the time the event-loop thread spent inside
callbacks while the span was the innermost one of the running context
(self time by construction: a child's steps are the child's).  Tasks
that serve no request run under a `loop_label()` instead — a name and a
layer for the meter, never a parent: a span opened under one is a root.
"""

from __future__ import annotations

import asyncio
import contextvars
import logging
import os
import random
import time
from threading import get_ident
from time import perf_counter_ns

logger = logging.getLogger("garage.tracing")

# span/trace ids only need uniqueness, not unpredictability; a seeded
# PRNG avoids two getrandom() syscalls per span on the hot path (the
# flight recorder keeps span creation on by default)
_ids = random.Random(int.from_bytes(os.urandom(16), "big") ^ os.getpid())

_current: contextvars.ContextVar["Span | None"] = contextvars.ContextVar(
    "garage_current_span", default=None
)

# perf_counter_ns -> unix ns, read once: a span's duration must not step
# with NTP, its exported timestamps only need to be near the wall clock
_WALL_OFFSET_NS = time.time_ns() - perf_counter_ns()


def wall_ns(ns: int) -> int:
    """A span timestamp (perf_counter_ns) as unix nanoseconds."""
    return ns + _WALL_OFFSET_NS


# The CLOSED set of layers the event-loop meter attributes time to (the
# `layer` label of `event_loop_busy_seconds_total`, held closed by the
# metrics-lint test like utils/latency.py PHASES).  The site that opens
# a span names its layer; "none" is what nobody has named.
LAYERS = ("api", "block", "codec", "table", "rpc", "background", "none")

_busy_keys: dict[tuple[str, str], tuple] = {}


def _busy_key(layer: str, name: str) -> tuple:
    """The registry key of `event_loop_busy_seconds_total{layer,span}`,
    built once per (layer, span name)."""
    key = _busy_keys.get((layer, name))
    if key is None:
        if layer not in LAYERS:
            raise ValueError(f"layer {layer!r} not in {LAYERS}")
        key = _busy_keys[(layer, name)] = (
            "event_loop_busy_seconds_total", (("layer", layer), ("span", name))
        )
    return key


class LoopLabel:
    """What the meter charges a callback to when no span is current: a
    name and a layer, shared by every task that runs under it."""

    __slots__ = ("name", "layer", "busy_ns", "_key")

    def __init__(self, name: str, layer: str):
        self.name, self.layer = name, layer
        self.busy_ns = 0
        self._key = _busy_key(layer, name)


NO_LABEL = LoopLabel("none", "none")
_labels: dict[tuple[str, str], LoopLabel] = {("none", "none"): NO_LABEL}

_label: contextvars.ContextVar[LoopLabel] = contextvars.ContextVar(
    "garage_loop_label", default=NO_LABEL
)

# the installed utils/flight.py LoopMeter, or None: set by its install()
_meter = None


def _holder():
    """What the running context's loop time goes to."""
    s = _current.get()
    return s if s is not None else _label.get()


def _meter_switch(to, now: int) -> None:
    """Inside a running loop callback, on the loop's thread: charge the
    step so far to the holder until now, the rest to `to`."""
    m = _meter
    if m is not None and m.in_step and get_ident() == m.ident:
        m.switch(to, now)


class _LabelScope:
    __slots__ = ("label", "_t_label", "_t_span")

    def __init__(self, label: LoopLabel):
        self.label = label

    def __enter__(self):
        self._t_span = _current.set(None)
        self._t_label = _label.set(self.label)
        _meter_switch(self.label, perf_counter_ns())
        return self.label

    def __exit__(self, exc_type, exc, tb):
        _label.reset(self._t_label)
        _current.reset(self._t_span)
        _meter_switch(_holder(), perf_counter_ns())
        return False


def loop_label(name: str, layer: str) -> _LabelScope:
    """`with loop_label("net:recv", "rpc"):` — the body, and whatever
    captures its context (tasks, transports), runs under a plain label
    with NO current span: a connection's loops and a background worker
    outlive the request whose context they were started in, and must not
    be charged to it.  `name` is a bounded string, never an id."""
    lab = _labels.get((layer, name))
    if lab is None:
        lab = _labels[(layer, name)] = LoopLabel(name, layer)
    return _LabelScope(lab)

MAX_BUFFER = 8192
FLUSH_INTERVAL = 3.0

TRACEPARENT_LEN = 16 + 8 + 1  # trace id + parent span id + flags
FLAG_SAMPLED = 0x01


class _NoopSpan:
    """Reusable, re-enterable no-op context manager: the disabled-tracing
    fast path.  Hot callers use `tracer.span(...) if tracer.enabled else
    NOOP_SPAN` so the disabled branch never builds span names or attrs."""

    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, exc_type, exc, tb):
        return False


NOOP_SPAN = _NoopSpan()


class RemoteParent:
    """A parent span living on another node, reconstructed from a
    traceparent.  Duck-typed to Span for the two fields a child reads."""

    __slots__ = ("trace_id", "span_id", "sampled")

    def __init__(self, trace_id: bytes, span_id: bytes, sampled: bool = True):
        self.trace_id = trace_id
        self.span_id = span_id
        self.sampled = sampled


class Span:
    """One traced operation, and its own context manager (`with
    tracer.span(...) as s:`)."""

    __slots__ = (
        "name", "trace_id", "span_id", "parent_id",
        "start_ns", "end_ns", "attrs", "ok",
        "layer", "busy_ns", "_key", "_tracer", "_token",
    )

    def __init__(
        self, name: str, parent: "Span | RemoteParent | None", attrs: dict,
        layer: str = "none", tracer: "Tracer | None" = None,
    ):
        self.name = name
        self.trace_id = (
            parent.trace_id if parent else _ids.getrandbits(128).to_bytes(16, "big")
        )
        self.span_id = _ids.getrandbits(64).to_bytes(8, "big")
        self.parent_id = parent.span_id if parent else None
        self.start_ns = perf_counter_ns()
        self.end_ns = 0
        self.attrs = attrs
        self.ok = True
        self.layer = layer
        self.busy_ns = 0  # on-loop self time (utils/flight.py LoopMeter)
        self._key = _busy_key(layer, name)
        self._tracer = tracer
        self._token = None

    def __enter__(self):
        self._token = _current.set(self)
        _meter_switch(self, self.start_ns)
        return self

    def __exit__(self, exc_type, exc, tb):
        if exc_type is not None:
            self.ok = False
        _current.reset(self._token)
        self._token = None  # a buffered span must not keep its context alive
        now = self.end_ns = perf_counter_ns()
        root = self.parent_id is None
        # a root's hooks walk its whole tree and read every busy_ns:
        # settle its own first, and keep it the holder while they run
        _meter_switch(self if root else _holder(), now)
        t = self._tracer
        if t is not None:
            # export buffer fills only when a sink is configured; hooks
            # (flight recorder, latency X-ray) see every span either way
            if t.sink is not None and len(t._buf) < MAX_BUFFER:
                t._buf.append(self)
            for hook in t._hooks:
                try:
                    hook(self)
                except Exception as e:  # noqa: BLE001 — hooks must not fail spans
                    logger.debug("span hook failed: %r", e)
        if root:
            _meter_switch(_holder(), perf_counter_ns())
        return False


class Tracer:
    def __init__(self):
        self.sink: str | None = None
        self.service_name = "garage-tpu"
        self._buf: list[Span] = []
        self._task: asyncio.Task | None = None
        self._session = None
        # span-end hooks (utils/flight.py SlowRequestRecorder): attaching
        # one enables span creation even without an export sink, so the
        # flight recorder works with zero external collectors
        self._hooks: list = []

    @property
    def enabled(self) -> bool:
        return self.sink is not None or bool(self._hooks)

    def add_hook(self, fn) -> None:
        """Register fn(span), called once per finished span."""
        if fn not in self._hooks:
            self._hooks.append(fn)

    def remove_hook(self, fn) -> None:
        try:
            self._hooks.remove(fn)
        except ValueError:
            pass

    def configure(self, sink: str | None, service_name: str = "garage-tpu") -> None:
        self.sink = sink
        self.service_name = service_name
        if sink and self._task is None:
            try:
                self._task = asyncio.get_event_loop().create_task(self._flusher())
            except RuntimeError:
                pass  # no loop yet; caller may start() later

    async def start(self) -> None:
        if self.sink and (self._task is None or self._task.done()):
            self._task = asyncio.get_event_loop().create_task(self._flusher())

    async def stop(self) -> None:
        if self._task is not None:
            from .aio import reap

            await reap([self._task], log=logger, what="trace flusher")
            self._task = None
        await self._flush()
        if self._session is not None:
            await self._session.close()
            self._session = None

    def span(
        self, name: str, remote_parent: RemoteParent | None = None,
        layer: str = "none", **attrs,
    ):
        """Context manager for a traced operation.  Cheap no-op (no span
        object at all, `as` binds None) when tracing is off.

        `remote_parent` (from `extract()`) parents the span across the
        wire.  When given it WINS over any context-inherited span; the
        traceparent the caller serialized is the truth.  On the
        local-dispatch shortcut both agree — the injected traceparent is
        the caller's current span.

        `layer` (one of `LAYERS`) is where the event-loop meter files
        the span's on-loop time."""
        if not self.enabled:
            return NOOP_SPAN
        return Span(name, remote_parent or _current.get(), attrs, layer, self)

    def current(self) -> Span | None:
        return _current.get()

    # --- cross-node propagation -----------------------------------------------

    def inject(self) -> bytes | None:
        """Serialize the current span for the wire: 16-byte trace id +
        8-byte span id + flags (W3C traceparent fields, binary).  None
        when tracing is off or no span is active — callers then omit the
        frame field entirely, keeping the disabled wire format identical."""
        if not self.enabled:
            return None
        s = _current.get()
        if s is None:
            return None
        return s.trace_id + s.span_id + bytes((FLAG_SAMPLED,))

    def extract(self, tp: bytes | None) -> RemoteParent | None:
        """Parse a traceparent produced by `inject()` on another node.
        Malformed or absent input yields None (the span becomes a local
        root — never an error: tracing must not fail requests)."""
        if not isinstance(tp, (bytes, bytearray)) or len(tp) != TRACEPARENT_LEN:
            return None
        tp = bytes(tp)
        return RemoteParent(tp[:16], tp[16:24], bool(tp[24] & FLAG_SAMPLED))

    # --- export ---------------------------------------------------------------

    async def _flusher(self) -> None:
        while True:
            await asyncio.sleep(FLUSH_INTERVAL)
            try:
                await self._flush()
            except Exception as e:  # noqa: BLE001 — tracing must never kill the daemon
                logger.debug("trace export failed: %r", e)

    async def _flush(self) -> None:
        if not self._buf or not self.sink:
            return
        spans, self._buf = self._buf, []
        import aiohttp

        if self._session is None or self._session.closed:
            self._session = aiohttp.ClientSession()
        url = self.sink.rstrip("/") + "/v1/traces"
        # chunked export: one giant POST can exceed a collector's request
        # size limit (aiohttp servers default to 1 MiB) and lose the whole
        # batch; ~500 spans stays comfortably under typical limits
        for i in range(0, len(spans), 500):
            payload = self._otlp(spans[i : i + 500])
            async with self._session.post(
                url, json=payload, timeout=aiohttp.ClientTimeout(total=10)
            ) as resp:
                if resp.status >= 400:
                    logger.debug("trace sink returned %d", resp.status)

    def _otlp(self, spans: list[Span]) -> dict:
        """OTLP/HTTP JSON encoding (trace ids hex, times in ns strings)."""

        def attr(k, v):
            if isinstance(v, bool):
                val = {"boolValue": v}
            elif isinstance(v, int):
                val = {"intValue": str(v)}
            elif isinstance(v, float):
                val = {"doubleValue": v}
            else:
                val = {"stringValue": str(v)}
            return {"key": k, "value": val}

        return {
            "resourceSpans": [
                {
                    "resource": {
                        "attributes": [attr("service.name", self.service_name)]
                    },
                    "scopeSpans": [
                        {
                            "scope": {"name": "garage-tpu"},
                            "spans": [
                                {
                                    "traceId": s.trace_id.hex(),
                                    "spanId": s.span_id.hex(),
                                    **(
                                        {"parentSpanId": s.parent_id.hex()}
                                        if s.parent_id
                                        else {}
                                    ),
                                    "name": s.name,
                                    "kind": 1,
                                    "startTimeUnixNano": str(wall_ns(s.start_ns)),
                                    "endTimeUnixNano": str(wall_ns(s.end_ns)),
                                    "attributes": [
                                        attr(k, v) for k, v in s.attrs.items()
                                    ],
                                    "status": {"code": 1 if s.ok else 2},
                                }
                                for s in spans
                            ],
                        }
                    ],
                }
            ]
        }


# process-wide tracer (configured by the daemon from admin.trace_sink)
tracer = Tracer()


def span(name: str, layer: str = "none", **attrs):
    return tracer.span(name, layer=layer, **attrs)
