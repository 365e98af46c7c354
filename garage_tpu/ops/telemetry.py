"""TPU offload telemetry: every device dispatch leaves a metrics trail.

The driver-gating throughput metric failed silently for five rounds
partly because the offload path exported nothing — no dispatch counts,
no batch sizes, no platform — so a wedge or a silent CPU fallback looked
identical to healthy traffic until a human read a JSON artifact.  This
module is the shared recorder the EC codec (ops/ec_tpu.py), the batched
hasher (ops/hash_tpu.py) and the block codec layer (block/codec/) call
around each device dispatch.  Families (rendered by the admin /metrics
endpoint via utils/metrics.py; catalogued in doc/monitoring.md):

  tpu_codec_dispatch_total{kernel,platform}      dispatches
  tpu_codec_bytes_total{kernel,platform}         payload bytes processed
  tpu_codec_batch_size{kernel}                   blocks/dispatch histogram
  tpu_codec_dispatch_duration{kernel,platform}   seconds histogram
  jax_backend_platform{platform}                 1 for each backend that
                                                 has actually served a
                                                 dispatch (scrape-time) —
                                                 a bench believing it ran
                                                 on TPU while the gauge
                                                 says {platform="cpu"} is
                                                 the five-round bug class
                                                 this plane exists for
  tpu_mesh_engaged_total{kernel,platform,devices}  dispatches actually
                                                 served by the multi-
                                                 device shard_map mesh
                                                 (vs falling back to a
                                                 single device) — the
                                                 repair planner's batch
                                                 coalescing exists to
                                                 make this advance

Codec X-ray families (ISSUE 17 — the instrument ROADMAP item 1's
pjit/AOT/double-buffering rewrite aims with; catalogued in
doc/monitoring.md §"Codec X-ray"):

  tpu_codec_pad_requested_total{kernel}   batch rows callers asked for
  tpu_codec_pad_padded_total{kernel}      batch rows actually dispatched
                                          (after pow2 bucketing) — the
                                          cumulative quotient is the
                                          pad-waste fraction
  tpu_codec_pad_waste{kernel}             cumulative pad-waste gauge,
                                          1 - requested/padded
  tpu_codec_transfer_duration{kernel}     host<->device copies per
                                          dispatch: pad, upload, the
                                          download of the results (H)
  tpu_codec_compute_duration{kernel}      enqueue + waiting for the
                                          device (`block_until_ready`
                                          inside the bracket) (H)
  tpu_codec_dispatch_cpu_seconds_total{kernel,platform}
                                          CPU seconds of the dispatching
                                          thread inside the dispatch;
                                          wall - cpu - device wait = the
                                          thread stood without the CPU
                                          (the interpreter lock the event
                                          loop holds, or descheduled)
  tpu_compile_duration{cache}             compile-event wall seconds (H):
                                          one observation per
                                          instrumented-cache miss AND per
                                          first dispatch of a cold
                                          (kernel, bucket) shape class —
                                          count = compile events, sum =
                                          total seconds lost to lowering
"""

from __future__ import annotations

import time
from contextlib import contextmanager, nullcontext

from ..utils.metrics import SIZE_BUCKETS, registry

registry.set_buckets("tpu_codec_batch_size", SIZE_BUCKETS)

_platforms_seen: set[str] = set()

# (kernel, padded-bucket) shape classes that have dispatched at least
# once in this process: the first dispatch of a class pays XLA lowering
# inside its wall time, so it is recorded as a compile event; repeats
# are executable-cache hits and record nothing
_shape_seen: set[tuple[str, int]] = set()


def annotate(name: str, platform: str):
    """A `jax.profiler.TraceAnnotation`: the dispatch and its steps run
    synchronously on one thread, so they nest correctly as TraceMe
    events and a profiler session's host plane carries the program's
    own names on the device trace's clock (near-free with no session).
    Host-codec dispatches never import jax for it."""
    if platform in ("host", ""):
        return nullcontext()
    import jax.profiler

    return jax.profiler.TraceAnnotation(name)


def resolved_platform(pin: str | None = None) -> str:
    """The platform label for a dispatch: the pinned platform if the
    caller has one, else jax's resolved default backend.  A backend
    that cannot initialize raises: naming it "unknown" would select the
    host paths (interpret-mode Pallas) on a chip we failed to name."""
    if pin:
        return pin
    import jax

    return jax.default_backend()


def is_host_platform(platform: str | None) -> bool:
    """THE definition of "this dispatch would run on the host" — the
    one backend-string comparison the codec surface is allowed (and
    lint-forced, rule `backend-gate`) to route through.  Scattered
    `plat == "cpu"` checks are how PR 4's silent single-device fallback
    stayed invisible; a shared gate keeps every fallback decision
    consistent and greppable."""
    return platform is None or platform in ("cpu", "")


def platforms_seen() -> list[str]:
    """Backends that have actually served a dispatch in this process
    (the label set behind the `jax_backend_platform` gauge) — consumed
    by the cluster telemetry digest (rpc/telemetry_digest.py)."""
    return sorted(_platforms_seen)


def note_platform(platform: str) -> None:
    """Register the scrape-time backend gauge once per resolved platform
    (labels are fixed at registration, so the platform must already be
    resolved — which it is by the time any dispatch runs)."""
    if platform in _platforms_seen:
        return
    _platforms_seen.add(platform)
    registry.register_gauge(
        "jax_backend_platform", (("platform", platform),), lambda: 1.0
    )


def mesh_engaged(kernel: str, platform: str, devices: int) -> None:
    """Count one dispatch that actually ran on the multi-device mesh
    path.  Recorded by EcTpu AFTER the mesh call returns (a mesh attempt
    that fell back to single-device must not count — the whole point is
    distinguishing the two)."""
    registry.incr(
        "tpu_mesh_engaged_total",
        (
            ("kernel", kernel),
            ("platform", platform),
            ("devices", str(devices)),
        ),
    )


def compile_event(cache: str, secs: float) -> None:
    """Record one compile event (wall seconds lost to lowering) for a
    cache/kernel family.  Two producers feed this histogram: the
    instrumented-cache miss path (utils/compile_cache.py — jit/trace
    construction) and the first dispatch of a cold (kernel, bucket)
    shape class (DispatchRecord._finish — the XLA lowering a fresh
    shape pays inside its first wall time)."""
    registry.observe("tpu_compile_duration", (("cache", cache),), secs)


def record_pad(kernel: str, requested: int, padded: int) -> None:
    """Account one dispatch's bucket padding: `requested` batch rows
    asked for, `padded` rows actually shipped.  The cumulative quotient
    is the per-kernel pad-waste fraction (gauge `tpu_codec_pad_waste`),
    bounded at 0.5 by pow2 bucketing — a value above that means a pad
    path stopped routing through ops/bucketing.py."""
    lbl = (("kernel", kernel),)
    registry.incr("tpu_codec_pad_requested_total", lbl, float(requested))
    registry.incr("tpu_codec_pad_padded_total", lbl, float(max(padded, requested)))
    req = registry.counters[("tpu_codec_pad_requested_total", lbl)]
    pad = registry.counters[("tpu_codec_pad_padded_total", lbl)]
    if pad > 0:
        registry.set_gauge(
            "tpu_codec_pad_waste", lbl, round(1.0 - req / pad, 4)
        )


def wait_ready(out):
    """Inside `rec.compute()`: start the results' copies to the host,
    then wait for the device.  The copy is queued behind the kernel, so
    it lands while this thread takes the interpreter lock back from the
    event loop, and the download that follows rarely has to let go of
    it again — each handoff costs up to a switch interval (5 ms) on a
    busy node (PERF.md §6 PR 27: 18 ms a dispatch against 29-31 ms
    without, beside a spinning thread)."""
    import jax

    for o in jax.tree_util.tree_leaves(out):
        o.copy_to_host_async()
    return jax.block_until_ready(out)


class DispatchRecord:
    """Per-dispatch X-ray handle yielded by `dispatch()`: the call site
    reports its pad geometry and brackets its transfer/compute phases;
    the exit path turns those into pad-waste counters and
    first-dispatch compile events."""

    __slots__ = ("kernel", "platform", "requested", "padded",
                 "transfer_secs", "compute_secs")

    def __init__(self, kernel: str, platform: str):
        self.kernel = kernel
        self.platform = platform
        self.requested: int | None = None
        self.padded: int | None = None
        self.transfer_secs = 0.0
        self.compute_secs = 0.0

    def pad(self, requested: int, padded: int) -> None:
        """Report this dispatch's batch geometry (first call wins: a
        mesh attempt that fell back must not double-count its pad)."""
        if self.requested is not None:
            return
        self.requested, self.padded = int(requested), int(padded)
        record_pad(self.kernel, requested, padded)

    @contextmanager
    def transfer(self, step: str):
        """Bracket a host<->device copy (`step`: "pad" — pad copy and
        upload — or "download", the fetch back to numpy of results the
        device has already finished)."""
        t0 = time.perf_counter()
        try:
            with annotate(step, self.platform):
                yield
        finally:
            dt = time.perf_counter() - t0
            self.transfer_secs += dt
            registry.observe(
                "tpu_codec_transfer_duration", (("kernel", self.kernel),), dt
            )

    @contextmanager
    def compute(self):
        """Bracket the device call AND the wait for its results: the
        jitted call returns at the enqueue, so the call site passes the
        outputs through `wait_ready` inside this bracket."""
        t0 = time.perf_counter()
        try:
            with annotate("execute", self.platform):
                yield
        finally:
            dt = time.perf_counter() - t0
            self.compute_secs += dt
            registry.observe(
                "tpu_codec_compute_duration", (("kernel", self.kernel),), dt
            )

    def _finish(self, wall: float) -> None:
        # first dispatch of a cold (kernel, bucket) shape class pays XLA
        # lowering inside `wall`; repeats are executable-cache hits and
        # record no compile time (asserted by tests/test_codec_xray.py).
        # The native "host" paths have no lowering step at all, so they
        # never produce shape-class compile events.
        if self.padded is not None and self.platform != "host":
            key = (self.kernel, self.padded)
            if key not in _shape_seen:
                _shape_seen.add(key)
                compile_event(self.kernel, wall)


@contextmanager
def dispatch(kernel: str, platform: str, batch: int, nbytes: int):
    """Instrument one device dispatch: counters + batch-size histogram on
    entry, duration histogram (and `_errors` counter, matching the
    registry-timer contract) around the body.  Yields a DispatchRecord
    the call site MAY feed pad geometry and transfer/compute phases —
    plain `with dispatch(...):` callers keep working unchanged."""
    lbl = (("kernel", kernel), ("platform", platform))
    registry.incr("tpu_codec_dispatch_total", lbl)
    registry.incr("tpu_codec_bytes_total", lbl, nbytes)
    registry.observe("tpu_codec_batch_size", (("kernel", kernel),), float(batch))
    note_platform(platform)
    rec = DispatchRecord(kernel, platform)
    t0, cpu0 = time.perf_counter(), time.thread_time_ns()
    try:
        with annotate("dispatch:" + kernel, platform):
            yield rec
    except BaseException:
        registry.observe(
            "tpu_codec_dispatch_duration", lbl, time.perf_counter() - t0
        )
        registry.incr("tpu_codec_dispatch_duration_errors", lbl)
        raise
    finally:
        registry.incr(
            "tpu_codec_dispatch_cpu_seconds_total", lbl,
            (time.thread_time_ns() - cpu0) * 1e-9,
        )
    wall = time.perf_counter() - t0
    registry.observe("tpu_codec_dispatch_duration", lbl, wall)
    rec._finish(wall)


def reset_xray_state() -> None:
    """Drop the process-wide shape-class state (tests that assert
    cold-class compile accounting need a cold process view)."""
    _shape_seen.clear()


def _finite_quantile(q: float | None) -> float | None:
    """Histogram quantiles above the top bucket come back as +Inf, which
    is not JSON-able; clamp to 2x the largest latency bucket bound so
    the snapshot stays serializable while still reading as 'way over'."""
    if q is None:
        return None
    return min(q, 16.384)


def codec_snapshot(r=None) -> dict:
    """One JSON-able view of the codec X-ray, computed from a metrics
    registry (default: the process registry).  The SINGLE source the
    digest `codec.*` keys, `GET /v1/codec` and the admin-RPC `codec` op
    all read, so the same numbers appear on every surface (the
    acceptance bar for ISSUE 17)."""
    r = r or registry
    req = r.counter_family_sum("tpu_codec_pad_requested_total")
    pad = r.counter_family_sum("tpu_codec_pad_padded_total")
    cm = r.family_merge("tpu_compile_duration")
    ll99 = _finite_quantile(
        r.family_quantile("block_codec_batch_lane_linger", 0.99)
    )
    kernels: dict[str, dict] = {}
    for (name, labels), v in sorted(r.counters.items()):
        if name not in (
            "tpu_codec_pad_requested_total", "tpu_codec_pad_padded_total"
        ):
            continue
        kern = dict(labels).get("kernel", "")
        k = kernels.setdefault(
            kern, {"requested": 0, "padded": 0, "padWaste": 0.0},
        )
        field = "requested" if name.endswith("requested_total") else "padded"
        k[field] += int(v)
    for k in kernels.values():
        if k["padded"]:
            k["padWaste"] = round(1.0 - k["requested"] / k["padded"], 4)
    compile_by_cache: dict[str, dict] = {}
    for (name, labels), (cnt, total, _b) in sorted(r.durations.items()):
        if name != "tpu_compile_duration":
            continue
        cache = dict(labels).get("cache", "")
        compile_by_cache[cache] = {
            "events": int(cnt), "secs": round(total, 6),
        }
    lanes: dict[str, dict] = {}
    for (name, labels), (cnt, total, _b) in sorted(r.durations.items()):
        if name != "block_codec_batch_lane_linger":
            continue
        ld = dict(labels)
        lane = lanes.setdefault(ld.get("lane", ""), {"flush": {}})
        p99 = _finite_quantile(r.quantile(name, labels, 0.99))
        lane["flush"][ld.get("flush", "")] = {
            "blocks": int(cnt),
            "lingerSecsTotal": round(total, 6),
            "lingerP99": round(p99, 6) if p99 is not None else None,
        }
    return {
        "dispatches": int(r.counter_family_sum("tpu_codec_dispatch_total")),
        "padWaste": round(1.0 - req / pad, 4) if pad else 0.0,
        "compileEvents": int(cm[0]) if cm else 0,
        "compileSecs": round(cm[1], 6) if cm else 0.0,
        "laneLingerP99": round(ll99, 6) if ll99 is not None else 0.0,
        "platforms": platforms_seen(),
        "kernels": kernels,
        "compile": compile_by_cache,
        "lanes": lanes,
    }
