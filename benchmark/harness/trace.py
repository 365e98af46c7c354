"""Reduction of a `jax.profiler` trace (`.xplane.pb`) to device busy time.

Written after reading one trace of the fused encode+hash and reconstruct
dispatches on a TPU v5 lite by hand (`tests/record_trace.py` prints it):

- a plane named `/device:TPU:<n>` per chip; its line `XLA Modules` has one
  event per program execution (`jit_body(<hash>)`: the program has no
  stable names yet), its line `XLA Ops` one event per HLO op executed, the
  ops of a `while` nested inside it (so durations are not summed: unions);
- event times are nanoseconds from the start of the profiling session,
  the same base on the device planes and on `/host:CPU`;
- host-to-device and device-to-host copies appear on the host plane only
  (`tpu::System::TransferToDevice`), not as device ops: the device is
  idle, by this reduction, while a shard batch is being uploaded.

Device busy is the union of the `XLA Modules` intervals clipped to the
traced span, averaged over the device planes.  Needs JAX only to read the
file (`jax.profiler.ProfileData`).
"""

from __future__ import annotations

import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
MODULES_LINE, OPS_LINE = "XLA Modules", "XLA Ops"
_OP_KIND = re.compile(r"^%?([A-Za-z_][A-Za-z_\-]*)")


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Disjoint sorted intervals covering the same points."""
    out: list[tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def gaps(busy: list[tuple[float, float]], lo: float, hi: float) -> list[tuple[float, float]]:
    """The complement of disjoint sorted `busy` within [lo, hi]."""
    out, at = [], lo
    for s, e in busy:
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if hi > at:
        out.append((at, hi))
    return out


def op_kind(name: str) -> str:
    """`%fusion.475 = s32[4]... fusion(...)` -> `fusion`: the name the trace
    prints, without its number and shapes, so that the same op of every
    batch bucket adds up."""
    m = _OP_KIND.match(name)
    return m.group(1).rstrip("-_") if m else name[:40]


def reduce_planes(planes: list[dict], lo_ns: float, hi_ns: float) -> dict:
    """`planes`: [{"modules": [(start_ns, dur_ns, name)], "ops": [...]}], one
    per device.  Busy seconds inside [lo_ns, hi_ns], averaged over devices,
    the busy intervals of the first device, and the ops that took most time."""
    if not planes:
        raise ValueError("the trace holds no device plane")
    busy_each, first_busy = [], []
    by_kind: dict[str, float] = {}
    for i, p in enumerate(planes):
        iv = clip(union([(s, s + d) for s, d, _n in p["modules"]]), lo_ns, hi_ns)
        busy_each.append(sum(e - s for s, e in iv))
        if i == 0:
            first_busy = iv
        # a `while` holds its body's ops nested inside its own interval:
        # the body's ops carry the time, the container is left out
        for s, d, n in p["ops"]:
            if s + d > lo_ns and s < hi_ns:
                k = op_kind(n)
                if k != "while":
                    by_kind[k] = by_kind.get(k, 0.0) + d / len(planes)
    top = sorted(by_kind.items(), key=lambda kv: -kv[1])[:10]
    return {
        "busy_s": sum(busy_each) / len(busy_each) / 1e9,
        "window_s": (hi_ns - lo_ns) / 1e9,
        "executions": sum(len(p["modules"]) for p in planes),
        "busy_intervals_ns": first_busy,
        "device_ops": [[k, v / 1e9] for k, v in top],
    }


def read_xplane(path: str) -> list[dict]:
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(path).planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        p = {"modules": [], "ops": []}
        for line in plane.lines:
            key = {MODULES_LINE: "modules", OPS_LINE: "ops"}.get(line.name)
            if key:
                p[key] = [(e.start_ns, e.duration_ns, e.name) for e in line.events]
        planes.append(p)
    return planes


def idle_by_host_activity(busy_ns, lo_ns, hi_ns, samples, period_s: float) -> list[list]:
    """Device-idle seconds by what the event-loop thread was in.  `samples`:
    [(t_ns on the trace's clock, label)] taken every `period_s`; a sample
    that falls in an idle gap of the device charges one period to its label."""
    idle = gaps(busy_ns, lo_ns, hi_ns)
    out: dict[str, float] = {}
    j = 0
    for t, label in sorted(samples):
        while j < len(idle) and idle[j][1] < t:
            j += 1
        if j < len(idle) and idle[j][0] <= t <= idle[j][1]:
            out[label] = out.get(label, 0.0) + period_s
    return [[k, v] for k, v in sorted(out.items(), key=lambda kv: -kv[1])[:10]]
