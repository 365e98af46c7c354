"""Readers of the per-layer metrics.

A per-layer metric is a file `benchmark/layers/<name>.json`: its layer,
unit, the end-to-end metric it should move, its cells, and a `read`
entry that one of the few readers here understands.  A later PR adds a
metric over a new counter as a file; a new KIND of source is a new
reader here, added by a `benchmark` PR.

All readings are deltas over the window (a snapshot of the program's
registry at its start and at its close), never process totals.  A reader
that finds nothing to read returns None and the metric is left out of
the line; it never returns 0 for something that was not there.

`read` kinds:

  ratio   {"num": [term...], "den": [term...], "scale": x}
          scale * sum(num) / sum(den); None when den is 0.  A term is one of
            {"counter": name, "labels": {...}}          counter delta
            {"duration_sum": name, "labels": {...}}     seconds observed
            {"duration_count": name, "labels": {...}}   observations
            {"client_ops": "PUT" | "GET" | ... | "all"} requests the clients sent in the window
            {"client_ops": "failed"}                    those not answered as expected
            {"const": x}
          A label value "$platform" stands for the JAX platform of the run.
  strain  {"field": name}     a field of HostStrain over the window
  trace   {"field": name}     a field of the trace reduction (trace.py, roofline.py)
  compiles                    persistent-cache misses inside the window
"""

from __future__ import annotations


def snapshot() -> dict:
    """The program's registry, copied: counters and (count, sum) of durations."""
    from garage_tpu.utils.metrics import registry

    return {
        "counters": dict(registry.counters),
        "durations": {k: (v[0], v[1]) for k, v in list(registry.durations.items())},
    }


def _matches(lbl: tuple, want: dict) -> bool:
    have = dict(lbl)
    return all(have.get(k) == v for k, v in want.items())


def delta(term: dict, before: dict, after: dict, ctx: dict) -> float:
    """One term of a `ratio` over the window (see the module's docstring)."""
    if "const" in term:
        return float(term["const"])
    if "client_ops" in term:
        return float(ctx["client_ops"].get(term["client_ops"], 0))
    want = {k: (ctx["platform"] if v == "$platform" else v)
            for k, v in term.get("labels", {}).items()}
    if "counter" in term:
        name = term["counter"]
        return sum(
            v - before["counters"].get(key, 0.0)
            for key, v in after["counters"].items()
            if key[0] == name and _matches(key[1], want)
        )
    name = term.get("duration_sum") or term.get("duration_count")
    if name is None:
        raise ValueError(f"unknown term {term}")
    idx = 1 if "duration_sum" in term else 0
    return sum(
        v[idx] - before["durations"].get(key, (0, 0.0))[idx]
        for key, v in after["durations"].items()
        if key[0] == name and _matches(key[1], want)
    )


def read(spec: dict, before: dict, after: dict, ctx: dict) -> float | None:
    """The value of one per-layer metric, or None where there was nothing to read."""
    r = spec["read"]
    kind = r["kind"]
    if kind == "ratio":
        num = sum(delta(t, before, after, ctx) for t in r["num"])
        den = sum(delta(t, before, after, ctx) for t in r["den"])
        if den <= 0:
            return None
        return float(r.get("scale", 1.0)) * num / den
    if kind == "strain":
        return ctx["strain"].get(r["field"])
    if kind == "trace":
        return (ctx.get("trace") or {}).get(r["field"])
    if kind == "compiles":
        return float(ctx["compiles_in_window"])
    raise ValueError(f"unknown reader kind {kind!r} in {spec.get('name')}")
