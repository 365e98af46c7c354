#!/usr/bin/env python3
"""Dashboard lint: dashboards must not silently rot.

Cross-checks every metric family referenced by the Grafana dashboard
(`script/telemetry/grafana-garage-tpu-dashboard.json`) against

  1. a live-node Prometheus scrape (`/metrics` and `/metrics/cluster`) —
     families the running code actually exports, and
  2. the catalogue in `doc/monitoring.md` — families documented to exist
     (some only appear under load, e.g. `repair_plan_*` while a plan
     runs, `tpu_mesh_engaged_total` on a real mesh).

A family referenced by a panel but present in NEITHER is a lint error:
either the panel is stale (family renamed) or the family was never
documented.  Run as a tier-1 test (tests/test_dashboard_lint.py) so a
rename that forgets the dashboard or the doc fails CI, and as a CLI
against a real deployment:

    python script/dashboard_lint.py --url http://node:3903 --token $TOK
    python script/dashboard_lint.py --scrape metrics.txt [--scrape more.txt]
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DASHBOARD = os.path.join(
    REPO, "script", "telemetry", "grafana-garage-tpu-dashboard.json"
)
DOC = os.path.join(REPO, "doc", "monitoring.md")

NAME_RE = re.compile(r"[a-zA-Z_:][a-zA-Z0-9_:]*")
# PromQL functions / keywords / literal units that tokenize like names
PROMQL_NOISE = {
    "rate", "irate", "increase", "delta", "idelta", "sum", "avg", "max",
    "min", "count", "topk", "bottomk", "quantile", "stddev", "stdvar",
    "by", "without", "on", "ignoring", "group_left", "group_right",
    "histogram_quantile", "label_replace", "label_join", "clamp_min",
    "clamp_max", "abs", "ceil", "floor", "round", "exp", "ln", "log2",
    "log10", "sqrt", "time", "timestamp", "vector", "scalar", "sort",
    "sort_desc", "absent", "changes", "deriv", "predict_linear", "resets",
    "and", "or", "unless", "offset", "bool", "count_values", "avg_over_time",
    "sum_over_time", "max_over_time", "min_over_time", "last_over_time",
}
# suffixes the exposition adds to a histogram family
HIST_SUFFIXES = ("_bucket", "_count", "_sum")

# --- cardinality guard --------------------------------------------------------
# Label names reserved for STATICALLY-bounded value sets: a `key` or
# `bucket` label whose values track live objects/tenants is how
# exposition cardinality explodes at millions of users.  Hot-key data is
# served from the traffic observatory's sketch JSON endpoints
# (`/v1/traffic`, rpc/traffic.py) ONLY — never as per-key Prometheus
# series.  A family may carry one of these labels only by declaring the
# complete value set here (histogram `le` is the exposition's own).
# `src`/`dst` carry node-id prefixes (bounded by cluster size, not object
# count) and `severity` a three-value enum — guarded so a new family
# cannot adopt them without declaring its bound below.
GUARDED_LABELS = ("key", "bucket", "src", "dst", "severity", "class")

# codec X-ray label sets (ISSUE 17): every kernel name a dispatch site
# passes and every compile-accounting cache label.  The compile family's
# values are the instrumented-cache names PLUS the device kernel names
# (a shape-class first dispatch attributes its lazy-lowering wall to the
# kernel; instrumented_cache misses attribute trace time to the cache).
_CODEC_KERNELS = frozenset({
    "ec_encode", "ec_reconstruct", "ec_encode_hash",
    "ec_encode_host", "ec_decode_host", "blake3_hash",
})
_COMPILE_CACHES = frozenset({
    "blake3_hasher", "ec_apply", "ec_apply_legacy", "ec_apply_mesh",
    "ec_encode_hash", "ec_batch_bucket", "ec_dispatch_bucket",
    "ec_recon_matrix", "ec_encode", "ec_reconstruct", "blake3_hash",
})
# rebalance observatory (ISSUE 18): src/dst are hex node-id prefixes —
# not statically enumerable, but bounded by cluster membership, so the
# declared "set" is a shape contract (compiled regex) instead of a
# frozenset.  lint_exposition accepts either form.
_HEX16 = re.compile(r"[0-9a-f]{1,16}")
_EVENT_SEVERITIES = frozenset({"info", "warn", "critical"})
# durability ledger classes (block/durability.py DUR_CLASSES)
_DUR_CLASSES = frozenset({"healthy", "degraded", "at_risk", "unreadable"})
# tenant SLO classes (ISSUE 20): operator-declared `[tenants]` section
# names — bounded by config, not by live tenants, so the contract is a
# shape regex (utils/config.py validation rejects empty names; tenant
# KEY IDS never become labels at all)
_TENANT_CLASS = re.compile(r"[a-zA-Z0-9][a-zA-Z0-9_.\-]{0,63}")
# event-loop meter (ISSUE 27, utils/flight.py LoopMeter): `layer` is
# utils/tracing.py LAYERS; `span` is a span's or loop label's NAME — a
# phase, an endpoint path, a worker's name: bounded by the code, so a
# shape contract, and never an id (no run of 12 hex digits)
_LOOP_LAYERS = frozenset({
    "api", "block", "codec", "table", "rpc", "background", "none",
})
_SPAN_NAME = re.compile(r"(?!.*[0-9a-f]{12})[A-Za-z][A-Za-z0-9_:/ .\-]{0,63}")
_CLOCK_CAUSES = frozenset({"host", "interpreter"})
_STANDSTILL_CAUSES = _CLOCK_CAUSES | {"loop_held"}
BOUNDED_LABEL_VALUES: dict[str, dict[str, object]] = {
    # A family listed here has EVERY listed label enforced against its
    # declared value set by lint_exposition (not just GUARDED_LABELS):
    # growing a new kernel/cache/lane means enrolling it here, or the
    # exposition lint fails — the declaration cannot silently rot.
    "tpu_codec_pad_requested_total": {"kernel": _CODEC_KERNELS},
    "tpu_codec_pad_padded_total": {"kernel": _CODEC_KERNELS},
    "tpu_codec_pad_waste": {"kernel": _CODEC_KERNELS},
    "tpu_codec_transfer_duration": {"kernel": _CODEC_KERNELS},
    "tpu_codec_compute_duration": {"kernel": _CODEC_KERNELS},
    "tpu_codec_dispatch_cpu_seconds_total": {"kernel": _CODEC_KERNELS},
    "tpu_compile_duration": {"cache": _COMPILE_CACHES},
    "event_loop_busy_seconds_total": {
        "layer": _LOOP_LAYERS, "span": _SPAN_NAME,
    },
    # the meter's standstill clock and long steps (utils/flight.py
    # LoopMeter.CAUSES), and the failure paths that ask it
    "event_loop_long_steps_total": {"layer": _LOOP_LAYERS, "span": _SPAN_NAME},
    "event_loop_long_step_seconds_total": {"layer": _LOOP_LAYERS, "span": _SPAN_NAME},
    "process_standstill_seconds_total": {"cause": _CLOCK_CAUSES},
    "process_standstills_total": {"cause": _CLOCK_CAUSES},
    "rpc_timeouts_in_standstill_total": {"cause": _STANDSTILL_CAUSES},
    "api_s3_refused_total": {
        "cause": frozenset({"quorum", "shed", "internal", "other"}),
        "standstill": _STANDSTILL_CAUSES | {"none"},
    },
    "block_codec_batch_lane_linger": {
        "lane": frozenset({"encode", "decode"}),
        "flush": frozenset({"full", "linger"}),
    },
    "layout_transition_pair_bytes_total": {"src": _HEX16, "dst": _HEX16},
    "flight_events_total": {"severity": _EVENT_SEVERITIES},
    "durability_blocks": {"class": _DUR_CLASSES},
    # tenant observatory (ISSUE 20): per-CLASS counters only — per-key
    # accounting lives in /v1/cluster/tenants JSON
    "api_tenant_class_requests_total": {"class": _TENANT_CLASS},
    "api_tenant_class_errors_total": {"class": _TENANT_CLASS},
    "api_tenant_class_over_latency_total": {"class": _TENANT_CLASS},
    "api_tenant_class_sheds_total": {"class": _TENANT_CLASS},
}

_LABEL_RE = re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\.)*)"')


def families_in_expr(expr: str) -> set[str]:
    """Metric families referenced by one PromQL expression."""
    # strip label selectors, grouping clauses and range selectors first:
    # what's left that looks like a name is a function or a family
    expr = re.sub(r"\{[^}]*\}", " ", expr)
    expr = re.sub(r"\b(by|without|on|ignoring|group_left|group_right)\s*"
                  r"\([^)]*\)", " ", expr)
    expr = re.sub(r"\[[^\]]*\]", " ", expr)
    out = set()
    for tok in NAME_RE.findall(expr):
        if tok in PROMQL_NOISE or len(tok) < 4 or "_" not in tok:
            continue
        out.add(tok)
    return out


def base_family(name: str) -> str:
    """Strip histogram exposition suffixes: `x_duration_bucket` and
    `x_duration_sum` both reference family `x_duration`."""
    for suf in HIST_SUFFIXES:
        if name.endswith(suf):
            return name[: -len(suf)]
    return name


def families_in_dashboard(path: str = DASHBOARD) -> dict[str, list[str]]:
    """family -> panel titles referencing it."""
    with open(path) as f:
        dash = json.load(f)
    out: dict[str, list[str]] = {}
    for panel in dash.get("panels", []):
        title = panel.get("title", "?")
        for target in panel.get("targets", []):
            expr = target.get("expr")
            if not expr:
                continue
            for fam in families_in_expr(expr):
                out.setdefault(base_family(fam), []).append(title)
    return out


def families_in_doc(path: str = DOC) -> set[str]:
    """Every metric-family-shaped token in backticks in the catalogue.
    Over-collects config knobs etc. — harmless for an allowlist.  Also
    expands the `` `x_counter` / `_duration` `` shorthand the tables
    use for counter+histogram pairs."""
    with open(path) as f:
        text = f.read()
    # fenced code blocks first: their ``` markers would desynchronize
    # the inline-backtick pairing below (an odd number of backticks per
    # fence), silently dropping every span after the first fence
    text = re.sub(r"```.*?```", " ", text, flags=re.S)
    out: set[str] = set()
    spans = re.findall(r"`([^`]+)`", text)
    for i, span in enumerate(spans):
        for tok in NAME_RE.findall(span):
            if "_" in tok and tok == tok.lower():
                out.add(base_family(tok))
        # shorthand: `a_counter` / `_duration` -> a_duration too
        if span.startswith("_") and i > 0:
            for tok in NAME_RE.findall(spans[i - 1]):
                if "_" in tok:
                    out.add(base_family(tok.rsplit("_", 1)[0] + span))
    return out


def lint_exposition(text: str) -> dict[str, str]:
    """Strict Prometheus-exposition parse: every family declares `# TYPE`
    before its first sample, no family declared twice, no duplicate
    (name, labels) sample, every value a number.  Returns family -> type;
    raises AssertionError with the offending line otherwise.  (Same
    rules as the metrics-lint test in tests/test_observability.py.)"""
    sample_re = re.compile(r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{[^}]*\})?\s(\S+)$")
    types: dict[str, str] = {}
    seen: set[tuple[str, str]] = set()
    started: set[str] = set()
    for lineno, line in enumerate(text.splitlines(), 1):
        if not line.strip():
            continue
        if line.startswith("# TYPE "):
            fam, typ = line[len("# TYPE "):].rsplit(" ", 1)
            assert NAME_RE.fullmatch(fam), line
            assert typ in ("counter", "gauge", "histogram"), line
            assert fam not in types, f"family {fam} declared twice"
            assert fam not in started, f"TYPE for {fam} after its samples"
            types[fam] = typ
            continue
        if line.startswith("#"):
            continue
        m = sample_re.match(line)
        assert m, f"line {lineno} unparseable: {line!r}"
        name, labels = m.group(1), m.group(2) or ""
        float(m.group(3))
        base = base_family(name)
        declared = BOUNDED_LABEL_VALUES.get(base, {})
        for lname, lval in _LABEL_RE.findall(labels):
            if lname in declared:
                # enrolled family: the label's value set is a contract —
                # a frozenset enumerates it, a compiled regex bounds its
                # shape (node-id prefixes: bounded by membership)
                allowed = declared[lname]
                ok = (
                    lval in allowed
                    if isinstance(allowed, frozenset)
                    else bool(allowed.fullmatch(lval))
                )
                assert ok, (
                    f"family {base} label {lname}={lval!r} is not in its "
                    "declared value set — enroll the new value in "
                    "BOUNDED_LABEL_VALUES (script/dashboard_lint.py) or "
                    "it is unbounded cardinality in disguise"
                )
                continue
            if lname not in GUARDED_LABELS:
                continue
            assert False, (
                f"family {base} carries a {lname!r} label "
                f"(value {lval!r}) without a declared static value set "
                "— per-object label cardinality is forbidden; serve "
                "hot-key data from the /v1/traffic sketch endpoints "
                "(see BOUNDED_LABEL_VALUES in script/dashboard_lint.py)"
            )
        key = (name, labels)
        assert key not in seen, f"duplicate sample {key}"
        seen.add(key)
        fam = name if name in types else None
        if fam is None:
            base = base_family(name)
            if base != name and types.get(base) == "histogram":
                fam = base
        assert fam is not None, f"sample {name} has no TYPE family"
        started.add(fam)
    return types


def families_in_exposition(text: str) -> set[str]:
    """Families exported by a scrape: TYPE declarations + sample names
    (suffix-stripped)."""
    out: set[str] = set()
    for line in text.splitlines():
        line = line.strip()
        if not line:
            continue
        if line.startswith("# TYPE "):
            out.add(line.split()[2])
            continue
        if line.startswith("#"):
            continue
        m = NAME_RE.match(line)
        if m:
            out.add(base_family(m.group(0)))
    return out


def lint(
    dashboard_families: dict[str, list[str]],
    doc_families: set[str],
    scraped_families: set[str],
) -> list[str]:
    """One error per dashboard family that neither a live node exports
    nor the doc catalogues."""
    errors = []
    for fam, panels in sorted(dashboard_families.items()):
        if fam in scraped_families or fam in doc_families:
            continue
        errors.append(
            f"dashboard family {fam!r} (panels: {', '.join(sorted(set(panels)))}) "
            "is neither exported by the live node nor catalogued in "
            "doc/monitoring.md"
        )
    return errors


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--dashboard", default=DASHBOARD)
    ap.add_argument("--doc", default=DOC)
    ap.add_argument("--url", help="admin API base (scrapes /metrics + /metrics/cluster)")
    ap.add_argument("--token", help="metrics/admin bearer token")
    ap.add_argument(
        "--scrape", action="append", default=[],
        help="file with Prometheus exposition text (repeatable)",
    )
    args = ap.parse_args(argv)

    scraped: set[str] = set()
    for path in args.scrape:
        with open(path) as f:
            scraped |= families_in_exposition(f.read())
    if args.url:
        from urllib.request import Request, urlopen

        for ep in ("/metrics", "/metrics/cluster"):
            req = Request(args.url.rstrip("/") + ep)
            if args.token:
                req.add_header("Authorization", f"Bearer {args.token}")
            with urlopen(req, timeout=10) as resp:
                scraped |= families_in_exposition(
                    resp.read().decode("utf-8", "replace")
                )

    errors = lint(
        families_in_dashboard(args.dashboard),
        families_in_doc(args.doc),
        scraped,
    )
    for e in errors:
        print(f"ERROR: {e}", file=sys.stderr)
    if not errors:
        n = len(families_in_dashboard(args.dashboard))
        print(f"dashboard lint ok: {n} families all accounted for")
    return 1 if errors else 0


if __name__ == "__main__":
    sys.exit(main())
