"""recompile-hazard: dispatches that defeat the fixed-shape discipline.

XLA compiles one executable per input SHAPE.  The codec surface lives
and dies by that fact: PR 9's batcher coalesces RAGGED batches (whatever
arrived during the linger window), so an unbucketed dispatch compiles a
fresh kernel for every distinct concurrency level the node ever sees —
on a real TPU that is seconds of Mosaic compile time injected into a
foreground PUT.  ``bucket_batch``/``pad_to_bucket``
(ops/bucketing.py) exist to bound the compile cache at log2(max_batch)
entries; this rule makes routing through them mechanical.

Two sub-rules:

- **unbucketed-dispatch** — a call to a compiled device callable (a
  local bound from one of the jit factories: ``fn = ec_apply_fn(...);
  fn(bitmat, x)``, or a direct ``jax.jit(...)`` result) where NO
  argument carries pad-to-bucket provenance.  The batch-carrying array
  must flow through a recognized pad helper (wrapper calls preserve
  provenance: ``device_put(jnp.asarray(x_padded))`` is fine); constant
  companions (the coding matrix) ride along.

- **traced-branch** — Python ``if``/``while``/``for`` on a traced
  value inside a def that is handed to jit/pjit/shard_map/pallas_call:
  each distinct value re-traces (or raises TracerBoolConversionError at
  runtime).  Branches on ``.shape``/``.ndim``/``.dtype`` and
  ``is None``/``is not None`` tests are static at trace time and
  exempt.

Suppression: ``# graft-lint: allow-recompile(<reason>)`` on the
dispatch/branch line — for intentionally shape-polymorphic paths
(e.g. a one-shot probe dispatch).

Known resolution limits: callables fetched back out of containers
(``step = self._fns[key]; step(x)``) are not recognized — keep the
factory-call-then-dispatch idiom so the rule can see the dispatch.
"""

from __future__ import annotations

import ast

from .core import Project, Violation
from .device_model import (
    SHAPE_ATTRS,
    carries_pad,
    compiled_locals,
    padded_names,
    traced_defs,
    walk_no_defs,
)

RULE = "recompile-hazard"


def _branches_on_param(test, params: set[str]) -> str | None:
    """Name of a parameter the test reads as a VALUE (not via a static
    shape/dtype attribute, not an `is (not) None` check), else None."""
    if isinstance(test, ast.Attribute) and test.attr in SHAPE_ATTRS:
        return None  # static at trace time — do not descend
    if isinstance(test, ast.Compare) and all(
        isinstance(op, (ast.Is, ast.IsNot)) for op in test.ops
    ):
        return None  # `x is None` dispatches at trace time
    if isinstance(test, ast.Name):
        return test.id if test.id in params else None
    for child in ast.iter_child_nodes(test):
        if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
            continue
        hit = _branches_on_param(child, params)
        if hit is not None:
            return hit
    return None


def check(project: Project) -> list[Violation]:
    out: list[Violation] = []
    traced = traced_defs(project)

    # iterate the per-module name index, NOT project.functions: the dict
    # is keyed by (module, qualname) and silently drops duplicates —
    # e.g. the TWO `_ec_body.body` defs (einsum + pallas branches) share
    # one qualname, and both must be checked for traced branches
    for mod, byname in project._by_name.items():
        sf = project.files[mod]
        seen_fns: set[int] = set()
        for fns in byname.values():
            for fn in fns:
                if id(fn) in seen_fns:
                    continue
                seen_fns.add(id(fn))

                # --- sub-rule 1: unbucketed dispatch ---------------------------
                compiled = compiled_locals(project, fn)
                if compiled:
                    padded = padded_names(fn.node)
                    for node in walk_no_defs(fn.node):
                        if not isinstance(node, ast.Call):
                            continue
                        if not (
                            isinstance(node.func, ast.Name)
                            and node.func.id in compiled
                        ):
                            continue
                        args = list(node.args) + [
                            kw.value for kw in node.keywords
                        ]
                        if not args:
                            continue
                        if any(carries_pad(a, padded) for a in args):
                            continue
                        if sf.pragma_for(node, "recompile"):
                            continue
                        out.append(
                            Violation(
                                RULE, mod, node.lineno, fn.qualname,
                                f"unbucketed-dispatch:{node.func.id}",
                                f"compiled callable {node.func.id}() "
                                "dispatched without pad-to-bucket "
                                "provenance on any argument — every "
                                "distinct batch shape compiles a fresh "
                                "XLA executable (foreground compile "
                                "storm); route the batch through "
                                "bucket_batch/pad_to_bucket "
                                "(ops/bucketing.py) or "
                                "# graft-lint: allow-recompile(<reason>)",
                            )
                        )

                # --- sub-rule 2: Python control flow on traced values ----------
                if (fn.module, fn.qualname) not in traced:
                    continue
                a = fn.node.args
                params = {
                    p.arg
                    for p in a.posonlyargs + a.args + a.kwonlyargs
                    if p.arg not in ("self", "cls")
                }
                for node in walk_no_defs(fn.node):
                    if isinstance(node, (ast.If, ast.While)):
                        hit = _branches_on_param(node.test, params)
                    elif isinstance(node, ast.For):
                        hit = _branches_on_param(node.iter, params)
                    else:
                        continue
                    if hit is None or sf.pragma_for(node, "recompile"):
                        continue
                    out.append(
                        Violation(
                            RULE, mod, node.lineno, fn.qualname,
                            f"traced-branch:{hit}",
                            f"Python control flow on traced value "
                            f"{hit!r} inside jitted def {fn.qualname} — "
                            "re-traces per value or raises "
                            "TracerBoolConversionError; use lax.cond/"
                            "lax.select or hoist the decision to a "
                            "static argument, or "
                            "# graft-lint: allow-recompile(<reason>)",
                        )
                    )
    out.sort(key=lambda v: (v.path, v.line, v.detail))
    return out
