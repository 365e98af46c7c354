"""host-sync: device→host synchronization points reachable from the loop.

``np.asarray(fn(x))`` on a jit result, ``jax.device_get``, ``.item()`` /
``.tolist()`` / ``float()`` / ``bool()`` on a device value, and
``block_until_ready()`` all BLOCK the calling thread until the device
round-trip completes — on a TPU backend that is milliseconds of dispatch
+ transfer latency.
Exactly like a synchronous fsync, one such call in a coroutine stalls
the single event loop every concurrent request shares; unlike fsync it
passed the PR 7 loop-blocker silently because the blocking happens
inside numpy/jax, not a catalogued syscall.

This is the loop-blocker rule for the device boundary: a host-sync
point is reported when its function is an ``async def`` or reachable
from one within two name-resolved sync hops (same BFS as loop-blocker).
Functions only ever *passed* to ``asyncio.to_thread(...)`` are —
correctly — not reachable: the worker-thread hop is the approved remedy
(the codec batcher's dispatch path, ``block/codec_batch.py``).

Device-value evidence is positive-only (no type inference): a value is
"jax-typed" when it comes from a compiled callable bound from one of
the repo's jit factories (``fn = ec_apply_fn(...)``), from ``jnp.*`` /
``jax.device_put``, or through simple assignment chains from either.
``np.asarray`` over plain numpy stays silent.  ``block_until_ready`` and
``device_get`` only exist on jax objects and always count.

Suppression: ``# graft-lint: allow-host-sync(<reason>)`` on the sync
point's line — for sites where host materialization IS the design
(e.g. a CPU-native LUT path that never sees a device array).
"""

from __future__ import annotations

import ast

from .core import Project, Violation, call_repr, iter_async_reachable
from .device_model import (
    compiled_locals,
    device_names,
    is_devish,
    walk_no_defs,
)

RULE = "host-sync"
MAX_DEPTH = 2  # sync hops between the coroutine and the sync point

# always host-syncs, whatever the receiver (these only exist on jax)
ALWAYS_LASTS = {"block_until_ready", "device_get"}

# numpy materializers: host-sync when the argument is device-valued
ASARRAY_REPRS = {"np.asarray", "numpy.asarray", "np.array", "numpy.array"}

# scalar extractors: host-sync when the receiver/argument is device-valued
ITEM_LASTS = {"item", "tolist"}
SCALAR_BUILTINS = {"float", "bool", "int"}


def _sync_points(project: Project, fn) -> list[tuple[ast.Call, str]]:
    """(call_node, label) for every host-sync point made directly by
    `fn` (nested defs excluded — they don't run at def time)."""
    compiled = compiled_locals(project, fn)
    dev = device_names(fn.node, compiled)
    out: list[tuple[ast.Call, str]] = []
    for node in walk_no_defs(fn.node):
        if not isinstance(node, ast.Call):
            continue
        r = call_repr(node.func)
        if r is None:
            continue
        tail = r.rsplit(".", 1)[-1]
        if tail in ALWAYS_LASTS:
            out.append((node, tail))
            continue
        if r in ASARRAY_REPRS:
            if any(is_devish(a, dev, compiled) for a in node.args):
                out.append((node, r))
            continue
        if tail in ITEM_LASTS and "." in r:
            recv = node.func.value if isinstance(node.func, ast.Attribute) else None
            if recv is not None and is_devish(recv, dev, compiled):
                out.append((node, tail))
            continue
        if r in SCALAR_BUILTINS and len(node.args) == 1:
            if is_devish(node.args[0], dev, compiled):
                out.append((node, r))
    return out


def check(project: Project) -> list[Violation]:
    out: list[Violation] = []
    reported: set[tuple[str, str, int, str]] = set()
    points_cache: dict[tuple[str, str], list[tuple[ast.Call, str]]] = {}

    def points_of(fn):
        key = (fn.module, fn.qualname)
        if key not in points_cache:
            points_cache[key] = _sync_points(project, fn)
        return points_cache[key]

    for (_mod, _qual), fn in project.functions.items():
        if not fn.is_async:
            continue
        # the shared loop-blocker-shaped reachability walk (core)
        for cur, chain, depth in iter_async_reachable(project, fn, MAX_DEPTH):
            sf = project.files[cur.module]
            for node, label in points_of(cur):
                if sf.pragma_for(node, "host-sync"):
                    continue
                dedup = (cur.module, fn.qualname, node.lineno, label)
                if dedup in reported:
                    continue
                reported.add(dedup)
                via = "" if depth == 0 else " via " + " -> ".join(chain[1:])
                detail = label + ("|" + ">".join(chain[1:]) if depth else "")
                out.append(
                    Violation(
                        RULE, cur.module, node.lineno, fn.qualname, detail,
                        f"device->host sync point {label} reachable from "
                        f"coroutine {fn.qualname}{via} — blocks the event "
                        "loop for a full device round-trip; dispatch via "
                        "asyncio.to_thread (codec-batcher pattern) or "
                        "# graft-lint: allow-host-sync(<reason>)",
                    )
                )
    out.sort(key=lambda v: (v.path, v.line, v.detail))
    return out
