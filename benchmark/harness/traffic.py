"""The one general traffic generator.

A traffic mix is a data file (`benchmark/traffic/<name>.json`) of
parameters; this module turns it and `--seed` into what the clients
send.  Everything a client does follows from (seed, client index, op
index) alone — never from timing — so a seed repeats its work exactly,
and every seed gets the same amounts of every op in another order (the
mix is a shuffled deck, not a draw per op).

Keys: client c owns the keys it preloaded and the keys it PUTs, and
GETs, STATs and DELETEs only among them, so no client deletes under
another's read; a read-only mix (`"key_choice": "uniform_all"`) lets
every client read every preloaded key.

Imports numpy, never JAX: the load generator's process must not touch
the chip.
"""

from __future__ import annotations

import numpy as np

OPS = ("GET", "STAT", "PUT", "DELETE")
DEFAULTS = {
    "bucket": "bench",
    "preload_objects": 0,
    "preload_concurrency": 4,
    "key_choice": "uniform_own",
    "preroll_s": 0.0,
    "stagger_s": 0.0,
    "trace_s": 15.0,
    "request_timeout_s": 60.0,
    "readback_objects": 24,
    "piece_check_blocks": 48,
}


def validate(t: dict) -> dict:
    """The traffic file with defaults filled in; raises on what the
    generator cannot run."""
    t = {**DEFAULTS, **t}
    for key in ("clients", "object_bytes", "mix"):
        if key not in t:
            raise ValueError(f"traffic file lacks {key!r}")
    if not t["mix"] or any(op not in OPS or int(n) < 0 for op, n in t["mix"].items()):
        raise ValueError(f"mix must count ops of {OPS}: {t['mix']}")
    if t["key_choice"] not in ("uniform_own", "uniform_all"):
        raise ValueError(f"key_choice {t['key_choice']!r}")
    mutating = any(t["mix"].get(op, 0) for op in ("PUT", "DELETE"))
    if t["key_choice"] == "uniform_all" and mutating:
        raise ValueError("uniform_all is for read-only mixes")
    reads = any(t["mix"].get(op, 0) for op in ("GET", "STAT", "DELETE"))
    if reads and t["preload_objects"] < t["clients"]:
        raise ValueError("a mix that reads needs a preloaded key per client")
    return t


def body(seed: int, body_id: int, size: int) -> bytes:
    """Object body `body_id` of this seed: distinct bytes for every id
    (a body reused would dedupe to one block)."""
    return np.random.default_rng([seed, 1, body_id]).bytes(size)


def preload_key(i: int) -> str:
    return f"pre-{i:06d}"


def put_key(client: int, n: int) -> str:
    return f"c{client:02d}-{n:06d}"


class ClientPlan:
    """What client `c` does next.  `next_op()` is a pure function of the
    seed and the calls made so far."""

    def __init__(self, t: dict, seed: int, c: int):
        self.t, self.seed, self.c = t, seed, c
        self.rng = np.random.default_rng([seed, 2, c])
        self.deck: list[str] = []
        self.n_ops = 0
        self.n_put = 0
        n_pre, n_cl = t["preload_objects"], t["clients"]
        own = range(n_pre) if t["key_choice"] == "uniform_all" else range(c, n_pre, n_cl)
        # key -> body id
        self.live: dict[str, int] = {preload_key(i): i for i in own}
        self._keys = list(self.live)  # index for O(1) uniform choice

    def _draw_op(self) -> str:
        if not self.deck:
            self.deck = [op for op, n in sorted(self.t["mix"].items()) for _ in range(int(n))]
            self.rng.shuffle(self.deck)
        return self.deck.pop()

    def next_op(self) -> tuple[str, str, int, int]:
        """(op, key, body id, frontend index)."""
        op = self._draw_op()
        if op != "PUT" and not self._keys:
            op = "PUT"  # nothing of its own left to read
        front = (self.c + self.n_ops) % 2
        self.n_ops += 1
        if op == "PUT":
            key = put_key(self.c, self.n_put)
            bid = self.t["preload_objects"] + self.c + self.t["clients"] * self.n_put
            self.n_put += 1
            self.live[key] = bid
            self._keys.append(key)
            return op, key, bid, front
        i = int(self.rng.integers(len(self._keys)))
        key = self._keys[i]
        bid = self.live[key]
        if op == "DELETE":
            self._keys[i] = self._keys[-1]
            self._keys.pop()
            del self.live[key]
        return op, key, bid, front


def sample(seed: int, stream: int, items: list, n: int, always: list = ()) -> list:
    """`n` of `items` drawn from the seed, with `always` among them."""
    rng = np.random.default_rng([seed, 3, stream])
    rest = [x for x in items if x not in set(always)]
    take = max(0, min(len(rest), n - len(always)))
    idx = sorted(rng.choice(len(rest), size=take, replace=False).tolist()) if take else []
    return list(always) + [rest[i] for i in idx]
