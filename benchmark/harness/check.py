"""The comparison that decides `correct`.

What the timed path produced is compared with the plain reference
(`reference.py`) once the window has closed, the device's memory peak has
been read and the cluster has been stopped:

- every answer of the window was judged by the load generator as it came
  (a GET's bytes against the body the seed gives, a HEAD's length, every
  status), and a sample drawn from the seed was read back through the
  OTHER frontend, deleted keys asked for again;
- here, the bytes the device wrote: for a sample of the blocks of
  acknowledged PUTs drawn from the seed (the last acknowledged PUT of
  every client among them), every piece file in the nodes' data
  directories — data shards, PARITY shards (a healthy GET never reads
  them) and the stored BLAKE3 piece hash — against the reference's file
  for that block and rank, and the count of distinct pieces against the
  write quorum the configuration states;
- after a node loss, every piece file the victim got back.

Every number compared is exact, so every limit is 0 (or, for work that
must have happened, a least count).
"""

from __future__ import annotations

import os
import sys

from . import reference as R
from . import traffic as T


def pieces_under(path: str):
    """((block hash, rank), file path) for every piece file below `path`."""
    for dirpath, _dirs, files in os.walk(path):
        for fn in files:
            stem, dot, ext = fn.partition(".p")
            if dot and len(stem) == 64 and ext.isdigit():
                yield (bytes.fromhex(stem), int(ext)), os.path.join(dirpath, fn)


def index_pieces(root: str) -> dict[tuple[bytes, int], list[str]]:
    """(block hash, rank) -> paths, over every node's data directory under `root`."""
    out: dict[tuple[bytes, int], list[str]] = {}
    for node in sorted(os.listdir(root)):
        for key, path in pieces_under(os.path.join(root, node, "data")):
            out.setdefault(key, []).append(path)
    return out


def read_file(path: str) -> bytes:
    with open(path, "rb") as f:
        return f.read()


def blocks_of(body: bytes, block_size: int) -> list[bytes]:
    return [body[i:i + block_size] for i in range(0, len(body), block_size)]


def check_stored(cfg: dict, t: dict, seed: int, objects: list[tuple[str, int]], root: str) -> dict:
    """Piece files of a sample of `objects` ((key, body id), acknowledged and
    not deleted) against the reference.  Returns the numbers compared."""
    k, m = cfg["k"], cfg["m"]
    quorum = cfg["guarantees"]["put_ack_pieces"]
    size = int(t["object_bytes"])
    per_object = -(-size // cfg["block_size"])
    n_objects = max(1, int(t["piece_check_blocks"]) // per_object)
    last = {key.split("-")[0]: (key, bid) for key, bid in objects if not key.startswith("pre-")}
    chosen = T.sample(seed, 4, sorted(objects), n_objects, always=sorted(last.values())[:n_objects // 2])
    index = index_pieces(root)
    blocks = [b for _key, bid in chosen for b in blocks_of(T.body(seed, bid, size), cfg["block_size"])]
    want = R.expected_piece_files(blocks, k, m)
    wrong = under_quorum = 0
    why: list[str] = []
    for block, files in zip(blocks, want):
        h = R.block_hash(block)
        present = 0
        for rank, expect in files.items():
            paths = index.get((h, rank), [])
            present += bool(paths)
            for p in paths:
                if read_file(p) != expect:
                    wrong += 1
                    why.append(f"piece {h.hex()[:12]}.p{rank} differs from the reference")
        if present < quorum:
            under_quorum += 1
            why.append(f"block {h.hex()[:12]} has {present} distinct pieces on disk, quorum {quorum}")
    for line in why[:8]:
        print("[check] " + line, file=sys.stderr)
    return {"blocks_checked": len(blocks), "pieces_wrong": wrong, "blocks_under_quorum": under_quorum}


def expected_victim_files(cfg: dict, t: dict, seed: int, n_objects: int, rank_of) -> dict[tuple[bytes, int], bytes]:
    """The reference's piece file for every block of the preloaded objects at
    the rank the victim holds (`rank_of(block hash)`)."""
    size = int(t["object_bytes"])
    blocks = [b for i in range(n_objects) for b in blocks_of(T.body(seed, i, size), cfg["block_size"])]
    out = {}
    by_rank: dict[int, list[bytes]] = {}
    for b in blocks:
        by_rank.setdefault(rank_of(R.block_hash(b)), []).append(b)
    for rank, bs in by_rank.items():
        for b, files in zip(bs, R.expected_piece_files(bs, cfg["k"], cfg["m"], ranks=[rank])):
            out[(R.block_hash(b), rank)] = files[rank]
    return out


def verdict(numbers: dict[str, tuple[float, float]], at_least: dict[str, tuple[float, float]]) -> tuple[bool, dict]:
    """`numbers`: name -> (value, most allowed); `at_least`: name -> (value,
    least allowed).  Prints each beside its limit as the last lines on
    standard error; returns (correct, the same for the result line)."""
    ok = True
    shown = {}
    for name, (value, limit) in numbers.items():
        good = value <= limit
        ok &= good
        shown[name] = {"value": value, "limit": limit, "rule": "<=", "ok": good}
    for name, (value, limit) in at_least.items():
        good = value >= limit
        ok &= good
        shown[name] = {"value": value, "limit": limit, "rule": ">=", "ok": good}
    for name, s in shown.items():
        print(f"[check] {name} = {s['value']} (limit {s['rule']} {s['limit']}) "
              f"{'ok' if s['ok'] else 'NOT OK'}", file=sys.stderr)
    sys.stderr.flush()
    return ok, shown
