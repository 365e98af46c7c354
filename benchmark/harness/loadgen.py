"""The load generator: closed-loop S3 clients in a process of their own.

Started by `run.py` as `python loadgen.py <traffic file> <seed>`; it never
imports JAX (the parent holds the chip and the cluster), so making bodies,
hashing payloads for SigV4 and comparing what GETs return are not charged
to the event loop that the cluster's nodes share.  It takes commands as
JSON lines on stdin and answers each with one JSON line on stdout:

    connect   endpoints and the access key
    preload   PUT the traffic file's preloaded objects
    window    run the clients for `seconds`; every answer judged as it comes
    verify    read back, through the other frontend, a sample drawn from
              the seed of what the window was told is stored or deleted
    quit

Closed loop, as minio/warp drives S3: each client sends its next request
when the last has completed.  A latency runs from request start to last
byte, on this process's clock.

Every request of the traffic is sent ONCE, and what came of it is one of
(`outcome`): answered as expected; `refused` (a 5xx or a transport error:
the server says it did not serve the request, which is true — a failed
request, counted in `failed`, in the tails as the longest and not in the
goodput, but not a wrong answer: the program turns a host that stood still
for some seconds into 500s, PERF.md); `unanswered` (nothing within
`request_timeout_s`); `wrong` (any other answer: other bytes, another
length, a 404 for a live key, a 403).  `correct` is for the last two.
The preload and the read-back establish and read STATE and are not traffic:
there a refused request is asked again until it is answered, a minute at
the most, every refusal counted and printed.
"""

from __future__ import annotations

import asyncio
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from harness import traffic as T  # noqa: E402
from harness.s3client import S3Client  # noqa: E402


READBACK_ALONE_OVER = 1 << 20  # the program's default block: larger objects are read back one at a time
REASK_FOR_S = 60.0  # preload and read-back: a refused request is asked again for this long,
REASK_PAUSE_S = 2.0  # this far apart


def outcome(why: str) -> str:
    """What came of a request, from `timed`'s `why` (see the module's docstring)."""
    if not why:
        return "ok"
    if why.startswith("no answer in"):
        return "unanswered"
    if why[:1] == "5" or why.startswith("client error"):
        return "refused"
    return "wrong"


class Tally:
    """Failed requests by what came of them, with the first few reasons of each."""

    def __init__(self):
        self.n = {"refused": 0, "unanswered": 0, "wrong": 0}
        self.why: dict[str, list[str]] = {k: [] for k in self.n}

    def add(self, why: str, as_: str | None = None) -> None:
        kind = as_ or outcome(why)
        self.n[kind] += 1
        if len(self.why[kind]) < 6:
            self.why[kind].append(why)

    def reply(self) -> dict:
        return {"n": self.n, "why": self.why}


class Generator:
    def __init__(self, t: dict, seed: int):
        self.t, self.seed = t, seed
        self.size = int(t["object_bytes"])
        self.bodies: dict[int, bytes] = {}
        self.made_late = 0
        self.clients: list[S3Client] = []
        self.plans = [T.ClientPlan(t, seed, c) for c in range(int(t["clients"]))]
        # (key, body id, frontend that took the PUT); keys whose DELETE was acknowledged
        self.acked: list[tuple[str, int, int]] = []
        self.deleted: list[str] = []
        # keys whose PUT or DELETE failed: their state is unknown, so no
        # later answer about them is judged
        self.unknown: set[str] = set()

    def make_bodies(self) -> float:
        t0 = time.perf_counter()
        n_pre, n_cl = int(self.t["preload_objects"]), int(self.t["clients"])
        ids = list(range(n_pre))
        for n in range(int(self.t.get("body_pool_per_client", 0))):
            ids += [n_pre + c + n_cl * n for c in range(n_cl)]
        for i in ids:
            self.bodies[i] = T.body(self.seed, i, self.size)
        return time.perf_counter() - t0

    def body(self, bid: int) -> bytes:
        b = self.bodies.get(bid)
        if b is None:
            b = self.bodies[bid] = T.body(self.seed, bid, self.size)
            self.made_late += 1
        return b

    def path(self, key: str) -> str:
        return f"/{self.t['bucket']}/{key}"

    async def one(self, op: str, key: str, bid: int, front: int) -> tuple[bool, int, str]:
        """(ok, payload bytes moved, why not ok).  `why` starts with the
        HTTP status where the server refused."""
        cl = self.clients[front]
        if op == "PUT":
            data = self.body(bid)
            st, _h, resp = await cl.request("PUT", self.path(key), data)
            if st != 200:
                return False, 0, f"{st} PUT {key}: {resp[:120]!r}"
            return True, len(data), ""
        if op == "GET":
            st, _h, resp = await cl.request("GET", self.path(key))
            if st != 200:
                return False, 0, f"{st} GET {key}: {resp[:120]!r}"
            if resp != self.body(bid):
                return False, 0, f"WRONG BYTES from GET {key} ({len(resp)} returned)"
            return True, len(resp), ""
        if op == "STAT":
            st, h, _resp = await cl.request("HEAD", self.path(key))
            if st != 200:
                return False, 0, f"{st} HEAD {key}"
            if int(h.get("Content-Length", -1)) != self.size:
                return False, 0, f"WRONG LENGTH from HEAD {key}: {h.get('Content-Length')}"
            return True, 0, ""
        st, _h, resp = await cl.request("DELETE", self.path(key))
        if st != 204:
            return False, 0, f"{st} DELETE {key}: {resp[:120]!r}"
        return True, 0, ""

    async def timed(self, op, key, bid, front, timeout) -> tuple[float, bool, int, str]:
        """One request, one attempt, as warp sends it: any status but the
        expected one, a wrong answer, a transport error or no answer within
        `timeout` is a failed request.  The latency runs from the request's
        start to the last byte of its answer."""
        t0 = time.perf_counter()
        try:
            ok, nbytes, why = await asyncio.wait_for(self.one(op, key, bid, front), timeout)
        except asyncio.TimeoutError:
            ok, nbytes, why = False, 0, f"no answer in {timeout} s to {op} {key}"
        except Exception as e:  # noqa: BLE001 — any client-side error is a failed request
            ok, nbytes, why = False, 0, f"client error on {op} {key}: {e!r}"
        return time.perf_counter() - t0, ok, nbytes, why

    async def until_answered(self, op, key, bid, front, timeout, tally: Tally) -> tuple[bool, str]:
        """For the preload and the read-back: one request, asked again while
        it is refused, for REASK_FOR_S at the most; each refusal goes into
        `tally`.  Returns the last (ok, why): still refused after that long
        is an answer that never came."""
        t_end = time.perf_counter() + REASK_FOR_S
        while True:
            _lat, ok, _n, why = await self.timed(op, key, bid, front, timeout)
            if outcome(why) != "refused":
                return ok, why
            tally.add(why)
            if time.perf_counter() >= t_end:
                return ok, why
            await asyncio.sleep(REASK_PAUSE_S)

    # --- commands -------------------------------------------------------------

    async def connect(self, msg: dict) -> dict:
        self.clients = [S3Client(e, msg["key_id"], msg["secret"]) for e in msg["endpoints"]]
        return {}

    async def preload(self, _msg: dict) -> dict:
        n = int(self.t["preload_objects"])
        todo = iter(range(n))
        tally = Tally()
        not_stored = 0
        t0 = time.perf_counter()

        async def worker():
            nonlocal not_stored
            for i in todo:
                ok, why = await self.until_answered(
                    "PUT", T.preload_key(i), i, i % len(self.clients), self.t["request_timeout_s"], tally)
                if not ok:
                    # refused for a whole minute is an answer that never came
                    tally.add(why, "unanswered" if outcome(why) == "refused" else None)
                    not_stored += 1
                    self.unknown.add(T.preload_key(i))

        await asyncio.gather(*[worker() for _ in range(int(self.t["preload_concurrency"]))])
        return {"objects": n, "not_stored": not_stored, **tally.reply(), "secs": time.perf_counter() - t0}

    async def window(self, msg: dict) -> dict:
        """Clients start staggered over `stagger_s` and run closed-loop through
        a pre-roll of `preroll_s` (part of set-up: the window opens on a
        system in its steady state, its clients out of step) and the window.
        A record's start is relative to the window's opening."""
        seconds = float(msg["seconds"])
        timeout = float(self.t["request_timeout_s"])
        records: list[tuple[str, float, float, bool, int]] = []  # op, start, latency, ok, bytes
        tally = Tally()
        inflight = 0
        t_open = time.perf_counter() + float(self.t["preroll_s"])
        deadline = t_open + seconds

        async def client(plan: T.ClientPlan, delay: float):
            nonlocal inflight
            await asyncio.sleep(delay)
            while time.perf_counter() < deadline:
                op, key, bid, front = plan.next_op()
                if key in self.unknown:
                    continue
                start = time.perf_counter() - t_open
                inflight += 1
                lat, ok, nbytes, why = await self.timed(op, key, bid, front, timeout)
                inflight -= 1
                records.append((op, start, lat, ok, nbytes))
                if not ok:
                    tally.add(why)
                    if op in ("PUT", "DELETE"):
                        self.unknown.add(key)
                elif op == "PUT":
                    self.acked.append((key, bid, front))
                elif op == "DELETE":
                    self.deleted.append(key)

        step = float(self.t["stagger_s"]) / len(self.plans)
        tasks = [asyncio.ensure_future(client(p, i * step)) for i, p in enumerate(self.plans)]
        await asyncio.sleep(max(0.0, t_open - time.perf_counter()))
        cpu0, made0 = time.process_time(), self.made_late
        await asyncio.sleep(max(0.0, deadline - time.perf_counter()))
        at_close = inflight
        cpu_share = (time.process_time() - cpu0) / seconds
        # every request sent in the window is waited for: one that comes
        # late is late (its latency says so), not lost
        await asyncio.gather(*tasks)
        drain = time.perf_counter() - deadline
        return {
            "seconds": seconds, "records": records, **tally.reply(),
            "inflight_at_close": at_close, "drain_secs": drain,
            "generator_cpu_share": cpu_share, "bodies_made_in_window": self.made_late - made0,
            "acked": self.acked, "deleted": self.deleted, "unknown": sorted(self.unknown),
        }

    async def verify(self, _msg: dict) -> dict:
        """Read-after-write through the OTHER frontend, and gone-after-delete,
        each judged by what its answer says: a refused GET says nothing of
        what is stored, so it is asked again (`until_answered`; the refusals
        are counted and printed), and one still refused after a minute never
        came and is wrong.  Objects of more than one block are read one at a
        time, the others three at a time: a multi-block GET prefetches 8
        blocks' pieces, and three of those at once after a window of PUTs
        open peer breakers in 2 of 23 runs (PERF.md, Open questions) — the
        check reads what is stored, it is not the cell's traffic."""
        n = int(self.t["readback_objects"])
        timeout = float(self.t["request_timeout_s"])
        acked = [a for a in self.acked if a[0] not in set(self.deleted)]
        # the last PUT each client had acknowledged is always among them
        last = {a[0].split("-")[0]: a for a in acked}
        stored = T.sample(self.seed, 1, acked, n, always=sorted(last.values()))
        live_pre = [
            (k, bid, bid % 2) for p in self.plans for k, bid in p.live.items()
            if k.startswith("pre-") and k not in self.unknown
        ]
        stored += T.sample(self.seed, 2, sorted(set(live_pre)), max(4, n // 4))
        gone = T.sample(self.seed, 3, sorted(self.deleted), n)
        wrong: list[str] = []
        refused = Tally()

        async def check_stored(key, bid, front):
            ok, why = await self.until_answered("GET", key, bid, 1 - front, timeout, refused)
            if not ok:
                wrong.append("read-back " + why)

        async def check_gone(key):
            for front in range(len(self.clients)):
                _ok, why = await self.until_answered("GET", key, 0, front, timeout, refused)
                if not why.startswith("404"):
                    wrong.append(f"deleted {key} still answers: {why or 'its old bytes'}")

        gate = asyncio.Semaphore(1 if self.size > READBACK_ALONE_OVER else 3)
        t0 = time.perf_counter()

        async def gated(coro):
            async with gate:
                try:
                    await coro
                except Exception as e:  # noqa: BLE001 — a check that cannot run has failed
                    wrong.append(f"check raised {e!r}")

        await asyncio.gather(
            *[gated(check_stored(*s)) for s in stored], *[gated(check_gone(k)) for k in gone])
        return {"readback_checked": len(stored), "gone_checked": len(gone),
                "wrong": len(wrong), "why": wrong[:10], "refused_and_asked_again": refused.n["refused"],
                "why_refused": refused.why["refused"], "secs": time.perf_counter() - t0}


async def main() -> int:
    with open(sys.argv[1]) as f:
        t = T.validate(json.load(f))
    gen = Generator(t, int(sys.argv[2]))
    loop = asyncio.get_running_loop()
    out = sys.stdout
    print(json.dumps({"ready": True, "bodies_secs": gen.make_bodies(),
                      "bodies": len(gen.bodies)}), file=out, flush=True)
    try:
        while True:
            line = await loop.run_in_executor(None, sys.stdin.readline)
            if not line:
                return 0
            msg = json.loads(line)
            if msg["cmd"] == "quit":
                return 0
            reply = await getattr(gen, msg["cmd"])(msg)
            print(json.dumps({"cmd": msg["cmd"], **reply}), file=out, flush=True)
    finally:
        for c in gen.clients:
            await c.close()


if __name__ == "__main__":
    sys.exit(asyncio.run(main()))
