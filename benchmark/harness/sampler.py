"""What the event-loop thread is in, sampled from a thread of the benchmark's own.

All nodes of the cluster share one event loop, so "what the host was doing
while the device idled" is "what that one thread was in".  Every 20 ms the
sampler reads the loop thread's stack (`sys._current_frames()`) and keeps
the innermost frame that lies in `garage_tpu/<package>/`; a stack parked in
the selector is `loop idle (select)`; anything else is named by its
top-level module (`aiohttp`, `asyncio`, `json`...).  Traced runs only.
"""

from __future__ import annotations

import os
import sys
import threading
import time

PERIOD_S = 0.02


def label_of(frame) -> str:
    top = frame
    f = frame
    while f is not None:
        fn = f.f_code.co_filename
        i = fn.find("/garage_tpu/")
        if i >= 0:
            rest = fn[i + len("/garage_tpu/"):].split("/")
            return "garage_tpu." + (rest[0] if len(rest) > 1 else rest[0].removesuffix(".py"))
        f = f.f_back
    fn = top.f_code.co_filename
    if top.f_code.co_name == "select" or fn.endswith("selectors.py"):
        return "loop idle (select)"
    if "site-packages/" in fn:
        return fn.split("site-packages/")[1].split("/")[0].removesuffix(".py")
    return os.path.basename(os.path.dirname(fn)) + "/" + os.path.basename(fn).removesuffix(".py")


class LoopSampler:
    """`samples`: [(perf_counter seconds, label)] of the thread `tid`."""

    def __init__(self, tid: int):
        self.tid = tid
        self.samples: list[tuple[float, str]] = []
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, name="bench-sampler", daemon=True)

    def start(self) -> None:
        self._thread.start()

    def _run(self) -> None:
        while not self._stop.wait(PERIOD_S):
            frame = sys._current_frames().get(self.tid)
            if frame is not None:
                self.samples.append((time.perf_counter(), label_of(frame)))

    def stop(self) -> None:
        self._stop.set()
        self._thread.join(timeout=5)
