"""The one traffic generator: reads a traffic file's ``feed`` and hands the
loop its batches, by count (set-up) or until a deadline (the window).

``staged``: a small pool of distinct seeded batches resident on the device,
cycled, so the step alone is timed.  ``loader``: the program's own loader over
its synthetic image source, host decode and host-to-device copy in the loop;
every seed reads the same image set in another order with other crops.

Each pull is timed on the host clock and wrapped in a profiler annotation, so a
traced run can say what the host was doing while the device idled.
"""

from __future__ import annotations

import itertools
import time

import jax


class Feed:
    def __init__(self, program, feed_cfg: dict, seed: int):
        self.kind = feed_cfg["kind"]
        self.first = []              # the first batches pulled, for the reference
        self.wait_s = []             # host seconds in each pull of the open window
        self.ready_ts = []           # host clock at which each of its batches was ready
        self.ready_t = 0.0           # ... and the last one's
        self._open = None
        if self.kind == "staged":
            self.pool = program.make_pool(seed, feed_cfg["pool"])
            jax.block_until_ready(self.pool)
            self._it = itertools.cycle(self.pool)
        elif self.kind == "loader":
            self.pool = None
            self._loader, self._epoch = program.make_loader(seed), 0
            self._it = iter(self._loader)
        else:
            raise ValueError(f"unknown feed kind {self.kind!r}")

    def batches(self, count=None, seconds=None, keep_first=0):
        """Yield ``count`` batches, or batches until ``seconds`` have passed.
        When the last is out, a ``bench.fetch`` annotation opens; ``close()``
        ends it once the loop has fetched its metrics."""
        self.wait_s, self.ready_ts = [], []
        deadline = None if seconds is None else time.perf_counter() + seconds
        n = 0
        while (count is None or n < count) and (
                deadline is None or time.perf_counter() < deadline):
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.data_wait"):
                batch = self._next()
            self.ready_t = time.perf_counter()
            self.ready_ts.append(self.ready_t)
            self.wait_s.append(self.ready_t - t0)
            if len(self.first) < keep_first:
                self.first.append(batch)
            n += 1
            yield batch
        self._open = jax.profiler.TraceAnnotation("bench.fetch")
        self._open.__enter__()

    def _next(self):
        try:
            return next(self._it)
        except StopIteration:       # the loader's epoch ran out: start the next
            self._epoch += 1
            self._loader.set_epoch(self._epoch)
            self._it = iter(self._loader)
            return next(self._it)

    def close(self):
        if self._open is not None:
            self._open.__exit__(None, None, None)
            self._open = None

    def release(self):
        """Drop the resident pool and stop the loader's workers."""
        self.pool = None
        it, self._it = self._it, None
        if hasattr(it, "close"):
            it.close()
