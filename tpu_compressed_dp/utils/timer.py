"""Wall-clock split timing.

Equivalent of the reference ``Timer`` (`CIFAR10/core.py:14-27`), which was
instantiated with ``torch.cuda.synchronize`` (`dawn.py:129`); the harnesses
here pass no sync: ``run_train_epoch``'s closing fetch has drained the device
before each split is taken.
"""

from __future__ import annotations

import time
from typing import Callable, Optional

__all__ = ["Timer"]


class Timer:
    """Split timer: each call returns the delta since the previous call and
    (optionally) accumulates it into ``total_time`` (`core.py:21-27`).

    Unlike the reference (which appended every split timestamp to a list
    forever — unbounded memory on long runs), only the LAST timestamp is
    kept; the split/total semantics are unchanged."""

    def __init__(self, synch: Optional[Callable[[], None]] = None):
        self.synch = synch or (lambda: None)
        self.synch()
        self.last_time = time.time()
        self.total_time = 0.0

    def __call__(self, include_in_total: bool = True) -> float:
        self.synch()
        now = time.time()
        delta_t = now - self.last_time
        self.last_time = now
        if include_in_total:
            self.total_time += delta_t
        return delta_t
