"""One run of a cell, and beside its result the process's host memory: the
peak (``resource.getrusage``) and the resident size every five seconds since
the start, so that the peak can be given to a phase of the ``setup`` line.
The comparison keeps several host copies of the model (the program's
parameters before and after, its optimizer state, the reference's lists), each
a whole model, and a cell of half a billion parameters has to fit them.

    python3 benchmark/tests/host_peak.py --workload W --seed N --seconds S --trace 0
"""

import os
import resource
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import run  # noqa: E402

samples, done = [], threading.Event()


def sample():
    page = os.sysconf("SC_PAGE_SIZE")
    while not done.wait(5.0):
        with open("/proc/self/statm") as f:
            samples.append((round(time.perf_counter() - run.T_START),
                            round(int(f.read().split()[1]) * page / 1e9, 1)))


threading.Thread(target=sample, daemon=True).start()
rc = run.main(sys.argv[1:])
done.set()
# Linux reports ru_maxrss in KiB
print("host_peak_gb %.3f" % (resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
                             * 1024 / 1e9))
print("host_resident_gb_by_second " + " ".join(f"{t}:{gb}" for t, gb in samples),
      flush=True)
sys.exit(rc)
