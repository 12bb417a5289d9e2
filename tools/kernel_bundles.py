#!/usr/bin/env python3
"""A flash-attention kernel's static schedule, with no chip: bundles an inner
loop (or, for a band kernel, a grid step), and how full each unit's slots are.

    JAX_PLATFORMS=cpu python3 tools/kernel_bundles.py fwd 1,16,4096,128 bfloat16 [window] [flash_attention.py]

Compiles ``_fa_fwd`` or ``_fa_bwd`` of one copy of ``ops/flash_attention.py``
(this checkout's unless a path is given; the module imports nothing of its
package) for a described ``v5e:2x2`` chip with libtpu's LLO dump switched on,
then reads the kernel's ``final_bundles`` and per-bundle utilization files: for
every region at the deepest nesting of the schedule the number of VLIW
bundles, each unit's used slots against its capacity, and the commonest
operations.  Without a window that is the whole-sequence kernel's pair loop.
With one (``fwd 1,64,8192,128 bfloat16 512``) the band kernels have no loop
inside a grid step, and the regions are the step itself (the long one) and the
pipeline's DMA issue and wait stubs around it (a few dozen bundles each); a
module whose calls with a window run the whole-sequence kernels (PR 45's and
older) prints its pair loop, two turns of which are a band of 512's grid step.
The compile runs in a child process: the flags must be set before libtpu
loads, and the dumper aborts the process on a report template this
installation lacks, after the files are written.

A bundle count is not a time, and is never written under a device metric's
name.  It ranks forms of one kernel before chip time is spent on them: PR 40's
forward read 0.92 ns a bundle on the chip at two block shapes, and a form whose
loop was 38 % shorter here ran 43 % faster there; forms within 2 % of each
other here came out either way round there.
"""

from __future__ import annotations

import collections
import glob
import os
import re
import subprocess
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MODULE = os.path.join(ROOT, "tpu_compressed_dp", "ops", "flash_attention.py")


def compile_kernel(which: str, shape: str, dtype: str, window: str,
                   path: str) -> None:
    """Child process: compile one kernel; libtpu dumps as it goes."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import importlib.util

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    spec = importlib.util.spec_from_file_location("flash_attention_dumped", path)
    fa = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(fa)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    dims = tuple(int(x) for x in shape.split(","))
    x = jax.ShapeDtypeStruct(dims, getattr(jnp, dtype), sharding=chip)
    band = {"window": int(window)} if window else {}
    if which == "fwd":
        jax.jit(lambda q, k, v: fa._fa_fwd(q, k, v, None, False, **band)[0]
                ).lower(x, x, x).compile()
    else:
        lse = jax.ShapeDtypeStruct(dims[:3], jnp.float32, sharding=chip)
        jax.jit(lambda q, k, v, o, lse, do: fa._fa_bwd(
            None, False, (q, k, v, o, lse), do, **band)
                ).lower(x, x, x, x, lse, x).compile()


def inner_loops(dump_dir: str, kernel: str):
    """(first bundle, bundles, {unit: (used, capacity)}, operation counts) of
    every region at the deepest nesting of the kernel's final schedule: a
    whole-sequence kernel's pair loop; a band kernel's grid step."""
    bundles_file, = [f for f in glob.glob(f"{dump_dir}/*{kernel}*final_bundles.txt")
                     if "schedule-analysis" not in f]
    util_file, = glob.glob(
        f"{dump_dir}/*{kernel}*final_hlo-static-per-bundle-utilization.txt")
    lines = open(util_file).read().split("\n")
    units = [u.strip() for u in lines[1].split(",")]
    capacity = [int(c) for c in lines[2].split()]
    used = [[int(c) for c in row.split()] for row in lines[4:] if row.strip()]
    body = {}       # bundle number -> (loop depth, text)
    for line in open(bundles_file):
        m = re.match(r"\s*(0x[0-9a-f]+|\d+)\s+(?:[A-Z]{2})?\s*:\s*(>*)\s*\{(.*)", line)
        if m:
            body[int(m.group(1), 0)] = (len(m.group(2)), m.group(3))
    deepest = max(depth for depth, _ in body.values())
    loops, start = [], None
    for i in sorted(body) + [max(body) + 1]:
        inside = i in body and body[i][0] == deepest
        if inside and start is None:
            start = i
        elif not inside and start is not None:
            rows = range(start, i)
            if len(rows) > 8:           # loop-control stubs of a bundle or two
                ops = collections.Counter(
                    op for j in rows
                    for op in re.findall(r"= ([a-z][a-z0-9_.]+)",
                                         body.get(j, (0, ""))[1]))
                loops.append((start, len(rows), {
                    u: (sum(used[j][c] for j in rows), capacity[c] * len(rows))
                    for c, u in enumerate(units)}, ops))
            start = None
    return loops


def main(argv) -> int:
    if len(argv) >= 2 and argv[0] == "--child":
        compile_kernel(*argv[1:6])
        return 0
    if len(argv) < 3 or argv[0] not in ("fwd", "bwd"):
        print(__doc__, file=sys.stderr)
        return 2
    which, shape, dtype = argv[:3]
    rest = argv[3:]
    window = rest.pop(0) if rest and rest[0].isdigit() else ""
    path = rest[0] if rest else MODULE
    with tempfile.TemporaryDirectory() as dump_dir:
        env = dict(os.environ, JAX_PLATFORMS="cpu", LIBTPU_INIT_ARGS=(
            f"--xla_jf_dump_to={dump_dir} --xla_jf_dump_llo_text=true"))
        child = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--child",
             which, shape, dtype, window, path],
            env=env, capture_output=True, text=True)
        # a module whose calls with a window have kernels of their own names
        # them flash_attn_band_*; PR 45's and older run the one pair of kernels
        kernel = next((k for k in (f"flash_attn_band_{which}", f"flash_attn_{which}")
                       if glob.glob(f"{dump_dir}/*{k}*final_bundles.txt")), None)
        if kernel is None:
            print(child.stderr[-3000:], file=sys.stderr)
            return 1
        what = f"{kernel} {shape} {dtype}" + (f" window {window}" if window else "")
        for start, n, units, ops in inner_loops(dump_dir, kernel):
            print(f"{what}: region at bundle {start:#x}: {n} bundles")
            print("  " + ", ".join(f"{u} {a}/{b} ({100 * a / b:.0f} %)"
                                   for u, (a, b) in units.items()))
            print("  " + ", ".join(f"{op} {c}" for op, c in ops.most_common(24)))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
