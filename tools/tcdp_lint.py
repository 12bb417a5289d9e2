#!/usr/bin/env python
"""tcdp-lint — two-pass static analyzer for the tpu_compressed_dp tree.

Pass 1 (``--spmd``) traces both sync engines and all three step factories
to jaxprs on a virtual CPU mesh and verifies the SPMD safety contract:
no collectives under worker-divergent control flow (TCDP001), ordered
collective-signature determinism across retraces / engine pairs / the
chunked schedule (TCDP002), donation that can actually alias (TCDP003),
overlap chunk-plan + optimization_barrier chain integrity (TCDP004), and
per-config jaxpr equation budgets that catch accidental unrolling
(TCDP005).  The trace matrix includes the fused compressor kernels under
``pallas_mode`` off AND force, pinning the collective signature across
the kernel toggle.

Pass 2 (``--host``) is an AST walk over the package and ``tools/``
enforcing host-side invariants: no wall-clock reads in replay-
deterministic modules (TCDP101), atomic tmp+``os.replace`` writes to
shared directories (TCDP102), stat-key literals declared in the obs
registry (TCDP103), named_scope strings in the ``tcdp.<phase>`` taxonomy
(TCDP104), and lock-guarded thread-shared attributes (TCDP105).

Usage::

    python tools/tcdp_lint.py                # both passes, human output
    python tools/tcdp_lint.py --json         # machine-readable findings
    python tools/tcdp_lint.py --host         # host pass only (sub-second)
    python tools/tcdp_lint.py --spmd --profile full   # whole 9x2x2x3 matrix
    python tools/tcdp_lint.py --diff HEAD~1  # changed files only (pre-commit)

Suppress a finding with a justified inline pragma::

    t = time.time()  # tcdp-lint: disable=TCDP101 -- operator-facing log only

Exit code 0 iff zero active findings.  Both passes are pure tracing /
parsing — no compilation — so the full run takes seconds on CPU.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time
from typing import List, Optional

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _changed_files(rev: str) -> Optional[List[str]]:
    """Repo-relative paths changed since ``rev`` (committed + worktree)."""
    try:
        out = subprocess.run(
            ["git", "diff", "--name-only", rev, "--"],
            cwd=_REPO_ROOT, capture_output=True, text=True, check=True,
            timeout=30).stdout
    except (subprocess.SubprocessError, FileNotFoundError) as e:
        print(f"tcdp-lint: --diff {rev}: {e}", file=sys.stderr)
        return None
    return [ln.strip() for ln in out.splitlines() if ln.strip()]


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="tcdp-lint", description=__doc__.splitlines()[0])
    ap.add_argument("--spmd", action="store_true",
                    help="run only pass 1 (jaxpr SPMD analysis)")
    ap.add_argument("--host", action="store_true",
                    help="run only pass 2 (host-side AST lint)")
    ap.add_argument("--profile", choices=("quick", "full"), default="full",
                    help="SPMD matrix size (default: full; tier-1 uses "
                         "quick)")
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="emit machine-readable JSON on stdout")
    ap.add_argument("--diff", metavar="REV", default=None,
                    help="lint only files changed since REV (fast "
                         "pre-commit path; skips pass 1 unless traced "
                         "modules changed)")
    args = ap.parse_args(argv)
    run_spmd = args.spmd or not args.host
    run_host = args.host or not args.spmd

    host_files = None
    if args.diff is not None:
        changed = _changed_files(args.diff)
        if changed is None:
            return 2
        host_files = [f for f in changed if f.endswith(".py") and (
            f.startswith("tpu_compressed_dp/") or f.startswith("tools/"))]
        # pass 1 traces whole subsystems, not files: only worth running
        # when a traced module changed
        traced_prefixes = ("tpu_compressed_dp/parallel/",
                           "tpu_compressed_dp/train/",
                           "tpu_compressed_dp/models/",
                           "tpu_compressed_dp/ops/",
                           "tpu_compressed_dp/analysis/")
        if run_spmd and not any(f.startswith(traced_prefixes)
                                for f in host_files):
            run_spmd = False
        if not host_files:
            run_host = False

    t0 = time.time()
    active = []
    suppressed = []
    stats = {}

    if run_host:
        from tpu_compressed_dp.analysis.hostlint import run_host_pass
        abs_files = (None if host_files is None else
                     [os.path.join(_REPO_ROOT, f) for f in host_files
                      if os.path.exists(os.path.join(_REPO_ROOT, f))])
        a, s = run_host_pass(_REPO_ROOT, files=abs_files)
        active += a
        suppressed += s
        stats["host_files"] = (len(host_files) if host_files is not None
                               else "all")

    if run_spmd:
        # virtual 8-device CPU mesh: XLA_FLAGS must land before the first
        # backend use (lint is pure tracing; it must never take a chip)
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8").strip()
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        import jax
        jax.config.update("jax_platforms", "cpu")
        from tpu_compressed_dp.analysis.spmd import run_spmd_pass
        f, spmd_stats = run_spmd_pass(args.profile)
        active += f
        stats.update(spmd_stats)

    elapsed = time.time() - t0
    if args.as_json:
        from tpu_compressed_dp.analysis.report import findings_to_json
        payload = findings_to_json(active, suppressed)
        payload["elapsed_s"] = round(elapsed, 2)
        payload["stats"] = stats
        json.dump(payload, sys.stdout, indent=2)
        print()
    else:
        from tpu_compressed_dp.analysis.report import format_findings
        body = format_findings(list(active) + list(suppressed))
        if body:
            print(body, file=sys.stderr)
        print(f"tcdp-lint: {len(active)} finding(s), "
              f"{len(suppressed)} suppressed, {elapsed:.1f}s "
              f"({stats})", file=sys.stderr)
    return 1 if active else 0


if __name__ == "__main__":
    if __package__ in (None, ""):
        sys.path.insert(0, _REPO_ROOT)
    sys.exit(main())
