"""Pass 2 — rule-based AST lint over the host-side code (package + tools).

Five rules, each enforcing an invariant a runtime drill already depends on
(see ISSUE/README); the linter makes them fail in milliseconds instead of
in a chaos drill:

  * **TCDP101** — no wall-clock reads (``time.time()``, ``datetime.now()``)
    in the replay-deterministic modules (chaos, elastic, rendezvous,
    guard).  Those paths replay under chaos and across resume; they take
    injectable ``now``/``wall`` callables.  *References* like
    ``now: Callable = time.monotonic`` are the injection seam and pass;
    only direct calls are flagged.
  * **TCDP102** — every write-mode ``open()`` in a shared-dir protocol
    module (rendezvous epoch/vote/join files, gossip heartbeats,
    checkpoint manifests, prometheus textfiles) must target a ``*.tmp``
    sibling that is later ``os.replace()``d — readers on shared storage
    must never see a torn record.  Append mode is exempt (JSONL event
    streams rely on O_APPEND).
  * **TCDP103** — every ``"family/name"`` stat-key string literal in a
    registry-governed family must be declared in ``obs/registry.py``.
    This extends the runtime conformance test (tests/test_observability)
    to keys only ever touched on cold paths.
  * **TCDP104** — ``jax.named_scope`` strings outside ``obs/trace.py``
    must live in the ``tcdp.<phase>`` taxonomy, and literal
    ``obs_trace.phase(...)`` arguments must name a declared phase —
    xprof tooling (tools/trace_report.py) groups by these.
  * **TCDP105** — attributes mutated inside a ``threading.Thread`` target
    must hold the owning class's lock; unsynchronised writer threads are
    how the heartbeat false-positive bug happened (utils/resilience.py).

``lint_source`` is the per-file engine (tests feed it fixtures);
``run_host_pass`` walks the real tree and applies ``# tcdp-lint:
disable=`` suppressions.
"""

from __future__ import annotations

import ast
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

from tpu_compressed_dp.analysis.report import Finding, filter_suppressed

__all__ = [
    "REPLAY_DETERMINISTIC_MODULES", "SHARED_DIR_MODULES", "STAT_KEY_RE",
    "lint_source", "lint_file", "run_host_pass", "iter_lintable_files",
    "roles_for_path",
]

#: modules whose behaviour must replay bit-identically under chaos/resume —
#: wall-clock reads (TCDP101) are banned here, injectable clocks only
REPLAY_DETERMINISTIC_MODULES = (
    "tpu_compressed_dp/utils/chaos.py",
    "tpu_compressed_dp/train/elastic.py",
    "tpu_compressed_dp/train/rendezvous.py",
    "tpu_compressed_dp/train/guard.py",
    # the adaptive-compression control plane: decisions must replay
    # bitwise across crash/resume (the 'modeled' signal is a pure function
    # of checkpointed state + analytic comm stats — no clock reads)
    "tpu_compressed_dp/control/config.py",
    "tpu_compressed_dp/control/controller.py",
    "tpu_compressed_dp/control/rungs.py",
    "tpu_compressed_dp/control/signals.py",
    "tpu_compressed_dp/control/state.py",
    # the fleet decision loop: admission order, placement, preemption and
    # the records/events they produce must replay from the same snapshot
    # (clocks are injected, timestamps ride in via the scheduler's wall)
    "tpu_compressed_dp/fleet/spec.py",
    "tpu_compressed_dp/fleet/placement.py",
    "tpu_compressed_dp/fleet/scheduler.py",
    # the flight recorder rides inside replay-deterministic callers (guard
    # check, elastic failure handling) — its timestamps come from an
    # injected clock; postmortem replays bundles offline and must order
    # records by seq, never by wall reads of its own
    "tpu_compressed_dp/obs/flight.py",
    "tools/postmortem.py",
    # the delta state stream: segment content and window accounting must
    # replay bitwise (the lossless-window invariant) — segment timestamps
    # ride in via the writer's injected wall clock
    "tpu_compressed_dp/stream/delta.py",
    "tpu_compressed_dp/stream/writer.py",
    "tpu_compressed_dp/stream/reader.py",
    "tpu_compressed_dp/stream/rejoin.py",
)

#: modules that write records other processes read over shared storage —
#: write-mode opens (TCDP102) must go through tmp + os.replace here
SHARED_DIR_MODULES = (
    "tpu_compressed_dp/train/rendezvous.py",
    "tpu_compressed_dp/train/elastic.py",
    "tpu_compressed_dp/utils/resilience.py",
    "tpu_compressed_dp/utils/checkpoint.py",
    "tpu_compressed_dp/obs/export.py",
    # fleet queue/job/pool records: multi-process readers (operator CLI,
    # dashboards) over the shared fleet dir
    "tpu_compressed_dp/fleet/state.py",
    # blackbox bundles + phase profiles: every rank writes, postmortem /
    # peers / the watchdog read concurrently over the shared dir
    "tpu_compressed_dp/obs/flight.py",
    "tools/postmortem.py",
    # stream segments: the training rank writes, joiners and serving
    # consumers tail the same directory concurrently
    "tpu_compressed_dp/stream/store.py",
    "tools/stream_serve.py",
)

#: registry-governed stat-key families (TCDP103); literals shaped
#: "<family>/<name>" with these families must be declared
STAT_FAMILIES = ("comm", "guard", "elastic", "ckpt", "throughput", "time",
                 "net", "control", "fleet", "flight", "straggler", "stream")
STAT_KEY_RE = re.compile(r"^(?:%s)/[a-z0-9_]+$" % "|".join(STAT_FAMILIES))

_WALLCLOCK_CALLS = frozenset({
    "time.time",
    "datetime.datetime.now", "datetime.datetime.utcnow",
    "datetime.datetime.today", "datetime.date.today",
})
_LOCK_FACTORIES = frozenset({
    "threading.Lock", "threading.RLock", "threading.Condition",
})
_ROLE_PRAGMA_RE = re.compile(r"#\s*tcdp-lint:\s*roles=([a-z_,\s]+)")
_CHUNK_SCOPE_RE = re.compile(r"^chunk\d+$")


def roles_for_path(rel_path: str) -> Set[str]:
    """Rule-applicability roles inferred from the repo-relative path."""
    p = rel_path.replace(os.sep, "/")
    roles: Set[str] = set()
    if any(p.endswith(m) for m in REPLAY_DETERMINISTIC_MODULES):
        roles.add("replay")
    if any(p.endswith(m) for m in SHARED_DIR_MODULES):
        roles.add("shared_dir")
    return roles


def _pragma_roles(source: str) -> Optional[Set[str]]:
    """Fixture files self-describe their roles via a header pragma
    (``# tcdp-lint: roles=replay,shared_dir``)."""
    for line in source.splitlines()[:5]:
        m = _ROLE_PRAGMA_RE.search(line)
        if m:
            return {r.strip() for r in m.group(1).split(",") if r.strip()}
    return None


# --------------------------------------------------------------- resolution

class _Imports(ast.NodeVisitor):
    """name -> dotted origin, so ``obs_trace.phase`` / ``from time import
    time`` call sites resolve to canonical dotted names."""

    def __init__(self) -> None:
        self.origin: Dict[str, str] = {}

    def visit_Import(self, node: ast.Import) -> None:
        for a in node.names:
            self.origin[a.asname or a.name.split(".")[0]] = (
                a.name if a.asname else a.name.split(".")[0])

    def visit_ImportFrom(self, node: ast.ImportFrom) -> None:
        base = node.module or ""
        for a in node.names:
            self.origin[a.asname or a.name] = (
                f"{base}.{a.name}" if base else a.name)


def _dotted(node: ast.AST, origin: Dict[str, str]) -> Optional[str]:
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if not isinstance(node, ast.Name):
        return None
    parts.append(origin.get(node.id, node.id))
    return ".".join(reversed(parts))


def _is_docstring_set(tree: ast.Module) -> Set[int]:
    ids: Set[int] = set()
    for node in ast.walk(tree):
        if isinstance(node, (ast.Module, ast.ClassDef, ast.FunctionDef,
                             ast.AsyncFunctionDef)):
            body = getattr(node, "body", [])
            if (body and isinstance(body[0], ast.Expr)
                    and isinstance(body[0].value, ast.Constant)
                    and isinstance(body[0].value.value, str)):
                ids.add(id(body[0].value))
    return ids


# -------------------------------------------------------------------- rules

def _check_wallclock(tree: ast.Module, origin: Dict[str, str], rel: str,
                     out: List[Finding]) -> None:
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        dotted = _dotted(node.func, origin)
        if dotted in _WALLCLOCK_CALLS:
            out.append(Finding(
                code="TCDP101", file=rel, line=node.lineno,
                col=node.col_offset,
                message=f"{dotted}() read in a replay-deterministic module; "
                        "thread an injectable clock through instead"))


def _check_atomic_writes(tree: ast.Module, origin: Dict[str, str], rel: str,
                         out: List[Finding]) -> None:
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Name)
                and origin.get(node.func.id, node.func.id) == "open"):
            continue
        mode = "r"
        if len(node.args) >= 2 and isinstance(node.args[1], ast.Constant):
            mode = str(node.args[1].value)
        for kw in node.keywords:
            if kw.arg == "mode" and isinstance(kw.value, ast.Constant):
                mode = str(kw.value.value)
        if not any(c in mode for c in "wx"):
            continue  # read / append modes cannot tear a committed record
        path_src = ast.unparse(node.args[0]) if node.args else ""
        if "tmp" in path_src.lower():
            continue  # tmp sibling, committed by a later os.replace
        out.append(Finding(
            code="TCDP102", file=rel, line=node.lineno, col=node.col_offset,
            message=f"open({path_src or '?'}, {mode!r}) writes a shared-dir "
                    "record in place; write '<path>.<pid>.tmp' and "
                    "os.replace() it"))


def _check_stat_keys(tree: ast.Module, rel: str, out: List[Finding]) -> None:
    from tpu_compressed_dp.obs import registry

    docstrings = _is_docstring_set(tree)
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Constant) and isinstance(node.value, str)
                and id(node) not in docstrings):
            continue
        key = node.value
        if STAT_KEY_RE.match(key) and not registry.is_declared(key):
            out.append(Finding(
                code="TCDP103", file=rel, line=node.lineno,
                col=node.col_offset,
                message=f"stat key {key!r} is not declared in "
                        "obs/registry.py"))


def _check_named_scopes(tree: ast.Module, origin: Dict[str, str], rel: str,
                        out: List[Finding]) -> None:
    from tpu_compressed_dp.obs import trace as obs_trace

    if rel.replace(os.sep, "/").endswith("tpu_compressed_dp/obs/trace.py"):
        return  # the taxonomy's own definition site
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        dotted = _dotted(node.func, origin) or ""
        lit = (node.args[0].value
               if node.args and isinstance(node.args[0], ast.Constant)
               and isinstance(node.args[0].value, str) else None)
        if dotted.endswith("jax.named_scope") or dotted == "jax.named_scope":
            if lit is None:
                continue
            name = lit[len("tcdp."):] if lit.startswith("tcdp.") else None
            if name is None or not (name in obs_trace.PHASES
                                    or _CHUNK_SCOPE_RE.match(name)):
                out.append(Finding(
                    code="TCDP104", file=rel, line=node.lineno,
                    col=node.col_offset,
                    message=f"named_scope({lit!r}) outside the tcdp.<phase> "
                            f"taxonomy {obs_trace.PHASES}"))
        elif dotted.endswith("obs.trace.phase") or dotted.endswith(
                "obs_trace.phase"):
            if lit is not None and lit not in obs_trace.PHASES:
                out.append(Finding(
                    code="TCDP104", file=rel, line=node.lineno,
                    col=node.col_offset,
                    message=f"phase({lit!r}) is not a declared phase "
                            f"{obs_trace.PHASES}"))


class _ThreadRule(ast.NodeVisitor):
    """TCDP105: per class, collect lock attributes and Thread targets, then
    require every ``self.<attr> = ...`` inside a target to sit under a
    ``with self.<lock>:`` block."""

    def __init__(self, origin: Dict[str, str], rel: str,
                 out: List[Finding]) -> None:
        self.origin = origin
        self.rel = rel
        self.out = out

    def visit_ClassDef(self, node: ast.ClassDef) -> None:
        locks: Set[str] = set()
        targets: List[ast.FunctionDef] = []
        methods = {m.name: m for m in node.body
                   if isinstance(m, ast.FunctionDef)}
        local_funcs: Dict[str, ast.FunctionDef] = {}
        for sub in ast.walk(node):
            if (isinstance(sub, ast.FunctionDef)
                    and sub.name not in methods):
                local_funcs[sub.name] = sub
            if isinstance(sub, ast.Assign):
                for t in sub.targets:
                    if (isinstance(t, ast.Attribute)
                            and isinstance(t.value, ast.Name)
                            and t.value.id == "self"
                            and isinstance(sub.value, ast.Call)
                            and _dotted(sub.value.func, self.origin)
                            in _LOCK_FACTORIES):
                        locks.add(t.attr)
            if (isinstance(sub, ast.Call)
                    and (_dotted(sub.func, self.origin) or "").endswith(
                        "threading.Thread")):
                for kw in sub.keywords:
                    if kw.arg != "target":
                        continue
                    fn = None
                    if (isinstance(kw.value, ast.Attribute)
                            and isinstance(kw.value.value, ast.Name)
                            and kw.value.value.id == "self"):
                        fn = methods.get(kw.value.attr)
                    elif isinstance(kw.value, ast.Name):
                        fn = local_funcs.get(kw.value.id)
                    if fn is not None:
                        targets.append(fn)
        for fn in targets:
            self._check_target(fn, locks, node.name)
        self.generic_visit(node)

    def _check_target(self, fn: ast.FunctionDef, locks: Set[str],
                      cls: str) -> None:
        def walk(stmts: Sequence[ast.stmt], guarded: bool) -> None:
            for st in stmts:
                if isinstance(st, ast.With):
                    holds = guarded or any(
                        isinstance(it.context_expr, ast.Attribute)
                        and isinstance(it.context_expr.value, ast.Name)
                        and it.context_expr.value.id == "self"
                        and it.context_expr.attr in locks
                        for it in st.items)
                    walk(st.body, holds)
                    continue
                if isinstance(st, (ast.Assign, ast.AugAssign, ast.AnnAssign)):
                    tgts = (st.targets if isinstance(st, ast.Assign)
                            else [st.target])
                    for t in tgts:
                        if (isinstance(t, ast.Attribute)
                                and isinstance(t.value, ast.Name)
                                and t.value.id == "self" and not guarded):
                            self.out.append(Finding(
                                code="TCDP105", file=self.rel,
                                line=st.lineno, col=st.col_offset,
                                message=(
                                    f"self.{t.attr} assigned in thread "
                                    f"target {cls}.{fn.name} without "
                                    f"holding a class lock "
                                    f"({sorted(locks) or 'none declared'})")))
                    continue
                if isinstance(st, ast.Try):
                    walk(st.body, guarded)
                    walk(st.orelse, guarded)
                    walk(st.finalbody, guarded)
                    for h in st.handlers:
                        walk(h.body, guarded)
                elif isinstance(st, (ast.For, ast.While, ast.If)):
                    walk(st.body, guarded)
                    walk(st.orelse, guarded)
                # nested defs are analysed only when they are themselves
                # Thread targets (collected by visit_ClassDef)

        walk(fn.body, guarded=False)


# ------------------------------------------------------------------ drivers

def lint_source(source: str, rel_path: str,
                roles: Optional[Set[str]] = None) -> List[Finding]:
    """Run all five rules over one file's source; returns RAW findings
    (no suppression applied — see :func:`run_host_pass`)."""
    tree = ast.parse(source, filename=rel_path)
    imports = _Imports()
    imports.visit(tree)
    origin = imports.origin
    if roles is None:
        roles = _pragma_roles(source) or roles_for_path(rel_path)
    out: List[Finding] = []
    if "replay" in roles:
        _check_wallclock(tree, origin, rel_path, out)
    if "shared_dir" in roles:
        _check_atomic_writes(tree, origin, rel_path, out)
    if not rel_path.replace(os.sep, "/").endswith(
            "tpu_compressed_dp/obs/registry.py"):
        _check_stat_keys(tree, rel_path, out)
    _check_named_scopes(tree, origin, rel_path, out)
    _ThreadRule(origin, rel_path, out).visit(tree)
    out.sort(key=lambda f: (f.file, f.line, f.code))
    return out


def lint_file(path: str, repo_root: str) -> Tuple[List[Finding], str]:
    with open(path, "r", encoding="utf-8") as f:
        source = f.read()
    rel = os.path.relpath(path, repo_root)
    return lint_source(source, rel), source


def iter_lintable_files(repo_root: str) -> List[str]:
    """Package + tools/ .py files — the scope Pass 2 gates."""
    out: List[str] = []
    for top in ("tpu_compressed_dp", "tools"):
        base = os.path.join(repo_root, top)
        for root, dirs, names in os.walk(base):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            out.extend(os.path.join(root, n) for n in names
                       if n.endswith(".py"))
    return sorted(out)


def run_host_pass(repo_root: str, files: Optional[Iterable[str]] = None,
                  ) -> Tuple[List[Finding], List[Finding]]:
    """Lint the tree (or an explicit file list) and apply suppressions.
    Returns ``(active, suppressed)``."""
    raw: List[Finding] = []
    sources: Dict[str, str] = {}
    for path in (files if files is not None
                 else iter_lintable_files(repo_root)):
        findings, source = lint_file(path, repo_root)
        raw.extend(findings)
        sources[os.path.relpath(path, repo_root)] = source
    return filter_suppressed(raw, sources)
