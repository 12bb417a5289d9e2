"""Elastic data-parallel training: failure detection, coordinated abort,
W-1 remesh with EF/PowerSGD state migration, and scale-up re-admission.

The robustness stack up to here survives nonfinite steps (the step guard)
and single-process death (checkpoint + watchdog relaunch) — but only by
restarting the WHOLE job: a dead host stalls every collective until the
supervisor kills the world.  This module is the other half: survivors
detect the failure, abort coherently, shrink the mesh by the dead worker,
and keep training.

Failure model (three detection planes, all raising :class:`PeerFailed`):

  * **heartbeat gossip** (:class:`PeerGossip`) — every worker process
    writes its own liveness file (:meth:`PeerGossip.beat`, same atomic
    record shape as :class:`~tpu_compressed_dp.utils.resilience.Heartbeat`)
    into a shared ``--elastic_dir``; every worker reads its peers' files
    each poll.  A peer whose record stays older than ``peer_timeout_s`` is
    dead.  Records carry an ``incarnation`` (seeded from
    ``TCDP_RESTART_COUNT``, exported by ``tools/watchdog.py --relaunch``):
    a restarted peer's fresh file has a HIGHER incarnation, so it reads as
    "this rank died and came back" (a rejoin candidate), never as
    continuity of the dead life.
  * **bounded collective fetch** (:func:`fetch_with_timeout`) — a
    ``device_get`` on results of an in-flight step normally returns in
    step time; when a peer died mid-collective it blocks forever.  The
    fetch runs in a worker thread with a deadline; blowing it raises
    ``PeerFailed`` instead of stalling silently.  (Honest limitation: an
    in-process XLA computation cannot be cancelled — on real multi-host
    deployments the abort is a process exit and the watchdog relaunches
    into the next remesh barrier; under the single-process simulation the
    deterministic ``crash=mid_collective`` chaos plays the dying peer.)
  * **deterministic chaos** — ``--chaos crash=mid_collective,...`` raises
    after step dispatch, while the step's collectives are in flight;
    :meth:`ElasticRuntime.failure_from` translates it into the same
    ``PeerFailed`` the real detectors raise, which is what lets the chaos
    drill prove the whole remesh path bitwise.

Remesh semantics (what the departing worker owes the run):

  * ``params`` / ``opt_state`` / ``batch_stats`` / ``guard`` are replicated
    — survivors already hold them; they are preserved **bitwise**.
  * ``TrainState.ef`` is per-worker unsent gradient mass (the memory of
    "Sparsified SGD with Memory"): the lost worker's residual row is either
    **folded** into a survivor's residual (an exact fp32 add — total EF
    mass is conserved, and the folded mass re-enters the very next step's
    gradients like any EF carry) or **dropped** and accounted in the
    ``elastic/dropped_ef_norm`` metric (the L2 norm of the gradient mass
    the run will never apply).
  * ``TrainState.comp`` (PowerSGD warm-start factors) is identical on
    every worker by construction (the P/Q psums average factors), so the
    dead worker's rows are simply deleted; on re-admission the returning
    worker's factors are re-warmed from a broadcast of a survivor's row —
    re-agreement is what keeps the power iteration meaningful.
  * The sharded transport's owner partition (``ops/wire_sharded.py``) is a
    pure function of the static world size read off the mesh at trace
    time, so rebuilding the train step over the W-1 mesh recomputes the
    shard boundaries automatically (tests/test_wire_sharded.py asserts the
    W -> W-1 partition keeps covering the flat unit space exactly).

Scale-up: a returning host rejoins at the next remesh barrier
(:meth:`ElasticRuntime.readmit`): the mesh is extended with the parked
device, the live (in-process) state plays the role of the live checkpoint,
the new EF row starts at zero (a fresh worker has not withheld anything)
and the comp rows are broadcast-re-warmed.

``tools/chaos_drill.py`` (``elastic_remesh`` / ``elastic_readmit`` /
``elastic_matrix``) proves the invariants end to end.
"""

from __future__ import annotations

import dataclasses
import json
import os
import threading
import time
from typing import Any, Callable, Dict, Iterable, List, Optional, Sequence, Tuple

import jax
import numpy as np

from tpu_compressed_dp.parallel.mesh import DATA_AXIS, make_data_mesh
from tpu_compressed_dp.utils.resilience import read_heartbeat

__all__ = [
    "PeerFailed", "ElasticConfig", "PeerGossip", "ElasticRuntime",
    "heartbeat_path", "write_peer_heartbeat", "fetch_with_timeout",
    "abandoned_fetch_count",
    "surviving_mesh", "extended_mesh", "migrate_ef", "migrate_comp",
    "expand_ef", "expand_comp", "shrink_state", "expand_state",
    "TrimBatches",
]

#: Default failure-detection budget: a peer heartbeat older than this (and
#: a collective fetch blocked longer than this) counts as a dead peer.
DEFAULT_PEER_TIMEOUT_S = 60.0


class PeerFailed(RuntimeError):
    """Coordinated abort signal: one or more peers are gone.

    ``failed`` — worker indices (mesh positions / gossip ranks) declared
    dead; may be empty when a collective timeout fired before the gossip
    named a culprit (the runtime then consults gossip to fill it in).
    ``step`` — the attempted global step, when known.  Every survivor
    raises the same verdict from the same evidence (stale files age out at
    the same wall-clock deadline; the chaos injection is step-keyed), which
    is what makes the abort coordinated rather than a stampede.
    """

    def __init__(self, failed: Iterable[int] = (), *,
                 step: Optional[int] = None, reason: str = "peer failure"):
        self.failed: Tuple[int, ...] = tuple(sorted(int(f) for f in failed))
        self.step = None if step is None else int(step)
        self.reason = reason
        who = list(self.failed) if self.failed else "unknown peer(s)"
        at = f" at step {self.step}" if self.step is not None else ""
        super().__init__(f"elastic: {who} failed{at}: {reason}")


@dataclasses.dataclass(frozen=True)
class ElasticConfig:
    """Knobs of the elastic runtime (CLI surface: ``--elastic*``).

    gossip_dir:      shared directory of per-rank heartbeat files (None =
                     no gossip plane; chaos / fetch timeouts still work)
    rank:            this worker's gossip rank
    peer_timeout_s:  staleness/fetch deadline before a peer counts as dead
    min_world:       refuse to shrink below this many workers (the job is
                     better off dying and relaunching than limping on a
                     mesh too small to be worth the lr/batch mismatch)
    ef_policy:       'fold' (conserve the lost EF mass into a survivor) |
                     'drop' (discard it; counted in elastic/dropped_ef_norm)
    """

    gossip_dir: Optional[str] = None
    rank: int = 0
    peer_timeout_s: float = DEFAULT_PEER_TIMEOUT_S
    min_world: int = 2
    ef_policy: str = "fold"

    def __post_init__(self):
        if self.ef_policy not in ("fold", "drop"):
            raise ValueError(
                f"ef_policy must be fold|drop, got {self.ef_policy!r}")
        if self.peer_timeout_s <= 0:
            raise ValueError("peer_timeout_s must be > 0")
        if self.min_world < 1:
            raise ValueError("min_world must be >= 1")


# ------------------------------------------------------------------ gossip

def heartbeat_path(gossip_dir: str, rank: int) -> str:
    return os.path.join(gossip_dir, f"rank{int(rank)}.json")


def write_peer_heartbeat(gossip_dir: str, rank: int, step: int, *,
                         incarnation: int = 0,
                         ts: Optional[float] = None,
                         wall: Callable[[], float] = time.time) -> str:
    """One atomic heartbeat write into the gossip directory — the
    thread-free form the harness step loops and the drill's simulated
    peers use (same record shape and atomic tmp+replace as
    :class:`~tpu_compressed_dp.utils.resilience.Heartbeat`).  ``ts``
    overrides the record timestamp outright; ``wall`` is the injectable
    clock it defaults to (peer staleness is judged on LOCAL monotonic
    freshness, never on this field — see :class:`PeerGossip`)."""
    os.makedirs(gossip_dir, exist_ok=True)
    path = heartbeat_path(gossip_dir, rank)
    rec = {"ts": wall() if ts is None else float(ts),
           "step": int(step), "rank": int(rank),
           "incarnation": int(incarnation)}
    tmp = f"{path}.{os.getpid()}.tmp"
    with open(tmp, "w") as f:
        json.dump(rec, f)
    os.replace(tmp, path)
    return path


class PeerGossip:
    """Decentralised failure detector over a shared heartbeat directory.

    Each worker runs one instance: it reads every peer's file per
    :meth:`check` and votes a peer dead once no FRESH record (a changed
    record under the admitted incarnation) has been seen for
    ``peer_timeout_s`` of local monotonic time.  Incarnation rules:

      * the first record seen for a rank admits its incarnation;
      * a record with a LOWER incarnation than admitted is a stale file of
        a dead prior life — it never refreshes liveness;
      * a record with a HIGHER incarnation means the peer process was
        replaced: the admitted life is declared dead (its in-memory EF row
        is gone regardless of how alive the new process looks) and the new
        incarnation becomes a rejoin candidate for the next barrier.

    Construction starts every peer's grace clock at "now", so a cold start
    where peers appear over ``peer_timeout_s`` does not false-positive.

    Clock discipline: staleness is measured on THIS process's monotonic
    clock, and a peer is fresh when its record *changed* since the last
    sweep — the writer's wall-clock ``ts`` is never compared against local
    time.  An NTP step (or plain cross-host clock skew) therefore cannot
    mass-declare live peers dead: as long as a peer keeps rewriting its
    file (``beat`` rewrites at least every ``peer_timeout_s / 4``), it
    keeps reading as alive no matter what its timestamps claim.  The one
    cost is that a pre-existing stale file buys its dead writer a single
    extra timeout window at first observation (it reads as a change) —
    the same grace a cold start already grants.
    """

    def __init__(self, gossip_dir: str, rank: int, world: int, *,
                 peer_timeout_s: float = DEFAULT_PEER_TIMEOUT_S,
                 incarnation: Optional[int] = None,
                 now: Callable[[], float] = time.monotonic):
        self.gossip_dir = gossip_dir
        self.rank = int(rank)
        self.world = int(world)
        self.peer_timeout_s = float(peer_timeout_s)
        if incarnation is None:
            try:
                incarnation = int(os.environ.get("TCDP_RESTART_COUNT", "0"))
            except ValueError:
                incarnation = 0
        self.incarnation = int(incarnation)
        self._last_beat = float("-inf")
        self._now = now
        t0 = now()
        self._last_fresh: Dict[int, float] = {
            r: t0 for r in range(self.world)}
        self._last_rec: Dict[int, Tuple] = {}    # rank -> last observed record
        self._admitted: Dict[int, Optional[int]] = {
            r: None for r in range(self.world)}
        self._dead: Dict[int, str] = {}          # rank -> reason
        self._rejoin: Dict[int, int] = {}        # rank -> new incarnation

    @property
    def dead(self) -> Tuple[int, ...]:
        return tuple(sorted(self._dead))

    def beat(self, step: int = 0) -> None:
        """Write THIS rank's own liveness file (rate-limited to a quarter of
        the timeout — peers need several fresh observations per window, and
        an atomic replace per step would be pure filesystem churn)."""
        now = self._now()
        if now - self._last_beat >= self.peer_timeout_s / 4:
            write_peer_heartbeat(self.gossip_dir, self.rank, step,
                                 incarnation=self.incarnation, ts=now)
            self._last_beat = now

    def note_dead(self, ranks: Iterable[int], reason: str = "declared dead"
                  ) -> None:
        """Record an externally-detected failure (chaos conversion, a peer
        named by another detector) so rejoin tracking stays consistent."""
        for r in ranks:
            self._dead.setdefault(int(r), reason)

    def check(self, now: Optional[float] = None) -> Dict[int, str]:
        """One gossip sweep; returns the NEWLY dead peers ``{rank: why}``
        (already-known dead peers are only re-reported via :attr:`dead`)."""
        now = self._now() if now is None else now
        newly: Dict[int, str] = {}
        for r in range(self.world):
            if r == self.rank:
                continue
            hb = read_heartbeat(heartbeat_path(self.gossip_dir, r))
            inc = None
            changed = False
            if hb is not None:
                inc = int(hb.get("incarnation", 0) or 0)
                rec = (hb.get("ts"), hb.get("step"), inc)
                changed = rec != self._last_rec.get(r)
                if changed:
                    self._last_rec[r] = rec
            if r in self._dead:
                dead_inc = self._admitted.get(r)
                if (hb is not None and changed and inc is not None
                        and (dead_inc is None or inc > dead_inc)):
                    self._rejoin[r] = inc
                continue
            if hb is not None:
                if self._admitted[r] is None:
                    self._admitted[r] = inc
                if inc > self._admitted[r]:
                    # the process we were tracking is gone; its replacement
                    # may rejoin, but the tracked life's state died with it
                    why = (f"incarnation advanced {self._admitted[r]} -> "
                           f"{inc} (peer restarted)")
                    self._dead[r] = why
                    newly[r] = why
                    self._rejoin[r] = inc
                    continue
                if changed and inc == self._admitted[r]:
                    # liveness = "the record is still being rewritten",
                    # stamped with OUR clock — never the writer's wall ts
                    self._last_fresh[r] = max(self._last_fresh[r],
                                              float(now))
            age = now - self._last_fresh[r]
            if age > self.peer_timeout_s:
                why = (f"no fresh heartbeat for {age:.1f}s "
                       f"(> {self.peer_timeout_s:g}s)")
                self._dead[r] = why
                newly[r] = why
        return newly

    def raise_if_dead(self, step: Optional[int] = None,
                      now: Optional[float] = None) -> None:
        newly = self.check(now)
        if newly:
            reason = "; ".join(f"rank {r}: {why}"
                               for r, why in sorted(newly.items()))
            raise PeerFailed(newly, step=step, reason=reason)

    def rejoin_candidates(self, now: Optional[float] = None
                          ) -> Dict[int, int]:
        """Dead ranks whose directory now shows a fresh, newer incarnation
        — ready for re-admission at the next barrier."""
        self.check(now)
        return dict(self._rejoin)

    def readmit(self, rank: int) -> None:
        """Move a rank back to the tracked set under its new incarnation."""
        rank = int(rank)
        inc = self._rejoin.pop(rank, None)
        self._dead.pop(rank, None)
        self._admitted[rank] = inc
        self._last_fresh[rank] = self._now()


# ------------------------------------------------- bounded collective fetch

# Timed-out fetch threads cannot be killed (a device_get blocked inside the
# runtime has no cancellation point), but they must not LEAK: each abandoned
# runner is tracked here and reaped (dropped from the list) as soon as it
# finishes, and its discard flag makes it drop the fetched buffer instead of
# pinning it in a result box nobody will ever read.
_ABANDONED_FETCHES: List[threading.Thread] = []
_ABANDONED_LOCK = threading.Lock()


def abandoned_fetch_count() -> int:
    """Live runner threads whose deadline expired (reaps finished ones
    first).  Steady state is 0 once their blocking fetches drain — the
    hammer test pins that repeated timeouts do not accumulate threads."""
    with _ABANDONED_LOCK:
        _ABANDONED_FETCHES[:] = [t for t in _ABANDONED_FETCHES
                                 if t.is_alive()]
        return len(_ABANDONED_FETCHES)


def fetch_with_timeout(thunk: Callable[[], Any], timeout_s: float, *,
                       step: Optional[int] = None,
                       what: str = "collective fetch") -> Any:
    """Run a blocking device fetch with a deadline.

    ``jax.device_get`` on an in-flight step's outputs normally completes in
    step time; with a peer dead mid-collective it blocks forever.  The
    thunk runs in a daemon thread; exceeding ``timeout_s`` raises
    :class:`PeerFailed` (with no culprit — gossip names the rank).  The
    thunk's own exception, if any, is re-raised on the caller's thread.

    On timeout the caller marks the runner DISCARDED before abandoning it:
    whenever the blocked fetch eventually returns, the runner drops the
    value on the floor (no reference survives the function) instead of
    parking a dead world's device buffers in a result box forever.  The
    abandoned thread itself is tracked and reaped once it exits
    (:func:`abandoned_fetch_count`).
    """
    box: Dict[str, Any] = {}
    done = threading.Event()
    lock = threading.Lock()
    discarded = [False]

    def runner():
        try:
            value = thunk()
            with lock:
                if not discarded[0]:
                    box["value"] = value
            del value
        except BaseException as e:
            with lock:
                if not discarded[0]:  # nobody is left to re-raise it to
                    box["error"] = e
        finally:
            done.set()

    t = threading.Thread(target=runner, daemon=True,
                         name=f"tcdp-elastic-fetch({what})")
    t.start()
    if not done.wait(timeout_s):
        with lock:
            discarded[0] = True
        with _ABANDONED_LOCK:
            _ABANDONED_FETCHES[:] = [a for a in _ABANDONED_FETCHES
                                     if a.is_alive()]
            _ABANDONED_FETCHES.append(t)
        raise PeerFailed((), step=step, reason=(
            f"{what} still blocked after {timeout_s:g}s — "
            "a peer died mid-collective"))
    if "error" in box:
        raise box["error"]
    return box.get("value")


# ------------------------------------------------------------ mesh surgery

def _mesh_grid(mesh) -> np.ndarray:
    """The mesh's devices as a ``(data_rows, model_cols)`` object grid:
    row ``i`` is data worker ``i``'s devices across every model axis
    (tensor/sequence/pipe), flattened in axis order.  Elastic membership
    changes are DATA-row changes — a dying host takes one data row (its
    model shards are replicated across data rows, so survivors still hold
    a full copy of the model); losing a model COLUMN would orphan model
    state and stays a job restart.  The grid view is what lets the surgery
    below work unchanged on ('data',), dp x tp, dp x sp, ... meshes."""
    names = tuple(mesh.axis_names)
    if DATA_AXIS not in names:
        raise ValueError(
            f"elastic remesh needs a '{DATA_AXIS}' axis; got axes {names}")
    dev = np.asarray(mesh.devices, dtype=object)
    i = names.index(DATA_AXIS)
    rows = int(dev.shape[i])
    return np.moveaxis(dev, i, 0).reshape(rows, -1)


def _rebuild_mesh(mesh, grid: np.ndarray):
    """A mesh over the ``(data_rows, model_cols)`` grid with the template
    mesh's axis names and model-axis sizes (only the data axis resizes, so
    the harness's PartitionSpecs keep resolving on the new mesh)."""
    names = tuple(mesh.axis_names)
    grid = np.asarray(grid, dtype=object)
    if names == (DATA_AXIS,):
        return make_data_mesh(devices=list(grid.reshape(-1)))
    i = names.index(DATA_AXIS)
    model_shape = [int(mesh.shape[n]) for n in names if n != DATA_AXIS]
    dev = grid.reshape([grid.shape[0]] + model_shape)
    dev = np.moveaxis(dev, 0, i)
    return jax.sharding.Mesh(dev, names)


def _as_rows(new_devices: Sequence, model_cols: int) -> np.ndarray:
    """Normalise readmitted devices to grid rows: a flat device list on a
    dp-only mesh (one device per row), or per-row device sequences on a
    sheared mesh."""
    rows = []
    for entry in new_devices:
        row = [entry] if not isinstance(entry, (list, tuple, np.ndarray)) \
            else list(entry)
        if len(row) != model_cols:
            raise ValueError(
                f"readmitted row has {len(row)} device(s); the mesh's "
                f"model axes need {model_cols} per data row")
        rows.append(row)
    return np.asarray(rows, dtype=object).reshape(len(rows), model_cols)


def surviving_mesh(mesh, failed: Sequence[int]):
    """The W-1 (or W-F) mesh over the surviving data rows, order
    preserved; returns ``(new_mesh, removed_rows)`` with each dead
    worker's devices (a full model row) parked for later re-admission."""
    grid = _mesh_grid(mesh)
    failed_set = {int(f) for f in failed}
    bad = [f for f in failed_set if not 0 <= f < grid.shape[0]]
    if bad:
        raise ValueError(f"failed worker index {bad} outside world "
                         f"{grid.shape[0]}")
    keep = [i for i in range(grid.shape[0]) if i not in failed_set]
    if not keep:
        raise ValueError("no survivors to remesh over")
    removed = [list(grid[i]) if grid.shape[1] > 1 else grid[i, 0]
               for i in sorted(failed_set)]
    return _rebuild_mesh(mesh, grid[keep]), removed


def extended_mesh(mesh, new_devices: Sequence):
    """The mesh with returning data rows appended (rejoiners take the tail
    positions — survivor worker indices, and with them the EF rows and the
    owner partition prefix, stay stable)."""
    grid = _mesh_grid(mesh)
    rows = _as_rows(new_devices, grid.shape[1])
    return _rebuild_mesh(mesh, np.concatenate([grid, rows], axis=0))


# -------------------------------------------------------- state migration

def migrate_ef(ef: Any, failed: Sequence[int], *, policy: str = "fold",
               fold_into: int = 0) -> Tuple[Any, float]:
    """Shrink the EF residual's leading worker axis by ``failed``.

    ``fold``: the lost rows are added into survivor row ``fold_into``
    (survivor order) with one exact fp32 add per leaf — total residual mass
    is conserved and re-enters the next step's gradients like any EF carry.
    ``drop``: the lost rows are discarded; returns their global L2 norm
    (root of the summed squares across all leaves, fp64 accumulate) so the
    caller can account the abandoned gradient mass.

    Host-side numpy on fetched arrays; returns ``(new_ef, dropped_norm)``.
    """
    if policy not in ("fold", "drop"):
        raise ValueError(f"ef policy must be fold|drop, got {policy!r}")
    if ef == ():
        return (), 0.0
    failed = sorted({int(f) for f in failed})
    dropped_sq = 0.0

    def one(a):
        nonlocal dropped_sq
        a = np.asarray(a)
        if a.ndim < 1 or a.shape[0] <= max(failed):
            raise ValueError(
                f"EF leaf with leading axis {a.shape} cannot lose "
                f"worker(s) {failed}")
        lost = a[failed]
        kept = np.delete(a, failed, axis=0)
        if policy == "fold":
            kept = kept.copy()
            kept[fold_into] = kept[fold_into] + lost.sum(axis=0)
        else:
            dropped_sq += float(np.sum(lost.astype(np.float64) ** 2))
        return kept

    new_ef = jax.tree.map(one, ef)
    return new_ef, float(np.sqrt(dropped_sq))


def migrate_comp(comp: Any, failed: Sequence[int]) -> Any:
    """Shrink the compressor state's leading worker axis: the PowerSGD
    warm-start rows are identical across workers (psum-averaged), so the
    dead rows are deleted with nothing to fold."""
    if comp == ():
        return ()
    failed = sorted({int(f) for f in failed})
    return jax.tree.map(
        lambda a: np.delete(np.asarray(a), failed, axis=0), comp)


def expand_ef(ef: Any, n_new: int = 1) -> Any:
    """Append zero rows for rejoining workers (a fresh worker has not
    withheld any gradient mass yet)."""
    if ef == () or n_new <= 0:
        return ef
    return jax.tree.map(
        lambda a: np.concatenate(
            [np.asarray(a),
             np.zeros((n_new,) + np.asarray(a).shape[1:],
                      np.asarray(a).dtype)], axis=0), ef)


def expand_comp(comp: Any, n_new: int = 1) -> Any:
    """Append broadcast copies of survivor row 0 for rejoining workers —
    the PowerSGD re-warm: every worker must iterate in the same basis, so
    the newcomer adopts the survivors' converged factors instead of a cold
    random restart."""
    if comp == () or n_new <= 0:
        return comp
    return jax.tree.map(
        lambda a: np.concatenate(
            [np.asarray(a)]
            + [np.asarray(a)[:1]] * n_new, axis=0), comp)


def _rows_per_data_row(tree: Any, data_world: Optional[int]) -> int:
    """How many leading-axis rows one DATA row owns in an EF/comp tree.

    The leading worker axis counts SYNC workers, which on a sheared mesh
    is the product of every axis the gradient sync spans — e.g. the LM
    harness's EF is ``P(('data', 'seq'), ...)``, so a dp x sp mesh has
    ``sp`` EF rows per data row, laid out data-major (data row ``d`` owns
    rows ``[d*sp, (d+1)*sp)``).  Derived from the leaves' actual leading
    dim against the mesh's data extent so no extra configuration can
    drift from the real layout."""
    if tree == () or data_world is None:
        return 1
    leaves = jax.tree.leaves(tree)
    if not leaves:
        return 1
    lead = int(np.asarray(leaves[0]).shape[0])
    if data_world <= 0 or lead % data_world:
        raise ValueError(
            f"EF/comp leading axis {lead} does not divide into "
            f"{data_world} data rows")
    return lead // data_world


def _worker_rows(data_rows: Sequence[int], m: int) -> List[int]:
    """Expand failed DATA-row indices into leading-axis row indices
    (identity when ``m == 1``, the dp-only layout)."""
    return [int(d) * m + j for d in sorted({int(x) for x in data_rows})
            for j in range(m)]


def shrink_state(state, failed: Sequence[int], *, policy: str = "fold",
                 fold_into: int = 0, data_world: Optional[int] = None):
    """Migrate a TrainState off the dead workers: fetch ef/comp to host,
    shrink their leading axes, keep every replicated field bitwise.
    ``failed`` are DATA-row indices; ``data_world`` (the data extent of
    the mesh being shrunk) translates them to leading-axis rows when the
    sync world is wider than the data axis (dp x sp — see
    :func:`_rows_per_data_row`); omitted, rows map 1:1.  Returns
    ``(new_state, dropped_ef_norm)`` — still host-side; the caller places
    it on the new mesh (``with_mesh_sharding`` / ``place_lm_state``)."""
    ef = jax.device_get(state.ef) if state.ef != () else ()
    comp = jax.device_get(state.comp) if state.comp != () else ()
    ef_rows = _worker_rows(failed, _rows_per_data_row(ef, data_world))
    comp_rows = _worker_rows(failed, _rows_per_data_row(comp, data_world))
    new_ef, dropped = migrate_ef(ef, ef_rows, policy=policy,
                                 fold_into=fold_into)
    new_comp = migrate_comp(comp, comp_rows)
    return dataclasses.replace(state, ef=new_ef, comp=new_comp), dropped


def expand_state(state, n_new: int = 1, *,
                 data_world: Optional[int] = None):
    """Extend a TrainState for ``n_new`` rejoining DATA rows (zero EF
    rows, broadcast-re-warmed comp rows — ``m`` leading-axis rows per data
    row, see :func:`_rows_per_data_row` with ``data_world`` the CURRENT
    pre-extension data extent); host-side, caller re-places."""
    ef = jax.device_get(state.ef) if state.ef != () else ()
    comp = jax.device_get(state.comp) if state.comp != () else ()
    m_ef = _rows_per_data_row(ef, data_world)
    m_comp = _rows_per_data_row(comp, data_world)
    return dataclasses.replace(state, ef=expand_ef(ef, n_new * m_ef),
                               comp=expand_comp(comp, n_new * m_comp))


class TrimBatches:
    """Iterable view trimming each batch dict to at most ``size`` rows —
    the remeshed world divides a smaller global batch, so after W -> W-1
    each batch is cut to ``(bs // W') * W'`` rows (short final batches pass
    through untouched for the eval padding to handle)."""

    def __init__(self, inner, size: int):
        self.inner = inner
        self.size = int(size)

    def __iter__(self):
        for batch in self.inner:
            yield {k: v[:self.size] for k, v in batch.items()}

    def __len__(self):
        return len(self.inner)


# ----------------------------------------------------------------- runtime

class ElasticRuntime:
    """The harness-facing elastic driver: owns the current mesh, converts
    failures, performs the remesh, and keeps the ``elastic/*`` counters.

    Typical harness shape::

        el = ElasticRuntime(cfg, mesh, chaos=chaos, events=events)
        while epoch < epochs:
            try:
                state, ... = train_epoch(step_for(el.mesh), state, ...)
            except Exception as e:
                failure = el.failure_from(e)
                if failure is None:
                    raise
                state = el.handle_failure(state, failure)
                continue        # retry the epoch on the W-1 mesh
            epoch += 1
    """

    def __init__(self, cfg: ElasticConfig, mesh, *, chaos=None,
                 gossip: Optional[PeerGossip] = None, events=None,
                 place: Optional[Callable[[Any, Any], Any]] = None,
                 crash=None, rendezvous=None,
                 ef_axes: Tuple[str, ...] = (DATA_AXIS,),
                 flight=None, stream=None, stream_armed=None,
                 log: Callable[[str], None] = print):
        _mesh_grid(mesh)  # validates the mesh shape up front
        self.cfg = cfg
        self.mesh = mesh
        self.chaos = chaos
        self.gossip = gossip
        self.events = events
        # flight recorder (obs/flight.py): peer failures dump a blackbox
        # bundle, remesh/cascade/readmit transitions land in its elastic
        # ring — observation only, never load-bearing
        self.flight = flight
        # how to re-place a migrated state on a new mesh; the CNN default
        # is the TrainState's own sharding rule, the LM harness passes its
        # place_lm_state closure
        self._place = place or (lambda s, m: s.with_mesh_sharding(m))
        # the armed CrashInjector (utils/chaos.py): handle_failure probes
        # its 'during_remesh' phase so a second death INSIDE the failure
        # handler cascades instead of wedging
        self.crash = crash
        # the rendezvous handle (train/rendezvous.py) — arms the
        # multi-process coordinated re-init path; None keeps every remesh
        # in-process (the single-process simulation and all the drills)
        self.rendezvous = rendezvous
        # the delta StreamWriter (stream/writer.py): every committed world
        # transition requests a keyframe (the delta window re-anchors on
        # the new membership) and the rejoin barrier flushes the stream so
        # a joiner catching up from it adopts the live params bitwise
        self.stream = stream
        # whether the delta stream is armed FLEET-WIDE (``--stream_dir``
        # on every process).  The writer itself lives only on process 0,
        # so the warm-rejoin barrier layout must key on this flag — a
        # value every survivor shares — never on ``self.stream`` (which
        # would make process 0 pick a different collective pytree than
        # the other survivors).  Defaults to following ``stream`` for
        # single-writer setups constructed directly (tests, drills).
        self.stream_armed = (stream is not None if stream_armed is None
                             else bool(stream_armed))
        self.stream_rejoin_bytes = 0.0     # newest warm rejoin's byte cost
        # which mesh axes the gradient sync spans — the EF leading axis
        # layout (the LM harness passes ('data', 'seq'))
        self.ef_axes = tuple(ef_axes)
        self._log = log
        self._parked: List = []            # (rank, device row) of removed peers
        self._proc_ranks: Tuple[int, ...] = tuple(
            range(jax.process_count()))    # surviving ORIGINAL process ranks
        self.epoch = 0                     # last committed rendezvous epoch
        self.peer_failures = 0
        self.remesh_count = 0
        self.cascade_count = 0             # failures converted DURING a remesh
        self.readmit_count = 0
        self.dropped_ef_norm = 0.0
        self.remesh_latency_ms = 0.0       # latest remesh's host latency
        self.remesh_ms = 0.0               # cumulative remesh downtime

    @property
    def world(self) -> int:
        return int(self.mesh.shape[DATA_AXIS])

    # -- detection -------------------------------------------------------
    def poll(self, step: Optional[int] = None) -> None:
        """Write our own gossip heartbeat, then sweep the peers'; raises
        :class:`PeerFailed` on newly-dead peers."""
        if self.gossip is not None:
            self.gossip.beat(0 if step is None else step)
            self.gossip.raise_if_dead(step)

    def bounded_get(self, x, *, step: Optional[int] = None,
                    what: str = "step metrics fetch"):
        """``jax.device_get`` with the peer-timeout deadline."""
        return fetch_with_timeout(lambda: jax.device_get(x),
                                  self.cfg.peer_timeout_s, step=step,
                                  what=what)

    def failure_from(self, exc: BaseException) -> Optional[PeerFailed]:
        """Translate an exception into the coordinated failure it signals,
        or None for faults that are not elastic's to handle.

        * :class:`PeerFailed` passes through; an empty culprit list (a
          fetch timeout) is filled in from the gossip's dead set.
        * A ``mid_collective`` :class:`~tpu_compressed_dp.utils.chaos.ChaosCrash`
          becomes the simulated death of ``chaos.worker`` — the same
          handler path real survivors reach through gossip/timeouts.
        """
        from tpu_compressed_dp.utils.chaos import ChaosCrash

        if isinstance(exc, PeerFailed):
            if not exc.failed and self.gossip is not None:
                dead = self.gossip.dead or tuple(self.gossip.check())
                if dead:
                    return PeerFailed(dead, step=exc.step,
                                      reason=f"{exc.reason}; gossip names "
                                             f"{list(dead)}")
            return exc
        if (isinstance(exc, ChaosCrash)
                and getattr(exc, "mode", "step") in ("mid_collective",
                                                     "during_remesh")):
            return PeerFailed((getattr(exc, "worker", 0),),
                              step=getattr(exc, "step", None),
                              reason=("chaos kill during remesh"
                                      if exc.mode == "during_remesh"
                                      else "chaos mid-collective kill"))
        return None

    # -- remesh ----------------------------------------------------------
    def handle_failure(self, state, failure: PeerFailed, *,
                       fold_into: int = 0):
        """Coordinated abort + remesh: shrink the mesh by the dead workers,
        migrate EF/comp per the configured policy, re-place the state, and
        account the event.  Returns the state ON the new mesh; the caller
        must rebuild its jitted steps against :attr:`mesh` (which is how
        the sharded transport's owner partition gets recomputed).

        Cascading failures: a peer dying while survivors are INSIDE this
        handler (the ``crash=during_remesh`` chaos phase plays it
        deterministically) re-enters failure handling — the dead set is
        unioned and the shrink restarts from the still-uncommitted
        original mesh/state, down to ``min_world``, instead of committing
        a world that is already stale.

        Under ``jax.process_count() > 1`` with a rendezvous armed, the
        commit goes through the coordinated re-init path
        (:meth:`_handle_failure_multiprocess`) — survivors agree on a new
        epoch, tear down and re-run ``jax.distributed.initialize`` over
        the reduced process set, then rebuild the mesh and state on the
        new runtime."""
        from tpu_compressed_dp.utils.chaos import ChaosCrash

        if not failure.failed:
            raise failure
        # dump the blackbox NOW, while the evidence is fresh: even though
        # this handler usually recovers, the dead peer's why/when must
        # survive a cascade that kills us mid-remesh
        if self.flight is not None:
            self.flight.observe(failure, step=failure.step)
        if self.rendezvous is not None and jax.process_count() > 1:
            return self._handle_failure_multiprocess(state, failure)
        failed = {int(f) for f in failure.failed}
        reason = failure.reason
        t0 = time.monotonic()
        while True:
            new_world = self.world - len(failed)
            if new_world < self.cfg.min_world:
                err = PeerFailed(
                    sorted(failed), step=failure.step,
                    reason=(f"{reason}; surviving world {new_world} "
                            f"below min_world {self.cfg.min_world} — "
                            "not remeshing"))
                if self.flight is not None:
                    self.flight.observe(err, step=failure.step)
                raise err
            new_mesh, removed = surviving_mesh(self.mesh, sorted(failed))
            new_state, dropped = shrink_state(
                state, sorted(failed), policy=self.cfg.ef_policy,
                fold_into=fold_into, data_world=self.world)
            # a second death while we are mid-remesh: probe the chaos
            # injector's during_remesh phase BEFORE committing — the
            # shrink restarts with the union against the original mesh
            if self.crash is not None:
                try:
                    probe = (failure.step if failure.step is not None
                             else getattr(self.crash, "crash_at_step", 0))
                    self.crash.check(probe, phase="during_remesh")
                except ChaosCrash as e:
                    more = self.failure_from(e)
                    if more is not None and more.failed:
                        extra = set(more.failed) - failed
                        failed |= set(more.failed)
                        self.cascade_count += 1
                        self.peer_failures += len(extra)
                        reason = f"{reason}; then {more.reason}"
                        self._log("elastic: peer(s) "
                                  f"{sorted(more.failed)} died during the "
                                  "remesh — re-entering failure handling "
                                  f"over {sorted(failed)}")
                        if self.events is not None:
                            self.events.emit(
                                "remesh_cascade", step=failure.step,
                                failed=sorted(failed),
                                added=sorted(extra))
                        if self.flight is not None:
                            self.flight.record(
                                "elastic", "remesh_cascade",
                                step=failure.step, failed=sorted(failed),
                                added=sorted(extra))
                        continue
            break
        state = self._place(new_state, new_mesh)
        self._parked.extend(zip(sorted(failed), removed))
        old_world = self.world
        self.mesh = new_mesh
        if self.gossip is not None:
            self.gossip.note_dead(failed, reason)
        self.peer_failures += len(set(failure.failed))
        self.remesh_count += 1
        self.dropped_ef_norm += dropped
        self.remesh_latency_ms = (time.monotonic() - t0) * 1e3
        self.remesh_ms += self.remesh_latency_ms
        self._log(f"elastic: remeshed {old_world}"
                  f" -> {new_world} workers after {reason} "
                  f"(ef={self.cfg.ef_policy}"
                  + (f", dropped ‖ef‖={dropped:.3e}" if dropped else "")
                  + f", {self.remesh_latency_ms:.0f} ms)")
        if self.events is not None:
            self.events.emit(
                "remesh", step=failure.step, failed=sorted(failed),
                world=new_world, ef_policy=self.cfg.ef_policy,
                dropped_ef_norm=float(dropped),
                latency_ms=self.remesh_latency_ms,
                remesh_ms=self.remesh_ms)
        if self.flight is not None:
            self.flight.record(
                "elastic", "remesh", step=failure.step,
                failed=sorted(failed), world=new_world,
                ef_policy=self.cfg.ef_policy,
                dropped_ef_norm=float(dropped),
                latency_ms=self.remesh_latency_ms)
        self._stream_keyframe()
        return state

    # -- re-admission ----------------------------------------------------
    def readmit(self, state, n: Optional[int] = None):
        """Scale back up at a remesh barrier: append up to ``n`` parked
        devices (all, by default) back onto the mesh tail, zero their EF
        rows, broadcast-re-warm their comp rows, and re-place the live
        state (the "live checkpoint" — in-process survivors already hold
        the replicated fields the rejoiner needs)."""
        n = len(self._parked) if n is None else min(int(n), len(self._parked))
        if n <= 0:
            return state
        t0 = time.monotonic()
        back, self._parked = self._parked[:n], self._parked[n:]
        ranks = [r for r, _ in back]
        new_mesh = extended_mesh(self.mesh, [d for _, d in back])
        state = self._place(
            expand_state(state, n_new=n, data_world=self.world), new_mesh)
        self.mesh = new_mesh
        self.remesh_ms += (time.monotonic() - t0) * 1e3
        self.readmit_count += n
        if self.gossip is not None:
            for r in ranks:
                self.gossip.readmit(r)
        self._log(f"elastic: readmitted {n} worker(s) {ranks} -> "
                  f"world {self.world}")
        if self.events is not None:
            self.events.emit("readmit", ranks=ranks, world=self.world)
        if self.flight is not None:
            self.flight.record("elastic", "readmit", ranks=ranks,
                               world=self.world)
        self._stream_keyframe()
        return state

    def _stream_keyframe(self) -> None:
        """Re-anchor the delta stream after a committed world transition —
        a consumer must never need segments that straddle a membership
        change to reconstruct the post-transition state."""
        st = self.stream
        if st is not None:
            try:
                st.request_keyframe()
            except Exception:
                pass  # the stream tee must never fail a remesh

    @property
    def parked(self) -> Tuple[int, ...]:
        """Ranks currently removed from the mesh (readmission pool)."""
        return tuple(r for r, _ in self._parked)

    # -- multi-process world transitions ---------------------------------
    # These paths only run under jax.process_count() > 1 with a rendezvous
    # armed; they are exercised by the 2-process drills
    # (tests/test_elastic_multiprocess.py).  The pure pieces (rank ->
    # row maps, local-shard gathers) are unit tested single-process.

    def _proc_data_rows(self, ranks: Iterable[int]) -> List[int]:
        """The mesh data rows owned by the given ORIGINAL process ranks
        (contiguous blocks in surviving-rank order)."""
        per = self.world // max(len(self._proc_ranks), 1)
        pos = {r: i for i, r in enumerate(self._proc_ranks)}
        return [pos[int(r)] * per + j for r in ranks for j in range(per)
                if int(r) in pos]

    def _host_snapshot(self, state):
        """Fetch what THIS process can still read before the distributed
        runtime is torn down: replicated fields in full (every process
        holds a replica shard), EF/comp as the locally-addressable leading
        rows.  Never touches non-addressable shards — those live(d) on
        peers and fetching them is exactly the hang we are escaping."""
        def full(x):
            if isinstance(x, jax.Array) and not x.is_fully_addressable:
                return np.asarray(x.addressable_data(0))
            return jax.device_get(x)

        def local_rows(x):
            x_arr = x
            shards = sorted(x_arr.addressable_shards,
                            key=lambda s: s.index[0].start or 0)
            rows = [np.asarray(s.data) for s in shards]
            if any(r.shape[1:] != tuple(x_arr.shape[1:]) for r in rows):
                raise NotImplementedError(
                    "multi-process elastic re-init supports EF/comp "
                    "sharded on the leading worker axis only; trailing "
                    "model-axis shards (dp x tp multi-host) need a full "
                    "restart")
            return np.concatenate(rows, axis=0)

        repl = jax.tree.map(full, dataclasses.replace(state, ef=(), comp=()))
        ef = (jax.tree.map(local_rows, state.ef)
              if state.ef != () else ())
        comp = (jax.tree.map(local_rows, state.comp)
                if state.comp != () else ())
        return repl, ef, comp

    def _assemble_multiprocess(self, repl, local_ef, local_comp, mesh):
        """Rebuild a global TrainState on a freshly re-initialised runtime:
        replicated fields place through the harness's place callback (every
        process holds the full value), EF/comp reassemble from each
        process's local rows (``make_array_from_process_local_data``)."""
        from jax.sharding import NamedSharding, PartitionSpec

        state = self._place(repl, mesh)
        sharding = NamedSharding(mesh, PartitionSpec(self.ef_axes))
        lead = int(mesh.shape[DATA_AXIS]) * int(
            np.prod([mesh.shape[a] for a in self.ef_axes
                     if a != DATA_AXIS] or [1]))

        def assemble(rows):
            rows = np.asarray(rows)
            return jax.make_array_from_process_local_data(
                sharding, rows, (lead,) + rows.shape[1:])

        ef = (jax.tree.map(assemble, local_ef) if local_ef != () else ())
        comp = (jax.tree.map(assemble, local_comp)
                if local_comp != () else ())
        return dataclasses.replace(state, ef=ef, comp=comp)

    def _handle_failure_multiprocess(self, state, failure: PeerFailed):
        """Coordinated multi-process shrink: snapshot local state, agree on
        the surviving world through the rendezvous, re-init
        ``jax.distributed`` over it, rebuild mesh + state.

        ``failure.failed`` are ORIGINAL process ranks (the gossip plane's
        currency).  The dead processes' EF rows lived only in their memory
        and are unrecoverable — multi-process death always behaves like
        the ``drop`` policy with an unknowable norm (logged, and flagged
        on the remesh event), whatever ``ef_policy`` says."""
        from tpu_compressed_dp.train.rendezvous import reinit_distributed

        t0 = time.monotonic()
        dead = {int(f) for f in failure.failed}
        live = [r for r in self._proc_ranks if r not in dead]
        if self.cfg.rank not in live:
            raise PeerFailed(sorted(dead), step=failure.step,
                             reason=f"{failure.reason}; this rank is among "
                                    "the declared dead — exiting for the "
                                    "watchdog")
        grid = _mesh_grid(self.mesh)
        dead_rows = self._proc_data_rows(dead)
        new_world = self.world - len(dead_rows)
        if new_world < self.cfg.min_world:
            raise PeerFailed(
                sorted(dead), step=failure.step,
                reason=(f"{failure.reason}; surviving world {new_world} "
                        f"below min_world {self.cfg.min_world} — "
                        "not remeshing"))
        repl, local_ef, local_comp = self._host_snapshot(state)
        decision = self.rendezvous.propose(
            live, deadline_s=self.cfg.peer_timeout_s * 4)
        reinit_distributed(decision, log=self._log)
        new_grid = np.asarray(jax.devices(), dtype=object).reshape(
            -1, grid.shape[1])
        new_mesh = _rebuild_mesh(self.mesh, new_grid)
        state = self._assemble_multiprocess(repl, local_ef, local_comp,
                                            new_mesh)
        self.mesh = new_mesh
        self._proc_ranks = decision.ranks
        self.epoch = decision.epoch
        if self.gossip is not None:
            self.gossip.note_dead(dead, failure.reason)
        self.peer_failures += len(dead)
        self.remesh_count += 1
        self.remesh_latency_ms = (time.monotonic() - t0) * 1e3
        self.remesh_ms += self.remesh_latency_ms
        self._log(f"elastic: epoch {decision.epoch}: re-initialised "
                  f"{len(live) + len(dead)} -> {len(live)} processes "
                  f"(world {new_world}) after {failure.reason}; dead "
                  "peers' EF rows unrecoverable (dropped, norm unknown); "
                  f"{self.remesh_latency_ms:.0f} ms")
        if self.events is not None:
            self.events.emit(
                "remesh", step=failure.step, failed=sorted(dead),
                world=new_world, epoch=decision.epoch,
                ef_policy="drop", ef_unrecoverable=True,
                dropped_ef_norm=float("nan"),
                latency_ms=self.remesh_latency_ms,
                remesh_ms=self.remesh_ms)
        if self.flight is not None:
            self.flight.record(
                "elastic", "remesh", step=failure.step,
                failed=sorted(dead), world=new_world,
                epoch=decision.epoch, ef_policy="drop",
                latency_ms=self.remesh_latency_ms)
        return state

    def rejoin_barrier(self, state):
        """Survivor half of multi-process scale-up, called at an epoch
        boundary: fold pending join requests (watchdog-relaunched hosts
        waiting in :meth:`Rendezvous.join`) into a new world epoch,
        re-init, and rebuild with zero EF rows for the joiners (their rows
        arrive via each process's local contribution — the joiner's own
        :meth:`join_world` supplies zeros).  Returns ``(state, changed)``;
        the caller rebuilds its jitted steps when ``changed``.

        Warm rejoin: when the delta stream is armed FLEET-WIDE
        (``stream_armed`` — ``--stream_dir`` on every process) and EVERY
        pending joiner's join record carries the ``stream`` flag (it
        caught up from the delta stream —
        :func:`tpu_compressed_dp.stream.rejoin.warm_rejoin`), the barrier
        flushes the stream first (:meth:`StreamWriter.sync`, on the one
        process that holds the writer — the head now reconstructs to the
        live params bitwise), publishes the warm bit in the epoch commit,
        and the broadcast SKIPS the params tree: the joiners already hold
        it, and the dominant rejoin byte cost moves from the full dense
        params onto the compressed delta wire.  Every participant —
        survivor or joiner, writer-holding or not — picks the collective
        layout from the COMMITTED ``decision.warm`` bit, so the pytree
        structures agree by construction."""
        if self.rendezvous is None or jax.process_count() <= 1:
            return state, False
        joins = self.rendezvous.pending_joins()
        ready = sorted(set(joins) - set(self._proc_ranks))
        if not ready:
            return state, False
        t0 = time.monotonic()
        # derived ONLY from fleet-shared state: the immutable join records
        # plus the fleet-wide armed flag — never from self.stream, which
        # only process 0 holds (harness/loop.py make_stream)
        want_warm = (self.stream_armed
                     and all(joins[r].get("stream") is not None
                             for r in ready))
        repl, local_ef, local_comp = self._host_snapshot(state)
        if want_warm and self.stream is not None:
            # pin stream == live params before the epoch commit: the
            # joiners' adopted reconstruction is bitwise what the
            # survivors hold, so skipping the params broadcast is safe
            self.stream.sync(repl.params, step=int(repl.step))
        new_ranks = sorted(set(self._proc_ranks) | set(ready))
        from jax.experimental import multihost_utils

        from tpu_compressed_dp.train.rendezvous import reinit_distributed
        # only survivors vote (the joiners are parked in Rendezvous.join);
        # the coordinator is therefore a survivor — the broadcast source
        # of the replicated state the joiners are missing
        decision = self.rendezvous.propose(
            new_ranks, voters=self._proc_ranks, warm=want_warm,
            deadline_s=self.cfg.peer_timeout_s * 4)
        reinit_distributed(decision, log=self._log)
        warm = decision.warm
        src = decision.ranks.index(decision.coordinator)
        if warm:
            params_local = repl.params
            bx = multihost_utils.broadcast_one_to_all(
                dataclasses.replace(repl, params=()),
                is_source=decision.process_id == src)
            repl = dataclasses.replace(bx, params=params_local)
        else:
            repl = multihost_utils.broadcast_one_to_all(
                repl, is_source=decision.process_id == src)
        if local_comp != ():
            # comp rows are identical across workers by construction, so
            # the coordinator's local rows re-warm the joiners' too
            local_comp = multihost_utils.broadcast_one_to_all(
                local_comp, is_source=decision.process_id == src)
        grid_cols = _mesh_grid(self.mesh).shape[1]
        new_grid = np.asarray(jax.devices(), dtype=object).reshape(
            -1, grid_cols)
        new_mesh = _rebuild_mesh(self.mesh, new_grid)
        state = self._assemble_multiprocess(repl, local_ef, local_comp,
                                            new_mesh)
        self.mesh = new_mesh
        self._proc_ranks = tuple(decision.ranks)
        self.epoch = decision.epoch
        self.readmit_count += len(ready)
        if self.gossip is not None:
            for r in ready:
                self.gossip.readmit(r)
        self.remesh_ms += (time.monotonic() - t0) * 1e3
        self._log(f"elastic: epoch {decision.epoch}: readmitted process(es) "
                  f"{ready} -> world {self.world}")
        if self.events is not None:
            self.events.emit("readmit", ranks=ready, world=self.world,
                             epoch=decision.epoch, warm=warm)
        if self.flight is not None:
            self.flight.record("elastic", "readmit", ranks=ready,
                               world=self.world, epoch=decision.epoch,
                               warm=warm)
        self._stream_keyframe()
        return state, True

    def join_world(self, state, decision, *, adopted_params=None,
                   adopted_info=None):
        """Joiner half of multi-process scale-up: called by a relaunched
        harness right after init, with the :class:`EpochDecision` its
        rendezvous join returned.  The fresh-init state supplies shapes;
        replicated values are adopted from the survivors' broadcast and
        the EF rows start at zero (a rejoiner has withheld nothing).

        ``adopted_params`` is the warm-rejoin reconstruction
        (:func:`tpu_compressed_dp.stream.rejoin.warm_rejoin`).  The
        broadcast layout follows the COMMITTED ``decision.warm`` bit —
        the same record the survivors read — never the local adoption
        outcome, so the collective's pytree structure cannot diverge
        across the fleet.  When the commit says warm the params tree is
        taken from the stream (the survivors skipped it); a warm commit
        with NO adoption in hand raises — joining the params-skipping
        collective with fresh-init params would silently train from
        garbage, so the safe move is to exit for the watchdog and retry
        (the next probe joins cold and the survivors commit accordingly).
        When the commit says cold, any stream catch-up is discarded and
        the full broadcast is taken.  ``adopted_info`` is the rejoin's
        accounting dict (bytes/segments/step)."""
        from jax.experimental import multihost_utils

        repl, local_ef, local_comp = self._host_snapshot(state)
        # the re-elected coordinator (a survivor) is the source of truth
        # for every replicated field and the comp re-warm; our fresh-init
        # values are discarded
        src = decision.ranks.index(decision.coordinator)
        warm = bool(getattr(decision, "warm", False))
        if warm and adopted_params is None:
            from tpu_compressed_dp.train.rendezvous import RendezvousError
            raise RendezvousError(
                f"epoch {decision.epoch} committed warm (survivors skip the "
                "params broadcast) but this joiner holds no stream "
                "reconstruction to adopt — exiting for the watchdog to "
                "relaunch; the next join probe re-decides warm vs cold")
        if warm:
            repl = dataclasses.replace(repl, params=adopted_params)
            bx = multihost_utils.broadcast_one_to_all(
                dataclasses.replace(repl, params=()),
                is_source=decision.process_id == src)
            repl = dataclasses.replace(bx, params=repl.params)
            self.stream_rejoin_bytes = float(
                (adopted_info or {}).get("bytes", 0))
            if self.flight is not None:
                self.flight.record("stream", "warm_join",
                                   epoch=decision.epoch,
                                   **dict(adopted_info or {}))
        else:
            if adopted_params is not None:
                self._log("elastic: stream catch-up unused — epoch "
                          f"{decision.epoch} committed a cold (full "
                          "broadcast) admission")
            repl = multihost_utils.broadcast_one_to_all(
                repl, is_source=decision.process_id == src)
        if local_comp != ():
            local_comp = multihost_utils.broadcast_one_to_all(
                local_comp, is_source=decision.process_id == src)
        local_ef = jax.tree.map(np.zeros_like, local_ef)
        grid_cols = _mesh_grid(self.mesh).shape[1]
        new_grid = np.asarray(jax.devices(), dtype=object).reshape(
            -1, grid_cols)
        new_mesh = _rebuild_mesh(self.mesh, new_grid)
        state = self._assemble_multiprocess(repl, local_ef, local_comp,
                                            new_mesh)
        self.mesh = new_mesh
        self._proc_ranks = tuple(decision.ranks)
        self.epoch = decision.epoch
        self._log(f"elastic: rejoined world epoch {decision.epoch} as "
                  f"process {decision.process_id}/{decision.num_processes}")
        return state

    # -- accounting ------------------------------------------------------
    def metrics(self) -> Dict[str, float]:
        """The declared ``elastic/*`` keys (obs/registry.py) for the
        harness exporters (Prometheus textfile, heartbeat payload)."""
        return {
            "elastic/peer_failures": float(self.peer_failures),
            "elastic/remesh_count": float(self.remesh_count),
            "elastic/dropped_ef_norm": float(self.dropped_ef_norm),
            "elastic/remesh_latency_ms": float(self.remesh_latency_ms),
            "elastic/remesh_ms": float(self.remesh_ms),
            "stream/rejoin_bytes": float(self.stream_rejoin_bytes),
        }
