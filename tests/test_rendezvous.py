"""Rendezvous protocol unit tests (train/rendezvous.py).

The protocol is plain files + injectable clocks, so every multi-rank
interleaving here is scripted deterministically from a single thread: a
follower's ``sleep`` callback runs the leader's ``propose`` (or writes the
epoch file directly), and the follower's next poll observes the commit.
The real ``jax.distributed`` wiring is exercised by the 2-process drills
in tests/test_elastic_multiprocess.py.
"""

import json
import os

import pytest

from tpu_compressed_dp.train import rendezvous as rdzv
from tpu_compressed_dp.train.rendezvous import (
    ADDR_ENV, DIR_ENV, EPOCH_ENV, EpochDecision, Rendezvous,
    RendezvousError, RendezvousTimeout, epoch_path, export_env,
    maybe_rejoin_from_env, read_epoch, reinit_distributed, write_epoch)

pytestmark = pytest.mark.quick


class FakeClock:
    """Injectable now/sleep pair: sleeping advances virtual time and runs
    an optional callback — the single-thread interleaving hook."""

    def __init__(self, on_sleep=None):
        self.t = 0.0
        self.on_sleep = on_sleep
        self.sleeps = 0

    def now(self):
        return self.t

    def sleep(self, s):
        self.t += s
        self.sleeps += 1
        if self.on_sleep is not None:
            self.on_sleep()


def make(rdzv_dir, rank, clock, **kw):
    return Rendezvous(str(rdzv_dir), rank, now=clock.now, sleep=clock.sleep,
                      **kw)


# ------------------------------------------------------------- epoch file

class TestEpochFile:
    def test_round_trip(self, tmp_path):
        rec = {"epoch": 3, "ranks": [0, 2, 5], "coordinator": 0,
               "address": "10.0.0.1:51303"}
        write_epoch(str(tmp_path), rec)
        got = read_epoch(str(tmp_path))
        assert got["epoch"] == 3 and got["ranks"] == [0, 2, 5]
        assert got["address"] == "10.0.0.1:51303"

    def test_missing_dir_reads_none(self, tmp_path):
        assert read_epoch(str(tmp_path / "nowhere")) is None

    def test_torn_or_foreign_content_reads_none(self, tmp_path):
        path = epoch_path(str(tmp_path))
        with open(path, "w") as f:
            f.write('{"epoch": 1, "ranks"')  # torn mid-write
        assert read_epoch(str(tmp_path)) is None
        with open(path, "w") as f:
            json.dump([1, 2, 3], f)  # wrong shape
        assert read_epoch(str(tmp_path)) is None
        with open(path, "w") as f:
            json.dump({"epoch": 1}, f)  # missing ranks
        assert read_epoch(str(tmp_path)) is None

    def test_decision_from_contiguous_process_id(self, tmp_path):
        clock = FakeClock()
        r5 = make(tmp_path, 5, clock)
        rec = {"epoch": 2, "ranks": [5, 0, 2], "address": "h:51302"}
        d = r5.decision_from(rec)
        assert d.ranks == (0, 2, 5)          # sorted original ranks
        assert d.process_id == 2             # contiguous index, not rank
        assert d.coordinator == 0            # defaults to lowest rank
        assert d.num_processes == 3
        # a rank outside the commit gets no process id (must park)
        assert make(tmp_path, 3, clock).decision_from(rec).process_id is None


# ----------------------------------------------------------- vote/propose

class TestPropose:
    def test_two_rank_commit(self, tmp_path):
        """Follower proposes first; its sleep hook runs the leader's
        propose, which sees both votes and commits; the follower's next
        poll adopts the commit."""
        done = {}
        c0 = FakeClock()
        r0 = make(tmp_path, 0, c0, host="leader-host")

        def leader_turn():
            if "d0" not in done:
                done["d0"] = r0.propose([0, 1])

        c1 = FakeClock(on_sleep=leader_turn)
        r1 = make(tmp_path, 1, c1)
        d1 = r1.propose([0, 1])
        d0 = done["d0"]
        # same committed world; process_id is each process's own index
        assert (d0.epoch, d0.ranks, d0.coordinator, d0.address) == \
            (d1.epoch, d1.ranks, d1.coordinator, d1.address)
        assert d1.epoch == 1 and d1.ranks == (0, 1) and d1.coordinator == 0
        assert d1.address == f"leader-host:{rdzv.DEFAULT_BASE_PORT + 1}"
        assert d0.process_id == 0 and d1.process_id == 1
        # committed-epoch votes are garbage-collected by the leader
        assert r0.read_votes(1) == {}

    def test_second_transition_bumps_epoch(self, tmp_path):
        write_epoch(str(tmp_path), {"epoch": 4, "ranks": [0, 1],
                                    "coordinator": 0, "address": "h:51304"})
        clock = FakeClock()
        r0 = make(tmp_path, 0, clock)
        d = r0.propose([0])  # sole survivor: quorum of one, commits alone
        assert d.epoch == 5 and d.ranks == (0,) and d.process_id == 0

    def test_voters_subset_quorum(self, tmp_path):
        """A readmission barrier: members include a parked joiner (rank 2)
        that CANNOT vote — the survivor subset alone reaches quorum."""
        done = {}
        c0 = FakeClock()
        r0 = make(tmp_path, 0, c0)

        def leader_turn():
            if "d0" not in done:
                done["d0"] = r0.propose([0, 1, 2], voters=[0, 1])

        c1 = FakeClock(on_sleep=leader_turn)
        r1 = make(tmp_path, 1, c1)
        d1 = r1.propose([0, 1, 2], voters=[0, 1])
        d0 = done["d0"]
        assert (d0.epoch, d0.ranks) == (d1.epoch, d1.ranks)
        assert d1.ranks == (0, 1, 2) and d1.coordinator == 0
        # rank 2 never voted, yet is in the committed world
        assert 2 not in r0.read_votes(1)

    def test_warm_bit_is_committed_and_defaults_false(self, tmp_path):
        """The readmission barrier's warm-rejoin layout bit rides in the
        epoch COMMIT: every participant — voter or parked joiner — reads
        the same bit back and picks the broadcast layout from it, and a
        record without one (pre-stream epochs, the failure path) decodes
        cold."""
        clock = FakeClock()
        r0 = make(tmp_path, 0, clock)
        d = r0.propose([0], warm=True)
        assert d.warm
        # a parked joiner decodes the committed record the same way
        assert r0.decision_from(read_epoch(str(tmp_path))).warm
        assert not r0.propose([0]).warm          # cold is the default
        write_epoch(str(tmp_path), {"epoch": 9, "ranks": [0],
                                    "coordinator": 0, "address": "h:1"})
        assert not r0.decision_from(read_epoch(str(tmp_path))).warm

    def test_conflicting_votes_are_split_brain(self, tmp_path):
        clock = FakeClock()
        r0 = make(tmp_path, 0, clock)
        r1 = make(tmp_path, 1, clock)
        r1.vote(1, [0, 1, 2])  # rank 1 believes in a different world
        with pytest.raises(RendezvousError, match="split-brain"):
            r0.propose([0, 1])
        assert read_epoch(str(tmp_path)) is None  # nothing committed

    def test_higher_epoch_commit_is_adopted(self, tmp_path):
        """A cascade won the race: the commit lands with a higher epoch
        than proposed, and is adopted as long as it names this rank."""
        def cascade_commit():
            if read_epoch(str(tmp_path)) is None:
                write_epoch(str(tmp_path),
                            {"epoch": 3, "ranks": [0, 1], "coordinator": 0,
                             "address": "h:51303"})

        clock = FakeClock(on_sleep=cascade_commit)
        r1 = make(tmp_path, 1, clock)
        d = r1.propose([0, 1])
        assert d.epoch == 3 and d.process_id == 1

    def test_commit_excluding_this_rank_raises(self, tmp_path):
        def hostile_commit():
            write_epoch(str(tmp_path),
                        {"epoch": 2, "ranks": [0, 2], "coordinator": 0,
                         "address": "h:51302"})

        clock = FakeClock(on_sleep=hostile_commit)
        r1 = make(tmp_path, 1, clock)
        with pytest.raises(RendezvousError, match="without rank 1"):
            r1.propose([0, 1])

    def test_proposing_a_world_without_self_raises(self, tmp_path):
        clock = FakeClock()
        r1 = make(tmp_path, 1, clock)
        with pytest.raises(RendezvousError, match="excludes itself"):
            r1.propose([0, 2])
        with pytest.raises(RendezvousError, match="voters"):
            r1.propose([0, 1], voters=[0])       # this rank cannot vote
        with pytest.raises(RendezvousError, match="voters"):
            r1.propose([0, 1], voters=[0, 1, 5])  # voter outside members

    def test_timeout_lists_missing_voters(self, tmp_path):
        clock = FakeClock()
        r0 = make(tmp_path, 0, clock)
        r1 = make(tmp_path, 1, clock)
        r1.vote(1, [0, 1, 2])  # rank 2 never shows up
        with pytest.raises(RendezvousTimeout, match=r"missing votes from \[2\]"):
            r0.propose([0, 1, 2], deadline_s=1.0)
        assert clock.sleeps > 0  # it actually polled before expiring

    def test_torn_vote_file_is_ignored(self, tmp_path):
        clock = FakeClock()
        r0 = make(tmp_path, 0, clock)
        with open(os.path.join(str(tmp_path), "vote.e1.rank7.json"), "w") as f:
            f.write('{"epoch": 1,')  # a writer died mid-replace-free write
        assert r0.read_votes(1) == {}


# ----------------------------------------------------------------- joins

class TestJoin:
    def test_admitted_by_a_commit_naming_this_rank(self, tmp_path):
        write_epoch(str(tmp_path), {"epoch": 2, "ranks": [0, 1, 2],
                                    "coordinator": 0, "address": "h:51302"})
        clock = FakeClock()
        r2 = make(tmp_path, 2, clock)
        d = r2.join(incarnation=3)
        assert d is not None and d.process_id == 2 and d.epoch == 2
        assert r2.pending_joins() == {}  # admission consumed the join file

    def test_stale_epoch_blocks_until_newer_commit(self, tmp_path):
        """The relaunch env advertised epoch 2 — the world this process
        DIED out of.  Even though the stale epoch file still names it,
        only a strictly newer commit admits."""
        write_epoch(str(tmp_path), {"epoch": 2, "ranks": [0, 1, 2],
                                    "coordinator": 0, "address": "h:51302"})

        def readmit_barrier():
            if clock.t > 0.5:
                write_epoch(str(tmp_path),
                            {"epoch": 3, "ranks": [0, 1, 2],
                             "coordinator": 0, "address": "h:51303"})

        clock = FakeClock(on_sleep=readmit_barrier)
        r2 = make(tmp_path, 2, clock)
        d = r2.join(incarnation=1, stale_epoch=2, deadline_s=30.0)
        assert d is not None and d.epoch == 3

    def test_deadline_parks_and_leaves_join_file(self, tmp_path):
        clock = FakeClock()
        r2 = make(tmp_path, 2, clock)
        d = r2.join(incarnation=1, stale_epoch=2, deadline_s=1.0)
        assert d is None  # park-and-retry: the watchdog's backoff retries
        joins = r2.pending_joins()
        assert joins[2]["incarnation"] == 1  # announcement left behind

    def test_pending_joins_and_clear(self, tmp_path):
        clock = FakeClock()
        r1 = make(tmp_path, 1, clock)
        r1.request_join(incarnation=2)
        make(tmp_path, 4, clock).request_join()
        with open(os.path.join(str(tmp_path), "join.rank9.json"), "w") as f:
            f.write("not json")  # torn announcement: ignored, not fatal
        joins = r1.pending_joins()
        assert sorted(joins) == [1, 4]
        assert joins[1]["incarnation"] == 2
        r1.clear_join(1)
        r1.clear_join(9)  # clearing a non-record is a no-op
        assert sorted(r1.pending_joins()) == [4]


# ----------------------------------------------- relaunch env + re-init

class TestRelaunchEnv:
    def test_export_then_rejoin_round_trip(self, tmp_path):
        """The watchdog's half (export_env) feeds the harness's half
        (maybe_rejoin_from_env) through a plain env dict."""
        env = {"TCDP_RESTART_COUNT": "2"}
        export_env(env, {"epoch": 2, "ranks": [0, 1, 2],
                         "address": "h:51302"})
        assert env[EPOCH_ENV] == "2" and env[ADDR_ENV] == "h:51302"
        env[DIR_ENV] = str(tmp_path)
        # the running world readmits at epoch 3 while we wait in the barrier
        write_epoch(str(tmp_path), {"epoch": 3, "ranks": [0, 1, 2],
                                    "coordinator": 0, "address": "h:51303"})
        clock = FakeClock()
        d = maybe_rejoin_from_env(None, 2, env=env, deadline_s=5.0,
                                  now=clock.now, sleep=clock.sleep)
        assert d is not None and d.epoch == 3 and d.process_id == 2

    def test_fresh_launch_returns_none(self, tmp_path):
        assert maybe_rejoin_from_env(str(tmp_path), 0, env={}) is None
        # an epoch with no directory anywhere is equally a fresh launch
        assert maybe_rejoin_from_env(None, 0, env={EPOCH_ENV: "2"}) is None

    def test_not_admitted_raises_timeout(self, tmp_path):
        env = {EPOCH_ENV: "2", DIR_ENV: str(tmp_path)}
        clock = FakeClock()
        with pytest.raises(RendezvousTimeout, match="parking"):
            maybe_rejoin_from_env(None, 2, env=env, deadline_s=1.0,
                                  now=clock.now, sleep=clock.sleep)


class TestReinitDistributed:
    def _decision(self, ranks, rank):
        ranks = tuple(sorted(ranks))
        pid = ranks.index(rank) if rank in ranks else None
        return EpochDecision(epoch=2, ranks=ranks, coordinator=ranks[0],
                             address="h:51302", process_id=pid)

    def test_excluded_process_refuses(self):
        with pytest.raises(RendezvousError, match="not in the committed"):
            reinit_distributed(self._decision([0, 1], rank=3),
                               shutdown=lambda: None,
                               initialize=lambda **kw: None)

    def test_teardown_then_init_against_new_coordinator(self):
        calls = []
        reinit_distributed(
            self._decision([0, 2, 5], rank=5),
            shutdown=lambda: calls.append("shutdown"),
            initialize=lambda **kw: calls.append(("init", kw)))
        assert calls[0] == "shutdown"
        assert calls[1] == ("init", {"coordinator_address": "h:51302",
                                     "num_processes": 3, "process_id": 2})

    def test_wedged_shutdown_is_tolerated(self):
        """A client wedged on the dead coordinator raises out of shutdown;
        re-init must proceed anyway."""
        calls, logs = [], []

        def wedged():
            raise RuntimeError("coordinator unreachable")

        reinit_distributed(self._decision([0, 1], rank=1), shutdown=wedged,
                           initialize=lambda **kw: calls.append(kw),
                           log=logs.append)
        assert len(calls) == 1 and calls[0]["process_id"] == 1
        assert any("shutdown raised" in m for m in logs)

    def test_single_process_world_skips_init(self):
        calls = []
        reinit_distributed(self._decision([3], rank=3),
                           shutdown=lambda: calls.append("shutdown"),
                           initialize=lambda **kw: calls.append("init"))
        assert calls == ["shutdown"]  # nothing to coordinate with
