"""Delta state streaming (tpu_compressed_dp/stream/): the lossless window
invariant, the store's manifest discipline, corruption walk-back, warm
rejoin end-to-end against the full-restore path, the fsck/serve tooling,
and the harness plumbing.

The core contract under test: segments carry CURRENT VALUES at selected
coordinates (set semantics, never additive), every window closes with a
bit-exact flush, so ``keyframe + deltas of one window`` reconstructs the
producer's fp32 params *bitwise* — what lets a warm joiner skip the params
broadcast and a serving replica trust its snapshots.
"""

import argparse
import copy
import dataclasses
import json
import os
import sys

import jax
import numpy as np
import pytest

from tpu_compressed_dp.stream import delta as sdelta
from tpu_compressed_dp.stream.reader import StreamReader
from tpu_compressed_dp.stream.rejoin import warm_rejoin
from tpu_compressed_dp.stream.store import (StreamCorrupt, head_path,
                                            is_stream_dir, list_segments,
                                            prune_segments, read_head,
                                            read_segment_manifest,
                                            segment_payload_path,
                                            verify_stream)
from tpu_compressed_dp.stream.writer import StreamWriter

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

pytestmark = pytest.mark.quick


def _quiet(*a, **k):
    pass


def _params(rng, scale=1.0):
    return {"dense": {"kernel": (rng.randn(24, 8) * scale).astype(np.float32)},
            "bias": (rng.randn(32) * scale).astype(np.float32)}


def _advance(params, rng, scale=0.01):
    return {"dense": {"kernel": (params["dense"]["kernel"]
                                 + (rng.randn(24, 8) * scale
                                    ).astype(np.float32))},
            "bias": (params["bias"]
                     + (rng.randn(32) * scale).astype(np.float32))}


def _assert_bitwise(a, b, what):
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        assert np.array_equal(np.asarray(la), np.asarray(lb)), (
            f"{what}: leaf not bitwise equal")


def _flip_payload(directory, seq):
    path = segment_payload_path(directory, seq)
    size = os.path.getsize(path)
    with open(path, "r+b") as f:
        f.seek(size // 2)
        b = f.read(1)
        f.seek(size // 2)
        f.write(bytes([b[0] ^ 0xFF]))


# ------------------------------------------------------------- delta codec

class TestDeltaCodec:
    def test_select_pack_topk_matches_numpy(self):
        """The reused wire compress step (threshold + select + pack) picks
        exactly the numpy argsort top-k by magnitude, payload gathered in
        ascending-index order."""
        from tpu_compressed_dp.ops import wire

        rng = np.random.RandomState(0)
        n, keep = 512, 37
        # distinct magnitudes => a unique top-k set, no tie ambiguity
        mags = rng.permutation(np.arange(1, n + 1)).astype(np.float32)
        vec = mags * np.where(rng.rand(n) < 0.5, -1.0, 1.0).astype(np.float32)
        payload, idx, count = jax.jit(
            lambda v: wire.select_pack_topk(v, keep))(vec)
        k = int(count)
        assert k == keep
        want = np.sort(np.argsort(np.abs(vec))[-keep:])
        np.testing.assert_array_equal(np.asarray(idx)[:k], want)
        np.testing.assert_array_equal(np.asarray(payload)[:k], vec[want])

    def test_flatten_round_trip_and_respec_guard(self):
        rng = np.random.RandomState(1)
        params = _params(rng)
        vec, spec = sdelta.flatten_params(params)
        assert vec.dtype == np.float32 and vec.ndim == 1
        back = sdelta.unflatten_like(params, vec, spec)
        _assert_bitwise(params, back, "flatten round trip")
        # template-free reconstruction agrees leaf for leaf
        d = sdelta.unflatten_dict(vec, spec)
        assert len(d) == len(spec)
        for ent in spec:
            assert d[ent["path"]].shape == tuple(ent["shape"])
        # a different model must fail loudly, not half-apply
        other = {"dense": {"kernel": np.zeros((3, 3), np.float32)},
                 "bias": np.zeros(32, np.float32)}
        with pytest.raises(ValueError):
            sdelta.unflatten_like(other, vec, spec)

    def test_keep_for_ratio_bounds(self):
        assert sdelta.keep_for_ratio(1000, 0.01) == 10
        assert sdelta.keep_for_ratio(10, 0.0) == 1      # never zero
        assert sdelta.keep_for_ratio(10, 5.0) == 10     # never past n

    def test_topk_delta_set_semantics_and_early_exact(self):
        """Payloads carry current VALUES at the selected coordinates; when
        fewer coordinates changed than the budget, the delta is exact
        without running the packer."""
        rng = np.random.RandomState(2)
        last = rng.randn(256).astype(np.float32)
        vec = last.copy()
        touched = np.array([3, 77, 200])
        vec[touched] += 1.5
        idx, vals = sdelta.topk_delta(vec, last, keep=16)
        np.testing.assert_array_equal(np.sort(idx), touched)
        np.testing.assert_array_equal(vals, vec[np.sort(idx)])
        recon = last.copy()
        sdelta.apply_delta(recon, idx, vals)
        np.testing.assert_array_equal(recon, vec)   # bitwise: set, not add

    def test_residual_identity(self):
        """Transmitted coordinates zero their residual; untransmitted ones
        carry the full remaining drift — transmitted + residual accounts
        for the cumulative drift bitwise."""
        rng = np.random.RandomState(3)
        last = rng.randn(512).astype(np.float32)
        vec = (last + rng.randn(512).astype(np.float32) * 0.1).astype(
            np.float32)
        idx, vals = sdelta.topk_delta(vec, last, keep=32)
        after = last.copy()
        sdelta.apply_delta(after, idx, vals)
        res = sdelta.residual_of(vec, after)
        assert np.all(res[idx] == 0.0)
        mask = np.ones(512, bool)
        mask[idx] = False
        np.testing.assert_array_equal(res[mask], (vec - last)[mask])

    def test_flush_covers_every_bitwise_change(self):
        """The window-closing flush compares bit patterns, not values —
        -0.0 vs 0.0 and changed NaN payloads are transmitted too."""
        last = np.array([0.0, 1.0, np.nan, 2.0], np.float32)
        vec = np.array([-0.0, 1.0, np.nan, 3.0], np.float32)
        vec[2] = np.float32(np.frombuffer(
            np.array([0x7fc00001], np.uint32).tobytes(), np.float32)[0])
        idx, vals = sdelta.flush_delta(vec, last)
        assert 0 in idx and 3 in idx and 2 in idx and 1 not in idx
        recon = last.copy()
        sdelta.apply_delta(recon, idx, vals)
        assert np.array_equal(recon.view(np.int32), vec.view(np.int32))


# --------------------------------------------------------- window invariant

class TestLosslessWindow:
    def test_keyframe_plus_deltas_reconstruct_bitwise(self, tmp_path):
        """Tier-1 pin of the lossless invariant: at every window close,
        ``keyframe + deltas`` == the producer's params, bitwise; mid-window
        the reconstruction differs ONLY at untransmitted coordinates."""
        sd = str(tmp_path / "stream")
        rng = np.random.RandomState(4)
        params = _params(rng)
        w = StreamWriter(sd, ratio=0.05, keyframe_every=4, log=_quiet)
        r = StreamReader(sd, log=_quiet)
        closes = 0
        for step in range(1, 10):
            w.append(params, step=step)
            r.catch_up()
            man = read_segment_manifest(sd, w.head_seq)
            pvec, _ = sdelta.flatten_params(params)
            rvec, _ = sdelta.flatten_params(r.params_like(params))
            if man["window_close"]:
                closes += 1
                assert r.exact
                assert np.array_equal(pvec.view(np.int32),
                                      rvec.view(np.int32)), (
                    f"window close at seq {w.head_seq} not bitwise")
            else:
                # mid-window: residual_norm tracks what was withheld, and
                # any mismatch is confined to untransmitted coordinates
                diff = pvec.view(np.int32) != rvec.view(np.int32)
                payload = np.load(segment_payload_path(sd, w.head_seq))
                sent = set(np.asarray(payload["idx"]).tolist())
                assert sent.isdisjoint(np.flatnonzero(diff).tolist())
            params = _advance(params, rng)
        assert closes >= 2, "expected at least two window closes"
        # pattern: K D D F repeating for keyframe_every=4
        kinds = [read_segment_manifest(sd, q)["kind"]
                 for q in list_segments(sd)]
        assert kinds[:8] == ["keyframe", "delta", "delta", "delta",
                             "keyframe", "delta", "delta", "delta"]

    def test_sync_pins_bitwise_mid_window(self, tmp_path):
        sd = str(tmp_path / "stream")
        rng = np.random.RandomState(5)
        params = _params(rng)
        w = StreamWriter(sd, ratio=0.02, keyframe_every=8, log=_quiet)
        for step in range(1, 4):
            w.append(params, step=step)
            params = _advance(params, rng)
        w.sync(params, step=4)       # forced window-closing flush
        r = StreamReader(sd, log=_quiet)
        r.catch_up()
        assert r.exact and r.applied_step == 4
        _assert_bitwise(params, r.params_like(params), "sync pin")
        assert w.metrics()["stream/residual_norm"] == 0.0

    def test_async_appends_commit_in_order(self, tmp_path):
        sd = str(tmp_path / "stream")
        rng = np.random.RandomState(6)
        params = _params(rng)
        w = StreamWriter(sd, ratio=0.05, keyframe_every=4, log=_quiet)
        for step in range(1, 7):
            w.append_async(params, step=step)
            params = _advance(params, rng)
        w.drain()
        assert list_segments(sd) == list(range(6))
        assert read_head(sd)["seq"] == 5
        assert w.last_append_error is None
        w.close()

    def test_reopen_resumes_seq_and_forces_keyframe(self, tmp_path):
        """A relaunched producer continues the seq space and re-anchors
        with a keyframe — consumers never need the dead writer's window."""
        sd = str(tmp_path / "stream")
        rng = np.random.RandomState(7)
        params = _params(rng)
        w = StreamWriter(sd, ratio=0.05, keyframe_every=4, log=_quiet)
        for step in (1, 2):
            w.append(params, step=step)
            params = _advance(params, rng)
        w.close()
        w2 = StreamWriter(sd, ratio=0.05, keyframe_every=4, log=_quiet)
        seq = w2.append(params, step=3)
        assert seq == 2
        assert read_segment_manifest(sd, 2)["kind"] == "keyframe"
        r = StreamReader(sd, log=_quiet)
        r.catch_up()
        _assert_bitwise(params, r.params_like(params), "resume keyframe")
        w2.close()

    def test_reopen_never_overwrites_committed_but_unheaded_segment(
            self, tmp_path):
        """write_segment commits payload -> manifest -> head; a crash
        between the last two leaves a committed segment the head pointer
        never saw.  A restarted writer must continue PAST it — overwriting
        it would make a tailing reader (which already scanned that seq)
        skip the replacement keyframe and apply later deltas onto a wrong
        base while still reporting exact."""
        sd = str(tmp_path / "stream")
        rng = np.random.RandomState(17)
        params = _params(rng)
        w = StreamWriter(sd, ratio=0.05, keyframe_every=4, log=_quiet)
        for step in (1, 2, 3):
            w.append(params, step=step)
            params = _advance(params, rng)
        w.close()
        # roll the head pointer one seq back: the on-disk picture a crash
        # between the manifest and head commits leaves behind
        head = read_head(sd)
        with open(head_path(sd), "w") as f:
            json.dump({**head, "seq": head["seq"] - 1}, f)
        # a long-lived tailing reader has already scanned seq 2
        r = StreamReader(sd, log=_quiet)
        r.catch_up()
        assert r.applied_seq == 2
        w2 = StreamWriter(sd, ratio=0.05, keyframe_every=4, log=_quiet)
        seq = w2.append(params, step=4)
        assert seq == 3, "restart must not reuse the unheaded seq 2"
        assert read_segment_manifest(sd, 3)["kind"] == "keyframe"
        r.catch_up()
        _assert_bitwise(params, r.params_like(params),
                        "tailing reader across a torn-head restart")
        assert r.exact
        w2.close()

    def test_request_keyframe_re_anchors(self, tmp_path):
        sd = str(tmp_path / "stream")
        rng = np.random.RandomState(8)
        params = _params(rng)
        w = StreamWriter(sd, ratio=0.05, keyframe_every=32, log=_quiet)
        w.append(params, step=1)
        params = _advance(params, rng)
        w.request_keyframe()        # the Checkpointer tee calls this
        w.append(params, step=2)
        assert read_segment_manifest(sd, 1)["kind"] == "keyframe"
        w.close()


# --------------------------------------------------- store / fsck / prune

class TestStoreAndFsck:
    def _stream(self, tmp_path, n=9, keyframe_every=4, seed=9):
        sd = str(tmp_path / "stream")
        rng = np.random.RandomState(seed)
        params = _params(rng)
        w = StreamWriter(sd, ratio=0.05, keyframe_every=keyframe_every,
                         log=_quiet)
        for step in range(1, n + 1):
            w.append(params, step=step)
            params = _advance(params, rng)
        w.close()
        return sd, params

    def test_verify_stream_clean_and_corrupt(self, tmp_path):
        sd, _ = self._stream(tmp_path)
        problems, seqs = verify_stream(sd)
        assert problems == [] and seqs == list(range(9))
        _flip_payload(sd, 5)
        problems, _ = verify_stream(sd)
        assert any("segment 5" in p for p in problems)

    def test_reader_walks_back_and_recovers(self, tmp_path):
        """Torn mid-window delta: the consumer reverts to its stored
        keyframe bitwise and re-anchors at the next keyframe."""
        sd = str(tmp_path / "stream")
        rng = np.random.RandomState(10)
        params = _params(rng)
        w = StreamWriter(sd, ratio=0.05, keyframe_every=4, log=_quiet)
        w.append(params, step=1)                     # seq 0 keyframe
        kf = copy.deepcopy(params)
        params = _advance(params, rng)
        w.append(params, step=2)                     # seq 1 delta
        params = _advance(params, rng)
        w.append(params, step=3)                     # seq 2 delta
        _flip_payload(sd, 2)
        r = StreamReader(sd, log=_quiet)
        r.catch_up()
        assert r.metrics()["stream/corrupt_segments"] == 1.0
        assert r.applied_seq == 0
        _assert_bitwise(kf, r.params_like(kf), "walk-back")
        # next keyframe re-anchors; sync closes the window bitwise
        params = _advance(params, rng)
        w.append(params, step=4)                     # seq 3 flush (skipped)
        params = _advance(params, rng)
        w.append(params, step=5)                     # seq 4 keyframe
        w.sync(params, step=5)
        r.catch_up()
        assert r.exact
        _assert_bitwise(params, r.params_like(params), "re-anchor")
        w.close()

    def test_fresh_reader_seeks_past_dead_history(self, tmp_path):
        """A fresh consumer (rejoin, relaunched server) anchors at the
        newest verifiable keyframe — older windows are never read — and
        a corrupt head keyframe falls back to the previous verifiable
        one, scanning forward from there."""
        sd, _ = self._stream(tmp_path, n=9, keyframe_every=3)
        # seqs 0..8, keyframes at 0 / 3 / 6
        r = StreamReader(sd, log=_quiet)
        r.catch_up()
        assert r.segments_applied == 3       # the last window only: 6 7 8
        assert r.applied_seq == 8 and r.exact
        total = sum(read_segment_manifest(sd, q)["bytes"]
                    for q in list_segments(sd))
        assert 0 < r.bytes_read < total
        _flip_payload(sd, 6)
        r2 = StreamReader(sd, log=_quiet)
        r2.catch_up()
        assert r2.corrupt_segments == 1      # met seq 6 scanning forward
        assert r2.applied_seq == 3 and not r2.exact

    def test_torn_head_never_claims_exact_while_behind(self, tmp_path):
        """``exact`` on an unreadable head pointer falls back to the
        committed-segment listing: a reader a window behind must not
        label its snapshot bitwise-at-head just because the head tore."""
        sd = str(tmp_path / "stream")
        rng = np.random.RandomState(19)
        params = _params(rng)
        w = StreamWriter(sd, ratio=0.05, keyframe_every=4, log=_quiet)
        w.append(params, step=1)
        p2 = _advance(params, rng)
        w.sync(p2, step=2)
        r = StreamReader(sd, log=_quiet)
        r.catch_up()
        assert r.exact
        p3 = _advance(p2, rng)
        w.sync(p3, step=3)               # reader now one flush behind
        with open(head_path(sd), "w") as f:
            f.write("{torn")
        assert not r.exact               # behind + torn head != exact
        r.catch_up()
        assert r.exact                   # caught up: listing fallback
        _assert_bitwise(p3, r.params_like(p3), "post-tear catch-up")
        w.close()

    def test_no_verifiable_keyframe_raises(self, tmp_path):
        sd, _ = self._stream(tmp_path, n=2, keyframe_every=4)
        _flip_payload(sd, 0)     # the only keyframe
        with pytest.raises(StreamCorrupt):
            StreamReader(sd, log=_quiet).catch_up()
        # ...and warm rejoin degrades to the full-restore path

        @dataclasses.dataclass
        class Joiner:
            params: dict

        j = Joiner(params=_params(np.random.RandomState(9)))
        out, info = warm_rejoin(j, sd, log=_quiet)
        assert out is j and info is None

    def test_empty_dir_is_not_corrupt(self, tmp_path):
        sd = str(tmp_path / "empty")
        os.makedirs(sd)
        r = StreamReader(sd, log=_quiet)
        assert r.catch_up() == 0     # a polling consumer just waits
        assert not is_stream_dir(sd)

    def test_fsck_cli_on_streams(self, tmp_path):
        from tools import ckpt_fsck as fsck

        sd, _ = self._stream(tmp_path)
        assert fsck.main([sd]) == 0
        assert fsck.main([sd, "--list"]) == 0
        _flip_payload(sd, 5)
        assert fsck.main([sd]) == 1          # detected offline
        empty = str(tmp_path / "none")
        os.makedirs(empty)
        assert fsck.main([empty]) == 2

    def test_fsck_finds_stream_next_to_checkpoints(self, tmp_path):
        from tools import ckpt_fsck as fsck

        self._stream(tmp_path)               # <tmp>/stream
        assert fsck.main([str(tmp_path)]) == 0
        _flip_payload(str(tmp_path / "stream"), 3)
        assert fsck.main([str(tmp_path)]) == 1

    def test_prune_keeps_trailing_windows(self, tmp_path):
        from tools import ckpt_fsck as fsck

        sd, params = self._stream(tmp_path, n=12, keyframe_every=3)
        before = list_segments(sd)
        assert fsck.main([sd, "--prune", "--keep_windows", "1"]) == 0
        after = list_segments(sd)
        assert after and after[0] > before[0]
        assert read_segment_manifest(sd, after[0])["kind"] == "keyframe"
        # the surviving tail still reconstructs the producer bitwise
        problems, _ = verify_stream(sd)
        assert problems == []
        r = StreamReader(sd, log=_quiet)
        r.catch_up()
        rvec, _ = sdelta.flatten_params(r.params_like(params))

    def test_stat_keys_declared(self):
        from tpu_compressed_dp.obs import registry

        rng = np.random.RandomState(11)
        w = StreamWriter("/tmp/_unused_stream_dir_decl", log=_quiet)
        for k in list(w.metrics()) + ["stream/lag_s",
                                      "stream/corrupt_segments",
                                      "stream/rejoin_bytes"]:
            assert registry.is_declared(k), k


# --------------------------------------------------------- checkpoint tee

class TestCheckpointTee:
    def test_committed_save_requests_keyframe(self, tmp_path):
        """A committed full checkpoint re-anchors the delta window, so
        delta history never needs to span past the newest restore point."""
        import dataclasses as dc

        import jax.numpy as jnp

        from tpu_compressed_dp.train.optim import SGD
        from tpu_compressed_dp.train.state import TrainState
        from tpu_compressed_dp.utils.checkpoint import Checkpointer

        params = {"w": jnp.zeros((4,))}
        opt = SGD(lr=0.1)
        state = TrainState.create(params, {}, opt.init(params), (),
                                  jax.random.key(0))

        class StubStream:
            calls = 0

            def request_keyframe(self):
                StubStream.calls += 1

        ckpt = Checkpointer(str(tmp_path / "ck"))
        ckpt.stream = StubStream()
        ckpt.save(state, {"step": 1})
        state = dc.replace(state, step=state.step + 1)
        ckpt.save(state, {"step": 2})
        ckpt.close()
        assert StubStream.calls == 2

    def test_stream_failure_never_fails_a_save(self, tmp_path):
        import jax.numpy as jnp

        from tpu_compressed_dp.train.optim import SGD
        from tpu_compressed_dp.train.state import TrainState
        from tpu_compressed_dp.utils.checkpoint import Checkpointer

        params = {"w": jnp.zeros((4,))}
        opt = SGD(lr=0.1)
        state = TrainState.create(params, {}, opt.init(params), (),
                                  jax.random.key(0))

        class BadStream:
            def request_keyframe(self):
                raise RuntimeError("disk full")

        ckpt = Checkpointer(str(tmp_path / "ck"))
        ckpt.stream = BadStream()
        ckpt.save(state, {"step": 1})    # must not raise
        ckpt.close()
        assert os.path.isdir(str(tmp_path / "ck" / str(int(state.step))))


# ------------------------------------------------------- warm rejoin e2e

class TestWarmRejoinEndToEnd:
    def test_joiner_adopts_from_stream_bitwise(self, tmp_path, mesh8,
                                               monkeypatch):
        """The acceptance row: a joiner catches up from the delta stream
        (no full Orbax read on the warm path), announces the ``stream``
        flag through the rendezvous join record, adopts through
        ``join_world`` — and lands bitwise identical to a joiner that took
        the full-restore path."""
        from tools import chaos_drill

        from tpu_compressed_dp.parallel.dp import CompressionConfig
        from tpu_compressed_dp.train.elastic import (ElasticConfig,
                                                     ElasticRuntime)
        from tpu_compressed_dp.train.rendezvous import Rendezvous
        from tpu_compressed_dp.utils import checkpoint as ck

        comp = CompressionConfig(method="topk", ratio=0.25,
                                 error_feedback=True)
        state, step = chaos_drill._tiny_setup(mesh8, comp, None, None)
        batch = chaos_drill._batch()
        sd = str(tmp_path / "stream")
        cd = str(tmp_path / "ckpt")
        w = StreamWriter(sd, ratio=0.05, keyframe_every=8, log=_quiet)
        ckpt = ck.Checkpointer(cd)
        ckpt.stream = w
        for _ in range(3):
            state, _ = step(state, batch)
            w.append(jax.device_get(state.params), step=int(state.step))
        ckpt.save(state, {"step": int(state.step)})
        ckpt.close()
        # the survivor side of the barrier protocol: flush so the stream
        # head reconstructs the live params bitwise
        live_params = jax.device_get(state.params)
        w.sync(live_params, step=int(state.step))

        # scripted single-process rendezvous: the survivor (rank 1)
        # admits the joiner (rank 0) as soon as its join record — with
        # the stream flag — appears
        class Clock:
            t = 0.0

            def now(self):
                return Clock.t

            def sleep(self, s):
                Clock.t += s
                survivor_turn()

        clock = Clock()
        rd = str(tmp_path / "rdzv")
        surv = Rendezvous(rd, 1, now=clock.now, sleep=clock.sleep)
        joiner_rdzv = Rendezvous(rd, 0, now=clock.now, sleep=clock.sleep)
        committed = {}

        def survivor_turn():
            joins = surv.pending_joins()
            if 0 in joins and "d" not in committed:
                assert joins[0]["stream"] == w.head_seq
                # the survivors derive warm from the immutable join
                # records (+ the fleet-wide armed flag) and PUBLISH the
                # bit in the commit — both sides of the admission
                # broadcast pick their layout from the committed record
                committed["d"] = surv.propose(
                    [0, 1], voters=[1],
                    warm=joins[0].get("stream") is not None)

        # -- warm joiner: adopt from the stream; Orbax must not be read
        fresh, _ = chaos_drill._tiny_setup(mesh8, comp, None, None)
        host_fresh = jax.device_get(fresh.params)

        @dataclasses.dataclass
        class Probe:
            params: dict

        adopted, info = warm_rejoin(Probe(params=host_fresh), sd, log=_quiet)
        assert info is not None and info["exact"]
        # the fresh reader seeks to the newest verifiable keyframe: the
        # joiner pays for one window's tail, never the whole history
        assert info["bytes"] > 0
        assert 1 <= info["segments"] < len(list_segments(sd))
        assert info["seq"] == w.head_seq
        decision = joiner_rdzv.join(incarnation=1, stream_seq=info["seq"],
                                    deadline_s=30.0)
        assert decision is not None and decision.ranks == (0, 1)
        assert decision.warm, "commit must carry the warm layout bit"
        monkeypatch.setattr(
            ck.Checkpointer, "restore",
            lambda *a, **k: (_ for _ in ()).throw(
                AssertionError("warm path read Orbax")))

        # the single-process broadcast shortcut np.asarray's every leaf,
        # which typed PRNG keys refuse — fold the key to its raw data for
        # the scripted barrier (the real multi-process path ships buffers)
        def raw_rng(st):
            return dataclasses.replace(st, rng=jax.random.key_data(st.rng))

        el = ElasticRuntime(ElasticConfig(), mesh8, log=_quiet)
        warm_state = el.join_world(raw_rng(fresh), decision,
                                   adopted_params=adopted.params,
                                   adopted_info=info)
        assert el.metrics()["stream/rejoin_bytes"] == float(info["bytes"])
        monkeypatch.undo()
        _assert_bitwise(live_params, jax.device_get(warm_state.params),
                        "warm joiner vs survivor")

        # -- control joiner: full Orbax restore under a COLD commit (the
        # layout a fleet without unanimous stream flags agrees on)
        fresh2, _ = chaos_drill._tiny_setup(mesh8, comp, None, None)
        restore = ck.Checkpointer(cd)
        cold, _meta = restore.restore(fresh2)
        restore.close()
        el2 = ElasticRuntime(ElasticConfig(), mesh8, log=_quiet)
        cold_state = el2.join_world(
            raw_rng(cold), dataclasses.replace(decision, warm=False))
        _assert_bitwise(jax.device_get(cold_state.params),
                        jax.device_get(warm_state.params),
                        "warm joiner vs full-restore joiner")

        # a warm commit with no adoption in hand must refuse to join the
        # params-skipping collective (fresh-init params would be garbage)
        from tpu_compressed_dp.train.rendezvous import RendezvousError
        el3 = ElasticRuntime(ElasticConfig(), mesh8, log=_quiet)
        with pytest.raises(RendezvousError):
            el3.join_world(raw_rng(fresh2), decision)
        w.close()


# ------------------------------------------------------- harness plumbing

class TestHarnessPlumbing:
    def _args(self, extra=()):
        from tpu_compressed_dp.harness import loop

        p = argparse.ArgumentParser()
        loop.add_stream_args(p, cadence_help="test cadence")
        return p.parse_args(list(extra))

    def test_stream_args_defaults(self):
        a = self._args()
        assert a.stream_dir is None and a.stream_every == 1
        assert a.stream_keyframe_every == 8 and a.stream_ratio == 0.01
        assert a.stream_rejoin is False

    def test_make_stream_gating(self, tmp_path):
        from tpu_compressed_dp.harness import loop

        assert loop.make_stream(self._args()) is None
        a = self._args(["--stream_dir", str(tmp_path / "s")])
        w = loop.make_stream(a, log=_quiet)
        assert isinstance(w, StreamWriter)
        w.close()

    def test_stream_join_seq_probe(self, tmp_path):
        from tpu_compressed_dp.harness import loop

        sd = str(tmp_path / "s")
        rng = np.random.RandomState(12)
        params = _params(rng)
        w = StreamWriter(sd, ratio=0.05, keyframe_every=4, log=_quiet)
        w.sync(params, step=1)
        w.close()
        # no --stream_rejoin => no probe
        assert loop.stream_join_seq(
            self._args(["--stream_dir", sd])) is None
        a = self._args(["--stream_dir", sd, "--stream_rejoin"])
        assert loop.stream_join_seq(a) == 0
        # an unusable stream degrades to a cold join, not a crash
        _flip_payload(sd, 0)
        assert loop.stream_join_seq(a) is None

    def test_rejoin_params_respects_cold_commit(self, tmp_path):
        """The joiner's catch-up obeys the COMMITTED warm bit: a cold
        admission skips the stream outright (the survivors take the full
        broadcast layout, so an adoption would be discarded anyway)."""
        from tpu_compressed_dp.harness.loop import stream_rejoin_params
        from tpu_compressed_dp.train.rendezvous import EpochDecision

        sd = str(tmp_path / "s")
        rng = np.random.RandomState(21)
        w = StreamWriter(sd, ratio=0.05, keyframe_every=4, log=_quiet)
        w.sync(_params(rng), step=1)
        w.close()
        a = self._args(["--stream_dir", sd, "--stream_rejoin"])
        cold = EpochDecision(epoch=1, ranks=(0, 1), coordinator=1,
                             address="h:1", process_id=0, warm=False)
        assert stream_rejoin_params(a, None, cold, log=_quiet) == (None,
                                                                   None)

    def test_elastic_runtime_warm_layout_is_fleet_shared(self, mesh8):
        """The barrier layout keys on ``stream_armed`` (a fleet-wide
        fact), never on holding the writer: a survivor WITHOUT the
        process-0 StreamWriter must still compute the warm layout."""
        from tpu_compressed_dp.train.elastic import (ElasticConfig,
                                                     ElasticRuntime)

        el = ElasticRuntime(ElasticConfig(), mesh8, log=_quiet,
                            stream=None, stream_armed=True)
        assert el.stream_armed and el.stream is None
        # directly-constructed runtimes (drills) follow the writer
        assert not ElasticRuntime(ElasticConfig(), mesh8,
                                  log=_quiet).stream_armed

    def test_all_harnesses_expose_stream_flags(self):
        for mod in ("dawn", "imagenet", "lm"):
            h = __import__(f"tpu_compressed_dp.harness.{mod}",
                           fromlist=[mod])
            p = h.build_parser()
            a = p.parse_args(["--stream_dir", "/tmp/x", "--stream_rejoin"])
            assert a.stream_dir == "/tmp/x" and a.stream_rejoin


# -------------------------------------------------------------- serve CLI

class TestServeCLI:
    def test_once_snapshot_and_heartbeat(self, tmp_path):
        from tools import stream_serve

        sd = str(tmp_path / "stream")
        rng = np.random.RandomState(13)
        params = _params(rng)
        w = StreamWriter(sd, ratio=0.05, keyframe_every=4, log=_quiet)
        for s in (1, 2):
            w.append(params, step=s)
            params = _advance(params, rng)
        w.sync(params, step=3)
        w.close()
        snap = str(tmp_path / "snap")
        hb = str(tmp_path / "hb.json")
        rc = stream_serve.main([sd, "--once", "--snapshot_dir", snap,
                                "--heartbeat", hb])
        assert rc == 0
        with np.load(os.path.join(snap, "snapshot-3.npz")) as z:
            got = {k: z[k] for k in z.files}
        vec, spec = sdelta.flatten_params(params)
        want = sdelta.unflatten_dict(vec, spec)
        assert set(got) == set(want)
        for k in want:
            assert np.array_equal(got[k], want[k]), k
        rec = json.load(open(hb))
        assert rec["exact"] is True and rec["applied_step"] == 3
        assert rec["stream_lag_s"] >= 0.0

    def test_exit_codes(self, tmp_path):
        from tools import stream_serve

        assert stream_serve.main([str(tmp_path / "nope"), "--once"]) == 2
        empty = str(tmp_path / "empty")
        os.makedirs(empty)
        assert stream_serve.main([empty, "--once"]) == 2
        sd = str(tmp_path / "stream")
        w = StreamWriter(sd, keyframe_every=4, log=_quiet)
        w.sync(_params(np.random.RandomState(14)), step=1)
        w.close()
        assert stream_serve.main([sd, "--once"]) == 0
        _flip_payload(sd, 0)
        assert stream_serve.main([sd, "--once"]) == 1
