"""ImageNet-side tests: data pipeline, phase schedule, checkpoint, harness e2e.

The reference had no tests (SURVEY.md §4); these cover the behaviors its
manual protocol relied on: DistValSampler equal-batch-count, rect-val AR
bucketing, progressive-resize phase swaps, Scheduler LR values, and
checkpoint/resume (including the EF residual the reference failed to save).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tpu_compressed_dp.data import imagenet as inet
from tpu_compressed_dp.train import schedules


def test_synthetic_images_interface():
    ds = inet.SyntheticImages(16, num_classes=10, seed=0)
    assert len(ds) == 16
    w, h = ds.size(3)
    img = ds.load(3)
    assert img.size == (w, h)
    assert 0 <= ds.label(3) < 10


def test_train_loader_shapes_and_determinism():
    ds = inet.SyntheticImages(64, num_classes=10)
    dl = inet.TrainLoader(ds, 16, 32, seed=3, workers=2)
    batches = list(dl)
    assert len(batches) == len(dl) == 4
    for b in batches:
        assert b["input"].shape == (16, 32, 32, 3)
        assert b["input"].dtype == np.uint8
        assert b["target"].shape == (16,)
    # same epoch -> same batches; next epoch -> reshuffled
    again = list(dl)
    np.testing.assert_array_equal(batches[0]["input"], again[0]["input"])
    dl.set_epoch(1)
    assert not np.array_equal(batches[0]["target"], list(dl)[0]["target"])


def test_val_loader_equal_batch_count_across_processes():
    # DistValSampler contract (`dataloader.py:133-161`): every process yields
    # the same number of batches even when it runs out of images.
    ds = inet.SyntheticImages(50, num_classes=10)
    loaders = [
        inet.ValLoader(ds, 8, 32, process_index=i, process_count=4, workers=2)
        for i in range(4)
    ]
    counts = [len(list(l)) for l in loaders]
    assert counts == [loaders[0].expected_num_batches] * 4
    total = sum(len(b["target"]) for l in loaders for b in l)
    assert total == 50  # every image seen exactly once


def test_rect_val_falls_back_to_square_multihost(monkeypatch):
    # Rect-val hands each process differently-shaped local batches — fine
    # under the reference's per-process NCCL (`dataloader.py:133-175`),
    # incompatible with one global SPMD array.  Pin the documented fallback
    # (VERDICT r1 weak #8): multi-process phases silently request square val.
    from tpu_compressed_dp.harness.imagenet import PhaseData

    ds_t = inet.SyntheticImages(64, num_classes=10)
    ds_v = inet.SyntheticImages(32, num_classes=10)
    phases = [{"ep": 0, "sz": 32, "bs": 16, "rect_val": True}]

    pd = PhaseData(ds_t, ds_v, phases, workers=1)
    pd.set_epoch(0)
    assert pd.val_loader.rect_val  # single-process: rect honoured

    monkeypatch.setattr(jax, "process_count", lambda: 2)
    monkeypatch.setattr(jax, "process_index", lambda: 0)
    pd2 = PhaseData(ds_t, ds_v, phases, workers=1)
    pd2.set_epoch(0)
    assert not pd2.val_loader.rect_val  # multi-host: square fallback


def test_val_loader_rect_shapes_bounded():
    ds = inet.SyntheticImages(64, num_classes=10)
    dl = inet.ValLoader(ds, 8, 32, rect_val=True, ar_buckets=4, workers=2)
    shapes = set()
    ars = []
    for b in dl:
        if len(b["target"]):
            shapes.add(b["input"].shape[1:3])
            ars.append(b["input"].shape[2] / b["input"].shape[1])
    assert len(shapes) <= 4  # palette bounds compile count
    assert ars == sorted(ars)  # AR-ascending batch order (sort_ar semantics)


def test_val_batch_size_rule():
    # `train_imagenet_nv.py:592-597`
    assert inet.val_batch_size(128, 512) == 512
    assert inet.val_batch_size(128, 64) == 512
    assert inet.val_batch_size(224, 224) == 256
    assert inet.val_batch_size(288, 128) == 128
    assert inet.val_batch_size(288, 512) == 512


def test_epoch_from_steps_and_variable_bs_lr():
    # 2 epochs at 10 steps, then 2 at 5 (bs doubled): LR-vs-epoch must not care
    to_epoch = schedules.epoch_from_steps([10, 10, 5, 5])
    assert float(to_epoch(0.0)) == 0.0
    assert float(to_epoch(10.0)) == 1.0
    assert float(to_epoch(25.0)) == 3.0
    assert float(to_epoch(27.5)) == pytest.approx(3.5)
    phases = [{"ep": (0, 2), "lr": (0.0, 1.0)}, {"ep": 2, "lr": 0.5},
              {"ep": (3, 4), "lr": (0.5, 0.0)}]
    lr = schedules.phase_lr_schedule_variable_bs(phases, [10, 10, 5, 5])
    assert float(lr(10.0)) == pytest.approx(0.5)   # epoch 1 of the ramp
    assert float(lr(22.0)) == pytest.approx(0.5)   # constant phase
    assert float(lr(30.0)) == pytest.approx(0.0)   # annealed to zero


class TestCheckpoint:
    def _tiny_state(self, ef=True):
        from tpu_compressed_dp.parallel.dp import CompressionConfig, init_ef_state
        from tpu_compressed_dp.train.optim import SGD
        from tpu_compressed_dp.train.state import TrainState

        params = {"w": jnp.arange(6.0).reshape(2, 3), "b": jnp.ones((3,))}
        opt = SGD(lr=0.1, momentum=0.9)
        cfg = CompressionConfig(method="randomk", ratio=0.5, error_feedback=ef)
        return TrainState.create(
            params, {}, opt.init(params), init_ef_state(params, cfg, 2),
            jax.random.key(5),
        )

    def test_roundtrip_with_ef(self, tmp_path):
        from tpu_compressed_dp.utils.checkpoint import restore_checkpoint, save_checkpoint
        import dataclasses

        state = self._tiny_state()
        state = dataclasses.replace(
            state,
            step=jnp.asarray(17, jnp.int32),
            ef=jax.tree.map(lambda e: e + 2.5, state.ef),
        )
        save_checkpoint(str(tmp_path / "ck"), state, {"epoch": 3})
        blank = self._tiny_state()
        restored, meta = restore_checkpoint(str(tmp_path / "ck"), blank)
        assert int(restored.step) == 17
        assert meta["epoch"] == 3
        jax.tree.map(np.testing.assert_allclose, restored.params, state.params)
        jax.tree.map(np.testing.assert_allclose, restored.ef, state.ef)  # EF saved!
        np.testing.assert_array_equal(
            jax.random.key_data(restored.rng), jax.random.key_data(state.rng)
        )

    def test_roundtrip_no_ef(self, tmp_path):
        from tpu_compressed_dp.utils.checkpoint import restore_checkpoint, save_checkpoint

        state = self._tiny_state(ef=False)
        assert state.ef == ()
        save_checkpoint(str(tmp_path / "ck"), state)
        restored, _ = restore_checkpoint(str(tmp_path / "ck"), self._tiny_state(ef=False))
        assert restored.ef == ()

    def test_save_if_best_gating(self, tmp_path):
        from tpu_compressed_dp.utils.checkpoint import Checkpointer

        ckpt = Checkpointer(str(tmp_path / "ck"))
        s = self._tiny_state()
        assert ckpt.save_if_best(s, 50.0)
        assert not ckpt.save_if_best(s, 49.0)   # not an improvement
        assert not ckpt.save_if_best(s, 50.0)   # ties don't save
        assert ckpt.save_if_best(s, 60.0)
        assert not ckpt.save_if_best(s, 93.0, floor=94.0)  # below floor
        ckpt.close()


@pytest.mark.slow  # 20-40 s of ResNet compiles
def test_imagenet_harness_e2e(tmp_path):
    """Full smoke: synthetic data, progressive resize (64->96 px with rect
    val), bf16 resnet18, layer-wise Top-K + EF, checkpoint every improvement,
    then resume for the last epoch."""
    from tpu_compressed_dp.harness import imagenet as h

    argv = [
        "--synthetic", "--synthetic_n", "96", "--num_classes", "8",
        "--arch", "resnet18", "--width", "16",
        "--compress", "layerwise", "--method", "topk", "--ratio", "0.1",
        "--error_feedback", "--no_bn_wd", "--init_bn0",
        "--short_epoch", "--workers", "2", "--seed", "11",
        "--checkpoint_dir", str(tmp_path / "ck"),
    ]
    summary = h.main(argv)
    assert summary["epoch"] == 2  # smoke schedule runs epochs 0..2
    assert np.isfinite(summary["train loss"])
    assert 0 < summary["sent frac"] < 0.12  # topk k=0.1 (+ tiny-tensor rounding)

    # resume from the stored checkpoint and run evaluate-only
    stats = h.main(argv + ["--resume", str(tmp_path / "ck"), "--evaluate"])
    assert stats["count"] > 0


def _make_image_tree(root, n_classes=3, per_class=8, seed=0):
    """Write a torchvision-layout tree with varied sizes/ARs to disk."""
    import os

    from PIL import Image as PILImage

    rng = np.random.default_rng(seed)
    for ci in range(n_classes):
        cdir = root / f"class_{ci:02d}"
        os.makedirs(cdir, exist_ok=True)
        for j in range(per_class):
            w = int(rng.integers(24, 72))
            h = int(rng.integers(24, 72))
            arr = np.full((h, w, 3), 40 * ci + 20, np.uint8)
            arr += rng.integers(0, 20, arr.shape).astype(np.uint8)
            PILImage.fromarray(arr).save(cdir / f"img_{j:03d}.png")


class TestImageFolderSizeCache:
    def test_cold_scan_then_warm_load(self, tmp_path, monkeypatch):
        """VERDICT r2 #7: the AR index persists; a warm start opens ZERO
        image files for size planning."""
        from tpu_compressed_dp.data import imagenet as inet

        _make_image_tree(tmp_path / "train")
        ds = inet.ImageFolder(str(tmp_path / "train"))
        wh = ds.sizes_bulk()
        assert wh.shape == (24, 2)
        cache = tmp_path / "train" / inet.ImageFolder.SIZE_CACHE
        assert cache.exists()

        # warm: a fresh instance must satisfy sizes_bulk from the cache only
        ds2 = inet.ImageFolder(str(tmp_path / "train"))
        opens = []
        real_open = inet.Image.open
        monkeypatch.setattr(inet.Image, "open",
                            lambda *a, **k: opens.append(a) or real_open(*a, **k))
        wh2 = ds2.sizes_bulk()
        assert opens == []
        np.testing.assert_array_equal(np.asarray(wh), np.asarray(wh2))
        # and size(i) agrees with a direct header read
        with real_open(ds2.samples[5][0]) as im:
            assert ds2.size(5) == im.size

    def test_stale_cache_rescans(self, tmp_path):
        from PIL import Image as PILImage

        from tpu_compressed_dp.data import imagenet as inet

        _make_image_tree(tmp_path / "train")
        ds = inet.ImageFolder(str(tmp_path / "train"))
        ds.sizes_bulk()
        # add a file: the sample list changes, cache must be ignored
        extra = tmp_path / "train" / "class_00" / "img_zzz.png"
        PILImage.fromarray(np.zeros((10, 30, 3), np.uint8)).save(extra)
        ds2 = inet.ImageFolder(str(tmp_path / "train"))
        wh = ds2.sizes_bulk()
        assert wh.shape == (25, 2)
        idx = [i for i, (p, _) in enumerate(ds2.samples)
               if p.endswith("img_zzz.png")][0]
        assert ds2.size(idx) == (30, 10)

    def test_readonly_root_falls_back_to_home_cache(self, tmp_path, monkeypatch):
        # chmod can't model a read-only mount when tests run as root (root
        # bypasses permission bits) — fail the in-tree write directly
        from tpu_compressed_dp.data import imagenet as inet

        _make_image_tree(tmp_path / "train")
        monkeypatch.setenv("HOME", str(tmp_path / "home"))
        root = str(tmp_path / "train")
        ds = inet.ImageFolder(root)
        real_savez = np.savez_compressed

        def savez(path, **kw):
            if str(path).startswith(root):
                raise OSError(30, "Read-only file system", str(path))
            return real_savez(path, **kw)

        monkeypatch.setattr(np, "savez_compressed", savez)
        ds.sizes_bulk()
        home_caches = list((tmp_path / "home").rglob("sizes-*.npz"))
        assert len(home_caches) == 1
        ds2 = inet.ImageFolder(root)
        assert ds2._load_size_cache() is not None


@pytest.mark.slow  # 20-40 s of ResNet compiles
def test_imagenet_harness_e2e_imagefolder(tmp_path):
    """On-disk ImageFolder end-to-end (VERDICT r2 #7): train + rect-val
    through the smoke schedule's two image sizes, driven by real files."""
    from tpu_compressed_dp.harness import imagenet as h

    _make_image_tree(tmp_path / "data" / "train", per_class=32)
    _make_image_tree(tmp_path / "data" / "validation", per_class=8, seed=5)
    import json

    phases = [
        {"ep": 0, "sz": 64, "bs": 32},
        {"ep": [0, 1], "lr": [0.1, 0.2]},
        {"ep": 1, "lr": 0.1},
        {"ep": 2, "sz": 96, "bs": 16, "rect_val": True},
        {"ep": [2, 3], "lr": [0.01, 0.001]},
    ]
    argv = [
        str(tmp_path / "data"),
        "--phases", json.dumps(phases),
        "--num_classes", "3", "--arch", "resnet18", "--width", "16",
        "--short_epoch", "--workers", "2", "--seed", "3",
    ]
    summary = h.main(argv)
    assert summary["epoch"] == 2  # smoke schedule: 64px then 96px rect-val
    assert np.isfinite(summary["train loss"])
    assert summary["top5"] >= 0.0
    # the rect-val planning persisted its AR index next to the data
    from tpu_compressed_dp.data.imagenet import ImageFolder

    assert (tmp_path / "data" / "validation" / ImageFolder.SIZE_CACHE).exists()
