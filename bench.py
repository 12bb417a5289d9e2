"""Headline benchmark: CIFAR-10 ResNet-9 training throughput (images/sec).

Baseline: the reference's DAWNBench result — 24 epochs x 50,000 images in 79 s
on one V100 (`/root/reference/CIFAR10/README.md:3`, SURVEY.md §6) =
~15,190 images/sec end-to-end.  We measure the same workload's steady-state
train-step throughput (forward + backward + gradient sync + SGD update,
batch 512) on the attached TPU and report ``vs_baseline = ours / 15190``.
Off-TPU it exits non-zero before compiling anything: a CPU step time is not
this record's metric.

The headline runs bf16 compute / fp32 masters — the TPU-native posture the
rest of the framework defaults to (models/resnet.py docstring; the
reference's own fp16 machinery is `fp16util.py`).  The fp32 protocol-parity
number is measured in the same process and reported as ``fp32_*`` fields.

Prints exactly ONE JSON line on stdout; progress goes to stderr.
"""

from __future__ import annotations

import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

BASELINE_IMAGES_PER_SEC = 24 * 50_000 / 79.0  # reference DAWNBench, 1x V100


def measure(dtype, batch, mesh, bs: int, ndev: int):
    """Steady-state images/sec + MFU fields for one compute dtype."""
    from tpu_compressed_dp.harness.dawn import MODELS
    from tpu_compressed_dp.models.common import init_model, make_apply_fn
    from tpu_compressed_dp.parallel.dp import CompressionConfig, init_ef_state
    from tpu_compressed_dp.train.optim import SGD
    from tpu_compressed_dp.train.schedules import piecewise_linear
    from tpu_compressed_dp.train.state import TrainState
    from tpu_compressed_dp.train.step import make_train_step
    from tpu_compressed_dp.utils.flops import cnn_mfu_record

    module = MODELS["resnet9"](1.0, dtype=dtype)
    params, stats = init_model(
        module, jax.random.key(0), jnp.zeros((1, 32, 32, 3), jnp.float32)
    )
    apply_fn = make_apply_fn(module)

    sched = piecewise_linear([0, 5, 24], [0, 0.4, 0])
    steps_per_epoch = 50_000 // bs
    opt = SGD(
        lr=lambda s: sched(s / steps_per_epoch) / bs,
        momentum=0.9,
        nesterov=True,
        weight_decay=5e-4 * bs,
    )
    comp = CompressionConfig(method=None)
    state = TrainState.create(
        params, stats, opt.init(params), init_ef_state(params, comp, ndev),
        jax.random.key(1),
    )
    train_step = make_train_step(apply_fn, opt, comp, mesh, grad_scale=float(bs))

    # Warmup: compile + settle (the reference's warmup_cudnn analog,
    # `torch_backend.py:18-29`).  Time-based — a freshly-attached chip ramps
    # for several seconds — with a barrier per burst so no dispatch backlog
    # leaks into the timed region.
    t0 = time.perf_counter()
    done = 0
    while done < 3 or time.perf_counter() - t0 < 3.0:
        for _ in range(8):
            state, metrics = train_step(state, batch)
            done += 1
        jax.block_until_ready(metrics)

    timed_steps = 60
    t0 = time.perf_counter()
    for _ in range(timed_steps):
        state, metrics = train_step(state, batch)
    jax.block_until_ready(metrics)
    dt = time.perf_counter() - t0

    images_per_sec = timed_steps * bs / dt
    print(f"{jnp.dtype(dtype).name}: {timed_steps} steps in {dt:.3f}s "
          f"({images_per_sec:.0f} img/s)", file=sys.stderr)

    # MFU (VERDICT r2 #3): model-only FLOPs at the measured step rate vs the
    # chip's bf16 peak (utils/flops.py conventions)
    return images_per_sec, cnn_mfu_record(
        apply_fn, params, stats, (bs // ndev, 32, 32, 3), timed_steps / dt)


def main() -> None:
    from tpu_compressed_dp.parallel.mesh import (make_data_mesh,
                                                 setup_compile_cache)

    setup_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"bench.py measures the TPU; JAX reports platform="
                 f"{dev.platform!r} ({dev.device_kind})")
    mesh = make_data_mesh()
    ndev = mesh.shape["data"]
    bs = 512
    if bs % ndev:
        bs = (bs // ndev + 1) * ndev
    print(f"devices={ndev} ({dev.platform}, {dev.device_kind}), batch={bs}",
          file=sys.stderr)

    rng = np.random.default_rng(0)
    batch = {
        "input": jnp.asarray(
            rng.standard_normal((bs, 32, 32, 3), dtype=np.float32)
        ),
        "target": jnp.asarray(rng.integers(0, 10, size=(bs,), dtype=np.int32)),
    }

    bf16_ips, bf16_mfu = measure(jnp.bfloat16, batch, mesh, bs, ndev)
    fp32_ips, fp32_mfu = measure(jnp.float32, batch, mesh, bs, ndev)

    record = {
        "metric": "cifar10_resnet9_train_images_per_sec",
        "value": round(bf16_ips, 1),
        "unit": "images/sec",
        "vs_baseline": round(bf16_ips / BASELINE_IMAGES_PER_SEC, 4),
        "dtype": "bfloat16",
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "devices": ndev,
    }
    record.update(bf16_mfu)
    record["fp32_images_per_sec"] = round(fp32_ips, 1)
    record["fp32_vs_baseline"] = round(fp32_ips / BASELINE_IMAGES_PER_SEC, 4)
    if "mfu" in fp32_mfu:
        record["fp32_mfu"] = fp32_mfu["mfu"]
    print(json.dumps(record))


if __name__ == "__main__":
    main()
