"""PP step-time microbenchmark (VERDICT r2 #6: head out of the tick loop).

Times the (data, pipe) train step at a realistic head size (vocab 32k) on
whatever backend is live (the 8-virtual-device CPU mesh in CI — pipe needs
multiple devices, and the repo has one real chip).  Relative numbers
before/after the deferred-head change are the point, not absolute ms.

Run: XLA_FLAGS=--xla_force_host_platform_device_count=8 python tools/pp_bench.py
"""
from __future__ import annotations

import os, sys, time

if __name__ == "__main__":
    # standalone: virtual 8-device CPU mesh, set before the first jax import
    # (importers — the test suite, tcdp-lint smoke — get no side effects)
    os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
    if "xla_force_host_platform_device_count" not in os.environ["XLA_FLAGS"]:
        os.environ["XLA_FLAGS"] += " --xla_force_host_platform_device_count=8"
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from tpu_compressed_dp.models import transformer as tf
from tpu_compressed_dp.parallel.dp import CompressionConfig
from tpu_compressed_dp.train.optim import SGD
from tpu_compressed_dp.train.state import TrainState
from tpu_compressed_dp.train.pp_step import (
    init_pp_ef_state, make_pp_mesh, make_pp_train_step, stack_layer_params)


def main():
    from tpu_compressed_dp.parallel.mesh import setup_compile_cache

    setup_compile_cache()
    import dataclasses
    cfg = dataclasses.replace(
        tf.tiny_llama(), vocab_size=32768, dim=128, n_layers=4,
        dtype=jnp.float32)
    dp, pp, M = 2, 4, 8
    mesh = make_pp_mesh(dp, pp)
    params = stack_layer_params(tf.init_llama(cfg, jax.random.key(0)))
    comp = CompressionConfig(method=None)
    opt = SGD(lr=1e-3, momentum=0.9)
    state = TrainState.create(params, {}, opt.init(params),
                              init_pp_ef_state(cfg, params, comp, mesh),
                              jax.random.key(1))
    step = make_pp_train_step(cfg, opt, comp, mesh, microbatches=M)
    T, B = 64, dp * M * 2
    rng = np.random.default_rng(0)
    batch = {"input": jnp.asarray(rng.integers(0, cfg.vocab_size, (B, T), dtype=np.int32)),
             "target": jnp.asarray(rng.integers(0, cfg.vocab_size, (B, T), dtype=np.int32))}
    for _ in range(2):  # two compiles (donated layouts)
        state, m = step(state, batch)
        jax.device_get(m)
    t0 = time.perf_counter()
    n = 5
    for _ in range(n):
        state, m = step(state, batch)
    jax.device_get(m)
    dt = (time.perf_counter() - t0) / n
    print(f"vocab={cfg.vocab_size} dim={cfg.dim} pp={pp} dp={dp} M={M} T={T} "
          f"B={B}: step {dt*1e3:.1f} ms  loss={float(m['loss']):.3f}")


if __name__ == "__main__":
    main()
