"""Standing chip check: the compressed-DP trainer, end to end, on the TPU.

``python chip_smoke.py`` drives the system's main path once through the entry
points a user calls — ``harness.dawn.main`` and ``harness.lm.main``, in this
one process — at the full width of the flagship model (ResNet-9, bf16, batch
512, synthetic data; depth and step count are what is cut), and runs every
Pallas kernel the ``auto`` dispatch can reach once, compiled, at a real leaf
size against the XLA chain it replaces.  Phases:

  dawn_dense       ResNet-9 dense
  dawn_topk_wire   layer-wise wire Top-K 1 % + error feedback
  dawn_terngrad    layer-wise wire TernGrad
  dawn_sharded     entire-model wire Top-K over the owner-sharded transport
                   (more than one device only)
  lm_dense         the 125 M decoder (flash attention forward and backward)
  memory_balance   per-device peak bytes within 2x of each other
  kernels          topk_threshold, fused_sparsify, fused_select_pack,
                   fused_bucket_route, terngrad/qsgd pack + quantize, the
                   hardware-PRNG uniform fill, flash attention fwd/bwd

It fails, with a non-zero exit code and no result line, unless JAX reports a
TPU, and when any phase fails; a failed phase does not stop the later ones,
so one run names everything that is broken.  The last line of a passing
run's standard output is one JSON object naming the device as JAX reports
it.  Step times and MFU printed on the way are set-up facts of a dozen-step
run, not benchmark metrics.
"""

from __future__ import annotations

import importlib.metadata
import json
import math
import os
import sys
import tempfile
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np

import tpu_compressed_dp
from tpu_compressed_dp.parallel.mesh import setup_compile_cache

LEAF_N = 2_359_296        # ResNet-9's largest leaves (512 x 512 x 3 x 3)
FLAT_N = 6_573_120        # the whole ResNet-9 gradient, entire-model granularity
RATIO = 0.01

BATCH, STEPS_PER_EPOCH, EPOCHS = 512, 4, 3
DAWN_ARGV = ["--synthetic", "--synthetic_n", str(BATCH * STEPS_PER_EPOCH),
             "--epochs", str(EPOCHS), "--batch_size", str(BATCH),
             "--dtype", "bfloat16", "--channels_scale", "1.0"]
TOPK_ARGV = ["--method", "topk", "--ratio", str(RATIO), "--mode", "wire",
             "--error_feedback"]
LM_ARGV = ["--dim", "768", "--layers", "12", "--heads", "12", "--kv_heads", "4",
           "--ffn", "2048", "--vocab", "32000", "--seq_len", "1024",
           "--global_batch", "8", "--steps", "12", "--log_every", "3",
           "--warmup_steps", "2"]


class SmokeFailure(Exception):
    """One check of one phase did not hold."""


def require(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


# ------------------------------------------------------------ compile clock

_COMPILES: list = []      # (fun_name, seconds) of every backend compile


def _on_compile(event: str, duration: float, **kw) -> None:
    if event.endswith("backend_compile_duration"):
        _COMPILES.append((kw.get("fun_name", "?"), duration))


class Phase:
    """Wall and compile seconds of one phase; the failure, if it had one."""

    def __init__(self, name: str):
        self.name, self.error, self.facts = name, None, {}

    def __enter__(self):
        self._t0, self._c0 = time.time(), len(_COMPILES)
        print(f"--- phase {self.name}", flush=True)
        return self

    def __exit__(self, etype, err, tb):
        self.wall = time.time() - self._t0
        compiles = _COMPILES[self._c0:]
        self.compile_s = sum(s for _, s in compiles)
        if err is not None and not isinstance(err, Exception):
            return False                     # KeyboardInterrupt, SystemExit
        if err is not None:
            self.error = f"{type(err).__name__}: {err}"
            if not isinstance(err, SmokeFailure):
                traceback.print_exception(etype, err, tb)
        big = ", ".join(f"{n} {s:.1f}s" for n, s in compiles if s >= 1.0)
        facts = " ".join(f"{k}={v}" for k, v in self.facts.items())
        print(f"phase {self.name}: {'FAILED ' + self.error if self.error else 'ok'}"
              f" wall={self.wall:.1f}s compile={self.compile_s:.1f}s"
              f" compiles={len(compiles)}" + (f" [{big}]" if big else "")
              + (f" {facts}" if facts else ""), flush=True)
        return True


# ------------------------------------------------------------ harness phases

def check_mfu(summary: dict, device) -> float:
    """The summary's ``mfu``; a chip the peak table does not know is an
    error here, not an omitted field."""
    from tpu_compressed_dp.utils import flops

    if "mfu" not in summary:
        known = flops.chip_peak_flops(device) is not None
        raise SmokeFailure(
            "summary carries no 'mfu': device_kind "
            f"{device.device_kind!r} is "
            + ("in" if known else "NOT in")
            + f" utils/flops.PEAK_FLOPS_BF16 {sorted(flops.PEAK_FLOPS_BF16)}")
    mfu = float(summary["mfu"])
    require(math.isfinite(mfu) and 0.0 < mfu < 1.0, f"mfu {mfu} not in (0, 1)")
    return mfu


def check_losses(losses: list, *, must_fall: bool) -> None:
    require(len(losses) >= 2, f"expected several rows, got {losses}")
    require(all(math.isfinite(x) for x in losses), f"non-finite loss in {losses}")
    if must_fall:
        require(losses[-1] < losses[0],
                f"dense loss did not fall: {losses[0]} -> {losses[-1]}")


def _rows(events_path: str, kind: str) -> list:
    from tpu_compressed_dp.obs.export import read_events

    return [r["metrics"] for r in read_events(events_path) if r["kind"] == kind]


def run_dawn(phase: Phase, extra: list, workdir: str, device, *,
             dense: bool = False, sent_frac=None, wire_frac=None) -> None:
    from tpu_compressed_dp.harness import dawn

    out = os.path.join(workdir, phase.name)
    events = os.path.join(out, "events.jsonl")
    summary = dawn.main(DAWN_ARGV + extra + ["--log_dir", out,
                                             "--events", events])
    rows = _rows(events, "epoch")
    require(len(rows) == EPOCHS, f"expected {EPOCHS} epoch rows, got {len(rows)}")
    check_losses([r["train loss"] for r in rows], must_fall=dense)
    require(all(math.isfinite(r["test loss"]) for r in rows),
            "non-finite test loss")
    phase.facts["loss"] = "->".join(f"{r['train loss']:.3f}" for r in rows)
    phase.facts["mfu"] = check_mfu(summary, device)
    # epoch 1 holds the compile; the last epoch is four steady steps, host
    # batch preparation included
    phase.facts["step_ms"] = round(
        rows[-1]["train time"] / STEPS_PER_EPOCH * 1e3, 2)
    phase.facts["img/s"] = rows[-1]["img/s"]
    for key, bounds in (("sent frac", sent_frac), ("wire frac", wire_frac)):
        if bounds is not None:
            got = summary.get(key)
            require(got is not None and bounds[0] <= got <= bounds[1],
                    f"{key} {got} outside {bounds}")
            phase.facts[key.replace(" ", "_")] = round(got, 5)


def run_lm(phase: Phase, workdir: str, device) -> None:
    from tpu_compressed_dp.harness import lm

    events = os.path.join(workdir, phase.name, "events.jsonl")
    summary = lm.main(LM_ARGV + ["--events", events])
    rows = _rows(events, "step")
    check_losses([r["loss"] for r in rows], must_fall=True)
    phase.facts["loss"] = "->".join(f"{r['loss']:.3f}" for r in rows)
    phase.facts["mfu"] = check_mfu(summary, device)
    phase.facts["tok/s"] = summary["tok/s"]


def flat_shard_plan(world: int):
    """``(keep, plan)`` of the dawn_sharded phase's one group: entire-model
    Top-K 1 % of ResNet-9 at the default capacity factors."""
    from tpu_compressed_dp.ops import compressors, wire_sharded
    from tpu_compressed_dp.parallel.dp import CompressionConfig

    cfg = CompressionConfig(method="topk", mode="wire", transport="sharded")
    keep = compressors.topk_keep_count(FLAT_N, RATIO)
    return keep, wire_sharded.make_shard_plan(
        FLAT_N, keep, world, 1, cfg.shard_route_factor,
        cfg.shard_return_factor)


def sharded_route_is_fused(world: int) -> bool:
    """Whether the dawn_sharded phase reaches `fused_bucket_route` (its gate
    is a trace-time size check)."""
    from tpu_compressed_dp.ops import kernels

    keep, plan = flat_shard_plan(world)
    return kernels.use_bucket_route(keep, world, plan.cap_dest)


def check_memory_balance(devices) -> None:
    """Trivially true on one device; on several, no device may hold what
    belongs to all (a state built on device 0 and never laid out would)."""
    peaks = [d.memory_stats()["peak_bytes_in_use"] for d in devices]
    print("peak_bytes_in_use per device:", peaks)
    require(max(peaks) <= 2 * min(peaks),
            f"per-device peak bytes differ by more than 2x: {peaks}")


# ------------------------------------------------------------- kernel phase

def _grad_like(n: int, dtype, seed: int):
    return jax.random.normal(jax.random.key(seed), (n,), jnp.float32
                             ).astype(dtype)


def _same(a, b, what: str) -> None:
    a, b = np.asarray(a), np.asarray(b)
    require(a.shape == b.shape and a.dtype == b.dtype,
            f"{what}: {a.dtype}{a.shape} vs {b.dtype}{b.shape}")
    require(np.array_equal(a, b),
            f"{what}: {int(np.sum(a != b))} of {a.size} elements differ")


def _kth_largest(mag, keep: int) -> np.float32:
    # on the host: XLA's top_k at these sizes costs half a minute to compile
    m = np.asarray(mag)
    return np.partition(m, m.size - keep)[m.size - keep]


def k_topk_threshold(n: int) -> None:
    from tpu_compressed_dp.ops import compressors, kernels

    keep = compressors.topk_keep_count(n, RATIO)
    mag = jnp.abs(_grad_like(n, jnp.float32, 1))
    t = jax.jit(lambda m: kernels.topk_threshold(m, keep))(mag)
    exact = _kth_largest(mag, keep)
    count = int(jnp.sum(mag >= t))
    require(float(t) <= float(exact), f"threshold {t} above the k-th {exact}")
    require(keep <= count <= keep + 2, f"count {count} for keep {keep}")


def k_fused_sparsify(n: int) -> None:
    from tpu_compressed_dp.ops import kernels

    acc = _grad_like(n, jnp.float32, 2)
    t = jnp.float32(2.5)
    comp, ef, count = jax.jit(kernels.fused_sparsify)(acc, t)
    keep = jnp.abs(acc) >= t
    ref = jnp.where(keep, acc, 0.0)
    _same(comp, ref, "comp")
    _same(ef, acc - ref, "ef")
    require(int(count) == int(jnp.count_nonzero(ref)), "survivor count")


def k_select_pack(n: int, dtype) -> None:
    from tpu_compressed_dp.ops import compressors, kernels, wire

    keep = compressors.topk_keep_count(n, RATIO)
    flat = _grad_like(n, dtype, 3)
    mag = jnp.abs(flat).astype(jnp.float32)
    t = jnp.float32(_kth_largest(mag, keep))
    vals, idx, count = jax.jit(
        lambda f, t: kernels.fused_select_pack(f, t, keep))(flat, t)
    mask = mag >= t
    ref_idx = jax.jit(lambda m: wire.packed_indices_from_mask(m, keep))(mask)
    _same(idx, ref_idx, "indices")
    _same(vals, flat[ref_idx], "values")
    require(int(count) == int(jnp.sum(mask)), "survivor count")


def k_bucket_route(dtype) -> None:
    from tpu_compressed_dp.ops import kernels, wire_sharded

    world = 4             # a local kernel: the world is only a parameter
    keep, plan = flat_shard_plan(world)
    cap, shard_n = plan.cap_dest, plan.shard_n
    require(kernels.use_bucket_route(keep, world, cap),
            "the auto gate does not dispatch this size")
    # ascending indices, crowded into the first shard so its bucket clips
    rng = np.random.default_rng(4)
    crowd = np.sort(rng.choice(shard_n, cap + 1000, replace=False))
    rest = np.sort(rng.choice(np.arange(shard_n, FLAT_N),
                              keep - crowd.size, replace=False))
    idx = jnp.asarray(np.concatenate([crowd, rest]), jnp.int32)
    vals = _grad_like(keep, dtype, 5)
    slot, _, dest = wire_sharded._per_dest_slots(idx, None, plan)
    bvals, bidx = jax.jit(lambda v, i, d: kernels.fused_bucket_route(
        v, i, d, world, cap, shard_n))(vals, idx, dest)
    ref_v = jnp.zeros((world * cap + 1,), dtype).at[slot].add(vals)[:-1]
    ref_i = jnp.full((world * cap + 1,), shard_n, jnp.int32).at[slot].set(
        idx - dest * shard_n)[:-1]
    _same(bvals, ref_v.reshape(world, cap), "bucket values")
    _same(bidx, ref_i.reshape(world, cap), "bucket indices")


def _check_dither(run, decode, g) -> None:
    """The hardware-PRNG kernels: deterministic per key, another stream for
    another key, and an unbiased estimate of ``g``."""
    a, b, c = run(jax.random.key(7)), run(jax.random.key(7)), run(jax.random.key(8))
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        _same(x, y, "same key, two calls")
    require(any(not np.array_equal(np.asarray(x), np.asarray(y))
                for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(c))),
            "another key drew the same stream")
    est, scale = decode(a)
    err = np.asarray(est, np.float64) - np.asarray(g, np.float64)
    bound = 4.0 * float(scale) / math.sqrt(g.shape[0])
    require(abs(err.mean()) < bound,
            f"biased: mean error {err.mean():.3e}, bound {bound:.3e}")


def k_terngrad_pack(n: int) -> None:
    from tpu_compressed_dp.ops import kernels, wire

    g = _grad_like(n, jnp.float32, 9)
    run = jax.jit(lambda k: kernels.terngrad_pack(g, k))

    def decode(out):
        packed, scale = out
        require(packed.dtype == jnp.uint8 and packed.shape == (-(-n // 4),),
                f"wire bytes {packed.dtype}{packed.shape}")
        codes = np.asarray(packed)[:, None] >> np.array([0, 2, 4, 6]) & 3
        require(codes.max() <= 2, "2-bit code 3 on the wire")
        require(float(scale) == float(jnp.max(jnp.abs(g))), "scale != max|g|")
        return wire.unpack_ternary(packed, n).astype(jnp.float32) * scale, scale

    _check_dither(run, decode, g)


def k_qsgd_pack(n: int) -> None:
    from tpu_compressed_dp.ops import kernels, wire

    g = _grad_like(n, jnp.float32, 10)
    run = jax.jit(lambda k: kernels.qsgd_pack(g, k, qstates=255))

    def decode(out):
        mags, signs, scale = out
        require(mags.shape == (n,) and signs.shape == (-(-n // 8),),
                f"wire bytes {mags.shape} {signs.shape}")
        lv = wire.qsgd_wire_unpack((mags, signs), n, 255)
        nz = np.asarray(lv) != 0
        require(np.array_equal(np.sign(np.asarray(lv))[nz],
                               np.sign(np.asarray(g))[nz]), "level signs")
        return lv * scale, scale

    _check_dither(run, decode, g)


def k_quantize_levels(n: int) -> None:
    from tpu_compressed_dp.ops import kernels

    g = _grad_like(n, jnp.float32, 11)
    tern = jax.jit(lambda k: kernels.terngrad_quantize(g, k))
    qsgd = jax.jit(lambda k: kernels.qsgd_quantize(g, k, qstates=255))

    def decode(out):
        levels, scale = out
        return levels.astype(jnp.float32) * scale, scale

    _check_dither(tern, decode, g)
    require(set(np.unique(np.asarray(tern(jax.random.key(7))[0]))) <= {-1, 0, 1},
            "ternary levels outside {-1, 0, 1}")
    _check_dither(qsgd, decode, g)


def k_uniform(n: int) -> None:
    from tpu_compressed_dp.ops import kernels

    run = jax.jit(lambda k: kernels.uniform(k, n))
    u = np.asarray(run(jax.random.key(12)), np.float64)
    _same(run(jax.random.key(12)), u.astype(np.float32), "same key, two calls")
    require(u.min() >= 0.0 and u.max() < 1.0, "draw outside [0, 1)")
    require(abs(u.mean() - 0.5) < 4 / math.sqrt(12 * n), f"mean {u.mean()}")
    require(abs(u.var() - 1 / 12) < 1e-3, f"variance {u.var()}")


def k_flash(dtype, tol: float) -> None:
    from tpu_compressed_dp.ops.flash_attention import flash_causal_attention

    b, h, t, d = 2, 12, 1024, 64                     # the lm_dense head shape
    q, k, v, w = (jax.random.normal(key, (b, h, t, d), jnp.float32).astype(dtype)
                  for key in jax.random.split(jax.random.key(13), 4))

    def reference(q, k, v):
        q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                       precision=jax.lax.Precision.HIGHEST) / math.sqrt(d)
        s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
        return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), v,
                          precision=jax.lax.Precision.HIGHEST)

    def both(fn):
        loss = lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32) * w)
        return jax.jit(lambda q, k, v: (
            fn(q, k, v), jax.grad(loss, argnums=(0, 1, 2))(q, k, v)))(q, k, v)

    (o, grads), (o_ref, grads_ref) = both(flash_causal_attention), both(reference)
    for name, got, ref in zip(("out", "dq", "dk", "dv"),
                              (o,) + grads, (o_ref,) + grads_ref):
        got, ref = np.asarray(got, np.float32), np.asarray(ref, np.float32)
        err = float(np.max(np.abs(got - ref)) / np.max(np.abs(ref)))
        require(err <= tol, f"{name}: relative error {err:.2e} > {tol}")


def kernel_checks() -> list:
    bf16, f32 = jnp.bfloat16, jnp.float32
    return [
        ("topk_threshold leaf", lambda: k_topk_threshold(LEAF_N)),
        ("topk_threshold flat", lambda: k_topk_threshold(FLAT_N)),
        ("fused_sparsify leaf", lambda: k_fused_sparsify(LEAF_N)),
        ("fused_sparsify flat", lambda: k_fused_sparsify(FLAT_N)),
        ("fused_select_pack leaf f32", lambda: k_select_pack(LEAF_N, f32)),
        ("fused_select_pack leaf bf16", lambda: k_select_pack(LEAF_N, bf16)),
        ("fused_select_pack flat f32", lambda: k_select_pack(FLAT_N, f32)),
        ("fused_bucket_route f32", lambda: k_bucket_route(f32)),
        ("fused_bucket_route bf16", lambda: k_bucket_route(bf16)),
        ("terngrad_pack leaf", lambda: k_terngrad_pack(LEAF_N)),
        ("qsgd_pack leaf", lambda: k_qsgd_pack(LEAF_N)),
        ("terngrad/qsgd_quantize leaf", lambda: k_quantize_levels(LEAF_N)),
        ("uniform flat", lambda: k_uniform(FLAT_N)),
        ("flash fwd/bwd f32", lambda: k_flash(f32, 5e-3)),
        ("flash fwd/bwd bf16", lambda: k_flash(bf16, 3e-2)),
    ]


def run_kernels() -> None:
    failed = []
    for name, check in kernel_checks():
        t0 = time.time()
        try:
            check()
        except Exception as err:  # noqa: BLE001 - each kernel is reported
            failed.append(name)
            msg = str(err)
            print(f"kernel {name}: FAILED {type(err).__name__}: "
                  f"{msg[:3000]}", flush=True)
        else:
            print(f"kernel {name}: ok {time.time() - t0:.1f}s", flush=True)
    require(not failed, f"kernels failed: {failed}")


# -------------------------------------------------------------------- main

def main() -> int:
    here = os.path.dirname(os.path.abspath(__file__))
    package = os.path.dirname(os.path.abspath(tpu_compressed_dp.__file__))
    if package != os.path.join(here, "tpu_compressed_dp"):
        print(f"chip_smoke: checks the checkout it sits in ({here}), but "
              f"tpu_compressed_dp was imported from {package}",
              file=sys.stderr)
        return 2
    cache_dir = setup_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU: platform={dev.platform!r} "
              f"device_kind={dev.device_kind!r} count={len(devices)}",
              file=sys.stderr)
        return 2
    print(f"device: platform={dev.platform} device_kind={dev.device_kind!r} "
          f"count={len(devices)}; jax {jax.__version__} jaxlib "
          f"{importlib.metadata.version('jaxlib')} libtpu "
          f"{importlib.metadata.version('libtpu')}; compile cache {cache_dir}",
          flush=True)
    jax.monitoring.register_event_duration_secs_listener(_on_compile)

    world = len(devices)
    phases = []

    def phase(name):
        phases.append(Phase(name))
        return phases[-1]

    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as workdir:
        with phase("dawn_dense") as ph:
            run_dawn(ph, [], workdir, dev, dense=True)
        with phase("dawn_topk_wire") as ph:
            # every leaf keeps ceil(1 % of its elements): a hair over 1 %
            run_dawn(ph, ["--compress", "layerwise"] + TOPK_ARGV, workdir,
                     dev, sent_frac=(RATIO, 1.02 * RATIO))
        with phase("dawn_terngrad") as ph:
            # 2 bits of 32 an element, plus the scales
            run_dawn(ph, ["--compress", "layerwise", "--method", "terngrad",
                          "--mode", "wire"], workdir, dev,
                     wire_frac=(2 / 32, 1.02 * 2 / 32))
        if world > 1:
            with phase("dawn_sharded") as ph:
                ph.facts["fused_route"] = sharded_route_is_fused(world)
                # the selection crowds into a few layers, so the owners'
                # fixed-capacity buckets clip most of it back into the EF
                # residual (a fifth of the 1 % travelled at W = 4)
                run_dawn(ph, ["--compress", "entiremodel", "--transport",
                              "sharded"] + TOPK_ARGV, workdir, dev,
                         sent_frac=(0.05 * RATIO, 1.02 * RATIO))
        with phase("lm_dense") as ph:
            run_lm(ph, workdir, dev)
        # before the kernel checks, which run on the first device alone
        with phase("memory_balance"):
            check_memory_balance(devices)
        with phase("kernels"):
            run_kernels()

    failed = [p.name for p in phases if p.error]
    print("phases: " + " ".join(
        f"{p.name}={'FAILED' if p.error else 'ok'}" for p in phases))
    print("compile seconds: " + " ".join(
        f"{p.name}={p.compile_s:.1f}" for p in phases))
    if failed:
        print(f"chip_smoke FAILED in: {', '.join(failed)}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
