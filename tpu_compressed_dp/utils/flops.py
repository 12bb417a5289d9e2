"""Analytic model FLOPs and MFU (model-FLOPs utilisation).

The reference's telemetry stopped at images/sec (`IMAGENET/training/
logger.py:66-68`); MFU normalises that to chip capability so throughput
claims transfer across hardware (VERDICT r2 #3).

Conventions (the standard ones, cf. PaLM appendix B):
  * model FLOPs = the FLOPs of the MODEL's forward+backward only — the
    compression/comm machinery is deliberately excluded (that overhead
    showing up as lost MFU is exactly what the metric is for);
  * backward = 2x forward (two matmuls per forward matmul), so
    ``train = 3 x forward``;
  * MFU is quoted against the chip's peak dense-matmul rate in its native
    matmul precision (bf16 for TPUs) regardless of the activation dtype in
    use — fp32 compute then simply shows as lower MFU.

Forward FLOPs come from XLA's own cost model (``compiled.cost_analysis()``)
of the jitted single-device forward — exact for any architecture (graph nets
included) with no hand-maintained per-layer walk; transformers at sharded
scale use the closed-form ``6N + 12*L*d*s`` per token instead.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

__all__ = [
    "fwd_flops_xla",
    "train_flops_per_step",
    "transformer_train_flops_per_token",
    "chip_peak_flops",
    "mfu",
    "throughput_record",
    "cnn_mfu_record",
    "PEAK_FLOPS_BF16",
]

# Peak dense-matmul TFLOP/s per chip, bf16 (public spec sheets).  Keyed by
# `device_kind` prefix; unknown kinds return None and MFU is omitted rather
# than quoted against a guessed peak.
PEAK_FLOPS_BF16: Dict[str, float] = {
    "TPU v2": 45e12,
    "TPU v3": 123e12,
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,   # v5e
    "TPU v5e": 197e12,
    "TPU v5": 459e12,        # v5p (after the more-specific v5-lite keys)
    "TPU v6 lite": 918e12,   # v6e (Trillium)
    "TPU v6e": 918e12,
}


def fwd_flops_xla(fn: Callable, *args: Any) -> Optional[float]:
    """FLOPs of one call of ``fn(*args)`` per XLA's compiled cost model.

    ``fn`` should be the bare model forward (apply_fn closed over
    hyperparams), NOT the train step — cost analysis of the step would count
    compression, optimizer, and collective work as "model" FLOPs.  Returns
    None where the backend doesn't expose an estimate.
    """
    # lower on abstract shapes: works with donated/deleted buffers and
    # moves no data to the device.  Tracing errors in `fn` propagate — only
    # a missing backend cost model degrades to None.
    abstract = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(jnp.shape(a), jnp.result_type(a)),
        args)
    compiled = jax.jit(fn).lower(*abstract).compile()
    try:
        cost = compiled.cost_analysis()
    except Exception:  # pragma: no cover - backend without cost model
        return None
    if isinstance(cost, list):  # older jax returns one dict per device
        cost = cost[0] if cost else {}
    val = float((cost or {}).get("flops", 0.0))
    return val if val > 0 else None


def train_flops_per_step(fwd_flops: float) -> float:
    """fwd + bwd = 3x fwd (bwd re-derives two matmuls per forward matmul)."""
    return 3.0 * fwd_flops


def transformer_train_flops_per_token(
    n_params: int, n_layers: int, d_model: int, seq_len: int
) -> float:
    """The standard decoder LM accounting (PaLM appendix B): ``6N`` for the
    parameter matmuls (2N fwd, 4N bwd) plus ``12 L d s`` for the attention
    score/value matmuls (QK^T and AV, fwd+bwd, causal factor ignored —
    matching common MFU practice, which makes causal models look slightly
    better, not worse)."""
    return 6.0 * n_params + 12.0 * n_layers * d_model * seq_len


def chip_peak_flops(device=None) -> Optional[float]:
    """Peak bf16 TFLOP/s of ``device`` (default: first local device)."""
    if device is None:
        devs = jax.local_devices()
        if not devs:
            return None
        device = devs[0]
    kind = getattr(device, "device_kind", "") or ""
    # longest-prefix match so "TPU v5 lite" doesn't resolve to "TPU v5"
    best = None
    for prefix, peak in PEAK_FLOPS_BF16.items():
        if kind.startswith(prefix) and (best is None or len(prefix) > best[0]):
            best = (len(prefix), peak)
    return best[1] if best else None


def mfu(model_flops_per_sec: float, device=None) -> Optional[float]:
    """``model_flops_per_sec / chip_peak`` — None off-TPU / unknown chip."""
    peak = chip_peak_flops(device)
    if not peak or model_flops_per_sec <= 0:
        return None
    return model_flops_per_sec / peak


def throughput_record(fwd_flops: Optional[float], steps_per_sec: float,
                      *, examples_per_sec: Optional[float] = None,
                      tokens_per_sec: Optional[float] = None
                      ) -> Dict[str, float]:
    """The registry-named throughput/MFU telemetry for one window.

    ``fwd_flops`` is the PER-CHIP forward cost of one step (from
    :func:`fwd_flops_xla` at the per-chip batch shape, or a closed form
    divided by chip count); shared by all three harness epilogues so
    examples/s, tokens/s, TFLOP/s-per-chip and MFU are computed the same
    way everywhere.  MFU is omitted off-TPU (unknown peak), TFLOPs when the
    backend exposes no cost model."""
    rec: Dict[str, float] = {}
    if examples_per_sec is not None:
        rec["throughput/examples_per_sec"] = examples_per_sec
    if tokens_per_sec is not None:
        rec["throughput/tokens_per_sec"] = tokens_per_sec
    if fwd_flops is None or steps_per_sec <= 0:
        return rec
    per_chip = train_flops_per_step(fwd_flops) * steps_per_sec
    rec["throughput/model_tflops_per_chip"] = per_chip / 1e12
    u = mfu(per_chip)
    if u is not None:
        rec["throughput/mfu"] = u
    return rec


def cnn_mfu_record(apply_fn, params, batch_stats, input_shape,
                   steps_per_sec: float) -> Dict[str, float]:
    """The benchmark-record MFU fields for a CNN-style ``apply_fn`` (the
    epilogue of bench/sweep.py): forward FLOPs from the
    XLA cost model at the given per-chip input shape, train = 3x fwd at the
    measured step rate, ``mfu`` vs the chip's bf16 peak.  Empty dict where
    the backend exposes no cost model; ``mfu`` omitted off-TPU."""
    fwd = fwd_flops_xla(
        lambda p, s, x: apply_fn(p, s, x, True, {}),
        params, batch_stats,
        jnp.zeros(input_shape, jnp.float32))
    if fwd is None:
        return {}
    per_chip = train_flops_per_step(fwd) * steps_per_sec
    rec = {"model_tflops_per_sec_per_chip": round(per_chip / 1e12, 3)}
    u = mfu(per_chip)
    if u is not None:
        rec["mfu"] = round(u, 4)
    return rec
