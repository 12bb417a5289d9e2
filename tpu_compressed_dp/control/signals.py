"""Deterministic signal models feeding the controller.

The controller equalizes two per-update quantities: **comm time** (what the
sync costs) against the **hideable-compute budget** (how much of that cost
the ``sync_overlap`` chunk schedule can bury under the remaining backward
pass).  This module converts what the system already measures into those two
numbers — and nothing here reads a clock: the 'modeled' path is a pure
function of the engines' analytic billed bits, and the 'measured' path takes
wall-times the HARNESS observed (StepTimeline) as plain arguments.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

from tpu_compressed_dp.control.config import ControlConfig

__all__ = ["WindowSignals", "modeled_comm_ms", "hideable_budget_ms",
           "billed_signal_bits"]


@dataclasses.dataclass(frozen=True)
class WindowSignals:
    """One tick's per-update signals, as the harness hands them to
    :meth:`~tpu_compressed_dp.control.controller.Controller.tick`."""

    bits: float       # billed wire bits per update (``comm/sent_bits`` mean)
    comm_ms: float    # comm-time signal per update, ms
    budget_ms: float  # hideable-compute budget per update, ms


def modeled_comm_ms(bits_per_update: float, bandwidth_mbps: float) -> float:
    """Analytic per-update comm time: billed bits over the configured wire
    bandwidth.  ``bits / (Mbit/s)`` = microseconds; divide by 1e3 for ms.

    This is the replay-deterministic signal: ``comm/sent_bits`` is computed
    analytically inside the engines (``parallel/dp.py``), so the same run
    replayed — crash, resume, chaos — models the identical comm time.
    """
    return float(bits_per_update) / (float(bandwidth_mbps) * 1e3)


def billed_signal_bits(comm_means, pods: int = 1) -> float:
    """The billed-bits series the modeled signal prices: on a flat mesh the
    whole ``comm/sent_bits``; on a 2-level topology (``pods > 1``) the
    DCN-billed share (``comm/sent_bits_dcn`` plus any flat whole-world
    collectives, which span the slow fabric too) — the inter-pod link is
    the binding constraint a cross-pod bandwidth budget is set against,
    and pricing intra-pod ICI payloads at DCN bandwidth would drive the
    controller to over-compress by orders of magnitude.

    ``comm_means`` is a ``comm/*`` metrics dict of per-update means.
    Deterministic: a pure function of the engines' analytic billed bits.
    """
    total = float(comm_means.get("comm/sent_bits", 0.0))
    if pods <= 1:
        return total
    ici = float(comm_means.get("comm/sent_bits_ici", 0.0))
    return total - ici


def hideable_budget_ms(cfg: ControlConfig, *,
                       compute_ms: Optional[float] = None,
                       hideable_fraction: float = 1.0) -> float:
    """The per-update compute budget comm should be tuned to fit inside.

    ``cfg.budget_ms > 0`` pins it (the CPU/CI path, and any deployment that
    calibrated the budget offline).  Otherwise the budget is the measured
    per-update compute time scaled by the overlap schedule's hideable
    fraction (:func:`tpu_compressed_dp.parallel.overlap.hideable_byte_fraction`
    — the serial head chunk of the pipeline can't hide, so only that
    fraction of the sync genuinely overlaps compute).
    """
    if cfg.budget_ms > 0.0:
        return float(cfg.budget_ms)
    if compute_ms is None:
        raise ValueError(
            "budget_ms=0 needs a measured compute_ms to derive the budget "
            "from (pass --adaptive_budget_ms, or use signal='measured' with "
            "a timeline)")
    return float(compute_ms) * float(hideable_fraction)


def mean_or_zero(values: Sequence[float]) -> float:
    """Mean of a possibly-empty sequence (0.0 when empty) — tick inputs for
    epochs where every step was skipped."""
    vals = list(values)
    return sum(vals) / len(vals) if vals else 0.0
