"""Operations and bytes the algorithm needs, from the configuration's shapes,
and the table of published peaks.

Kept with the benchmark so that no PR which claims a gain can change the
numerator of ``mfu`` or of a kernel's roofline share.  ``model`` is the
configuration's reference module (``cfg["reference"]``).
"""

from __future__ import annotations

import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> dict:
    """Published per-chip peaks; a device that is not in the table is an error."""
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no published peaks for device_kind {device_kind!r} in "
                       "benchmark/peaks.json")
    return table[device_kind]


def train_flops_per_sample(model, cfg) -> float:
    """Forward plus backward (two products per forward product): 3 x forward.
    Recomputed operations do not count."""
    return 3.0 * model.forward_flops_per_sample(cfg)


def leaf_sizes(model, cfg) -> list:
    """Element count of every parameter tensor, in tree order."""
    import jax

    sizes = []
    for shape in jax.tree.leaves(model.param_shapes(cfg),
                                 is_leaf=lambda s: isinstance(s, tuple)):
        n = 1
        for d in shape:
            n *= d
        sizes.append(n)
    return sizes


def select_pack_min_bytes(sizes, keep_count, min_elems: int):
    """(bytes, launches) of one step's select+pack at its least traffic: every
    float32 input element read once and the payload (value + index) written
    once, over the leaves large enough to take the kernel.  ``keep_count(n)``
    is the sync semantics' count of coordinates sent of ``n``."""
    big = [n for n in sizes if n >= min_elems]
    return sum(4 * n + 8 * keep_count(n) for n in big), len(big)
