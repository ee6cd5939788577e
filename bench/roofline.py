"""The work each kernel's problem needs, from its shapes, and the share
of the chip's roofline that a measured kernel time reaches.

Counted: real rows only (never padding), each byte read or written once,
at the width the index stores it. Not counted: the one-hot MXU products
the kernels use to scatter, the extra passes of ``Precision.HIGHEST``,
and intermediate state a kernel re-reads between grid steps. So the
same problem counts the same work whatever implementation runs it, and
an honest time never reads above 100%. All three kernels are bound by
memory: none needs floating-point work that the roofline would see.
"""
from __future__ import annotations

import json
import os
from typing import Dict, Iterable

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")

#: bytes per stored value (float32 / int32 columns, path hash, pid, mask)
WORD = 4
#: DDSketch state beside the bucket counts: zero count, count, total,
#: min, max
SKETCH_SCALARS = 5


def peaks(device_kind: str) -> Dict[str, float]:
    """The chip's published peaks; an unknown kind is an error."""
    with open(PEAKS_FILE) as f:
        table = json.load(f)["kinds"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; add it "
                       f"to {PEAKS_FILE} with its source")
    return table[device_kind]


def hashshard_bytes(path_lengths: Iterable[int]) -> float:
    """Path bytes at their real lengths read, one 4-byte hash written
    per path."""
    lens = list(path_lengths)
    return float(sum(lens) + WORD * len(lens))


def ddsketch_bytes(rows: int, n_streams: int, n_attrs: int,
                   n_principals: int, n_buckets: int) -> float:
    """One aggregate step over ``rows`` real rows: for each attribute and
    principal stream, the value, the principal id and the mask of every
    row read; the (principals x attributes) sketch state written once."""
    read = n_attrs * n_streams * rows * 3 * WORD
    state = n_attrs * n_principals * (n_buckets + SKETCH_SCALARS) * WORD
    return float(read + state)


def roofline_pct(bytes_moved: float, seconds: float,
                 device_kind: str, flops: float = 0.0) -> float:
    """Least time the chip could take for the work, over the time it
    took, in percent. None-safe callers check ``seconds > 0`` first."""
    pk = peaks(device_kind)
    least = max(bytes_moved / pk["hbm_bytes_per_s"],
                flops / pk["bf16_flops"])
    return 100.0 * least / seconds
