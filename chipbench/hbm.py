"""Bytes the archive's kernels must move through HBM, from the unpadded
sizes a stripe's manifests record.

Write program, per shard: the int8 codes are read (``n_raw``), the rANS
stream is written and read back by the seal (``n_comp`` each way), and
the sealed body is written (``4 * n_words``); per stripe, P and Q are
written, each as long as the longest body.  Padded bucket rows are
not work, so a program that stops padding gains in these shares; the
kernels' real traffic is never below these counts, so a share of the HBM
peak computed from them cannot pass 100%.
"""

from __future__ import annotations

import json
import os
from typing import Dict, Iterable, Tuple

__all__ = ["seal_bytes", "hbm_peak"]

PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def seal_bytes(stripes: Iterable[Iterable[Tuple[int, int, int]]],
               parity_strips: int = 2) -> int:
    """HBM bytes of sealing stripes given, per stripe, each shard's
    (n_raw, n_comp, n_words)."""
    total = 0
    for shards in stripes:
        shards = list(shards)
        for n_raw, n_comp, n_words in shards:
            total += n_raw + 2 * n_comp + 4 * n_words
        total += parity_strips * 4 * max(w for _, _, w in shards)
    return total


def hbm_peak(device_kind: str) -> float:
    """Published HBM bandwidth of one chip, bytes/s.  A device the table
    does not hold is an error, not a default."""
    with open(PEAKS) as f:
        table = json.load(f)
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {PEAKS}")
    return float(table["devices"][device_kind]["hbm_bytes_per_s"])
