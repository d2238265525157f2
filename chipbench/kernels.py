"""The programs that hold the archive's kernels, as the device trace
names their executions, grouped by path (``kernels.json``: the write
path's ``jit__fused_core`` runs ``rans_histogram``, ``rans_tables``,
``rans_encode``, ``rans_pack`` and ``seal_stripes``; a later path or
program is a new name there), and the sizes a stored shard's manifest
records."""

from __future__ import annotations

import json
import os
from typing import List, Tuple

__all__ = ["names", "shard_sizes"]

TABLE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "kernels.json")


def names(group: str) -> List[str]:
    """Substrings of the program names of one path ("write")."""
    with open(TABLE) as f:
        return list(json.load(f)[group])


def shard_sizes(block) -> Tuple[int, int, int]:
    """(n_raw, n_comp, n_words) of one stored shard."""
    em = block.manifest["entropy"]
    return int(em["n_raw"]), int(em["n_comp"]), int(block.sealed.n_valid_u32)
