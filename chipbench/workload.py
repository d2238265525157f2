"""Seed-deterministic camera-fleet traffic: GOP sizes, novelty, salience
features and GOP payload bytes.

Copied from the program's ``benchmarks/ingest_workload.py`` (same size
model: lognormal around the deployment's median GOP, clipped, whole
uint32 words) and extended so that it serves a benchmark, whose yardstick
the program may not move:

* every seed gets the same GOPs, only in another order: each run of
  ``SIZE_STRATA`` consecutive GOPs holds the lognormal's stratified
  quantiles once as sizes, each with a salience feature of its own that
  travels with it.  A seed changes which GOP comes when, and so which
  GOPs share a stripe, and not how much work a run does;
* payload bytes come from a pool made once per run and reused cyclically
  by capture index, drawn from the deployment's measured codec symbol
  histogram, so the on-device rANS coder sees the compression ratio of
  real codec output without the codec running in the benchmark;
* every GOP carries a novelty score of its own, drawn from the seed
  (the journal's catalog record names a GOP by stream, novelty and
  size).

Nothing here imports the program or JAX.
"""

from __future__ import annotations

from statistics import NormalDist
from typing import Dict, NamedTuple

import numpy as np

__all__ = ["Gop", "Workload", "gop_median_bytes", "symbol_table"]

# sizes are drawn from this many stratified quantiles, then permuted
SIZE_STRATA = 1024
# inverse-CDF resolution of the symbol table (16-bit uniforms)
TABLE_BITS = 16


class Gop(NamedTuple):
    """One recorded GOP, by its index in capture order."""

    index: int
    stream: int
    seq: int
    nbytes: int
    novelty: float


def gop_median_bytes(cfg: Dict) -> int:
    """Codec payload bytes of a median GOP of the deployment."""
    n = int(cfg["bytes_per_pixel_frame"] * cfg["gop_frames"]
            * cfg["height"] * cfg["width"])
    return n - n % 4


def symbol_table(counts: Dict[str, int]) -> np.ndarray:
    """(2**TABLE_BITS,) uint8 inverse CDF of a byte histogram given as
    ``{"<byte value>": count}``: a uniform 16-bit index maps to a byte
    with the histogram's probabilities (to 1/65536)."""
    p = np.zeros(256, np.float64)
    for k, v in counts.items():
        p[int(k)] = float(v)
    if p.sum() <= 0:
        raise ValueError("symbol histogram is empty")
    cdf = np.cumsum(p / p.sum())
    n = 1 << TABLE_BITS
    u = (np.arange(n) + 0.5) / n
    return np.minimum(np.searchsorted(cdf, u), 255).astype(np.uint8)


def _sizes(cfg: Dict) -> np.ndarray:
    """The deployment's GOP-size multiset: stratified lognormal quantiles,
    clipped to [min_frac x median, max bytes], whole uint32 words."""
    med = gop_median_bytes(cfg)
    z = np.array([NormalDist().inv_cdf((i + 0.5) / SIZE_STRATA)
                  for i in range(SIZE_STRATA)])
    b = np.exp(np.log(med) + float(cfg["gop_sigma"]) * z)
    b = np.clip(b, float(cfg["gop_min_frac"]) * med, int(cfg["gop_max_bytes"]))
    b = b.astype(np.int64)
    return b - b % 4


class Workload:
    """The deployment's GOPs for one seed.

    ``cfg`` is the configuration file's dict, ``traffic`` the mix's.  The
    GOP at capture index ``g`` comes from stream ``g % cameras`` (every
    camera's recording, interleaved as captured); its size, novelty and
    feature come from the seed.
    """

    def __init__(self, cfg: Dict, traffic: Dict, seed: int, *,
                 n_gops: int = 1 << 16):
        self.cfg = cfg
        self.traffic = traffic
        self.seed = int(seed)
        self.cameras = int(cfg["cameras"])
        self.feature_dim = int(cfg["feature_dim"])
        rng = np.random.default_rng([self.seed, 0x5A11E7])
        sizes = _sizes(cfg)
        # block b holds SIZE_STRATA (size, feature) pairs that do not
        # depend on the seed; the seed orders each block
        blocks = -(-n_gops // SIZE_STRATA)
        perm = [rng.permutation(SIZE_STRATA) for _ in range(blocks)]
        self.sizes = np.concatenate([sizes[p] for p in perm])[:n_gops]
        self.features = np.concatenate([
            np.random.default_rng([0xFEA7, b]).standard_normal(
                (SIZE_STRATA, self.feature_dim)).astype(np.float32)[p]
            for b, p in enumerate(perm)])[:n_gops]
        self.novelty = rng.random(n_gops)
        self.streams = np.arange(n_gops) % self.cameras
        self.seqs = np.arange(n_gops) // self.cameras
        self._pool = None

    @property
    def n_gops(self) -> int:
        return int(self.sizes.shape[0])

    def gop(self, g: int) -> Gop:
        if not 0 <= g < self.n_gops:
            raise IndexError(f"GOP {g} beyond the {self.n_gops} generated")
        return Gop(g, int(self.streams[g]), int(self.seqs[g]),
                   int(self.sizes[g]), float(self.novelty[g]))

    # ------------------------------------------------------------ payloads
    @property
    def pool(self) -> np.ndarray:
        """(pool_gops, max GOP bytes) int8, made on first use from the
        seed and the deployment's codec symbol histogram."""
        if self._pool is None:
            rng = np.random.default_rng([self.seed, 0xB17E5])
            table = symbol_table(self.cfg["symbol_counts"])
            idx = rng.integers(
                0, 1 << TABLE_BITS,
                size=(int(self.cfg["pool_gops"]), int(self.cfg["gop_max_bytes"])),
                dtype=np.uint16,
            )
            self._pool = table[idx].view(np.int8)
        return self._pool

    def payload(self, g: int) -> np.ndarray:
        """GOP g's codec payload (a view into the pool)."""
        pool = self.pool
        return pool[g % pool.shape[0], : int(self.sizes[g])]
