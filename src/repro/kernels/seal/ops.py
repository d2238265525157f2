"""Public wrappers for the fused seal datapath: padding, dispatch, accounting.

``seal_stripe`` / ``unseal_stripe`` accept ragged per-shard payloads, pad
them to the kernel's (R, 512)-int8 tile grid, and dispatch either the fused
Pallas kernel (one launch per stripe) or the staged jnp oracle
(``use_pallas=False``).  Both paths are bit-identical: same sealed bodies,
same P/Q parity, zero-padded tails.
"""

from __future__ import annotations

import functools
from typing import List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.archival.raid import gf_pow_gen
from repro.kernels import (
    as_payload_list,
    host_prefixes,
    stack_rows,
    use_interpret,
)
from repro.kernels.seal import ref as _ref
from repro.kernels.seal.seal import (
    LANES,
    R_TILE,
    ROW_BYTES,
    seal_stripe_pallas,
    unseal_stripe_pallas,
)
from repro.obs import OBS

__all__ = [
    "HostRows",
    "SealedStripe",
    "seal_stripe",
    "unseal_stripe",
    "pad_rows_for",
    "bucket_rows_for",
    "datapath_traffic",
]


class HostRows(np.ndarray):
    """Sealed rows or parity held on the host: a fused launch's, cut from
    the copy of its outputs that its dispatch started.  ``at`` is
    ``jax.Array``'s functional update, applied to a device copy, so a
    stripe's rows are edited the same way wherever they are held."""

    @property
    def at(self):
        return jnp.asarray(self).at


class SealedStripe(NamedTuple):
    # device arrays, or HostRows where a fused launch's finalize cut them
    sealed: jax.Array            # (S, R, 128) uint32, zero-padded tails
    p: Optional[jax.Array]       # (R, 128) uint32 RAID-5 parity (or None)
    q: Optional[jax.Array]       # (R, 128) uint32 RAID-6 parity (or None)
    n_words: Tuple[int, ...]     # valid uint32 words per shard
    n_i8: Tuple[int, ...]        # valid int8 payload bytes per shard
    # the device that keeps each shard's body (a mesh launch: shard s on the
    # chip that sealed it); None keeps every body on the default device
    devices: Optional[Tuple] = None
    # where P and Q go when they are host rows (a mesh launch: every chip);
    # None: the default device
    parity_sharding: Optional[jax.sharding.Sharding] = None

    def body(self, s: int) -> jax.Array:
        """Exact-length flat uint32 sealed body of shard s."""
        return self.sealed[s].reshape(-1)[: self.n_words[s]]

    def bodies(self) -> List[jax.Array]:
        """Exact-length flat bodies of every shard, as device arrays, each
        on its shard's device.

        Cut on the host: a fused launch's rows are there already
        (``HostRows``), so nothing is fetched; device rows are fetched once.
        A device slice per ragged length would compile a program per GOP
        size, which on a TPU costs more than the whole seal."""
        with OBS.span("kernels.slice", stripes=1):
            host = np.asarray(self.sealed).reshape(self.sealed.shape[0], -1)
            devices = self.devices or (None,) * len(self.n_words)
            return [jax.device_put(host[s, :n], d)
                    for s, (n, d) in enumerate(zip(self.n_words, devices))]

    @property
    def pad_words(self) -> int:
        return self.sealed.shape[1] * LANES


def pad_rows_for(n_words: int) -> int:
    """Rows of 128 words covering n_words, rounded to the 8-row tile."""
    rows = max(1, -(-n_words // LANES))
    return -(-rows // R_TILE) * R_TILE


def bucket_rows_for(n_words: int) -> int:
    """Smallest power-of-two multiple of ``R_TILE`` rows covering n_words.

    ``_seal_core`` retraces per distinct (S, R) shape; bucketing stripe
    heights to pow2 tile counts bounds traces at log2(max_rows/R_TILE) for
    arbitrarily mixed GOP sizes (same idea as ``chacha.bucket_n_words``).
    """
    tiles = -(-pad_rows_for(n_words) // R_TILE)
    return R_TILE * (1 << (tiles - 1).bit_length())


# callers (distributed/archival, benches) reach this via the seal namespace
_as_payload_list = as_payload_list


def _stack_padded(
    flats: Sequence[jax.Array], pad_rows: Optional[int] = None
) -> Tuple[jax.Array, Tuple[int, ...], Tuple[int, ...]]:
    if not flats:
        raise ValueError("stripe must contain at least one shard payload")
    n_i8 = tuple(int(f.shape[0]) for f in flats)
    n_words = tuple(-(-n // 4) for n in n_i8)
    R = pad_rows_for(max(n_words))
    if pad_rows is not None:
        if pad_rows < R or pad_rows % R_TILE:
            raise ValueError(
                f"pad_rows={pad_rows} must be a multiple of {R_TILE} "
                f"covering the largest shard ({R} rows)"
            )
        R = pad_rows
    return stack_rows(flats, R, ROW_BYTES, np.int8), n_words, n_i8


def _meta_arrays(
    keys, nonces, n_words, shard_ids: Optional[Sequence[int]] = None
) -> Tuple[jax.Array, ...]:
    """Per-shard kernel operands.  ``shard_ids`` carries each row's GLOBAL
    stripe-shard index so the RAID-6 Q coefficient g^s stays correct when a
    subset read hands the kernel only some of a stripe's shards."""
    S = len(n_words)
    ids = range(S) if shard_ids is None else shard_ids
    keys = jnp.asarray(keys, jnp.uint32).reshape(S, 8)
    nonces = jnp.asarray(nonces, jnp.uint32).reshape(S, 3)
    n_valid = jnp.asarray(n_words, jnp.int32).reshape(S, 1)
    q_coef = jnp.asarray(
        [gf_pow_gen(int(s)) for s in ids], jnp.uint32
    ).reshape(S, 1)
    return keys, nonces, n_valid, q_coef


@functools.partial(
    jax.jit, static_argnames=("parity", "use_pallas", "interpret")
)
def _seal_core(codes, keys, nonces, n_valid, q_coef, *,
               parity: str, use_pallas: bool, interpret: bool):
    if use_pallas:
        return seal_stripe_pallas(
            codes, keys, nonces, n_valid, q_coef, parity=parity,
            interpret=interpret,
        )
    return _ref.seal_stripe_ref(
        codes, keys, nonces, n_valid, q_coef, parity=parity
    )


@functools.partial(
    jax.jit, static_argnames=("parity", "use_pallas", "interpret")
)
def _unseal_core(sealed, keys, nonces, n_valid, q_coef, *,
                 parity: str, use_pallas: bool, interpret: bool):
    if use_pallas:
        return unseal_stripe_pallas(
            sealed, keys, nonces, n_valid, q_coef, parity=parity,
            interpret=interpret,
        )
    return _ref.unseal_stripe_ref(
        sealed, keys, nonces, n_valid, q_coef, parity=parity
    )


def seal_stripe(payloads, keys, nonces, *, parity: str = "raid6",
                use_pallas: bool = True,
                interpret: Optional[bool] = None,
                pad_rows: Optional[int] = None) -> SealedStripe:
    """Seal all S shards of a stripe (+ parity) in one fused pass.

    payloads: list of flat int8 arrays (ragged ok) or an (S, N) int8 array.
    keys: (S, 8) uint32 ChaCha session keys; nonces: (S, 3) uint32.
    pad_rows: optional row-count override (multiple of ``R_TILE`` covering
    the largest shard).  Multi-stream coalescers pass a pow2 bucket here so
    mixed GOP sizes share one jit trace per bucket instead of one per
    distinct padded length.
    """
    flats = _as_payload_list(payloads)
    codes, n_words, n_i8 = _stack_padded(flats, pad_rows)
    meta = _meta_arrays(keys, nonces, n_words)
    sealed, p, q = _seal_core(
        codes, *meta, parity=parity, use_pallas=use_pallas,
        interpret=use_interpret(interpret),
    )
    return SealedStripe(sealed, p, q, n_words, n_i8)


def unseal_stripe(stripe: SealedStripe, keys, nonces, *,
                  parity: str = "raid6", use_pallas: bool = True,
                  interpret: Optional[bool] = None,
                  shard_ids: Optional[Sequence[int]] = None):
    """Fused decode: returns (host payload list, P, Q) with parity recomputed
    from the stored bodies (compare against the seal-time parity to verify
    stripe integrity before trusting the decode).

    ``shard_ids``: global stripe-shard index per row, for SUBSET reads —
    a retrieval plan that wants shards {1, 3} of a 4-shard stripe stacks
    just those two bodies and passes ``shard_ids=(1, 3)``; parity recompute
    over a subset is meaningless, so such reads run ``parity="none"``.
    """
    if not stripe.n_words:
        raise ValueError("stripe must contain at least one shard payload")
    meta = _meta_arrays(keys, nonces, stripe.n_words, shard_ids)
    codes, p, q = _unseal_core(
        stripe.sealed, *meta, parity=parity, use_pallas=use_pallas,
        interpret=use_interpret(interpret),
    )
    return host_prefixes(codes, stripe.n_i8), p, q


def datapath_traffic(S: int, n_words: int, parity: str = "raid6") -> dict:
    """Structural HBM-byte accounting per stripe: staged pipeline vs fused.

    n_words: padded uint32 words per shard.  The fused kernel touches each
    payload byte once on read (int8) and once on write (uint32), plus one
    parity write per parity output; every staged pass re-reads and/or
    re-writes the full stripe (see ``ref.STAGED_PASSES``).
    """
    body_u8 = 4 * n_words          # bytes of one shard's packed body
    stripe_u8 = S * body_u8
    n_par = {"none": 0, "raid5": 1, "raid6": 2}[parity]
    fused = stripe_u8 + stripe_u8 + n_par * body_u8  # read i8 + write u32 + parity
    staged = (
        2 * stripe_u8            # pack: read i8, write u32
        + stripe_u8              # keystream: write u32
        + 3 * stripe_u8          # xor: read payload + keystream, write
        + 2 * stripe_u8          # mask: read + write
        + (2 * stripe_u8 if n_par else 0)   # u8 bitcast: read + write
        + n_par * (stripe_u8 + body_u8)     # parity: read S shards per parity + write
    )
    return {
        "staged_bytes": staged,
        "fused_bytes": fused,
        "reduction": staged / fused,
        "staged_passes": _ref.N_STAGED_PASSES,
        "fused_launches": 1,
    }
