"""The archival write chain on the device: rANS entropy encode, stream pack,
adaptive raw-skip, ChaCha20 XOR-seal and RAID-5 P / RAID-6 Q, for a batch
of K coalesced stripes.

Per batch the write path launches, in order (every stage compiled for the
device, each under a stable name):

  1. ``rans_histogram`` — Pallas, per-shard byte histogram (MXU);
  2. ``rans_tables`` — XLA, frequency and encode tables (256-wide);
  3. ``rans_encode`` — Pallas, the interleaved coding loop over row tiles,
     which also compacts each row's emitted words into the shard's
     row-major stream (written back to front through a VMEM window);
  4. ``rans_pack`` — XLA, stream slice + header serialization, the
     adaptive raw-skip select and the little-endian u32 packing;
  5. ``seal_stripes`` — Pallas, keystream + XOR-seal + valid-length mask +
     P/Q folds for all K stripes (``kernels/seal``).

So the packed streams visit HBM between the coder and the seal: three
Pallas launches per stripe batch, whatever K is, with XLA glue between
them.  Every stage is the same computation as the chained
``kernels/entropy`` -> ``kernels/seal`` path, so the stored bits are
identical to it (and to ``ref.py``).

Capacity invariants (why fixed-size outputs lose nothing):

* stream word cap: a shard whose emission count reaches
  ``(T*128 - HEADER_BYTES) // 2`` words compresses to >= its raw size and
  is stored raw, so capping the stream there discards only streams the
  raw-skip select would discard anyway.
* sealed rows cap: the stored body (raw or v1 stream) of a T-row shard
  never exceeds T*128 bytes — the v1 stream is exactly T*128 bytes at the
  raw-skip boundary — so ``pad_rows_for(T*32)`` rows always cover it, and
  every word past a shard's stored length is masked to zero, making the
  host-side slice back to the chained path's row count exact.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import le_words
from repro.kernels.entropy.ops import HEADER_BYTES, stream_words
from repro.kernels.entropy.rans import (
    N_LANES,
    T_TILE,
    rans_encode_pallas,
    stream_word_cap,
)
from repro.kernels.seal.ops import pad_rows_for
from repro.kernels.seal.seal import LANES, seal_words_pallas

# ``stream_word_cap`` moved next to the pack it sizes (entropy ops); the
# fused module re-exports it because the seal-side capacity story lives here
__all__ = ["entropy_seal_pallas", "stream_word_cap", "seal_rows_cap"]


def seal_rows_cap(T: int) -> int:
    """Sealed-row capacity covering any stored body of a T-row shard
    (raw and v1 stream are both <= T*128 bytes = T*32 uint32 words)."""
    return pad_rows_for(T * N_LANES // 4)


def entropy_seal_pallas(
    codes, n_valid, keys, nonces, q_coef, *, n_shards: int,
    parity: str = "raid6", interpret: bool = True,
):
    """rANS-encode, pack, ChaCha20-XOR-seal and parity-fold a batch of
    K = B // n_shards coalesced stripes (see the module doc for the
    launches).

    codes: (B, T, 128) int8 payload rows, zero-padded (stripes contiguous:
    shard s of stripe k is row k*n_shards + s); n_valid: (B, 1) int32 RAW
    byte counts (pre-compression — the raw-skip decision is made here);
    keys (B, 8) / nonces (B, 3) / q_coef (B, 1) uint32 per-shard session
    material and RAID-6 GF coefficients.

    Returns (sealed (B, R_cap, 128) u32, n_words (B, 1) int32 emitted rANS
    word counts, p (K, R_cap, 128) u32 | None, q (K, R_cap, 128) u32 |
    None).  Everything a host needs to reconstruct streams, metas and
    chained-path row counts derives from n_words + the raw lengths.
    """
    B, T, L = codes.shape
    if L != N_LANES:
        raise ValueError(f"expected {N_LANES} lanes, got {L}")
    if T % T_TILE:
        raise ValueError(f"rows {T} not a multiple of {T_TILE}")
    if n_shards <= 0 or B % n_shards:
        raise ValueError(f"batch of {B} shards not a multiple of {n_shards}")
    if parity not in ("none", "raid5", "raid6"):
        raise ValueError(f"unknown parity {parity!r}")
    R_cap = seal_rows_cap(T)

    words, n_words, lane_lens, freq, states = rans_encode_pallas(
        codes, n_valid, interpret=interpret
    )
    with jax.named_scope("rans_pack"):
        stream = stream_words(words, lane_lens, freq, states)
        raw = le_words(codes.reshape(B, T * L).astype(jnp.int32) & 0xFF, 4)
        # adaptive raw-skip select (n_words is the TRUE emission count, not
        # capped).  Both branches are zero past their stored length: raw by
        # the ops-layer padding contract, the stream because words past
        # n_words are zeroed.
        n_raw = n_valid.reshape(B)
        n_comp = HEADER_BYTES + 2 * n_words
        is_raw = n_comp >= n_raw
        n_body = T * L // 4
        body = jnp.where(is_raw[:, None], raw, stream[:, :n_body])
        body = jnp.pad(body, ((0, 0), (0, R_cap * LANES - n_body)))
        stored = jnp.where(is_raw, n_raw, n_comp)
        n_sealed = -(-stored // 4)                           # stored u32 words
    sealed, p, q = seal_words_pallas(
        body.reshape(B, R_cap, LANES), keys, nonces, n_sealed.reshape(B, 1),
        q_coef, n_shards=n_shards, parity=parity, interpret=interpret,
    )
    return sealed, n_words[:, None], p, q
