"""Staged pure-jnp oracle for the fused entropy+seal write program.

The pre-fusion pipeline kept as the bit-exact reference and the
``use_pallas=False`` fallback: the entropy stage runs the scan-based rANS
oracle (``kernels/entropy/ref.py`` — an independent schedule from the
kernel's row loop) and its sort-based stream compaction, the header
is serialized byte by byte, and the seal stages run the staged seal reference
(``kernels/seal/ref.py`` — per-shard ``chacha20_block`` keystream and the
log/antilog-table GF(256) parity, both independent implementations of the
kernel's plane-batched ChaCha and SWAR GF multiply).

Each tuple entry below is one full-payload HBM round-trip of the staged
pipeline; the fused program does them in three kernels per stripe batch.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.entropy import ref as eref
from repro.kernels.entropy.ops import HEADER_BYTES
from repro.kernels.fused.entropy_seal import seal_rows_cap, stream_word_cap
from repro.kernels.seal import ref as sref
from repro.kernels.seal.seal import ROW_BYTES

__all__ = ["STAGED_PASSES", "N_STAGED_PASSES", "entropy_seal_ref"]

STAGED_PASSES = (
    eref.STAGED_PASSES
    + (
        "v1 stream serialization to bytes (read words, write u8)",
        "adaptive raw-skip select (read stream + raw bytes, write u8)",
    )
    + sref.STAGED_PASSES
)
N_STAGED_PASSES = len(STAGED_PASSES)


def _u16_to_u8(w: jax.Array) -> jax.Array:
    """(..., n) uint16 -> (..., 2n) uint8, little-endian."""
    lo = (w & jnp.uint16(0xFF)).astype(jnp.uint8)
    hi = (w >> jnp.uint16(8)).astype(jnp.uint8)
    return jnp.stack([lo, hi], axis=-1).reshape(*w.shape[:-1], -1)


def _u32_to_u8(w: jax.Array) -> jax.Array:
    """(..., n) uint32 -> (..., 4n) uint8, little-endian."""
    parts = [
        ((w >> jnp.uint32(8 * k)) & jnp.uint32(0xFF)).astype(jnp.uint8)
        for k in range(4)
    ]
    return jnp.stack(parts, axis=-1).reshape(*w.shape[:-1], -1)


def _serialize_streams(words, lane_lens, freq, states):
    """Header + word area of B v1 streams -> (B, HEADER + 2 * cap) uint8,
    byte by byte (the kernel path packs u32 words directly)."""
    header = jnp.concatenate(
        [
            _u16_to_u8(freq.astype(jnp.uint16)),
            _u32_to_u8(lane_lens.astype(jnp.uint32)),
            _u32_to_u8(states),
        ],
        axis=1,
    )
    return jnp.concatenate([header, _u16_to_u8(words)], axis=1)


def entropy_seal_ref(
    codes, n_valid, keys, nonces, q_coef, *,
    n_shards: int, parity: str = "raid6", division: str = "divide",
):
    """Staged fused archival: same signature/outputs as
    ``entropy_seal_pallas`` (sealed, n_words, p, q), bit-for-bit."""
    B, T, L = codes.shape
    R_cap = seal_rows_cap(T)

    # entropy stage: independent scan-schedule oracle
    words, mask, freq, states = eref.rans_encode_ref(
        codes, n_valid, division=division
    )
    comp, n_words, lane_lens = eref.compact_ref(
        words, mask, stream_word_cap(T)
    )
    stream_u8 = _serialize_streams(comp, lane_lens, freq, states)

    # raw-skip select + pad to the sealed-rows capacity
    n_raw = n_valid.reshape(B)
    n_comp = HEADER_BYTES + 2 * n_words
    is_raw = n_comp >= n_raw
    buf = T * L
    raw_u8 = (codes.astype(jnp.int32) & 0xFF).reshape(B, buf).astype(jnp.uint8)
    body_u8 = jnp.where(is_raw[:, None], raw_u8, stream_u8[:, :buf])
    body_u8 = jnp.pad(body_u8, ((0, 0), (0, R_cap * ROW_BYTES - buf)))

    # seal stage: the staged seal reference, end to end
    body_i8 = jax.lax.bitcast_convert_type(body_u8, jnp.int8)
    packed = sref._pack_rows(body_i8.reshape(B, R_cap, ROW_BYTES))
    ks = sref._keystream_rows(keys, nonces, R_cap)
    stored = jnp.where(is_raw, n_raw, n_comp)
    sealed = sref._mask_valid(packed ^ ks, -(-stored // 4))
    n_words_out = n_words[:, None]
    if parity == "none":
        return sealed, n_words_out, None, None
    K = B // n_shards
    ps, qs = [], []
    for k in range(K):
        sl = slice(k * n_shards, (k + 1) * n_shards)
        p, q = sref._parity(sealed[sl], q_coef[sl], parity)
        ps.append(p)
        qs.append(q)
    p = jnp.stack(ps)
    q = jnp.stack(qs) if parity == "raid6" else None
    return sealed, n_words_out, p, q
