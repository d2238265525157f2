"""Staged pure-jnp oracle for the interleaved-rANS coder (bit-exact target).

The reference runs the coder as separate full-stripe passes — one-hot
matmul histogram, table build (frequencies + Granlund-Montgomery
reciprocals), then one ``lax.scan`` over rows vectorized over
(shard, lane) — i.e. the pre-fusion pipeline with one HBM round-trip per
stage, exactly like ``kernels/seal/ref.py`` mirrors the fused seal kernel.
Outputs must match ``rans.rans_encode_pallas`` / ``rans_decode_pallas``
bit-for-bit: the coder is all-integer (and the histogram's f32 partial
sums are all exact integer counts < 2^24, so any summation order agrees),
so there is no tolerance anywhere.  The scan steps per *row* while the
kernel steps per (G, 128) lane-group tile; the carried math is identical,
so the schedules agree bit-for-bit.

Both stream versions are mirrored: ``rans_decode_ref`` consumes the
version-1 row-major word stream with a scalar prefix-sum pointer per
shard, ``rans_decode_ref_v0`` the PR-4 lane-major layout with per-lane
pointers.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.kernels.entropy.rans import (
    N_LANES,
    RANS_L,
    _dec_step,
    _enc_step,
    _histogram,
    build_dec_table,
    build_enc_tables,
    build_freq_table,
    slot_to_symbol,
)

__all__ = [
    "STAGED_PASSES",
    "N_STAGED_PASSES",
    "rans_encode_ref",
    "compact_ref",
    "rans_decode_ref",
    "rans_decode_ref_v0",
]

# One entry per full-payload pass in the staged pipeline (the fused kernel
# does all of them in one VMEM residency per shard).
STAGED_PASSES = (
    "one-hot matmul histogram (read payload)",
    "table build: freqs + integer reciprocals (256-entry, table-only)",
    "interleaved encode scan (read payload, write words+mask)",
    "emission rank-select compaction (read words+mask, write stream)",
)
N_STAGED_PASSES = len(STAGED_PASSES)


def _valid_mask(S: int, T: int, n_valid: jax.Array) -> jax.Array:
    """(S, T, 128) bool: position r*128+l is a real (non-padding) byte."""
    gidx = jnp.arange(T * N_LANES, dtype=jnp.int32).reshape(1, T, N_LANES)
    return gidx < n_valid.reshape(S, 1, 1)


def rans_encode_ref(codes: jax.Array, n_valid: jax.Array,
                    division: str = "divide") -> Tuple[jax.Array, ...]:
    """Staged encode: same signature/outputs as ``rans_encode_pallas``."""
    S, T, L = codes.shape
    assert L == N_LANES, codes.shape
    vals = (codes.astype(jnp.int32)) & 0xFF                  # (S, T, 128)
    vmask = _valid_mask(S, T, n_valid)

    # pass 1-2: one-hot matmul histogram + tables per shard
    counts = jax.vmap(_histogram)(vals, n_valid.reshape(S))
    freq = jax.vmap(build_freq_table)(counts)                # (S, 256)
    packed, mprime, rcp = jax.vmap(build_enc_tables)(freq)
    aux = {"divide": packed, "reciprocal": mprime, "rcp32": rcp}[division]

    # pass 3: encode scan over rows, reversed (rANS codes backwards),
    # vectorized over the (shard, lane) axes
    def step(x, xs):
        row, valid = xs                                      # (S, 128) each
        p = jnp.take_along_axis(packed, row, axis=-1)
        a = jnp.take_along_axis(aux, row, axis=-1)
        x2, x_pre, e = _enc_step(x, p, a, division=division)
        x = jnp.where(valid, x2, x)                          # pad lanes: no-op
        w = (x_pre & jnp.uint32(0xFFFF)).astype(jnp.uint16)
        return x, (w, (e & valid).astype(jnp.uint8))

    x0 = jnp.full((S, N_LANES), RANS_L, jnp.uint32)
    states, (w_rev, m_rev) = jax.lax.scan(
        step,
        x0,
        (jnp.swapaxes(vals, 0, 1)[::-1], jnp.swapaxes(vmask, 0, 1)[::-1]),
    )
    words = jnp.swapaxes(w_rev[::-1], 0, 1)                  # back to (S, T, 128)
    mask = jnp.swapaxes(m_rev[::-1], 0, 1)
    return words, mask, freq, states


def compact_ref(words: jax.Array, mask: jax.Array, cap: int):
    """Stream compaction oracle: the emitted words of (S, T, 128) in
    row-major decoder-read order, by a stable sort on the emission flags.
    Returns (words (S, cap) uint16 zero past n_words, n_words (S,),
    lane_lens (S, 128) per-lane word counts)."""
    S, T, L = words.shape
    flags = mask.reshape(S, T * L) != 0
    order = jnp.argsort(jnp.logical_not(flags), axis=1, stable=True)[:, :cap]
    w = jnp.take_along_axis(words.reshape(S, T * L), order, axis=1)
    n_words = flags.sum(axis=1).astype(jnp.int32)
    k = jnp.arange(cap, dtype=jnp.int32)[None, :]
    w = jnp.where(k < n_words[:, None], w, 0).astype(jnp.uint16)
    return w, n_words, mask.astype(jnp.int32).sum(axis=1)


def rans_decode_ref(
    stream: jax.Array,
    freq: jax.Array,
    states: jax.Array,
    n_valid: jax.Array,
    *,
    rows: int,
) -> jax.Array:
    """Version-1 staged decode: same outputs as ``rans_decode_pallas``.

    stream: (S, W) uint16 row-major words; one scalar read pointer per
    shard advances by popcount(need) each row (exclusive in-row prefix sum
    assigns the words to lanes in lane order).
    """
    S, W = stream.shape
    vmask = _valid_mask(S, rows, n_valid)
    dec_packed = jax.vmap(build_dec_table)(freq)
    slot2sym = jax.vmap(slot_to_symbol)(freq)

    def step(carry, valid):
        x, base = carry
        x2, s, need = jax.vmap(_dec_step)(x, dec_packed, slot2sym)
        need = need & valid
        csum = jnp.cumsum(need.astype(jnp.int32), axis=-1)   # (S, 128)
        pos = base[:, None] + csum - need.astype(jnp.int32)  # exclusive
        w = jnp.take_along_axis(
            stream, jnp.minimum(pos, W - 1), axis=1
        ).astype(jnp.uint32)
        x2 = jnp.where(need, (x2 << jnp.uint32(16)) | w, x2)
        x = jnp.where(valid, x2, x)                          # pad lanes: no-op
        base = base + csum[:, -1]
        signed = jnp.where(valid, s - ((s & 0x80) << 1), 0).astype(jnp.int8)
        return (x, base), signed

    base0 = jnp.zeros((S,), jnp.int32)
    _, out = jax.lax.scan(step, (states, base0), jnp.swapaxes(vmask, 0, 1))
    return jnp.swapaxes(out, 0, 1)                           # (S, rows, 128)


def rans_decode_ref_v0(
    lane_words: jax.Array,
    freq: jax.Array,
    states: jax.Array,
    n_valid: jax.Array,
) -> jax.Array:
    """Version-0 staged decode: lane-major words, per-lane read pointers."""
    S, T, L = lane_words.shape
    assert L == N_LANES, lane_words.shape
    vmask = _valid_mask(S, T, n_valid)
    dec_packed = jax.vmap(build_dec_table)(freq)
    slot2sym = jax.vmap(slot_to_symbol)(freq)

    def step(carry, valid):
        x, ptr = carry
        x2, s, need = jax.vmap(_dec_step)(x, dec_packed, slot2sym)
        need = need & valid
        w = jnp.take_along_axis(
            lane_words, jnp.minimum(ptr, T - 1)[:, None, :], axis=1
        )[:, 0].astype(jnp.uint32)
        x2 = jnp.where(need, (x2 << jnp.uint32(16)) | w, x2)
        x = jnp.where(valid, x2, x)                          # pad lanes: no-op
        ptr = ptr + need.astype(jnp.int32)
        signed = jnp.where(valid, s - ((s & 0x80) << 1), 0).astype(jnp.int8)
        return (x, ptr), signed

    ptr0 = jnp.zeros((S, N_LANES), jnp.int32)
    _, rows = jax.lax.scan(step, (states, ptr0), jnp.swapaxes(vmask, 0, 1))
    return jnp.swapaxes(rows, 0, 1)                          # (S, T, 128)
