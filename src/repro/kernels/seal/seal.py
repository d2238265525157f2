"""Pallas TPU kernel: fused archival seal datapath (and its unseal twin).

One grid step seals one (tile, 128)-uint32 block of one shard: generate the
ChaCha20 keystream in VMEM, XOR-seal, mask to the shard's valid length, and
fold the block into the stripe's RAID-5 P / RAID-6 Q parity accumulators.
The grid is (stripe, row tile, shard) with the shard axis innermost, so the
parity output block of a (stripe, row tile) stays resident while all S
shards stream through it (Pallas accumulation via a revisited output block).
One launch seals a whole batch of K stripes.

Per-shard session material (ChaCha key and nonce, valid word count, GF(256)
Q coefficient) rides in SMEM by scalar prefetch, so no operand needs a
sub-8-row block.  The int8 <-> uint32 little-endian byte packing is an XLA
pass around the launch (Mosaic cannot relayout (R, 512) int8 to
(R, 128, 4)); the kernel itself only touches uint32 words.

Memory-bound VPU kernel: HBM traffic is read-u32 + write-u32 (+parity),
vs ~6 HBM round-trips for the staged jnp pipeline (pack, keystream, XOR,
mask, uint8 bitcast, per-shard parity loops).

GF(256) (poly 0x11D, generator 2 — same field as ``core/archival/raid.py``)
is computed without tables: the per-shard coefficient g^s is a scalar and
the multiply is an 8-step SWAR shift/xor peasant product on 4 bytes packed
per uint32 lane, which is bit-identical to the log/antilog-table reference
and pure VPU work.
"""

from __future__ import annotations

import functools
from typing import List

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.crypto.chacha import CONSTANTS, chacha_rounds_planes
from repro.kernels import le_words

__all__ = ["seal_stripe_pallas", "unseal_stripe_pallas", "seal_words_pallas",
           "pack_words", "unpack_words", "R_TILE", "LANES", "ROW_BYTES"]

R_TILE = 8                        # row granularity of every sealed body
LANES = 128                       # uint32 words per row
ROW_BYTES = 4 * LANES             # int8 payload bytes per row
_MAX_TILE = 64                    # rows per grid step (16 ChaCha planes of
                                  # (64, 128) u32 stay a few hundred KiB)


def pack_words(codes: jax.Array) -> jax.Array:
    """(..., R, 512) int8 -> (..., R, 128) uint32 little-endian lanes."""
    b = codes.reshape(-1, ROW_BYTES).astype(jnp.int32) & 0xFF
    return le_words(b, 4).reshape(*codes.shape[:-1], LANES)


def unpack_words(words: jax.Array) -> jax.Array:
    """(..., R, 128) uint32 -> (..., R, 512) int8 (explicit two's complement,
    so the cast is backend-independent)."""
    v = jnp.stack(
        [((words >> jnp.uint32(8 * k)) & jnp.uint32(0xFF)).astype(jnp.int32)
         for k in range(4)],
        axis=-1,
    )
    signed = v - ((v & 0x80) << 1)
    return signed.reshape(*words.shape[:-1], ROW_BYTES).astype(jnp.int8)


def _keystream_tile(key, nonce, row0, rows: int):
    """(rows, LANES) uint32 keystream starting at row ``row0`` of a shard.

    Word l of row r is word l % 16 of ChaCha block 8 * (row0 + r) + l // 16
    — the contiguous mapping of ``chacha.keystream``, so the seal is
    bit-identical to the staged xor_stream path.  Every lane runs its own
    block's 20 rounds on full (rows, 128) planes and keeps the one word its
    position needs: on the VPU that costs the same vector ops as 16 planes
    of (rows, 8) blocks would (those fill a sixteenth of each vreg), and it
    needs no lane relayout.
    """
    shp = (rows, LANES)
    lane = jax.lax.broadcasted_iota(jnp.uint32, shp, 1)
    row = jax.lax.broadcasted_iota(jnp.uint32, shp, 0) + row0
    ctr = row * jnp.uint32(LANES // 16) + (lane >> jnp.uint32(4))
    state = (
        [jnp.full(shp, c, jnp.uint32) for c in CONSTANTS]
        + [jnp.full(shp, k, jnp.uint32) for k in key]
        + [ctr]
        + [jnp.full(shp, n, jnp.uint32) for n in nonce]
    )
    planes = chacha_rounds_planes(state)
    word = lane & jnp.uint32(15)
    ks = planes[0]
    for w in range(1, 16):
        ks = jnp.where(word == jnp.uint32(w), planes[w], ks)
    return ks


def _gf_mul_const_u32(x, coef):
    """GF(256) multiply of 4 packed bytes per uint32 lane by scalar coef.

    Peasant product over the 8 bits of coef; xtime is the SWAR shift/xor
    form of multiply-by-x mod 0x11D (0x1D = (1<<4)^(1<<3)^(1<<2)^1), so no
    byte ever carries into its neighbour.
    """
    res = jnp.zeros_like(x)
    for bit in range(8):
        lsb = (coef >> jnp.uint32(bit)) & jnp.uint32(1)
        res = res ^ (x & (jnp.uint32(0) - lsb))
        hi = (x >> jnp.uint32(7)) & jnp.uint32(0x01010101)
        red = (hi << jnp.uint32(4)) ^ (hi << jnp.uint32(3)) ^ (hi << jnp.uint32(2)) ^ hi
        x = ((x << jnp.uint32(1)) & jnp.uint32(0xFEFEFEFE)) ^ red
    return res


def _accumulate_parity(stored, p_ref, q_ref, qcoef, shard_id):
    first = shard_id == 0

    @pl.when(first)
    def _init_p():
        p_ref[0] = stored

    @pl.when(jnp.logical_not(first))
    def _acc_p():
        p_ref[0] = p_ref[0] ^ stored

    if q_ref is not None:
        contrib = _gf_mul_const_u32(stored, qcoef)

        @pl.when(first)
        def _init_q():
            q_ref[0] = contrib

        @pl.when(jnp.logical_not(first))
        def _acc_q():
            q_ref[0] = q_ref[0] ^ contrib


def _seal_kernel(keys_ref, nonces_ref, nvalid_ref, qcoef_ref, words_ref,
                 *out_refs, n_shards: int, tile: int, unseal: bool,
                 with_p: bool, with_q: bool):
    i = pl.program_id(1)  # row tile within the shard
    s = pl.program_id(2)  # shard index within the stripe
    b = pl.program_id(0) * n_shards + s
    out_ref = out_refs[0]
    p_ref = out_refs[1] if with_p else None
    q_ref = out_refs[2] if with_q else None

    key = [keys_ref[b * 8 + j] for j in range(8)]
    nonce = [nonces_ref[b * 3 + j] for j in range(3)]
    ks = _keystream_tile(key, nonce, (i * tile).astype(jnp.uint32), tile)
    widx = (
        (i * tile + jax.lax.broadcasted_iota(jnp.int32, (tile, LANES), 0))
        * LANES
        + jax.lax.broadcasted_iota(jnp.int32, (tile, LANES), 1)
    )
    valid = widx < nvalid_ref[b]
    words = words_ref[0]
    out = jnp.where(valid, words ^ ks, jnp.uint32(0))
    out_ref[0] = out

    # parity over the bodies AS STORED: the sealed output when sealing, the
    # stored input when unsealing (the integrity recompute)
    if with_p:
        _accumulate_parity(words if unseal else out, p_ref, q_ref,
                           qcoef_ref[b], s)


def _parity_flags(parity: str):
    if parity not in ("none", "raid5", "raid6"):
        raise ValueError(f"unknown parity mode {parity!r}")
    return parity != "none", parity == "raid6"


def _row_tile(R: int) -> int:
    """Largest power-of-two multiple of ``R_TILE`` dividing R, capped."""
    tile = R_TILE
    while tile * 2 <= _MAX_TILE and R % (tile * 2) == 0:
        tile *= 2
    return tile


def seal_words_pallas(words, keys, nonces, n_valid, q_coef, *, n_shards: int,
                      parity: str = "raid6", unseal: bool = False,
                      interpret: bool = True):
    """Seal (or unseal) a batch of K = B // n_shards stripes in ONE launch.

    words: (B, R, 128) uint32 (stripes contiguous: shard s of stripe k is
    row k * n_shards + s); keys (B, 8) / nonces (B, 3) uint32; n_valid
    (B, 1) int32 valid uint32 words per shard; q_coef (B, 1) uint32 GF(256)
    RAID-6 coefficient per shard.  Returns (out (B, R, 128) uint32, P, Q)
    with P/Q (K, R, 128) uint32 per ``parity`` (None otherwise) — folded
    over the sealed output, or over the stored input when ``unseal``.
    """
    B, R, L = words.shape
    if L != LANES:
        raise ValueError(f"expected {LANES} lanes, got {L}")
    if R % R_TILE:
        raise ValueError(f"rows {R} not a multiple of {R_TILE}")
    if n_shards <= 0 or B % n_shards:
        raise ValueError(f"batch of {B} shards not a multiple of {n_shards}")
    with_p, with_q = _parity_flags(parity)
    K, S = B // n_shards, n_shards
    tile = _row_tile(R)
    blk = pl.BlockSpec((1, tile, LANES), lambda k, i, s, *_: (k * S + s, i, 0))
    par = pl.BlockSpec((1, tile, LANES), lambda k, i, s, *_: (k, i, 0))
    out_shape: List[jax.ShapeDtypeStruct] = [
        jax.ShapeDtypeStruct((B, R, LANES), jnp.uint32)
    ] + [jax.ShapeDtypeStruct((K, R, LANES), jnp.uint32)] * (with_p + with_q)
    outs = pl.pallas_call(
        functools.partial(_seal_kernel, n_shards=S, tile=tile, unseal=unseal,
                          with_p=with_p, with_q=with_q),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(K, R // tile, S),  # shard innermost: parity revisited S times
            in_specs=[blk],
            out_specs=[blk] + [par] * (with_p + with_q),
        ),
        out_shape=out_shape,
        interpret=interpret,
        name="unseal_stripes" if unseal else "seal_stripes",
    )(
        jnp.asarray(keys, jnp.uint32).reshape(-1),
        jnp.asarray(nonces, jnp.uint32).reshape(-1),
        jnp.asarray(n_valid, jnp.int32).reshape(-1),
        jnp.asarray(q_coef, jnp.uint32).reshape(-1),
        words,
    )
    p = outs[1] if with_p else None
    q = outs[2] if with_q else None
    return outs[0], p, q


def seal_stripe_pallas(codes, keys, nonces, n_valid, q_coef, *,
                       parity: str = "raid6", interpret: bool = True):
    """Fused seal of one stripe in a single kernel launch.

    codes: (S, R, 512) int8 codec payload, zero-padded per shard.
    keys: (S, 8) uint32 ChaCha session keys; nonces: (S, 3) uint32.
    n_valid: (S, 1) int32 valid uint32-word count per shard.
    q_coef: (S, 1) uint32 GF(256) RAID-6 coefficient g^s per shard.

    Returns (sealed (S, R, 128) uint32, P (R, 128) uint32 | None,
    Q (R, 128) uint32 | None) — P/Q per ``parity`` mode.
    """
    S, R, C = codes.shape
    if C != ROW_BYTES:
        raise ValueError(f"expected row width {ROW_BYTES}, got {C}")
    sealed, p, q = seal_words_pallas(
        pack_words(codes), keys, nonces, n_valid, q_coef, n_shards=S,
        parity=parity, interpret=interpret,
    )
    return sealed, None if p is None else p[0], None if q is None else q[0]


def unseal_stripe_pallas(sealed, keys, nonces, n_valid, q_coef, *,
                         parity: str = "raid6", interpret: bool = True):
    """Fused decode twin: keystream + XOR + parity-recompute, then unpack.

    sealed: (S, R, 128) uint32 bodies as stored (zero-padded tails).
    Returns (codes (S, R, 512) int8, P, Q) where P/Q are recomputed from the
    stored bodies so callers can verify stripe integrity against the parity
    written at seal time.
    """
    S, R, C = sealed.shape
    if C != LANES:
        raise ValueError(f"expected {LANES} lanes, got {C}")
    words, p, q = seal_words_pallas(
        sealed, keys, nonces, n_valid, q_coef, n_shards=S, parity=parity,
        unseal=True, interpret=interpret,
    )
    return (
        unpack_words(words),
        None if p is None else p[0],
        None if q is None else q[0],
    )
