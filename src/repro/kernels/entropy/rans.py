"""Pallas TPU kernels: interleaved-rANS byte coder for the archival datapath.

A shard's flat int8 payload is laid out as (T, 128) rows whose 128 columns
are 128 *independent* rANS lanes (lane l owns bytes l, 128+l, 256+l, ...),
the interleaved layout from Giesen's SIMD rANS with the lane axis mapped
onto the TPU lane dimension.  32-bit states with 16-bit renormalization
mean a lane emits (or, decoding, consumes) at most one 16-bit word per row,
which is what makes the coder branchlessly vectorizable.

The encode of a batch of B shards is three device programs:

  1. ``rans_histogram`` (Pallas): per-shard byte histogram on the MXU.  The
     byte splits into hi/lo nibbles; for each 8-row group the one-hot
     matrices A[(h, r), p] = [hi(r, p) = h] and B[(l, r), p] = [lo(r, p) = l]
     (128 x 128, bf16 0/1) multiply as A @ B^T, and the diagonal r = r'
     blocks of the product are the group's (16 x 16) joint nibble counts.
     Every partial sum is an integer below 2^24, so f32 accumulation is
     exact.  Row tiles ride an ``"arbitrary"`` grid axis.
  2. ``rans_tables`` (XLA): :func:`build_freq_table` and
     :func:`build_enc_tables` on the (B, 256) counts — table-sized work with
     cumulative sums and an argmax that Mosaic does not lower.
  3. ``rans_encode`` (Pallas): the coding loop.  rANS encodes backwards, so
     the grid walks the row tiles in reverse on an ``"arbitrary"`` axis with
     the (B, 128) lane states in VMEM scratch; inside a tile a
     ``fori_loop`` walks the rows in reverse.  Codes arrive row-major as
     (T, B, 128) so one row of every shard is one (B, 128) vector: shards
     ride the sublanes.  Each row looks its symbols' table entries up with
     two in-register lane gathers (the 256-entry tables are two 128-lane
     halves), swaps padding positions for the identity sentinel (see
     ``_ENC_SENTINEL``), and steps every lane once.  The row's emitted
     words are compacted on the spot: an exclusive in-row prefix sum (one
     0/1 matmul against an upper-triangular matrix) gives each emitting
     lane its slot, a one-hot placement matmul moves the words (as bytes,
     exact in bf16) to their lanes, and they are merged into a per-shard
     VMEM window of the stream.  rANS runs backwards, so the stream is
     written back to front; the window is refilled from and flushed to
     HBM by DMA once per row tile, and XLA slices each shard's stream
     from its final write position.  The output is the v1 word area
     itself — no dense per-position buffer leaves the kernel.

The per-symbol division x // freq runs inside the kernel as the
error-repaired f32 reciprocal (``division="rcp32"`` of :func:`_enc_step`):
Mosaic has no integer division.  The renorm invariant bounds the quotient
by 2^20, so any faithful rounding is within +-0.2 of the true quotient and
the +-1 integer repair makes the result exact — bit-identical to the
hardware udiv and the Granlund-Montgomery mulhi strategies, which the jnp
oracle (``ref.py``) still runs.

Decoding (``rans_decode``, Pallas) mirrors it forward: per row, the symbol
is found by an 8-step branchless binary search over the shard's inclusive
cumulative frequencies (lane gathers again), the lanes that renormalize
take the next words of the row-major stream in lane order (exclusive
in-row prefix sum as one 0/1 matmul against an upper-triangular matrix),
and the words come from a per-shard VMEM window of the stream that a DMA
refills at the start of each row tile: consumption is at most 128 words
per row, so a tile of R rows reads at most R + 2 stream rows past its
start.  The read pointers are scalars (one per shard).

Stream format (``STREAM_VERSION = 1``): header freq u16[256] |
lane_lens u32[128] | states u32[128], then the words in *row-major
decoder-read order* (row by row, lanes in order within a row).  Version 0
streams (PR-4 lane-major words) decode through the jnp oracle only.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "N_LANES",
    "PROB_BITS",
    "PROB_SCALE",
    "RANS_L",
    "T_TILE",
    "STREAM_VERSION",
    "build_freq_table",
    "build_enc_tables",
    "build_dec_table",
    "slot_to_symbol",
    "byte_histogram",
    "stream_word_cap",
    "rans_encode_pallas",
    "rans_decode_pallas",
]

N_LANES = 128                 # interleaved rANS lanes == TPU lane width
PROB_BITS = 12                # frequency table quantization: sum(freq) = 4096
PROB_SCALE = 1 << PROB_BITS
RANS_L = 1 << 16              # state lower bound; 16-bit renormalization
T_TILE = 8                    # sublane-aligned row granularity
STREAM_VERSION = 1            # row-major word order; 0 = PR-4 lane-major

_SYM_MASK = 0x1FFF            # 13 bits: freq and cum both reach 4096
_SUBLANES = 8                 # shards are padded to whole sublane groups
_HIST_TILE = 512              # rows per histogram grid step
_DEC_TILE = 128               # rows per decode grid step (one stream DMA)
_TILE_ELEMS = 1 << 18         # encode block budget: rows * shards * 128


def build_freq_table(counts: jax.Array) -> jax.Array:
    """(256,) int32 byte counts -> (256,) int32 freqs summing to PROB_SCALE.

    Precondition: ``counts.sum() < 2^31`` — the total is summed in int32.
    A shard holds at most ``MAX_ROWS * 128 = 2^24`` bytes, so every real
    histogram meets it.

    Integer-exact and overflow-safe in int32: counts are right-shifted until
    their total is < 2^19 (so count*budget < 2^31), every present symbol is
    reserved one slot up front, the remaining budget is floor-allocated
    proportionally, and the rounding remainder goes to the most frequent
    symbol.  Present symbols always get freq >= 1; the sum is exactly
    PROB_SCALE.  Shared verbatim by the coder's XLA table stage and the jnp
    reference.
    """
    present = (counts > 0).astype(jnp.int32)
    total = counts.sum()
    # shift = #{k : total >= 2^(19+k)}  -- smallest shift with total>>shift < 2^19
    thresholds = 19 + jax.lax.broadcasted_iota(jnp.int32, (12,), 0)
    shift = (total >= (1 << thresholds)).sum()
    c2 = jnp.maximum(counts >> shift, present)
    n2 = jnp.maximum(c2.sum(), 1)
    budget = PROB_SCALE - present.sum()
    extra = (c2 * budget) // n2        # c2 < 2^19, budget < 2^12: no overflow
    freq = present + extra
    rem = budget - extra.sum()
    # remainder to the most frequent symbol, scatter-free (one-hot select)
    sym = jax.lax.broadcasted_iota(jnp.int32, (256,), 0)
    return freq + jnp.where(sym == jnp.argmax(c2), rem, 0)


def build_enc_tables(freq: jax.Array):
    """(256,) int32 freqs -> per-symbol encode tables (packed, mprime, rcp).

    ``packed[s] = f | (shift-1) << 13 | cum_excl << 19`` (f clamped to
    >= 1: only padding lanes ever look up an absent symbol, their update
    is discarded, and the clamp keeps every division strategy defined).
    ``mprime[s]`` is the Granlund-Montgomery round-up integer reciprocal
    ``ceil(2^(32+shift)/f) - 2^32`` (fits u32), giving the exact quotient

        t = mulhi(x, mprime);  q = (t + ((x - t) >> 1)) >> (shift - 1)

    for every f in [2, PROB_SCALE] and x < 2^32 (f <= 1 short-circuits to
    q = x in :func:`_enc_step`; brute-verified over all f in the tests).
    ``rcp[s] = 1/f`` in f32 drives the fast error-repaired strategy (see
    ``division="rcp32"`` in :func:`_enc_step`).  Built once per shard right
    after :func:`build_freq_table` — the two table divides below run
    256-wide once per shard, not per symbol, and never appear in the hot
    loop.
    """
    f = freq.astype(jnp.uint32)
    cum = (jnp.cumsum(freq) - freq).astype(jnp.uint32)
    # shift = ceil_log2(f) = #{k in [0,13) : 2^k < f}
    pows = jnp.uint32(1) << jax.lax.broadcasted_iota(jnp.uint32, (256, 13), 1)
    shift = (pows < f[:, None]).astype(jnp.uint32).sum(axis=1)
    s1 = jnp.maximum(shift, jnp.uint32(1)) - jnp.uint32(1)
    # ceil(2^(32+shift)/f) - 2^32 via 16+16-bit long division, u32-only:
    # hi2 = 2^(16+shift) <= 2^28; q_hi in [2^16, 2^17) so q_hi - 2^16 < 2^16
    fq = jnp.maximum(f, jnp.uint32(1))
    hi2 = jnp.uint32(1) << (jnp.uint32(16) + shift)
    q_hi = hi2 // fq
    num = (hi2 - q_hi * fq) << jnp.uint32(16)
    q_lo = num // fq
    r2 = num - q_lo * fq
    mprime = (
        ((q_hi - jnp.uint32(1 << 16)) << jnp.uint32(16))
        + q_lo
        + (r2 != 0).astype(jnp.uint32)
    )
    packed = fq | (s1 << jnp.uint32(13)) | (cum << jnp.uint32(19))
    return packed, mprime, jnp.float32(1.0) / fq.astype(jnp.float32)


def build_dec_table(freq: jax.Array) -> jax.Array:
    """(256,) int32 freqs -> packed u32 decode table ``f | cum_excl << 13``."""
    f = freq.astype(jnp.uint32)
    cum = (jnp.cumsum(freq) - freq).astype(jnp.uint32)
    return f | (cum << jnp.uint32(13))


def slot_to_symbol(freq: jax.Array) -> jax.Array:
    """(256,) freqs -> (PROB_SCALE,) inverse cumulative table, slot -> symbol.

    Direct cumulative-bucket fill: scatter-max each symbol id at its
    cumulative start slot, then a running max floods it across the
    symbol's [cum, cum + freq) bucket.  Zero-frequency symbols share a
    start slot with their successor and lose the max (the last symbol at a
    slot always has freq > 0 while any slot < PROB_SCALE remains), so no
    ``searchsorted`` — a 4096-wide binary-search gather per table — is
    needed anywhere in the decoder.
    """
    cum_excl = jnp.cumsum(freq) - freq
    sym = jax.lax.broadcasted_iota(jnp.int32, (256,), 0)
    start = jnp.where(freq > 0, cum_excl, PROB_SCALE)  # absent: dropped
    marks = jnp.zeros((PROB_SCALE,), jnp.int32).at[start].max(sym, mode="drop")
    return jax.lax.cummax(marks)


def _mulhi_u32(a: jax.Array, b: jax.Array) -> jax.Array:
    """High 32 bits of the u32 x u32 product, from 16-bit partials (no u64:
    x64 stays off, and the VPU has no 64-bit lanes either)."""
    al = a & jnp.uint32(0xFFFF)
    ah = a >> jnp.uint32(16)
    bl = b & jnp.uint32(0xFFFF)
    bh = b >> jnp.uint32(16)
    ll = al * bl
    lh = al * bh
    hl = ah * bl
    mid = (ll >> jnp.uint32(16)) + (lh & jnp.uint32(0xFFFF)) + (hl & jnp.uint32(0xFFFF))
    return ah * bh + (lh >> jnp.uint32(16)) + (hl >> jnp.uint32(16)) + (
        mid >> jnp.uint32(16)
    )


def _histogram(vals: jax.Array, n_valid) -> jax.Array:
    """Exact byte histogram of a zero-padded (T, 128) shard -> (256,) int32:
    the jnp oracle of :func:`byte_histogram`.

    One-hot matmul form: hist.reshape(16, 16) = onehot(hi)^T @ onehot(lo),
    an (N, 16) x (N, 16) f32 contraction over N — exact, because every
    partial sum is an integer <= N = T*128 <= 2^24.  Padding positions past
    ``n_valid`` are *zero bytes* by the ``ops.py`` contract, so their whole
    contribution lands in bin 0 and is subtracted back out.
    """
    n = vals.shape[0] * vals.shape[1]
    v = vals.reshape(n)
    eye16 = (
        jax.lax.broadcasted_iota(jnp.int32, (16, 16), 0)
        == jax.lax.broadcasted_iota(jnp.int32, (16, 16), 1)
    ).astype(jnp.float32)
    h2 = jax.lax.dot_general(
        eye16[v >> 4], eye16[v & 15], (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    counts = h2.reshape(256).astype(jnp.int32)
    sym = jax.lax.broadcasted_iota(jnp.int32, (256,), 0)
    return counts - jnp.where(sym == 0, n - n_valid, 0)


def _enc_step(x, packed, aux, *, division: str = "divide"):
    """One interleaved encode step: (states, sym tables) -> states'.

    Renorm-before-update with the 16-bit word convention: shift out the low
    half when x >= f << 20 (written shift-compare so f = PROB_SCALE cannot
    overflow the uint32 threshold); the caller recovers the emitted words
    and the emission mask from the returned pre-renorm states, so the hot
    loop carries nothing else.  The state update divides by freq with one
    of three exact, bit-identical strategies (asserted in the tests):

      * ``"divide"`` — the hardware udiv.  Fewest ops on paper, but LLVM
        scalarizes it on CPU (no vector u32 divide on x86) so the SIMD
        mulhi path beats it there; Mosaic has no integer division at all
        (which is what kept the PR-3 kernel off real TPUs).
      * ``"rcp32"`` — f32 reciprocal multiply with a +-1 integer repair.
        The renorm invariant bounds the true quotient by 2^20, so the
        faithful-rounding error of f32(x) * (1/f) is < 0.2 quotient units
        and the two-sided repair makes the result exact under ANY IEEE
        rounding — in particular it is immune to the x/c -> x*(1/c) jit
        canonicalization that breaks naive float kernels.  ``aux`` is the
        f32 reciprocal table value.
      * ``"reciprocal"`` — the all-integer Granlund-Montgomery mulhi
        path; ``aux`` is ``mprime``.  More vector ops than ``rcp32`` but
        float-free, for backends where that matters.

    Padding lanes look up a clamped f = 1 table entry; their state update
    is discarded by the caller, so the math only has to stay defined.
    Returns (updated states, pre-renorm states, emission flags).
    """
    f = packed & jnp.uint32(_SYM_MASK)
    c = packed >> jnp.uint32(19)
    x_pre = x
    emit = (x >> jnp.uint32(20)) >= f
    x = jnp.where(emit, x >> jnp.uint32(16), x)
    if division == "divide":
        q = x // f
    elif division == "rcp32":
        qh = (_u32_to_f32(x) * aux).astype(jnp.int32).astype(jnp.uint32)
        r = (x - qh * f).astype(jnp.int32)
        q = (
            qh
            + (r >= f.astype(jnp.int32)).astype(jnp.uint32)
            - (r < 0).astype(jnp.uint32)
        )
    else:  # "reciprocal"
        t = _mulhi_u32(x, aux)
        q = (t + ((x - t) >> jnp.uint32(1))) >> (
            (packed >> jnp.uint32(13)) & jnp.uint32(0x3F)
        )
        q = jnp.where(f <= jnp.uint32(1), x, q)
    # x' = (q << PROB_BITS) + (x mod f) + c, in ryg's mod-free arrangement
    x = x + q * (jnp.uint32(PROB_SCALE) - f) + c
    return x, x_pre, emit


def _dec_step(x, dec_packed, slot2sym):
    """One interleaved decode step -> (pre-renorm states, symbols, need-word).

    ``dec_packed``/``slot2sym`` are (..., 256) / (..., PROB_SCALE) tables
    indexed along their last axis (gathered by the caller so kernel and
    reference share one step body).
    """
    slot = (x & jnp.uint32(PROB_SCALE - 1)).astype(jnp.int32)
    s = jnp.take_along_axis(slot2sym, slot, axis=-1)
    p = jnp.take_along_axis(dec_packed, s, axis=-1)
    f = p & jnp.uint32(_SYM_MASK)
    c = (p >> jnp.uint32(13)) & jnp.uint32(_SYM_MASK)
    x = f * (x >> jnp.uint32(PROB_BITS)) + slot.astype(jnp.uint32) - c
    return x, s, x < jnp.uint32(RANS_L)


# Identity sentinel symbol entry: f = PROB_SCALE (shift = 12 -> s1 = 11),
# cum = 0.  _enc_step on it is an exact no-op for every division strategy:
# emit = (x >> 20) >= PROB_SCALE never fires for a 32-bit state, and
# x' = x + q*(PROB_SCALE - f) + cum = x regardless of what q computes.
_ENC_SENTINEL = PROB_SCALE | (11 << 13)


def _u32_to_f32(x):
    """uint32 -> nearest f32 through two exact int32 halves (Mosaic has no
    unsigned-to-float convert; one rounding, so it equals ``astype``)."""
    hi = (x >> jnp.uint32(16)).astype(jnp.int32).astype(jnp.float32)
    lo = (x & jnp.uint32(0xFFFF)).astype(jnp.int32).astype(jnp.float32)
    return hi * jnp.float32(65536.0) + lo


def _pad_shards(a, b_pad: int, fill=0):
    """Pad the leading shard axis to ``b_pad`` rows with ``fill``."""
    if a.shape[0] == b_pad:
        return a
    pad = [(0, b_pad - a.shape[0])] + [(0, 0)] * (a.ndim - 1)
    return jnp.pad(a, pad, constant_values=fill)


def _shards_padded(B: int, interpret: bool) -> int:
    """Shards ride the sublanes: Mosaic's lane gathers want whole sublane
    groups.  The interpreter takes any count, and every padding shard would
    only add unrolled per-shard work to its trace."""
    return B if interpret else -(-B // _SUBLANES) * _SUBLANES


def _halves(table):
    """(B, 256) per-shard table -> (2, B, 128): lane-gatherable halves."""
    return jnp.moveaxis(table.reshape(table.shape[0], 2, N_LANES), 1, 0)


def _gather256(halves, idx):
    """Per-shard 256-entry table lookup: idx (B, 128) int32 in [0, 256)."""
    li = idx & (N_LANES - 1)
    lo = jnp.take_along_axis(halves[0], li, axis=1, mode="promise_in_bounds")
    hi = jnp.take_along_axis(halves[1], li, axis=1, mode="promise_in_bounds")
    return jnp.where(idx >= N_LANES, hi, lo)


def _upper_ones():
    """(128, 128) bf16 [j <= l]: x @ it is the inclusive lane prefix sum."""
    return (
        jax.lax.broadcasted_iota(jnp.int32, (N_LANES, N_LANES), 0)
        <= jax.lax.broadcasted_iota(jnp.int32, (N_LANES, N_LANES), 1)
    ).astype(jnp.bfloat16)


def _prefix_sum(flags, upper):
    """Inclusive prefix count over the lanes of a (B, 128) bool array, as
    one 0/1 matmul (exact: every sum is <= 128)."""
    return jax.lax.dot_general(
        flags.astype(jnp.bfloat16), upper, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(jnp.int32)


# ------------------------------------------------------------- histogram
def _histogram_kernel(codes_ref, acc_ref, *, tile: int):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    n = 16 * _SUBLANES
    h_id = jax.lax.broadcasted_iota(jnp.int32, (n, N_LANES), 0) // _SUBLANES
    diag = (
        jax.lax.broadcasted_iota(jnp.int32, (n, n), 0) % _SUBLANES
        == jax.lax.broadcasted_iota(jnp.int32, (n, n), 1) % _SUBLANES
    )
    group = min(tile, 32)

    def body(g, acc):
        v = codes_ref[0, pl.ds(pl.multiple_of(g * group, group), group), :]
        v = v.astype(jnp.int32) & 0xFF
        for r0 in range(0, group, _SUBLANES):
            rows = v[r0:r0 + _SUBLANES]
            hi = jnp.concatenate([rows >> 4] * 16, axis=0)
            lo = jnp.concatenate([rows & 15] * 16, axis=0)
            a = (hi == h_id).astype(jnp.bfloat16)
            b = (lo == h_id).astype(jnp.bfloat16)
            c = jax.lax.dot_general(
                a, b, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            acc = acc + jnp.where(diag, c, 0.0)
        return acc

    acc_ref[0] += jax.lax.fori_loop(
        0, tile // group, body, jnp.zeros((n, n), jnp.float32)
    )


def byte_histogram(codes, n_valid, *, interpret: bool = True):
    """Exact byte histograms of B zero-padded (T, 128) int8 shards ->
    (B, 256) int32.  Padding positions past ``n_valid`` are zero bytes (the
    ``ops.py`` contract), so their count is subtracted from bin 0."""
    B, T, L = codes.shape
    tile = min(T, _HIST_TILE)
    n = 16 * _SUBLANES
    acc = pl.pallas_call(
        functools.partial(_histogram_kernel, tile=tile),
        grid=(B, T // tile),
        in_specs=[pl.BlockSpec((1, tile, L), lambda b, t: (b, t, 0))],
        out_specs=pl.BlockSpec((1, n, n), lambda b, t: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, n, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
        name="rans_histogram",
    )(codes)
    with jax.named_scope("rans_histogram_fold"):
        blocks = acc.reshape(B, 16, _SUBLANES, 16, _SUBLANES)
        counts = jnp.einsum("bhrlr->bhl", blocks).reshape(B, 256)
        counts = counts.astype(jnp.int32)
        pad = T * L - n_valid.reshape(B, 1)
        sym = jax.lax.broadcasted_iota(jnp.int32, (1, 256), 1)
        return counts - jnp.where(sym == 0, pad, 0)


# ---------------------------------------------------------------- encode
def stream_word_cap(T: int) -> int:
    """Worst-case u16 stream words worth keeping for a T-row shard (any
    shard emitting more compresses to >= its raw size and is stored raw,
    so capping the stream here discards only streams the raw-skip select
    would discard anyway)."""
    return max(1, (T * N_LANES - 1536) // 2)


def _encode_geometry(T: int, tile: int):
    """(window rows, first back-to-front stream position, buffer rows)."""
    win = tile + 16
    p0 = (T + win + 8) * N_LANES
    return win, p0, T + T // 2 + 2 * win + 16


def _encode_kernel(codes_ref, pk_ref, rcp_ref, nv_ref, buf_hbm, lens_ref,
                   states_ref, x_ref, pos_ref, win_ref, sem, *, tile: int,
                   n_tiles: int, n_shards: int, win_rows: int, p0: int):
    j = pl.program_id(0)
    t = n_tiles - 1 - j                       # row tiles run in reverse

    @pl.when(j == 0)
    def _init():
        x_ref[...] = jnp.full(x_ref.shape, RANS_L, jnp.uint32)
        lens_ref[...] = jnp.zeros(lens_ref.shape, jnp.int32)
        for s in range(n_shards):
            pos_ref[s] = jnp.int32(p0)

    # the stream is written back to front: this tile's words land in the
    # rows just below each shard's write position.  Stage those rows (the
    # top one may hold words of the previous tile) in a VMEM window.
    w0 = [
        ((pos_ref[s] - tile * N_LANES) >> 10) << 3 for s in range(n_shards)
    ]

    def window_copies(to_hbm: bool):
        out = []
        for s in range(n_shards):
            hbm = buf_hbm.at[s, pl.ds(pl.multiple_of(w0[s], 8), win_rows)]
            src, dst = (win_ref.at[s], hbm) if to_hbm else (hbm, win_ref.at[s])
            out.append(pltpu.make_async_copy(src, dst, sem.at[s]))
        for c in out:
            c.start()
        for c in out:
            c.wait()

    window_copies(False)

    pk = (pk_ref[0], pk_ref[1])
    rc = (rcp_ref[0], rcp_ref[1])
    nv = nv_ref[...]
    B = n_shards
    lane = jax.lax.broadcasted_iota(jnp.int32, nv.shape, 1)
    upper = _upper_ones()
    dest = jax.lax.broadcasted_iota(jnp.int32, (B, N_LANES, 2 * N_LANES), 2)
    out_lane = jax.lax.broadcasted_iota(jnp.int32, (B, 2 * N_LANES), 1)

    def row(i, carry):
        x, lens, offs = carry                 # offs: (B, 1) window offsets
        r = tile - 1 - i
        v = codes_ref[r].astype(jnp.int32) & 0xFF          # (B, 128)
        valid = (t * tile + r) * N_LANES + lane < nv
        p = jnp.where(valid, _gather256(pk, v), jnp.uint32(_ENC_SENTINEL))
        x2, x_pre, emit = _enc_step(x, p, _gather256(rc, v), division="rcp32")
        e = emit.astype(jnp.int32)
        incl = _prefix_sum(emit, upper)
        n = incl[:, N_LANES - 1:]                          # words this row
        o = offs - n
        lo = o & (N_LANES - 1)
        # one-hot placement: emitting lane l's word goes to lane
        # lo + excl[l] of the 256-lane pair of window rows (o >> 7, +1);
        # its two bytes are exact in bf16 and each sum has one term
        slot = jnp.where(emit, incl - e + lo, -1)
        place = (slot[:, :, None] == dest).astype(jnp.bfloat16)
        word = (x_pre & jnp.uint32(0xFFFF)).astype(jnp.int32)
        bts = jnp.stack([word & 0xFF, word >> 8], axis=1).astype(jnp.bfloat16)
        placed = jax.lax.dot_general(
            bts, place, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        ).astype(jnp.int32)                                # (B, 2, 256)
        val = placed[:, 0] + (placed[:, 1] << 8)
        hit = (out_lane >= lo) & (out_lane < lo + n)
        ro = o >> 7
        cur = jnp.concatenate(
            [
                jnp.concatenate(
                    [win_ref[s, pl.ds(ro[s, 0], 1), :],
                     win_ref[s, pl.ds(ro[s, 0] + 1, 1), :]], axis=1,
                )
                for s in range(B)
            ],
            axis=0,
        )
        merged = jnp.where(hit, val, cur)
        for s in range(B):
            win_ref[s, pl.ds(ro[s, 0], 1), :] = merged[s:s + 1, :N_LANES]
            win_ref[s, pl.ds(ro[s, 0] + 1, 1), :] = merged[s:s + 1, N_LANES:]
        return x2, lens + e, o

    sub = jax.lax.broadcasted_iota(jnp.int32, (B, 1), 0)
    offs0 = jnp.zeros((B, 1), jnp.int32)
    for s in range(B):
        offs0 = jnp.where(sub == s, pos_ref[s] - w0[s] * N_LANES, offs0)
    x, lens, offs = jax.lax.fori_loop(
        0, tile, row, (x_ref[...], lens_ref[...], offs0)
    )
    window_copies(True)
    x_ref[...] = x
    states_ref[...] = x
    lens_ref[...] = lens
    for s in range(B):
        pos_ref[s] = w0[s] * N_LANES + offs[s, 0]


def rans_encode_pallas(codes, n_valid, *, interpret: bool = True):
    """Encode B shards: histogram -> tables -> coding loop (see module doc).

    codes: (B, T, 128) int8 payload rows, zero-padded (the histogram's pad
    correction requires the padding bytes to BE zero — ``ops.py``
    guarantees it); T % T_TILE == 0.
    n_valid: (B, 1) int32 valid byte count per shard — positions past it
    are padding: they are excluded from the histogram and their lanes idle,
    costing zero stream bytes.
    Returns (words (B, cap) uint16, n_words (B,) int32, lane_lens (B, 128)
    int32, freq (B, 256) int32, states (B, 128) uint32): each shard's
    emitted words in row-major decoder-read order (zero past n_words; cap
    = :func:`stream_word_cap`), its per-lane word counts, frequency table
    and the final lane states the decoder starts from.
    """
    B, T, L = codes.shape
    if L != N_LANES:
        raise ValueError(f"expected {N_LANES} lanes, got {L}")
    if T % T_TILE:
        raise ValueError(f"rows {T} not a multiple of {T_TILE}")
    Bp = _shards_padded(B, interpret)
    # rows per grid step: a power of two (so it divides T) sized to the
    # block budget; a pure schedule, the outputs do not depend on it
    budget = max(T_TILE, _TILE_ELEMS // (Bp * N_LANES))
    tile = min(T, 1 << (budget.bit_length() - 1))
    win_rows, p0, buf_rows = _encode_geometry(T, tile)
    codes_p = _pad_shards(codes, Bp)
    nv = _pad_shards(n_valid.reshape(B, 1).astype(jnp.int32), Bp)
    counts = byte_histogram(codes_p, nv, interpret=interpret)
    with jax.named_scope("rans_tables"):
        freq = jax.vmap(build_freq_table)(counts)
        packed, _, rcp = jax.vmap(build_enc_tables)(freq)
        codes_t = jnp.swapaxes(codes_p, 0, 1)                # (T, Bp, 128)
        nv_b = jnp.broadcast_to(nv, (Bp, N_LANES))
    n_tiles = T // tile
    rev = lambda j: (n_tiles - 1 - j, 0, 0)
    whole = pl.BlockSpec((2, Bp, N_LANES), lambda j: (0, 0, 0))
    lanes = pl.BlockSpec((Bp, N_LANES), lambda j: (0, 0))
    buf, lens, states = pl.pallas_call(
        functools.partial(_encode_kernel, tile=tile, n_tiles=n_tiles,
                          n_shards=Bp, win_rows=win_rows, p0=p0),
        grid=(n_tiles,),
        in_specs=[pl.BlockSpec((tile, Bp, N_LANES), rev), whole, whole, lanes],
        out_specs=[pl.BlockSpec(memory_space=pl.ANY), lanes, lanes],
        out_shape=[
            jax.ShapeDtypeStruct((Bp, buf_rows, N_LANES), jnp.int32),
            jax.ShapeDtypeStruct((Bp, N_LANES), jnp.int32),
            jax.ShapeDtypeStruct((Bp, N_LANES), jnp.uint32),
        ],
        scratch_shapes=[
            pltpu.VMEM((Bp, N_LANES), jnp.uint32),
            pltpu.SMEM((Bp,), jnp.int32),
            pltpu.VMEM((Bp, win_rows, N_LANES), jnp.int32),
            pltpu.SemaphoreType.DMA((Bp,)),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)
        ),
        interpret=interpret,
        name="rans_encode",
    )(codes_t, _halves(packed), _halves(rcp), nv_b)
    with jax.named_scope("rans_stream_slice"):
        cap = stream_word_cap(T)
        n_words = lens.sum(axis=1)
        words = jax.vmap(
            lambda b, p: jax.lax.dynamic_slice(b, (p,), (cap,))
        )(buf.reshape(Bp, -1), p0 - n_words)
        k = jax.lax.broadcasted_iota(jnp.int32, (1, cap), 1)
        words = jnp.where(k < n_words[:, None], words, 0).astype(jnp.uint16)
    return words[:B], n_words[:B], lens[:B], freq[:B], states[:B]


# ---------------------------------------------------------------- decode
def _decode_kernel(stream_hbm, cum_ref, states_ref, nv_ref, codes_ref,
                   x_ref, base_ref, win_ref, sem, *, tile: int, n_shards: int,
                   win_rows: int):
    j = pl.program_id(0)

    @pl.when(j == 0)
    def _init():
        x_ref[...] = states_ref[...]
        for s in range(n_shards):
            base_ref[s] = jnp.int32(0)

    # refill each shard's stream window: rows [w0, w0 + win_rows) with w0
    # the read pointer's row, aligned down to a sublane group
    w0 = [(base_ref[s] >> 10) << 3 for s in range(n_shards)]
    copies = [
        pltpu.make_async_copy(
            stream_hbm.at[s, pl.ds(pl.multiple_of(w0[s], 8), win_rows)],
            win_ref.at[s], sem.at[s],
        )
        for s in range(n_shards)
    ]
    for c in copies:
        c.start()
    for c in copies:
        c.wait()

    cum = (cum_ref[0], cum_ref[1])               # inclusive cumulative freqs
    nv = nv_ref[...]
    B = n_shards
    shape = nv.shape
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    upper = _upper_ones()

    def row(r, carry):
        x, offs = carry                       # offs: (B, 1) window offsets
        slot = (x & jnp.uint32(PROB_SCALE - 1)).astype(jnp.int32)
        # symbol = #{k : cum_incl[k] <= slot}, by branchless binary search
        sym = jnp.zeros(shape, jnp.int32)
        for b in (128, 64, 32, 16, 8, 4, 2, 1):
            t = sym + b
            sym = jnp.where(_gather256(cum, t - 1) <= slot, t, sym)
        c_lo = jnp.where(sym > 0, _gather256(cum, jnp.maximum(sym - 1, 0)), 0)
        c_hi = _gather256(cum, sym)
        x2 = (c_hi - c_lo).astype(jnp.uint32) * (x >> jnp.uint32(PROB_BITS)) + (
            slot - c_lo
        ).astype(jnp.uint32)
        valid = (j * tile + r) * N_LANES + lane < nv
        need = (x2 < jnp.uint32(RANS_L)) & valid
        incl = _prefix_sum(need, upper)
        # the renormalizing lanes take the next words in lane order: lane l
        # reads word offs + excl[l], from window row (offs >> 7) or the next
        q = (offs & (N_LANES - 1)) + incl - need.astype(jnp.int32)
        wr = offs >> 7
        rows_a = jnp.concatenate(
            [win_ref[s, pl.ds(wr[s, 0], 1), :] for s in range(B)], axis=0
        )
        rows_b = jnp.concatenate(
            [win_ref[s, pl.ds(wr[s, 0] + 1, 1), :] for s in range(B)], axis=0
        )
        li = q & (N_LANES - 1)
        w = jnp.where(
            q >= N_LANES,
            jnp.take_along_axis(rows_b, li, axis=1, mode="promise_in_bounds"),
            jnp.take_along_axis(rows_a, li, axis=1, mode="promise_in_bounds"),
        ).astype(jnp.uint32)
        x2 = jnp.where(need, (x2 << jnp.uint32(16)) | w, x2)
        codes_ref[r] = jnp.where(valid, sym - ((sym & 0x80) << 1), 0).astype(
            jnp.int8
        )
        return jnp.where(valid, x2, x), offs + incl[:, N_LANES - 1:]

    sub = jax.lax.broadcasted_iota(jnp.int32, (B, 1), 0)
    offs0 = jnp.zeros((B, 1), jnp.int32)
    for s in range(B):
        offs0 = jnp.where(sub == s, base_ref[s] - w0[s] * N_LANES, offs0)
    x, offs = jax.lax.fori_loop(0, tile, row, (x_ref[...], offs0))
    x_ref[...] = x
    for s in range(B):
        base_ref[s] = w0[s] * N_LANES + offs[s, 0]


def rans_decode_pallas(stream, freq, states, n_valid, *, rows: int,
                       interpret: bool = True):
    """Version-1 decode: flat row-major word streams -> original bytes.

    stream: (B, W) uint16 — each shard's words in global decoder-read order
    (tails past the shard's word count are never consumed).  freq: (B, 256)
    int32 tables; states: (B, 128) uint32 initial lane states; n_valid:
    (B, 1) int32 — must equal the encoder's.
    Returns (B, rows, 128) int8 decoded payload rows, zeros past n_valid.
    """
    B, W = stream.shape
    if rows % T_TILE:
        raise ValueError(f"rows {rows} not a multiple of {T_TILE}")
    Bp = _shards_padded(B, interpret)
    tile = min(rows, _DEC_TILE)
    win_rows = tile + 16
    with jax.named_scope("rans_decode_tables"):
        # dummy shards decode against a degenerate-but-valid table (symbol
        # 0 owns the whole range); n_valid = 0 idles their lanes
        freq_p = jnp.concatenate(
            [freq.astype(jnp.int32),
             jnp.zeros((Bp - B, 256), jnp.int32).at[:, 0].set(PROB_SCALE)]
        )
        cum = jnp.cumsum(freq_p, axis=1)
        n_rows = -(-W // N_LANES) + win_rows + 8
        words = jnp.pad(
            stream.astype(jnp.int32), ((0, Bp - B), (0, n_rows * N_LANES - W))
        ).reshape(Bp, n_rows, N_LANES)
        st = _pad_shards(states.astype(jnp.uint32), Bp, RANS_L)
        nv = jnp.broadcast_to(
            _pad_shards(n_valid.reshape(B, 1).astype(jnp.int32), Bp),
            (Bp, N_LANES),
        )
    whole = lambda shape: pl.BlockSpec(shape, lambda j: (0,) * len(shape))
    codes = pl.pallas_call(
        functools.partial(_decode_kernel, tile=tile, n_shards=Bp,
                          win_rows=win_rows),
        grid=(rows // tile,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            whole((2, Bp, N_LANES)),
            whole((Bp, N_LANES)),
            whole((Bp, N_LANES)),
        ],
        out_specs=pl.BlockSpec((tile, Bp, N_LANES), lambda j: (j, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, Bp, N_LANES), jnp.int8),
        scratch_shapes=[
            pltpu.VMEM((Bp, N_LANES), jnp.uint32),
            pltpu.SMEM((Bp,), jnp.int32),
            pltpu.VMEM((Bp, win_rows, N_LANES), jnp.int32),
            pltpu.SemaphoreType.DMA((Bp,)),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)
        ),
        interpret=interpret,
        name="rans_decode",
    )(words, _halves(cum), st, nv)
    return jnp.swapaxes(codes, 0, 1)[:B]
