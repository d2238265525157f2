"""Pallas TPU kernel: block-matching motion estimation.

TPU adaptation of the paper's FPGA motion-estimation stage (§3: "dedicated
hardware blocks ... leverage FPGA's DSP slices for fast cross-correlation or
block matching").  The VPU plays the DSP-slice role: for one row of blocks per
grid step, all (2R+1)^2 candidate offsets are evaluated as full-row absolute
differences, reduced per block, and arg-minimized.

Halo handling: the previous frame is padded by one *full block row* top and
bottom (edge replication) plus R columns left/right, and fetched as three
consecutive row-blocks (i, i+1, i+2 of the padded frame = i-1, i, i+1 of the
original).  The (block + 2R)-row search window is staged in VMEM scratch
as one (block, W + 2R) row set per vertical offset; a ``fori_loop`` over
the vertical offset loads its set by a dynamic leading index, and the
horizontal offsets are static lane slices.

Per-block SADs: the (block, W) absolute differences reduce over each
block's 16 columns as one matmul against a (W, nbx) 0/1 block-indicator
matrix (f32 at HIGHEST precision: every partial sum is an exact integer),
then over the block's rows.  All SAD values are exact integers, so ties
break exactly as in ref.py (the smallest linear offset index wins).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["block_motion_pallas"]


def _motion_kernel(cur_ref, ptop_ref, pmid_ref, pbot_ref, dy_ref, dx_ref,
                   sad_ref, win_ref, *, block: int, radius: int, nbx: int):
    side = 2 * radius + 1
    W = nbx * block
    cur = cur_ref[...].astype(jnp.float32)                   # (block, W)
    rows = jnp.concatenate(
        [ptop_ref[...], pmid_ref[...], pbot_ref[...]], axis=0
    )                                                        # (3*block, Wp)
    # one (block, Wp) candidate row set per vertical offset, on a leading
    # scratch axis so the loop below indexes it dynamically
    for dy in range(side):
        r0 = block - radius + dy
        win_ref[dy] = rows[r0:r0 + block].astype(jnp.float32)
    ind = (
        jax.lax.broadcasted_iota(jnp.int32, (W, nbx), 0) // block
        == jax.lax.broadcasted_iota(jnp.int32, (W, nbx), 1)
    ).astype(jnp.float32)

    def body(dy, carry):
        best_sad, best_o = carry
        cand_rows = win_ref[dy]                              # (block, Wp)
        for dx in range(side):
            diff = jnp.abs(cur - cand_rows[:, dx:dx + W])
            per_col = jax.lax.dot_general(
                diff, ind, (((1,), (0,)), ((), ())),
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32,
            )                                                # (block, nbx)
            sad = jnp.sum(per_col, axis=0, keepdims=True).astype(jnp.int32)
            take = sad < best_sad
            best_sad = jnp.where(take, sad, best_sad)
            best_o = jnp.where(take, dy * side + dx, best_o)
        return best_sad, best_o

    init = (
        jnp.full((1, nbx), jnp.iinfo(jnp.int32).max, jnp.int32),
        jnp.zeros((1, nbx), jnp.int32),
    )
    best_sad, best_o = jax.lax.fori_loop(0, side, body, init)
    dy_ref[0] = best_o // side - radius
    dx_ref[0] = best_o % side - radius
    sad_ref[0] = best_sad


def block_motion_pallas(
    cur: jax.Array,
    prev_padded: jax.Array,
    *,
    block: int = 16,
    radius: int = 8,
    interpret: bool = True,
):
    """cur: (H, W) int32 luma in [0, 255]; prev_padded: (H + 2*block,
    W + 2*radius) int32 (one block row of edge padding top/bottom, radius
    columns left/right — built by ops.py).  Returns (dy, dx, sad) each
    (nby, nbx) int32.
    """
    H, W = cur.shape
    if H % block or W % block:
        raise ValueError(f"frame {cur.shape} not a multiple of block {block}")
    if radius > block:
        raise ValueError(f"radius {radius} > block {block} unsupported by halo trick")
    nby, nbx = H // block, W // block
    Hp, Wp = prev_padded.shape
    if Hp != H + 2 * block or Wp != W + 2 * radius:
        raise ValueError(f"prev_padded {prev_padded.shape} != {(H + 2 * block, W + 2 * radius)}")
    # whole lane tiles for the padded rows (the extra columns are never read)
    Wl = -(-Wp // 128) * 128
    prev_l = jnp.pad(prev_padded, ((0, 0), (0, Wl - Wp)))

    kernel = functools.partial(
        _motion_kernel, block=block, radius=radius, nbx=nbx
    )
    out = jax.ShapeDtypeStruct((nby, 1, nbx), jnp.int32)
    row_spec = pl.BlockSpec((1, 1, nbx), lambda i: (i, 0, 0))
    dy, dx, sad = pl.pallas_call(
        kernel,
        grid=(nby,),
        in_specs=[
            pl.BlockSpec((block, W), lambda i: (i, 0)),  # current block row
            pl.BlockSpec((block, Wl), lambda i: (i, 0)),  # prev row-block i-1 (padded)
            pl.BlockSpec((block, Wl), lambda i: (i + 1, 0)),  # prev row-block i
            pl.BlockSpec((block, Wl), lambda i: (i + 2, 0)),  # prev row-block i+1
        ],
        out_specs=[row_spec, row_spec, row_spec],
        out_shape=[out, out, out],
        scratch_shapes=[pltpu.VMEM((2 * radius + 1, block, Wl), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)
        ),
        interpret=interpret,
        name="motion_search",
    )(cur, prev_l, prev_l, prev_l)
    return dy[:, 0], dx[:, 0], sad[:, 0]
