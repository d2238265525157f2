"""Public wrappers for the interleaved-rANS entropy stage: padding, dispatch,
stream packing, accounting.

``encode_payloads`` / ``decode_payloads`` accept ragged per-shard payloads,
pad them to the kernel's (T, 128) lane grid (T pow2-bucketed like
``seal_ops.bucket_rows_for`` so jit traces stay bounded for mixed GOP
sizes), dispatch either the Pallas coder (histogram + coding kernels) or
the staged jnp oracle (``use_pallas=False``), and pack the result into a
self-contained compressed byte stream per shard:

    [freq table: 256 x u16][lane lengths: 128 x u32][lane states: 128 x u32]
    [16-bit words in global decoder-read order (row-major across lanes)]

Everything a decoder needs except the raw/compressed lengths and the stream
``version`` (tiny host metadata, recorded in the archive manifest like
``n_i8``) travels inside the stream, so the compression-ratio accounting is
honest: ``n_comp`` includes the 1536-byte header.  The stream bytes are
what the seal kernel encrypts and parity-codes — the entropy stage output
never has to visit the host.

Stream versions: version 1 (current) packs words row-major — the order a
forward decode consumes them — so the decoder runs a single prefix-summed
stream pointer and parsing is a straight byte split.  Version 0 (PR-4)
packed per-lane-contiguous word runs; those streams still decode through
``_parse_streams_v0`` + the lane-major kernel twin.  Both share one header
layout (the lane-length table is self-description/integrity metadata for
v1 — its offsets are only *required* for v0's re-gather), so a version
bump never changes ``n_comp``: the compression ratio is identical by
construction.

Compaction of the emitted words into the row-major stream happens inside
the encode kernel (``rans.rans_encode_pallas``); this module serializes the
header and the word area as little-endian u32 words (``stream_words``, the
stream's bytes four to a word).  ``core_fn`` overrides the coder launch
itself; the sharded path (``repro.distributed.archival``) passes a
shard_map'd wrapper with the same signature, exactly like
``seal_fn``/``unseal_fn`` in the seal pipeline.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import (
    as_payload_list,
    host_prefixes,
    le_words,
    stack_rows,
    use_interpret,
)
from repro.kernels.entropy import ref as _ref
from repro.kernels.entropy.rans import (
    N_LANES,
    STREAM_VERSION,
    T_TILE,
    rans_decode_pallas,
    rans_encode_pallas,
    stream_word_cap,
)

__all__ = [
    "HEADER_BYTES",
    "MAX_ROWS",
    "rows_for",
    "stream_word_cap",
    "encode_payloads",
    "decode_payloads",
    "entropy_traffic",
]

# freq u16[256] + lane_lens u32[128] + states u32[128]
HEADER_BYTES = 2 * 256 + 4 * N_LANES + 4 * N_LANES
# 2^17 lane rows = 16 MiB per shard: the practical bound (one stripe
# shard is a GOP or a checkpoint chunk, not GBs), and it keeps the
# histogram's one-hot operands and the coder's working set a size one
# kernel residency can reasonably hold
MAX_ROWS = 1 << 17


def rows_for(n_bytes: int) -> int:
    """Smallest pow2 multiple of ``T_TILE`` lane rows covering n_bytes.

    Pow2 bucketing bounds jit traces at log2(max_rows) for arbitrarily
    ragged payload mixes (same scheme as ``seal_ops.bucket_rows_for``); the
    padding bytes are zeros, which the coder squeezes to ~0 bits each.
    """
    rows = max(1, -(-n_bytes // N_LANES))
    tiles = -(-rows // T_TILE)
    return T_TILE * (1 << (tiles - 1).bit_length())


@functools.partial(
    jax.jit, static_argnames=("use_pallas", "interpret", "division")
)
def _encode_core(codes, n_valid, *, use_pallas: bool, interpret: bool,
                 division: Optional[str] = None):
    """The coder launch -> (words, n_words, lane_lens, freq, states), the
    compacted stream words of ``rans.rans_encode_pallas``.  ``division``
    picks the jnp oracle's per-symbol division strategy
    (``use_pallas=False``); the kernel always runs the repaired f32
    reciprocal — all strategies give identical bits."""
    if use_pallas:
        return rans_encode_pallas(codes, n_valid, interpret=interpret)
    words, mask, freq, states = _ref.rans_encode_ref(
        codes, n_valid, division=division or "divide"
    )
    comp, n_words, lane_lens = _ref.compact_ref(
        words, mask, stream_word_cap(codes.shape[1])
    )
    return comp, n_words, lane_lens, freq, states


@functools.partial(
    jax.jit, static_argnames=("version", "rows", "use_pallas", "interpret")
)
def _decode_core(words, freq, states, n_valid, *, version: int, rows: int,
                 use_pallas: bool, interpret: bool):
    if version == 0:
        # PR-4 lane-major streams decode through the jnp oracle only
        return _ref.rans_decode_ref_v0(words, freq, states, n_valid)
    if use_pallas:
        return rans_decode_pallas(
            words, freq, states, n_valid, rows=rows, interpret=interpret
        )
    return _ref.rans_decode_ref(words, freq, states, n_valid, rows=rows)


def stream_words(words, lane_lens, freq, states):
    """Header + word area of B v1 streams as little-endian u32 words ->
    (B, 384 + ceil(cap / 2)) uint32: the stream's bytes, four per word
    (the 1536-byte header is 384 whole words).  ``words`` (B, cap) uint16
    is zero past each shard's emission count."""
    with jax.named_scope("rans_serialize"):
        return jnp.concatenate(
            [le_words(freq, 2), lane_lens.astype(jnp.uint32),
             states.astype(jnp.uint32), le_words(words, 2)],
            axis=1,
        )


@jax.jit
def _pack_streams(words, n_words, lane_lens, freq, states):
    """Host-path serialize: (the streams as u32 words — the host views
    them as bytes — and the (S,) emission counts)."""
    return stream_words(words, lane_lens, freq, states), n_words


def _parse_header(comp):
    """Padded compressed bytes (S, C) uint8 -> (freq, lane_lens, states)."""
    u = comp.astype(jnp.int32)
    freq = u[:, 0:512:2] | (u[:, 1:512:2] << 8)              # (S, 256)
    lane_lens = (
        u[:, 512:1024:4]
        | (u[:, 513:1024:4] << 8)
        | (u[:, 514:1024:4] << 16)
        | (u[:, 515:1024:4] << 24)
    )                                                        # (S, 128)
    su = comp.astype(jnp.uint32)
    states = (
        su[:, 1024:1536:4]
        | (su[:, 1025:1536:4] << jnp.uint32(8))
        | (su[:, 1026:1536:4] << jnp.uint32(16))
        | (su[:, 1027:1536:4] << jnp.uint32(24))
    )                                                        # (S, 128)
    return freq, lane_lens, states


@jax.jit
def _parse_streams(comp):
    """Version-1 parse: header split + flat u16 word view, no re-gather.

    The row-major word area is already in decoder-read order, so the
    decode kernel consumes it directly with its prefix-summed pointer.
    """
    freq, _, states = _parse_header(comp)
    body = comp[:, HEADER_BYTES:].astype(jnp.int32)
    W = body.shape[1] // 2
    stream = (body[:, 0 : 2 * W : 2] | (body[:, 1 : 2 * W : 2] << 8)).astype(
        jnp.uint16
    )
    return stream, freq, states


@functools.partial(jax.jit, static_argnames=("rows",))
def _parse_streams_v0(comp, *, rows: int):
    """Version-0 parse: re-gather the lane-major word runs into the
    (S, T, 128) per-lane layout the legacy decode twin scans: word j of
    lane l sits at stream[off(l) + j].  Positions past a lane's length
    gather a clamped index — never consumed, because the decoder's renorm
    flags mirror the encoder's emissions."""
    S, C = comp.shape
    freq, lane_lens, states = _parse_header(comp)
    body = comp[:, HEADER_BYTES:].astype(jnp.int32)
    W = body.shape[1] // 2
    stream = (body[:, 0 : 2 * W : 2] | (body[:, 1 : 2 * W : 2] << 8)).astype(
        jnp.uint16
    )
    off = jnp.cumsum(lane_lens, axis=-1) - lane_lens         # exclusive
    idx = off[:, None, :] + jnp.arange(rows, dtype=jnp.int32)[None, :, None]
    idx = jnp.clip(idx, 0, W - 1).reshape(S, rows * N_LANES)
    lane_words = jnp.take_along_axis(stream, idx, axis=1).reshape(
        S, rows, N_LANES
    )
    return lane_words, freq, states


def encode_payloads(
    payloads,
    *,
    use_pallas: bool = True,
    interpret: Optional[bool] = None,
    division: Optional[str] = None,
    core_fn=None,
) -> Tuple[List[jax.Array], List[Dict]]:
    """rANS-encode S ragged shard payloads in one fused launch.

    payloads: list of flat int8 arrays (ragged ok) or an (S, N) int8 array.
    Returns (compressed int8 streams — exact length, header included; coded
    shards come back as host numpy slices of the one blocking fetch, raw
    shards pass their device payload through — and per-shard metas
    ``{"codec", "version", "n_raw", "n_comp", "rows"}``).
    ``rows`` is the padded lane-row count the whole stripe was coded at;
    decode needs it back.  ``version`` is the stream format version the
    decoder dispatches on.  ``core_fn`` overrides the coder launch (the
    sharded path).
    """
    flats = as_payload_list(payloads)
    if not flats:
        raise ValueError("stripe must contain at least one shard payload")
    n_raw = tuple(int(f.shape[0]) for f in flats)
    T = rows_for(max(n_raw))
    if T > MAX_ROWS:
        raise ValueError(
            f"payload of {max(n_raw)} bytes needs {T} lane rows (max "
            f"{MAX_ROWS}); split it across more stripe shards"
        )
    codes = stack_rows(flats, T, N_LANES, np.int8)
    n_valid = jnp.asarray(n_raw, jnp.int32).reshape(-1, 1)
    if core_fn is None:
        core_fn = functools.partial(
            _encode_core, use_pallas=use_pallas,
            interpret=use_interpret(interpret), division=division,
        )
    comp_pad, n_words_dev = _pack_streams(*core_fn(codes, n_valid))
    # ONE blocking device->host fetch covers the stream bytes and the
    # emission counts the manifest needs; slicing the host buffer is then
    # free, where per-shard eager device slices each paid a dispatch
    buf = np.asarray(comp_pad).view(np.int8)
    n_words = [int(n) for n in np.asarray(n_words_dev)]
    n_comp = [HEADER_BYTES + 2 * nw for nw in n_words]
    comps, metas = [], []
    for s, (nr, nc) in enumerate(zip(n_raw, n_comp)):
        if nc >= nr:
            # adaptive raw-skip: an incompressible shard (or one smaller
            # than the 1536-byte stream header) is stored as-is; the
            # manifest flag is what the decode path dispatches on
            comps.append(flats[s].reshape(-1).astype(jnp.int8))
            metas.append(
                {"codec": "rans", "version": STREAM_VERSION, "raw": True,
                 "n_raw": nr, "n_comp": nr, "rows": T}
            )
        else:
            comps.append(buf[s, :nc])
            metas.append(
                {"codec": "rans", "version": STREAM_VERSION,
                 "n_raw": nr, "n_comp": nc, "rows": T}
            )
    return comps, metas


def decode_payloads(
    comps: Sequence[jax.Array],
    metas: Sequence[Dict],
    *,
    use_pallas: bool = True,
    interpret: Optional[bool] = None,
    core_fn=None,
) -> List[np.ndarray]:
    """Decode twin: compressed streams + metas -> exact original payloads.

    Dispatches on the *recorded* stream ``version`` (absent = 0, the PR-4
    lane-major format, so old archives and checkpoints stay readable).
    Shards the encoder flagged ``raw`` (adaptive raw-skip: compressed would
    have been >= raw) pass through untouched; only the genuinely coded
    shards enter the kernel launch, so a stripe that mixes both still runs
    one launch.  Works identically under the sharded ``core_fn``.  Returns
    host int8 arrays.
    """
    if len(comps) != len(metas):
        raise ValueError(f"{len(comps)} streams vs {len(metas)} metas")
    if not comps:
        raise ValueError("stripe must contain at least one shard payload")
    T = int(metas[0]["rows"])
    if any(int(m["rows"]) != T for m in metas):
        raise ValueError("all shards of a stripe share one padded row count")
    # ragged streams are staged and cut on the host: a device pad or slice
    # per ragged length would compile a program per GOP size
    flats = [np.asarray(c).reshape(-1).astype(np.uint8) for c in comps]
    out: List[Optional[np.ndarray]] = [None] * len(flats)
    coded: List[int] = []
    for i, (f, m) in enumerate(zip(flats, metas)):
        if int(f.shape[0]) != int(m["n_comp"]):
            raise ValueError(
                f"stream is {int(f.shape[0])} bytes, manifest says {m['n_comp']}"
            )
        if m.get("raw"):
            if int(m["n_comp"]) != int(m["n_raw"]):
                raise ValueError(
                    f"raw-skip shard must store n_raw bytes, manifest says "
                    f"{m['n_comp']} vs {m['n_raw']}"
                )
            out[i] = f.view(np.int8)
            continue
        if int(f.shape[0]) < HEADER_BYTES:
            raise ValueError("compressed stream shorter than its header")
        coded.append(i)
    if coded:
        versions = {int(metas[i].get("version", 0)) for i in coded}
        if len(versions) != 1:
            raise ValueError(
                f"stripe mixes stream versions {sorted(versions)}"
            )
        version = versions.pop()
        # common padded width: the bucket's stream capacity (one program
        # per row bucket), stream area even and >= one word (tails unread)
        C = max(
            HEADER_BYTES + 2 * stream_word_cap(T),
            max(int(flats[i].shape[0]) for i in coded),
        )
        C += (C - HEADER_BYTES) % 2
        comp = np.zeros((len(coded), C), np.uint8)
        for j, i in enumerate(coded):
            comp[j, : flats[i].shape[0]] = flats[i]
        comp = jnp.asarray(comp)
        if version == 0:
            words, freq, states = _parse_streams_v0(comp, rows=T)
        else:
            words, freq, states = _parse_streams(comp)
        n_valid = jnp.asarray(
            [int(metas[i]["n_raw"]) for i in coded], jnp.int32
        ).reshape(-1, 1)
        if core_fn is None:
            core_fn = functools.partial(
                _decode_core, use_pallas=use_pallas,
                interpret=use_interpret(interpret),
            )
        codes = core_fn(words, freq, states, n_valid, version=version, rows=T)
        got = host_prefixes(codes, [int(metas[i]["n_raw"]) for i in coded])
        for j, i in enumerate(coded):
            out[i] = got[j]
    return out


def entropy_traffic(n_raw: int, n_comp: int) -> dict:
    """Structural byte accounting: on-device coder vs host entropy stage.

    The host path must round-trip every payload byte over the host link
    (the exact traffic the paper's CSD offload exists to remove); the fused
    path ships zero payload bytes host-side — only O(1) manifest ints.
    """
    return {
        "ratio": n_raw / n_comp if n_comp else float("nan"),
        "host_entropy_bytes": 0,
        "host_bytes_eliminated": n_raw,
        "staged_passes": _ref.N_STAGED_PASSES,
        "fused_launches": 1,
    }
