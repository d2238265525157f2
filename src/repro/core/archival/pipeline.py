"""End-to-end archival pipeline: the full ingest -> archive -> query ->
replay loop of Salient Store (Fig. 1, both directions).

Write path (runs where the data shard lives — the CSD analogue):
  1. layered neural codec encodes the GOP (int8 codes + int8 motion fields);
  2. the flat codes go through the ONE-LAUNCH archival kernel
     (``repro.kernels.fused``, ``codec_name="rans"`` — the default): one
     Pallas launch per stripe batch runs interleaved-rANS entropy coding
     (with per-shard adaptive raw-skip, flagged in the manifest and
     honored by every decode path), v1 stream packing into uint32 words,
     the ChaCha20 XOR-seal (session keys R-LWE-KEM-encapsulated host-side,
     tiny), and RAID-5/6 parity over the S shards — the packed streams
     are never materialized in HBM between stages, and K coalesced
     stripes batch onto the launch's stripe axis so dispatch overhead
     amortizes K-fold.  (The pre-fusion chained launches —
     ``repro.kernels.entropy`` then ``repro.kernels.seal`` — remain the
     decode path, the host-codec path, and the bit-exact reference.);
  3. AT SEAL TIME the stripe is indexed into the salience catalog
     (``core/archival/catalog.py``): per-GOP pooled feature + novelty,
     recorded while the backbone features are hot — queries never decode.

Read path (the archive is an ACTIVE participant in continuous learning,
not a write-only sink):
  4. the trainer asks the query planner (``core/csd/retrieval.py``) for
     the most-novel archived GOPs vs its CURRENT exemplar centroids; the
     plan prices host-vs-CSD decode (``csd/costmodel.py``) and names, per
     stripe, exactly the shard subset to read;
  5. ``restore_stripe(shards=...)`` decodes ONLY those shards — one fused
     unseal launch over the subset — falling back to a parity-based
     degraded read (``recover_stripe``) when a wanted shard is missing or
     its CSD is flagged dead by the ``StragglerMonitor``;
  6. the decoded GOPs join the training batch (``train/trainer.py``'s
     replay stage), closing the loop: ingest -> archive -> query -> replay.

Durability loop (scrub -> rebuild -> retire, ``core/archival/scrub.py``):

  7. a background scrubber walks sealed stripes on a byte-budgeted round
     schedule and recomputes P/Q *over the sealed bodies* through the same
     unseal kernel (``recompute_stripe_parity`` — parity is defined on
     ciphertext, so the scrub holds ZERO key material); a nonzero syndrome
     against the stored parity detects silent corruption, and for RAID-6
     the P/Q syndrome pair LOCATES the corrupt shard
     (``raid.raid6_syndrome_locate``) so it can be repaired in place;
  8. a shard whose CSD the ``StragglerMonitor`` declares dead is rebuilt
     onto a replacement by the sharded parity pass
     (``distributed/archival.rebuild_csd_sharded``), budget-bounded per
     round so replay traffic is never starved, priority-ordered by catalog
     salience;
  9. stripes whose salience has decayed past a TTL are *retired*: the
     retirement is journaled first, then catalog + journal compact (live
     records rewritten, retired bodies dropped) — only after that is the
     stripe's key/nonce material recycled.

With the whole codes -> entropy -> pack -> ChaCha20 -> parity chain in one
device program per stripe batch nothing round-trips the host mid-chain (the
packed streams pass through HBM between the coder and seal kernels); only
disk I/O and O(1) manifest metadata (lengths, KEM polys, nonces, salience
descriptors) are host-side, and they cover *sealed, compressed* data — the paper's
data-movement thesis in BOTH directions: ingest moves compressed bytes,
retrieval moves only the planned shard subset (the ``retrieval`` bench
gates on that byte ratio).  ``ArchiveConfig.codec_name`` selects ``"rans"``
(on-device, default), ``"zstd"``/``"zlib"`` (the legacy host-side codec via
``repro.common.compress``, kept as the fallback for hosts that want a
byte-for-byte zstd archive), or ``"none"``; manifests record the codec (and
the raw-skip flag) so ``restore_stripe`` dispatches on what was written.

Granularities and seams:

* ``archive_stripe`` / ``restore_stripe`` — the batched hot path.  All S
  shards of a stripe are entropy-coded, packed, ChaCha-sealed, and
  parity-coded in one device program (``repro.kernels.fused``: three
  Pallas kernels with XLA glue); only the tiny per-shard KEM runs outside
  it.  ``seal_payload_stripes`` is the K-stripe batched entry (one
  dispatch per homogeneous stripe group).
  ``use_pallas=False`` dispatches the staged jnp reference instead
  (bit-identical outputs).
* ``restore_stripe_payloads`` — the retrieval datapath below the neural
  codec: subset unseal + entropy decode + degraded-read fallback, shared
  by ``restore_stripe`` and the byte-accounting benches.
* ``archive_gop`` / ``restore_gop`` + ``stripe_parity`` — the per-block
  reference path, kept as the dispatch/compat layer and for single-GOP use.
* ``stripe_manifests`` (+ ``..._to_json``/``..._from_json``) — the
  replicated metadata tier: KEM polys, nonces, packing manifests and body
  lengths, journaled next to the bodies so restarts and degraded reads
  never depend on in-memory state.

Sharded archival (mesh axis <-> CSD array):

The stripe's shard axis IS the paper's CSD array: shard s of a stripe lives
on storage device s, and the whole point of the CSD offload is that each
device seals *its own* shard locally while only the tiny parity reduction
crosses devices.  On the TPU adaptation the ``data`` mesh axis plays the
CSD-array role (see ``distributed/sharding.py``): ``repro.distributed.
archival`` shard_maps the fused entropy+seal kernel over ``data`` so every
mesh shard runs one local kernel launch on its slice of the stripe, then
combines RAID-5 P / RAID-6 Q with a cross-shard XOR reduce (exact, order-
free, bit-identical to this module's single-device path).  The hooks below
(``encode_gop_payload`` / ``seal_payload_stripe`` / the ``fused_fn`` /
``seal_fn`` / ``unseal_fn`` / ``entropy_fn`` / ``entropy_decode_fn``
parameters) are the seams that path plugs into — subset reads ride the
same seams via ``shard_ids``.

Telemetry (``repro.obs``, off by default — one branch per site when off):

Every byte this pipeline moves is billed to a named ledger edge, each at
exactly ONE site so the totals conserve:

* ``ingest.host_to_device`` — raw codec payload bytes entering the seal
  (the pre-compression volume a host-codec design would ship); billed in
  ``_assemble_stripe``, the join point of the fused AND chained write
  paths.
* ``ingest.entropy_raw`` / ``ingest.entropy_comp`` — bytes through the
  entropy stage and the streams they became; their ratio is the archive's
  compression ratio, recomputable from the ledger alone.
* ``ingest.device_to_journal`` — sealed body bytes leaving the kernel for
  the journal (the only payload traffic the CSD design ships host-side).
* ``ingest.shard_to_parity`` — P/Q strip bytes per sealed stripe.
* ``ingest.cross_chip`` — bytes a mesh write launch moves between chips
  (the parity partials its reduce gathers); billed in
  ``distributed/archival.entropy_seal_sharded``.
* ``replay.read`` — sealed bytes a restore actually moved (present wanted
  shards); ``replay.parity`` — degraded-read amplification (surviving
  unwanted peers + parity strips fed to ``recover_stripe``); both billed
  in ``restore_stripe_payloads``.
* ``ingest.shed`` — payload bytes the streaming admission controller
  (``serving/ingest.py``) refused under queue pressure; billed at exactly
  one site (``StreamIngestFrontend._shed``), each shed journaled — never
  a silent drop.
* ``replay.planned`` / ``replay.full_baseline`` are billed by the query
  planner (``core/csd/retrieval.py``); ``scrub.*`` / ``rebuild.*`` by the
  durability tier (``core/archival/scrub.py``, ``distributed/archival``).

Pipelined submission: ``seal_payload_stripes`` splits into a dispatch
half (KEM + host staging + async fused launch) and a finalize half (the
single blocking device→host fetch + archive assembly).  The streaming
ingest tier (``serving/ingest.py``) runs them through a two-slot submit
ring so batch k's seal overlaps batch k+1's host prep; the synchronous
entry is literally ``finalize(dispatch(...))``, so both paths are
bit-identical by construction.

Spans (``archive.seal`` / ``archive.seal_chained`` / ``archive.unseal`` /
``archive.entropy_*`` / ``archive.parity_recompute``) carry stripe shape,
codec, parity mode and the exact fused-launch count, and export as a
Perfetto-loadable trace via ``repro.obs.export``.
"""

from __future__ import annotations

import functools
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.common import compress as host_entropy
from repro.core.archival import raid
from repro.core.codec.layered_codec import (
    CodecConfig,
    FrameCode,
    decode_gop,
    encode_gop,
)
from repro.core.crypto import rlwe
from repro.core.crypto.hybrid import (
    SealedBlock,
    SessionMaterial,
    encapsulate_session,
    encapsulate_sessions,
    seal,
    unseal,
)
from repro.kernels import stack_rows
from repro.kernels.entropy import ops as entropy_ops
from repro.kernels.fused import ops as fused_ops
from repro.kernels.seal import ops as seal_ops
from repro.obs import (
    EDGE_DEVICE_TO_JOURNAL,
    EDGE_ENTROPY_COMP,
    EDGE_ENTROPY_RAW,
    EDGE_HOST_TO_DEVICE,
    EDGE_REPLAY_PARITY,
    EDGE_REPLAY_READ,
    EDGE_SHARD_TO_PARITY,
    OBS,
)
from repro.obs import names as obs_names

__all__ = [
    "ArchiveConfig",
    "ArchivedBlock",
    "StripeArchive",
    "pack_i8_to_u32",
    "unpack_u32_to_i8",
    "archive_gop",
    "restore_gop",
    "encode_gop_payload",
    "entropy_encode_payloads",
    "entropy_decode_payloads",
    "seal_payload_stripe",
    "seal_payload_stripes",
    "seal_payload_stripes_dispatch",
    "seal_payload_stripes_finalize",
    "PendingStripeSeal",
    "archive_stripe",
    "restore_stripe",
    "restore_stripe_payloads",
    "stripe_manifests",
    "stripe_manifests_to_json",
    "stripe_manifests_from_json",
    "stripe_parity",
    "recover_stripe",
    "recompute_stripe_parity",
]


class ArchiveConfig(NamedTuple):
    codec: CodecConfig = CodecConfig()
    rlwe: rlwe.RLWEParams = rlwe.RLWEParams()
    n_layers: Optional[int] = None  # quality-layer prefix (None = all)
    parity: str = "raid6"  # "raid5" | "raid6" | "none"
    # entropy stage: "rans" (on-device kernel) | "zstd"/"zlib" (host
    # fallback via repro.common.compress) | "none"
    codec_name: str = "rans"


class ArchivedBlock(NamedTuple):
    sealed: SealedBlock
    manifest: Dict  # shapes/lengths to invert packing (host-side metadata)


class StripeArchive(NamedTuple):
    """One parity stripe: S archived shards + their P/Q parity."""

    blocks: List[ArchivedBlock]
    parity: Optional[Dict]  # {"p": u8, "q"?: u8, "pad_to": words} or None


def pack_i8_to_u32(x: jax.Array) -> jax.Array:
    """Flat int8 (4N,) -> (N,) uint32 (little-endian lanes)."""
    b = (x.astype(jnp.int32) & 0xFF).astype(jnp.uint32).reshape(-1, 4)
    sh = jnp.arange(4, dtype=jnp.uint32) * 8
    return (b << sh).sum(-1, dtype=jnp.uint32)


def unpack_u32_to_i8(w: jax.Array, n: int) -> jax.Array:
    """(N,) uint32 -> flat int8 (n,)."""
    sh = jnp.arange(4, dtype=jnp.uint32) * 8
    b = ((w[:, None] >> sh) & jnp.uint32(0xFF)).astype(jnp.uint8)
    return b.reshape(-1)[:n].astype(jnp.int8)


def _flatten_codes(frame_codes: List[FrameCode]) -> Tuple[jax.Array, Dict]:
    parts, spec = [], []
    for fc in frame_codes:
        centry = []
        for z in fc.codes:
            parts.append(z.astype(jnp.int8).reshape(-1))
            centry.append(tuple(z.shape))
        mv_shape = None
        if fc.mv is not None:
            parts.append(fc.mv.astype(jnp.int8).reshape(-1))
            mv_shape = tuple(fc.mv.shape)
        spec.append({"codes": centry, "mv": mv_shape})
    flat = jnp.concatenate(parts)
    n = int(flat.shape[0])
    pad = (-n) % 4
    flat = jnp.pad(flat, (0, pad))
    return flat, {"spec": spec, "n_i8": n}


def _unflatten_codes(flat_i8: jax.Array, manifest: Dict) -> List[FrameCode]:
    out = []
    off = 0
    for entry in manifest["spec"]:
        codes = []
        for shp in entry["codes"]:
            sz = int(np.prod(shp))
            codes.append(
                flat_i8[off : off + sz].astype(jnp.float32).reshape(shp)
            )
            off += sz
        mv = None
        if entry["mv"] is not None:
            sz = int(np.prod(entry["mv"]))
            mv = flat_i8[off : off + sz].astype(jnp.int32).reshape(entry["mv"])
            off += sz
        out.append(FrameCode(codes, mv))
    return out


def archive_gop(
    codec_params,
    pub: rlwe.PublicKey,
    frames: jax.Array,
    key: jax.Array,
    cfg: ArchiveConfig = ArchiveConfig(),
) -> Tuple[ArchivedBlock, jax.Array]:
    """frames: (T, B, H, W, 3). Returns (ArchivedBlock, recons)."""
    frame_codes, recons = encode_gop(
        codec_params, cfg.codec, frames, n_layers=cfg.n_layers
    )
    flat, manifest = _flatten_codes(frame_codes)
    words = pack_i8_to_u32(flat)
    sealed = seal(pub, words, key, cfg.rlwe)
    manifest = dict(manifest, frames_shape=tuple(frames.shape))
    return ArchivedBlock(sealed, manifest), recons


def restore_gop(
    codec_params,
    s: jax.Array,
    block: ArchivedBlock,
    cfg: ArchiveConfig = ArchiveConfig(),
) -> jax.Array:
    words = unseal(s, block.sealed, cfg.rlwe)
    flat = unpack_u32_to_i8(words, block.manifest["n_i8"])
    frame_codes = _unflatten_codes(flat, block.manifest)
    return decode_gop(codec_params, cfg.codec, frame_codes)


# ------------------------------------------------------------ batched stripe
def _u32_rows_to_u8(rows: jax.Array, sharding=None) -> jax.Array:
    """(R, 128) uint32 parity tile -> flat uint8 (R*512,) on the device.
    Host rows (a fused launch's, cut on the host) are viewed as bytes there
    and placed with ``sharding`` by one transfer, so no device program
    queues behind a launch in flight; device rows are bitcast in place."""
    if isinstance(rows, np.ndarray):
        return jax.device_put(rows.view(np.uint8).reshape(-1), sharding)
    return jax.lax.bitcast_convert_type(rows, jnp.uint8).reshape(-1)


def encode_gop_payload(
    codec_params,
    frames: jax.Array,
    cfg: ArchiveConfig = ArchiveConfig(),
) -> Tuple[jax.Array, Dict, jax.Array]:
    """Codec-encode one GOP to a flat int8 seal payload.

    frames: (T, B, H, W, 3).  Returns (flat int8 payload, manifest, recons).
    This is the encode half of ``archive_gop``/``archive_stripe``, split out
    so ingest layers (``repro.distributed.archival.StripeCoalescer``) can
    encode GOPs as they arrive and defer sealing until a full stripe exists.
    """
    frame_codes, recons = _encode_gop_device(codec_params, frames, cfg)
    flat, manifest = _flatten_codes(frame_codes)
    return flat, dict(manifest, frames_shape=tuple(frames.shape)), recons


@functools.partial(jax.jit, static_argnames=("cfg",))
def _encode_gop_device(codec_params, frames, cfg: ArchiveConfig):
    """The codec half of ``encode_gop_payload`` as ONE device program: run
    op by op, a GOP is hundreds of small launches, each compiled on first
    use."""
    return encode_gop(codec_params, cfg.codec, frames, n_layers=cfg.n_layers)


def entropy_encode_payloads(
    flats: List[jax.Array],
    cfg: ArchiveConfig = ArchiveConfig(),
    *,
    use_pallas: bool = True,
    entropy_fn=None,
) -> Tuple[List[jax.Array], List[Dict]]:
    """Entropy-code S shard payloads per ``cfg.codec_name``.

    Returns (compressed flats, per-shard entropy metas recorded into the
    manifests).  ``entropy_fn`` overrides the on-device coder launch — the
    sharded path passes a shard_map'd wrapper with the same signature as
    ``entropy_ops.encode_payloads`` (the ``seal_fn`` pattern).  Host codecs
    (zstd/zlib) pull the payload to the host — that is the traffic the
    on-device coder exists to remove; they are kept as the compatibility
    fallback.
    """
    name = cfg.codec_name
    if name == "none":
        return list(flats), [
            {"codec": "none", "n_raw": int(f.shape[0]), "n_comp": int(f.shape[0])}
            for f in flats
        ]
    if name == "rans":
        with OBS.span("archive.entropy_encode", codec=name, shards=len(flats)):
            if entropy_fn is not None:
                return entropy_fn(flats, use_pallas=use_pallas)
            return entropy_ops.encode_payloads(flats, use_pallas=use_pallas)
    if name in ("zstd", "zlib"):
        comps, metas = [], []
        for f in flats:
            raw = np.asarray(f, np.int8).tobytes()
            blob = host_entropy.compress_as(name, raw)
            if len(blob) >= len(raw):
                # adaptive raw-skip, same manifest flag as the rANS path
                comps.append(jnp.asarray(np.frombuffer(raw, np.int8)))
                metas.append(
                    {"codec": name, "raw": True,
                     "n_raw": len(raw), "n_comp": len(raw)}
                )
            else:
                comps.append(jnp.asarray(np.frombuffer(blob, np.int8)))
                metas.append(
                    {"codec": name, "n_raw": len(raw), "n_comp": len(blob)}
                )
        return comps, metas
    raise ValueError(f"unknown entropy codec {name!r}")


def entropy_decode_payloads(
    comps: List[jax.Array],
    metas: List[Dict],
    *,
    use_pallas: bool = True,
    entropy_decode_fn=None,
) -> List[jax.Array]:
    """Invert ``entropy_encode_payloads``, dispatching on the *recorded*
    codec (the manifest is ground truth, not the caller's current config)."""
    if not metas:
        return []
    names = {m["codec"] for m in metas}
    if len(names) != 1:
        raise ValueError(f"stripe mixes entropy codecs {sorted(names)}")
    name = names.pop()
    if name == "none":
        return list(comps)
    if name == "rans":
        with OBS.span("archive.entropy_decode", codec=name, shards=len(comps)):
            if entropy_decode_fn is not None:
                return entropy_decode_fn(comps, metas, use_pallas=use_pallas)
            return entropy_ops.decode_payloads(
                comps, metas, use_pallas=use_pallas
            )
    if name in ("zstd", "zlib"):
        out = []
        for c, m in zip(comps, metas):
            if m.get("raw"):  # adaptive raw-skip: stored bytes ARE the payload
                out.append(jnp.asarray(c).reshape(-1).astype(jnp.int8))
                continue
            raw = host_entropy.decompress_as(
                name, np.asarray(c, np.int8).tobytes(),
                max_output_size=m["n_raw"],
            )
            out.append(jnp.asarray(np.frombuffer(raw, np.int8)))
        return out
    raise ValueError(f"unknown entropy codec {name!r}")


def _bill_ingest(stripe, manifests: List[Dict], parity: Optional[Dict]) -> None:
    """Bill one sealed stripe's ingest edges to the byte-flow ledger.

    This is the SINGLE ingest billing site: the fused batched path and the
    chained reference path both assemble here with entropy-merged
    manifests, so every sealed stripe is billed exactly once.
    """
    raw = comp = host = 0
    for m in manifests:
        em = m.get("entropy") or {"codec": "none"}
        n_raw = int(em.get("n_raw", m.get("n_i8", 0)))
        host += n_raw
        if em.get("codec", "none") != "none":
            raw += n_raw
            comp += int(em.get("n_comp", n_raw))
    S = len(manifests)
    OBS.flow(EDGE_HOST_TO_DEVICE, host, events=S)
    if raw:
        OBS.flow(EDGE_ENTROPY_RAW, raw, events=S)
        OBS.flow(EDGE_ENTROPY_COMP, comp, events=S)
    OBS.flow(
        EDGE_DEVICE_TO_JOURNAL,
        sum(4 * int(n) for n in stripe.n_words),
        events=S,
    )
    if parity is not None:
        nb = int(parity["p"].size)
        q = parity.get("q")
        if q is not None:
            nb += int(q.size)
        OBS.flow(EDGE_SHARD_TO_PARITY, nb)


def _assemble_stripe(stripe, mats, manifests: List[Dict]) -> StripeArchive:
    """Wrap a SealedStripe + its KEM material as a ``StripeArchive``."""
    bodies = stripe.bodies()
    blocks = [
        ArchivedBlock(
            SealedBlock(
                m.kem_c1, m.kem_c2, m.nonce, bodies[s], stripe.n_words[s]
            ),
            manifests[s],
        )
        for s, m in enumerate(mats)
    ]
    parity = None
    if stripe.p is not None:
        at = stripe.parity_sharding
        parity = {"p": _u32_rows_to_u8(stripe.p, at),
                  "pad_to": stripe.pad_words}
        if stripe.q is not None:
            parity["q"] = _u32_rows_to_u8(stripe.q, at)
    if OBS.enabled:
        _bill_ingest(stripe, manifests, parity)
    return StripeArchive(blocks, parity)


class PendingStripeSeal(NamedTuple):
    """A dispatched-but-unfetched stripe-seal batch.

    Exactly one of the three payload fields is populated:

    * ``kernel``   — a ``fused_ops.PendingSeal`` (the default async path:
      the jitted launch is in flight, nothing has synced);
    * ``results``  — eager ``[(SealedStripe, emetas), ...]`` from a legacy
      one-shot ``fused_fn`` override (already blocked at dispatch);
    * ``archives`` — fully assembled ``StripeArchive``s (host-codec /
      non-rans fallback, which has no async seam).

    ``mats`` / ``manifests`` ride along so the finalize half can assemble
    without re-deriving KEM material.
    """

    kernel: Optional[fused_ops.PendingSeal]
    results: Optional[List]
    archives: Optional[List[StripeArchive]]
    mats: List[List]
    manifests: List[List[Dict]]


#: sessions per KEM program: a full launch's 4 stripes x 4 data shards,
#: and a multiple of the 8-row tile ``polymul_fixed`` takes.  Every
#: dispatch pads its session count up to a multiple of this, so one
#: program serves them all and none is built after the warm-up.
KEM_ROWS = 16


@functools.partial(jax.jit, static_argnames=("params",))
def _encapsulate_rows(pub, stripe_keys, shards, params):
    """One device program for ``KEM_ROWS`` sessions: row v is
    ``encapsulate_session(pub, fold_in(stripe_keys[v], shards[v]))``.
    Returns one ``SessionMaterial`` per row, so that nothing is sliced
    eagerly afterwards."""
    keys = jax.vmap(jax.random.fold_in)(jnp.stack(stripe_keys), shards)
    sm = encapsulate_sessions(pub, keys, params)
    return [SessionMaterial(*(f[v] for f in sm))
            for v in range(len(stripe_keys))]


# a stripe's key (or nonce) rows in one dispatch; an eager ``jnp.stack``
# of S rows makes S + 1
_stack = jax.jit(jnp.stack)


def _encapsulate_stripes(pub, keys, shard_counts, params):
    """Every shard's session material, per stripe, shard s of a stripe
    keyed by ``fold_in(stripe key, s)``.  The V sessions run as
    ceil(V / KEM_ROWS) launches of one program; a chunk's padding rows
    repeat its first row and are dropped."""
    rows = [(k, s) for k, S in zip(keys, shard_counts) for s in range(S)]
    mats = []
    for c in range(0, len(rows), KEM_ROWS):
        chunk = rows[c:c + KEM_ROWS]
        real = len(chunk)
        chunk += chunk[:1] * (KEM_ROWS - real)
        OBS.count(obs_names.KEM_LAUNCHES)
        OBS.count(obs_names.KEM_SESSIONS, real)
        OBS.count(obs_names.KEM_PADDED, KEM_ROWS - real)
        mats += _encapsulate_rows(
            pub, [k for k, _ in chunk],
            np.array([s for _, s in chunk], np.uint32), params,
        )[:real]
    it = iter(mats)
    return [[next(it) for _ in range(S)] for S in shard_counts]


def seal_payload_stripes_dispatch(
    pub: rlwe.PublicKey,
    stripes: List[List[jax.Array]],
    manifests: List[List[Dict]],
    keys: List[jax.Array],
    cfg: ArchiveConfig = ArchiveConfig(),
    *,
    use_pallas: bool = True,
    pad_rows=None,
    fused_fn=None,
    fused_dispatch_fn=None,
) -> PendingStripeSeal:
    """Async half of ``seal_payload_stripes``: KEM-encapsulate the session
    keys, stage the payloads, and dispatch the fused launch WITHOUT the
    device→host sync.  The returned handle is redeemed by
    ``seal_payload_stripes_finalize``; the two-slot submit ring
    (``repro.serving.ingest``) dispatches batch k+1's host prep between
    the two halves so host staging overlaps the in-flight seal.

    ``fused_dispatch_fn`` overrides the async launch (the sharded path
    passes ``entropy_seal_stripes_dispatch`` with a shard_map'd
    ``core_fn``); a legacy one-shot ``fused_fn`` still works but blocks
    at dispatch (its results are carried to finalize eagerly).
    """
    n = len(stripes)
    if not (n == len(manifests) == len(keys)):
        raise ValueError(
            f"{n} stripes vs {len(manifests)} manifests / {len(keys)} keys"
        )
    if isinstance(pad_rows, (list, tuple)):
        pr_list = list(pad_rows)
    else:
        pr_list = [pad_rows] * n
    if cfg.codec_name != "rans":
        archives = [
            seal_payload_stripe(
                pub, f, m, k, cfg, use_pallas=use_pallas, pad_rows=pr
            )
            for f, m, k, pr in zip(stripes, manifests, keys, pr_list)
        ]
        return PendingStripeSeal(None, None, archives, [], [])
    with OBS.span("ingest.kem", stripes=n):
        mats = _encapsulate_stripes(
            pub, keys, [len(f) for f in stripes], cfg.rlwe
        )
        keys_a = [_stack([m.session for m in ms]) for ms in mats]
        nonces_a = [_stack([m.nonce for m in ms]) for ms in mats]
    with OBS.span(
        "archive.seal", stripes=n, shards=len(stripes[0]),
        codec=cfg.codec_name, parity=cfg.parity,
    ) as sp:
        launches0 = OBS.metrics.get(obs_names.FUSED_LAUNCHES) if OBS.enabled else 0
        if fused_fn is not None:
            results = fused_fn(
                stripes, keys_a, nonces_a, parity=cfg.parity,
                use_pallas=use_pallas, pad_rows=pr_list,
            )
            kernel = None
        else:
            dispatch = fused_dispatch_fn or fused_ops.entropy_seal_stripes_dispatch
            kernel = dispatch(
                stripes, keys_a, nonces_a, parity=cfg.parity,
                use_pallas=use_pallas, pad_rows=pr_list,
            )
            results = None
        if OBS.enabled:
            sp.set(launches=int(
                OBS.metrics.get(obs_names.FUSED_LAUNCHES) - launches0
            ))
    return PendingStripeSeal(kernel, results, None, mats, manifests)


def seal_payload_stripes_finalize(
    pending: PendingStripeSeal,
) -> List[StripeArchive]:
    """Blocking half: fetch the dispatched batch's rANS word counts (the
    only device→host sync) and assemble + ledger-bill the archives."""
    if pending.archives is not None:
        return pending.archives
    if pending.kernel is not None:
        results = fused_ops.entropy_seal_stripes_finalize(pending.kernel)
    else:
        results = pending.results
    return [
        _assemble_stripe(
            stripe, ms, [dict(m, entropy=em) for m, em in zip(mfs, emetas)]
        )
        for (stripe, emetas), ms, mfs in zip(
            results, pending.mats, pending.manifests
        )
    ]


def seal_payload_stripes(
    pub: rlwe.PublicKey,
    stripes: List[List[jax.Array]],
    manifests: List[List[Dict]],
    keys: List[jax.Array],
    cfg: ArchiveConfig = ArchiveConfig(),
    *,
    use_pallas: bool = True,
    pad_rows=None,
    fused_fn=None,
) -> List[StripeArchive]:
    """Batched ``seal_payload_stripe``: K stripes per fused kernel launch.

    stripes / manifests / keys are per-stripe lists; ``pad_rows`` is None,
    an int, or a per-stripe sequence (same re-bucketing semantics as the
    singular).  For ``codec_name="rans"`` the whole batch goes through the
    fused write program (``repro.kernels.fused``): homogeneous stripes
    share ONE dispatch with K stripes on the batch axis, so per-dispatch
    cost amortizes K-fold.  ``fused_fn`` overrides the batched launch
    (the sharded path passes ``entropy_seal_stripes`` with a shard_map'd
    ``core_fn``).  Host codecs fall back to the per-stripe chained path.
    Outputs are bit-identical to mapping ``seal_payload_stripe`` — and,
    being exactly ``finalize(dispatch(...))``, to the pipelined submit
    ring by construction.
    """
    return seal_payload_stripes_finalize(
        seal_payload_stripes_dispatch(
            pub, stripes, manifests, keys, cfg, use_pallas=use_pallas,
            pad_rows=pad_rows, fused_fn=fused_fn,
        )
    )


def seal_payload_stripe(
    pub: rlwe.PublicKey,
    flats: List[jax.Array],
    manifests: List[Dict],
    key: jax.Array,
    cfg: ArchiveConfig = ArchiveConfig(),
    *,
    use_pallas: bool = True,
    pad_rows: Optional[int] = None,
    seal_fn=None,
    entropy_fn=None,
    fused_fn=None,
) -> StripeArchive:
    """Entropy-code + seal pre-encoded payloads as one parity stripe.

    For ``codec_name="rans"`` the default path is the ONE-LAUNCH fused
    kernel (``repro.kernels.fused``): codes -> histogram/freq-table ->
    rANS -> v1 pack -> raw-skip -> ChaCha20 XOR-seal -> RAID-P/Q in a
    single Pallas launch, packed streams never materialized in HBM.
    Per-shard session keys are KEM-encapsulated first, in one program
    for the batch (the ``fold_in`` order matches the chained path, so
    archives are bit-identical).  ``fused_fn`` overrides the fused launch
    (the sharded path passes a shard_map'd wrapper); passing only ``seal_fn`` /
    ``entropy_fn`` (same signatures as ``seal_ops.seal_stripe`` /
    ``entropy_ops.encode_payloads``) keeps the two-launch chained path —
    which also serves host codecs and stays the decode-side reference.
    """
    if cfg.codec_name == "rans" and (
        fused_fn is not None or (seal_fn is None and entropy_fn is None)
    ):
        return seal_payload_stripes(
            pub, [flats], [manifests], [key], cfg, use_pallas=use_pallas,
            pad_rows=[pad_rows], fused_fn=fused_fn,
        )[0]
    with OBS.span(
        "archive.seal_chained", shards=len(flats),
        codec=cfg.codec_name, parity=cfg.parity,
    ):
        flats, emetas = entropy_encode_payloads(
            flats, cfg, use_pallas=use_pallas, entropy_fn=entropy_fn
        )
        manifests = [dict(m, entropy=em) for m, em in zip(manifests, emetas)]
        if cfg.codec_name != "none" and pad_rows is not None:
            # the caller's bucket covered the RAW payload; re-bucket on the
            # compressed sizes (still pow2, so jit traces stay bounded) — an
            # incompressible shard can exceed its raw bucket (stream header +
            # 16-bit renorm slack)
            pad_rows = seal_ops.bucket_rows_for(
                max(-(-int(f.shape[0]) // 4) for f in flats)
            )
        mats = [
            encapsulate_session(pub, jax.random.fold_in(key, s), cfg.rlwe)
            for s in range(len(flats))
        ]
        seal_fn = seal_fn or seal_ops.seal_stripe
        stripe = seal_fn(
            flats,
            jnp.stack([m.session for m in mats]),
            jnp.stack([m.nonce for m in mats]),
            parity=cfg.parity,
            use_pallas=use_pallas,
            pad_rows=pad_rows,
        )
        return _assemble_stripe(stripe, mats, manifests)


def archive_stripe(
    codec_params,
    pub: rlwe.PublicKey,
    frames_list: List[jax.Array],
    key: jax.Array,
    cfg: ArchiveConfig = ArchiveConfig(),
    *,
    use_pallas: bool = True,
    seal_fn=None,
    entropy_fn=None,
    fused_fn=None,
) -> Tuple[StripeArchive, List[jax.Array]]:
    """Archive S GOPs as one parity stripe: codes -> fused entropy+seal.

    frames_list: S clips, each (T, B, H, W, 3) — one per storage shard.
    ``use_pallas=False`` runs the staged jnp references instead
    (bit-identical streams, bodies and parity); ``seal_fn``/``entropy_fn``/
    ``fused_fn`` dispatch the launches (see ``seal_payload_stripe``).
    """
    flats, manifests, recons = [], [], []
    for frames in frames_list:
        flat, manifest, rec = encode_gop_payload(codec_params, frames, cfg)
        flats.append(flat)
        manifests.append(manifest)
        recons.append(rec)
    stripe = seal_payload_stripe(
        pub, flats, manifests, key, cfg, use_pallas=use_pallas,
        seal_fn=seal_fn, entropy_fn=entropy_fn, fused_fn=fused_fn,
    )
    return stripe, recons


def restore_stripe_payloads(
    s: jax.Array,
    stripe: StripeArchive,
    cfg: ArchiveConfig = ArchiveConfig(),
    *,
    shards: Optional[Sequence[int]] = None,
    use_pallas: bool = True,
    verify_parity: bool = True,
    manifests: Optional[List[Dict]] = None,
    unseal_fn=None,
    entropy_decode_fn=None,
) -> Tuple[List[jax.Array], List[ArchivedBlock]]:
    """Unseal + entropy-decode a stripe down to codec payloads.

    This is the retrieval datapath below the neural codec: everything
    ``restore_stripe`` does except the final ``decode_gop``.  Returns
    (flat int8 payloads, the blocks they came from) in ``shards`` order.

    Shard-subset reads: ``shards`` names the stripe shards a query plan
    actually wants (``core/csd/retrieval.plan_retrieval`` emits them) —
    ONLY those bodies are stacked into the unseal launch, so a top-k
    retrieval moves/decodes k shards instead of the whole stripe.  Parity
    cannot be recomputed from a subset, so subset reads skip the
    recompute-and-compare integrity check (full-stripe reads keep it).

    Degraded reads: entries of ``stripe.blocks`` may be ``None`` (shard
    lost, or its CSD flagged dead by the ``StragglerMonitor``).  Wanted
    missing shards are rebuilt from RAID parity via ``recover_stripe``
    first — that read touches the surviving shards + parity (the classic
    degraded-read amplification; the planner bills it), and needs the
    replicated metadata records (``stripe_manifests`` format) in
    ``manifests`` for the lost shards' KEM polys/nonces/lengths.
    """
    if not stripe.blocks:
        raise ValueError("stripe must contain at least one shard payload")
    S = len(stripe.blocks)
    subset = shards is not None
    wanted = list(range(S)) if shards is None else [int(i) for i in shards]
    if not wanted:
        raise ValueError("shard subset must name at least one shard")
    if len(set(wanted)) != len(wanted):
        raise ValueError(f"duplicate shard ids in {wanted}")
    if any(i < 0 or i >= S for i in wanted):
        raise ValueError(f"shard ids {wanted} out of range for S={S}")
    blocks = list(stripe.blocks)
    missing = [i for i, b in enumerate(blocks) if b is None]
    if any(i in missing for i in wanted):
        if stripe.parity is None:
            raise ValueError(
                f"shards {sorted(set(missing) & set(wanted))} are missing "
                "and the stripe has no parity to rebuild from"
            )
        if manifests is None:
            raise ValueError(
                "degraded read needs the replicated metadata records "
                "(stripe_manifests format) for the missing shards"
            )
        body_lens = [
            int(manifests[i]["n_words"])
            if blocks[i] is None
            else int(blocks[i].sealed.n_valid_u32)
            for i in range(S)
        ]
        blocks = recover_stripe(
            blocks, stripe.parity, missing, manifests, body_lens
        )
    sub = [blocks[i] for i in wanted]
    if OBS.enabled:
        # replay.read: sealed bytes the subset read actually moved (wanted
        # shards that were present on their CSD)
        OBS.flow(
            EDGE_REPLAY_READ,
            sum(
                4 * int(stripe.blocks[i].sealed.n_valid_u32)
                for i in wanted
                if stripe.blocks[i] is not None
            ),
            events=len(wanted),
        )
        deg = set(missing) & set(wanted)
        if deg:
            # replay.parity: the degraded-read amplification — surviving
            # peers OUTSIDE the wanted subset plus both parity strips, all
            # of which recover_stripe had to pull in
            amp = sum(
                4 * int(stripe.blocks[i].sealed.n_valid_u32)
                for i in range(S)
                if stripe.blocks[i] is not None and i not in wanted
            )
            amp += int(stripe.parity["p"].size)
            q_strip = stripe.parity.get("q")
            if q_strip is not None:
                amp += int(q_strip.size)
            OBS.flow(EDGE_REPLAY_PARITY, amp, events=len(deg))
    sessions, nonces = [], []
    for b in sub:
        sessions.append(
            rlwe.kem_decapsulate(
                s, rlwe.Ciphertext(b.sealed.kem_c1, b.sealed.kem_c2), cfg.rlwe
            )
        )
        nonces.append(b.sealed.nonce)

    n_words = tuple(int(b.sealed.body.shape[0]) for b in sub)
    emetas = [b.manifest.get("entropy", {"codec": "none"}) for b in sub]
    # bytes inside the sealed body: the compressed stream when an entropy
    # stage ran, the raw payload otherwise
    n_i8 = tuple(
        int(em.get("n_comp", b.manifest["n_i8"]))
        for b, em in zip(sub, emetas)
    )
    # pow2 row buckets: one unseal program per bucket, not per stripe
    R = seal_ops.bucket_rows_for(max(n_words))
    sealed = stack_rows([b.sealed.body for b in sub], R)
    packed = seal_ops.SealedStripe(sealed, None, None, n_words, n_i8)
    # recompute parity in the mode the stripe was actually sealed with (the
    # stored parity dict is ground truth), not whatever the caller's cfg
    # says — otherwise verify_parity could silently compare nothing.  A
    # subset read cannot recompute stripe-wide parity, so it runs "none".
    if subset or stripe.parity is None:
        parity_mode = "none"
    else:
        parity_mode = "raid6" if "q" in stripe.parity else "raid5"
    unseal_fn = unseal_fn or seal_ops.unseal_stripe
    with OBS.span(
        "archive.unseal", shards=len(wanted), subset=subset,
        degraded=len(set(missing) & set(wanted)), parity=parity_mode,
    ):
        flats, p2, q2 = unseal_fn(
            packed,
            jnp.stack(sessions),
            jnp.stack(nonces),
            parity=parity_mode,
            use_pallas=use_pallas,
            shard_ids=tuple(wanted),
        )
    if not subset and verify_parity and stripe.parity is not None:
        for name, got in (("p", p2), ("q", q2)):
            want = stripe.parity.get(name)
            if want is None or got is None:
                continue
            got_u8 = np.asarray(_u32_rows_to_u8(got))
            want_u8 = np.asarray(want)
            n = min(got_u8.size, want_u8.size)
            if not (
                np.array_equal(got_u8[:n], want_u8[:n])
                and not got_u8[n:].any()
                and not want_u8[n:].any()
            ):
                raise ValueError(f"stripe parity mismatch on {name.upper()}")

    payloads = entropy_decode_payloads(
        [flats[j][: n_i8[j]] for j in range(len(sub))],
        [dict(em, codec=em.get("codec", "none")) for em in emetas],
        use_pallas=use_pallas,
        entropy_decode_fn=entropy_decode_fn,
    )
    return (
        [p[: b.manifest["n_i8"]] for p, b in zip(payloads, sub)],
        sub,
    )


def restore_stripe(
    codec_params,
    s: jax.Array,
    stripe: StripeArchive,
    cfg: ArchiveConfig = ArchiveConfig(),
    *,
    shards: Optional[Sequence[int]] = None,
    use_pallas: bool = True,
    verify_parity: bool = True,
    manifests: Optional[List[Dict]] = None,
    unseal_fn=None,
    entropy_decode_fn=None,
) -> List[jax.Array]:
    """Decode stripe shards: fused unseal -> entropy decode -> GOPs.

    ``shards=None`` decodes the whole stripe with the recompute-and-compare
    parity integrity check; ``shards=[...]`` is the retrieval fast path —
    only the named shards' bodies enter the unseal launch (see
    ``restore_stripe_payloads`` for subset/degraded-read semantics; missing
    wanted shards are parity-rebuilt when ``manifests`` carries their
    replicated metadata).  The entropy codec is dispatched from the
    manifest (what was written wins over the caller's cfg).
    ``unseal_fn``/``entropy_decode_fn`` dispatch the launches (the sharded
    path passes shard_map'd wrappers).  Returns one decoded GOP per
    requested shard, in ``shards`` order.
    """
    payloads, sub = restore_stripe_payloads(
        s, stripe, cfg, shards=shards, use_pallas=use_pallas,
        verify_parity=verify_parity, manifests=manifests,
        unseal_fn=unseal_fn, entropy_decode_fn=entropy_decode_fn,
    )
    return [
        decode_gop(
            codec_params, cfg.codec, _unflatten_codes(p, b.manifest)
        )
        for p, b in zip(payloads, sub)
    ]


def stripe_manifests(stripe: StripeArchive) -> List[Dict]:
    """Replicated-metadata records in the format ``recover_stripe`` and the
    degraded-read path expect (``n_words`` sizes a lost shard's body)."""
    return [
        {
            "kem_c1": b.sealed.kem_c1,
            "kem_c2": b.sealed.kem_c2,
            "nonce": b.sealed.nonce,
            "manifest": b.manifest,
            "n_words": int(b.sealed.n_valid_u32),
        }
        for b in stripe.blocks
    ]


def stripe_manifests_to_json(manifests: List[Dict]) -> List[Dict]:
    """JSON-able form of ``stripe_manifests`` records, so the replicated
    metadata tier can live in the power-loss-safe journal and a restarted
    trainer can still execute retrieval plans against old stripes."""
    return [
        {
            "kem_c1": np.asarray(m["kem_c1"]).tolist(),
            "kem_c2": np.asarray(m["kem_c2"]).tolist(),
            "nonce": np.asarray(m["nonce"]).tolist(),
            "manifest": m["manifest"],
            "n_words": int(m["n_words"]),
        }
        for m in manifests
    ]


def stripe_manifests_from_json(data: List[Dict]) -> List[Dict]:
    """Invert ``stripe_manifests_to_json`` (arrays back on device)."""
    return [
        {
            "kem_c1": jnp.asarray(m["kem_c1"], jnp.int32),
            "kem_c2": jnp.asarray(m["kem_c2"], jnp.int32),
            "nonce": jnp.asarray(m["nonce"], jnp.uint32),
            "manifest": m["manifest"],
            "n_words": int(m["n_words"]),
        }
        for m in data
    ]


# --------------------------------------------------------------- parity tier
def _bodies_u8(blocks: List[ArchivedBlock], pad_to: int) -> jnp.ndarray:
    rows = []
    for b in blocks:
        w = b.sealed.body
        w = jnp.pad(w, (0, pad_to - w.shape[0]))
        rows.append(jax.lax.bitcast_convert_type(w, jnp.uint8).reshape(-1))
    return jnp.stack(rows)  # (S, pad_to*4) uint8


def stripe_parity(blocks: List[ArchivedBlock], mode: str = "raid6"):
    """Parity over the sealed bodies of one stripe (S storage shards)."""
    if mode == "none":
        return None
    pad_to = max(int(b.sealed.body.shape[0]) for b in blocks)
    data = _bodies_u8(blocks, pad_to)
    if mode == "raid5":
        return {"p": raid.raid5_encode(data), "pad_to": pad_to}
    p, q = raid.raid6_encode(data)
    return {"p": p, "q": q, "pad_to": pad_to}


def recover_stripe(
    blocks: List[Optional[ArchivedBlock]],
    parity: Dict,
    missing: List[int],
    manifests: List[Dict],
    body_lens: List[int],
    *,
    stripe_id: str = "",
) -> List[ArchivedBlock]:
    """Rebuild missing shards' sealed bodies from parity.

    Note: parity protects the *body*; KEM polys + nonce are tiny and stored
    replicated in the manifest tier (standard metadata replication).
    ``stripe_id`` (optional) names the stripe in error messages so a
    degraded read that exceeds the parity mode's erasure budget is
    diagnosable from the exception alone.
    """
    pad_to = parity["pad_to"]
    mode = "raid6" if "q" in parity else "raid5"
    rows: List[Optional[jnp.ndarray]] = []
    for b in blocks:
        rows.append(None if b is None else _bodies_u8([b], pad_to)[0])
    if mode == "raid6":
        full = raid.raid6_reconstruct(rows, parity["p"], parity.get("q"), missing)
    else:
        if len(missing) != 1:
            which = f"stripe {stripe_id!r}" if stripe_id else "stripe"
            raise ValueError(
                f"{which}: RAID-5 parity covers exactly 1 erasure but shards "
                f"{sorted(missing)} are missing — data is unrecoverable "
                "without a RAID-6 Q strip or a replica"
            )
        full = list(rows)
        full[missing[0]] = raid.raid5_reconstruct(rows, parity["p"], missing[0])
    out: List[ArchivedBlock] = []
    for i, b in enumerate(blocks):
        if b is not None:
            out.append(b)
            continue
        words = jax.lax.bitcast_convert_type(
            full[i].reshape(-1, 4), jnp.uint32
        ).reshape(-1)[: body_lens[i]]
        meta = manifests[i]
        sealed = SealedBlock(
            meta["kem_c1"], meta["kem_c2"], meta["nonce"], words, body_lens[i]
        )
        out.append(ArchivedBlock(sealed, meta["manifest"]))
    return out


def recompute_stripe_parity(
    stripe: StripeArchive,
    *,
    use_pallas: bool = True,
    unseal_fn=None,
) -> Dict[str, np.ndarray]:
    """Recompute a sealed stripe's P/Q WITHOUT any key material.

    The seal kernel defines parity over the *sealed* bodies (ciphertext),
    so the scrubber can drive the same fused unseal launch with all-zero
    session keys/nonces: the ChaCha XOR it applies is garbage, but the
    P/Q accumulation runs on the input bodies and is exact.  This is what
    lets scrubbing run on the CSD tier — it never decrypts, never holds
    keys, and ships only syndrome bytes (see ``csd/costmodel.py``).

    Bodies are stacked at the stripe's seal-time geometry
    (``parity["pad_to"]`` words) so recomputed strips align byte-for-byte
    with the stored ones.  Returns ``{"p": u8, "q"?: u8}`` as numpy.
    """
    parity = stripe.parity
    if parity is None:
        raise ValueError("stripe has no parity strips to recompute")
    if any(b is None for b in stripe.blocks):
        raise ValueError(
            "parity recompute needs every shard body present; rebuild "
            "missing shards first (recover_stripe / rebuild_csd_sharded)"
        )
    S = len(stripe.blocks)
    pad_to = int(parity["pad_to"])
    R = pad_to // 128
    n_words = tuple(int(b.sealed.body.shape[0]) for b in stripe.blocks)
    if max(n_words) > pad_to:
        raise ValueError(
            f"shard body of {max(n_words)} words exceeds the stripe's "
            f"seal-time pad_to={pad_to}"
        )
    sealed = stack_rows([b.sealed.body for b in stripe.blocks], R)
    packed = seal_ops.SealedStripe(sealed, None, None, n_words, n_words)
    mode = "raid6" if "q" in parity else "raid5"
    fn = unseal_fn or seal_ops.unseal_stripe
    with OBS.span("archive.parity_recompute", shards=S, parity=mode):
        _, p2, q2 = fn(
            packed,
            jnp.zeros((S, 8), jnp.uint32),
            jnp.zeros((S, 3), jnp.uint32),
            parity=mode,
            use_pallas=use_pallas,
        )
    out = {"p": np.asarray(_u32_rows_to_u8(p2))}
    if q2 is not None:
        out["q"] = np.asarray(_u32_rows_to_u8(q2))
    return out
