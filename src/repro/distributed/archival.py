"""Sharded archival: the fused seal kernel distributed over the storage mesh.

Sharded archival (mesh axis <-> CSD array):

Salient Store's headline wins come from running compression/encryption/
parity *where the shards live*, in parallel across the CSD array, so only
parity-sized traffic crosses the interconnect.  On the TPU adaptation the
``data`` mesh axis is the designated storage-shard axis (see
``distributed/sharding.py``): mesh shard d owns stripe shards
``s % D == d``-style contiguous slices, exactly as CSD d owns its disks'
stripes in the paper.  ``seal_stripe_sharded`` shard_maps the fused Pallas
seal kernel (``repro.kernels.seal``) over that axis:

  * each mesh shard runs ONE local kernel launch over its (S/D, R, 512)
    slice of the stripe — pack + ChaCha20 + XOR-seal + local partial
    RAID-5 P / RAID-6 Q;
  * the only cross-shard communication is an XOR reduce of the partial
    parities (``_xor_allreduce``).  XOR is exact, associative and
    commutative, so the reduce order cannot change bits: sharded outputs
    are bit-identical to the single-device ``seal_stripe`` for every mesh
    shape.  (GF(256) Q coefficients g^s ride in as per-shard operands
    carrying the *global* shard index, so Q partials are globally correct
    before the reduce.)

``entropy_seal_sharded`` is the fused write program's twin
(``repro.kernels.fused`` — rANS + pack + raw-skip + ChaCha20 + parity, K
stripes batched per dispatch) shard_mapped the same way, so the rans write
path runs one local program per mesh shard per stripe batch, with the
identical parity-reduce story.  The
chained ``seal_stripe_sharded`` / ``entropy_encode_sharded`` pair stays
the decode-side and host-codec path.

Multi-stream ingest coalescing:

Continuous-learning edge servers batch retraining data from many cameras;
GOPs arrive ragged and one-at-a-time, and sealing each alone wastes the
stripe-wide kernel (one launch per GOP, parity over a single shard).
``StripeCoalescer`` buckets incoming GOPs by pow2-padded stripe height and
emits full S-shard stripes, so N streams' small GOPs amortize into one
fused launch per mesh shard, and the jit trace count stays bounded at
log2(max_rows) regardless of the GOP-size mix.
"""

from __future__ import annotations

import functools
import math
import time
from typing import Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def _shard_map(f, *, mesh, in_specs, out_specs):
    """shard_map with the replication check off."""
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
    )

from repro.core.archival.pipeline import (
    ArchiveConfig,
    PendingStripeSeal,
    StripeArchive,
    archive_stripe,
    restore_stripe,
    seal_payload_stripe,
    seal_payload_stripes,
    seal_payload_stripes_dispatch,
    seal_payload_stripes_finalize,
)
from repro.core.crypto import rlwe
from repro.kernels import host_prefixes, stack_rows, use_interpret
from repro.kernels.entropy import ops as entropy_ops
from repro.kernels.entropy.rans import PROB_SCALE
from repro.kernels.fused import ops as fused_ops
from repro.kernels.fused import ref as fused_ref
from repro.kernels.fused.entropy_seal import entropy_seal_pallas
from repro.kernels.seal import ops as seal_ops
from repro.kernels.seal import ref as _ref
from repro.obs import (
    EDGE_CROSS_CHIP,
    EDGE_REBUILD_READ,
    EDGE_REBUILD_WRITE,
    Metrics,
    OBS,
)
from repro.obs import names as obs_names
from repro.kernels.seal.ops import SealedStripe
from repro.kernels.seal.seal import (
    seal_stripe_pallas,
    unseal_stripe_pallas,
)

__all__ = [
    "seal_stripe_sharded",
    "unseal_stripe_sharded",
    "entropy_encode_sharded",
    "entropy_decode_sharded",
    "entropy_seal_sharded",
    "archive_stripe_sharded",
    "restore_stripe_sharded",
    "PendingGOP",
    "CoalescedStripe",
    "StripeCoalescer",
    "seal_coalesced_stripe",
    "seal_coalesced_stripes",
    "seal_coalesced_stripes_dispatch",
    "seal_coalesced_stripes_finalize",
    "RebuildItem",
    "RebuildRound",
    "plan_rebuild",
    "rebuild_csd_sharded",
]


# ------------------------------------------------------------ sharded seal
def _xor_allreduce(x: jax.Array, axis: str, D: int) -> jax.Array:
    """Cross-shard XOR reduce (the RAID-parity analogue of ``psum``).

    ``psum`` adds, which is wrong for GF(2) parity; gather + fold keeps the
    reduction exact.  D is static (mesh size) so the fold unrolls.
    """
    if D == 1:
        return x
    g = jax.lax.all_gather(x, axis)  # (D, R, LANES) on every shard
    acc = g[0]
    for i in range(1, D):
        acc = acc ^ g[i]
    return acc


@functools.lru_cache(maxsize=None)
def _sharded_core(mesh: Mesh, axis: str, parity: str, unseal: bool,
                  use_pallas: bool, interpret: bool):
    """jit'd shard_map'd seal/unseal core, cached per (mesh, mode).

    Inputs arrive stacked over the full stripe (S_pad divisible by the mesh
    axis); each mesh shard sees its local (S_loc, ...) slice and runs the
    fused kernel exactly once — launches/stripe/device = 1.
    """
    D = int(mesh.shape[axis])
    with_p = parity != "none"
    with_q = parity == "raid6"

    def local_fn(payload, keys, nonces, n_valid, q_coef):
        if use_pallas:
            fn = unseal_stripe_pallas if unseal else seal_stripe_pallas
            out, p, q = fn(payload, keys, nonces, n_valid, q_coef,
                           parity=parity, interpret=interpret)
        else:
            fn = _ref.unseal_stripe_ref if unseal else _ref.seal_stripe_ref
            out, p, q = fn(payload, keys, nonces, n_valid, q_coef,
                           parity=parity)
        outs = [out]
        if with_p:
            outs.append(_xor_allreduce(p, axis, D))
        if with_q:
            outs.append(_xor_allreduce(q, axis, D))
        return tuple(outs)

    n_extra = int(with_p) + int(with_q)
    fn = _shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(P(axis), P(axis), P(axis), P(axis), P(axis)),
        out_specs=(P(axis),) + (P(),) * n_extra,
    )
    return jax.jit(fn)


def _pad_shard_axis(arr: jax.Array, s_pad: int) -> jax.Array:
    if arr.shape[0] == s_pad:
        return arr
    pad = [(0, s_pad - arr.shape[0])] + [(0, 0)] * (arr.ndim - 1)
    return jnp.pad(arr, pad)


def seal_stripe_sharded(payloads, keys, nonces, *, mesh: Mesh,
                        axis: str = "data", parity: str = "raid6",
                        use_pallas: bool = True,
                        interpret: Optional[bool] = None,
                        pad_rows: Optional[int] = None) -> SealedStripe:
    """``seal_ops.seal_stripe`` with the shard axis partitioned over ``mesh``.

    Same inputs/outputs as the single-device wrapper; each mesh shard on
    ``axis`` seals its local slice in one fused launch and partial parities
    are XOR-combined across shards.  Stripes whose shard count does not
    divide the mesh axis are padded with zero-length dummy shards
    (``n_valid = 0`` masks them to zero, so they cannot perturb parity).
    """
    flats = seal_ops._as_payload_list(payloads)
    codes, n_words, n_i8 = seal_ops._stack_padded(flats, pad_rows)
    meta = seal_ops._meta_arrays(keys, nonces, n_words)
    S = len(n_words)
    D = int(mesh.shape[axis])
    s_pad = -(-S // D) * D
    args = [_pad_shard_axis(a, s_pad) for a in (codes, *meta)]
    core = _sharded_core(
        mesh, axis, parity, False, use_pallas, use_interpret(interpret)
    )
    outs = core(*args)
    sealed = outs[0][:S]
    p = outs[1] if parity != "none" else None
    q = outs[2] if parity == "raid6" else None
    return SealedStripe(sealed, p, q, n_words, n_i8)


def unseal_stripe_sharded(stripe: SealedStripe, keys, nonces, *, mesh: Mesh,
                          axis: str = "data", parity: str = "raid6",
                          use_pallas: bool = True,
                          interpret: Optional[bool] = None,
                          shard_ids: Optional[Tuple[int, ...]] = None):
    """Sharded twin of ``seal_ops.unseal_stripe`` (same outputs).

    Parity is recomputed from the stored bodies per mesh shard and
    XOR-reduced, so the integrity check covers the whole stripe while each
    device only reads its own slice.  ``shard_ids`` carries global stripe
    shard indices for subset reads (a retrieval plan's shards land on the
    mesh devices that own them; the rest of the stripe never moves).
    """
    if not stripe.n_words:
        raise ValueError("stripe must contain at least one shard payload")
    meta = seal_ops._meta_arrays(keys, nonces, stripe.n_words, shard_ids)
    S = stripe.sealed.shape[0]
    D = int(mesh.shape[axis])
    s_pad = -(-S // D) * D
    args = [_pad_shard_axis(a, s_pad) for a in (stripe.sealed, *meta)]
    core = _sharded_core(
        mesh, axis, parity, True, use_pallas, use_interpret(interpret)
    )
    outs = core(*args)
    p = outs[1] if parity != "none" else None
    q = outs[2] if parity == "raid6" else None
    return host_prefixes(outs[0][:S], stripe.n_i8), p, q


# ------------------------------------------------ sharded fused archival
@functools.lru_cache(maxsize=None)
def _mesh_write_program(mesh: Mesh, axis: str, n_shards: int, parity: str,
                        use_pallas: bool, interpret: bool):
    """The fused entropy+seal write program over ``mesh``: one jitted
    program, cached per (mesh, stripe width, mode) and built once per
    (stripes, rows) bucket.

    Codes arrive placed, (K, S', T, 128): stripes on axis 0, stripe shards
    on axis 1 (S' the width padded to a multiple of the mesh axis), the
    SHARD axis split over the mesh — the CSD-array mapping: mesh shard d
    compresses and seals the stripe shards it owns.  The raw lengths,
    session keys, nonces and GF(256) Q coefficients arrive flat (B, ...)
    on every chip; the program regroups and pads them, and each chip keeps
    its own rows.  Dummy shards get ``n_valid = 0``, which raw-skips them to
    zero stored bytes, so sealed rows and parity partials are unperturbed.

    Each mesh shard flattens its local (K, S'/D) slice onto the kernel
    batch axis and runs the fused kernels exactly ONCE — rANS + pack +
    raw-skip + ChaCha20 + local partial P/Q.  The only cross-shard traffic
    is the XOR reduce of the per-stripe parity partials (exact, order-free
    — bit-identical to the single-device launch); Q coefficients carry the
    *global* shard index, so Q partials are globally correct before the
    reduce.  Sealed rows and word counts stay on the chips that made them,
    (K, S', ...) split over the mesh; P and Q come out on every chip.
    """
    D = int(mesh.shape[axis])
    s_pad = -(-n_shards // D) * D
    s_loc = s_pad // D
    with_p = parity != "none"
    with_q = parity == "raid6"

    def local_fn(codes, n_valid, keys, nonces, q_coef):
        K = codes.shape[0]

        def flat(a):
            return a.reshape((K * s_loc,) + a.shape[2:])

        fn = entropy_seal_pallas if use_pallas else fused_ref.entropy_seal_ref
        kw = {"interpret": interpret} if use_pallas else {}
        sealed, nw, p, q = fn(
            flat(codes), flat(n_valid), flat(keys), flat(nonces),
            flat(q_coef), n_shards=s_loc, parity=parity, **kw,
        )
        outs = [
            sealed.reshape((K, s_loc) + sealed.shape[1:]),
            nw.reshape(K, s_loc, 1),
        ]
        if with_p:
            outs.append(_xor_allreduce(p, axis, D))
        if with_q:
            outs.append(_xor_allreduce(q, axis, D))
        return tuple(outs)

    n_extra = int(with_p) + int(with_q)
    local = _shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(P(None, axis),) * 5,
        out_specs=(P(None, axis), P(None, axis)) + (P(),) * n_extra,
    )

    def _fused_core_mesh(codes, n_valid, keys, nonces, q_coef):
        K = codes.shape[0]

        def regroup(a):
            a = a.reshape((K, n_shards) + a.shape[1:])
            pad = [(0, 0), (0, s_pad - n_shards)] + [(0, 0)] * (a.ndim - 2)
            return jnp.pad(a, pad)

        return local(codes, *(regroup(a)
                              for a in (n_valid, keys, nonces, q_coef)))

    return jax.jit(_fused_core_mesh)


def _cross_chip_bytes(x, sharding) -> int:
    """Bytes that placing ``x`` on ``sharding`` copies between devices:
    each device's part of the target that the device does not already hold.
    A host array is staged from the host and moves nothing between them."""
    if not isinstance(x, jax.Array):
        return 0
    held = x.sharding.devices_indices_map(x.shape)
    n = 0
    for dev, want in sharding.devices_indices_map(x.shape).items():
        want = [range(*w.indices(d)) for w, d in zip(want, x.shape)]
        have = [range(*h.indices(d))
                for h, d in zip(held.get(dev, ()), x.shape)]
        if have and all(h.start <= w.start and w.stop <= h.stop
                        for w, h in zip(want, have)):
            continue
        n += x.dtype.itemsize * math.prod(len(w) for w in want)
    return n


def entropy_seal_sharded(codes, n_valid, keys, nonces, q_coef, *,
                         mesh: Mesh, axis: str = "data", n_shards: int,
                         parity: str = "raid6", use_pallas: bool = True,
                         interpret: Optional[bool] = None):
    """Sharded twin of the fused write core: the ``core_fn`` of
    ``fused_ops.entropy_seal_stripes`` (bake ``mesh``/``axis`` with
    ``functools.partial``; the batching layer supplies the remaining static
    config as keyword arguments).

    ``codes`` is the launch's (B, T, 128) host array, stripe shard s of
    stripe k at row k*n_shards + s, as the one-device core takes it.  Each
    stripe shard's rows go from the host straight to the chip that owns
    it, one transfer per chip; the small per-shard arrays go to every chip.
    Returns sealed rows (K, S', R_cap, 128) and word counts (K, S', 1),
    each split over the mesh along its shard axis, and P, Q (K, R_cap, 128)
    on every chip (None where ``parity`` has none).

    With telemetry on, the bytes the launch moves between chips are billed
    here, at the one site: what placing its inputs copies from one chip to
    another (nothing of the codes, which come from the host) and the
    parity partials the reduce gathers — each chip receives the other
    D - 1 chips' partial of every strip.
    """
    B = codes.shape[0]
    K = B // n_shards
    D = int(mesh.shape[axis])
    s_pad = -(-n_shards // D) * D
    grouped = np.asarray(codes).reshape((K, n_shards) + codes.shape[1:])
    if s_pad != n_shards:
        grouped = np.pad(grouped, [(0, 0), (0, s_pad - n_shards)]
                         + [(0, 0)] * (grouped.ndim - 2))
    program = _mesh_write_program(
        mesh, axis, n_shards, parity, use_pallas, use_interpret(interpret)
    )
    placed = NamedSharding(mesh, P(None, axis))
    everywhere = NamedSharding(mesh, P())
    small = (n_valid, keys, nonces, q_coef)
    outs = program(jax.device_put(grouped, placed),
                   *(jax.device_put(a, everywhere) for a in small))
    sealed, n_words = outs[:2]
    p = outs[2] if parity != "none" else None
    q = outs[3] if parity == "raid6" else None
    if OBS.enabled:
        moved = sum(_cross_chip_bytes(a, everywhere) for a in small)
        moved += D * (D - 1) * sum(x.nbytes for x in (p, q) if x is not None)
        OBS.count(obs_names.MESH_CROSS_CHIP_BYTES, moved)
        OBS.flow(EDGE_CROSS_CHIP, moved)
    return sealed, n_words, p, q


def _sharded_fused_fn(mesh: Mesh, axis: str):
    """The ``fused_fn`` seam value: the fused batching layer with its
    kernel launch shard_map'd over ``mesh`` (see ``entropy_seal_sharded``)."""
    return functools.partial(
        fused_ops.entropy_seal_stripes,
        core_fn=functools.partial(entropy_seal_sharded, mesh=mesh, axis=axis),
    )


def _sharded_fused_dispatch_fn(mesh: Mesh, axis: str):
    """``_sharded_fused_fn``'s async twin — the ``fused_dispatch_fn`` seam
    value for the pipelined submit ring (dispatch only, no device sync)."""
    return functools.partial(
        fused_ops.entropy_seal_stripes_dispatch,
        core_fn=functools.partial(entropy_seal_sharded, mesh=mesh, axis=axis),
    )


# --------------------------------------------------- sharded entropy stage
@functools.lru_cache(maxsize=None)
def _sharded_entropy_core(mesh: Mesh, axis: str, decode: bool,
                          use_pallas: bool, interpret: bool,
                          version: int = 0, rows: int = 0):
    """jit'd shard_map'd rANS core, cached per (mesh, mode, stream version).

    The coder has no cross-shard term at all — each mesh shard runs the
    fused histogram+table+scan kernel on its local slice of the stripe
    (launches/stripe/device = 1), which is exactly the paper's per-CSD
    compression: only the seal stage's parity reduce ever crosses shards.
    ``version``/``rows`` (pow2-bucketed, so the cache stays bounded) pick
    the decode twin: row-major streams for version 1, the PR-4 lane-major
    layout for version 0.
    """

    def local_encode(codes, n_valid):
        return entropy_ops._encode_core(
            codes, n_valid, use_pallas=use_pallas, interpret=interpret
        )

    def local_decode(words, freq, states, n_valid):
        return entropy_ops._decode_core(
            words, freq, states, n_valid, version=version, rows=rows,
            use_pallas=use_pallas, interpret=interpret,
        )

    if decode:
        fn = _shard_map(
            local_decode, mesh=mesh,
            in_specs=(P(axis),) * 4, out_specs=P(axis),
        )
    else:
        fn = _shard_map(
            local_encode, mesh=mesh,
            in_specs=(P(axis), P(axis)), out_specs=(P(axis),) * 5,
        )
    return jax.jit(fn)


def entropy_encode_sharded(payloads, *, mesh: Mesh, axis: str = "data",
                           use_pallas: bool = True,
                           interpret: Optional[bool] = None):
    """``entropy_ops.encode_payloads`` with the coder shard_map'd over
    ``mesh`` — same streams/metas bit-for-bit for every mesh shape (dummy
    zero-length shards pad non-divisible stripes; ``n_valid = 0`` idles
    their lanes so they emit nothing)."""
    D = int(mesh.shape[axis])
    core = _sharded_entropy_core(
        mesh, axis, False, use_pallas, use_interpret(interpret)
    )

    def core_fn(codes, n_valid):
        S = codes.shape[0]
        s_pad = -(-S // D) * D
        outs = core(
            _pad_shard_axis(codes, s_pad), _pad_shard_axis(n_valid, s_pad)
        )
        return tuple(o[:S] for o in outs)

    return entropy_ops.encode_payloads(
        payloads, use_pallas=use_pallas, core_fn=core_fn
    )


def entropy_decode_sharded(comps, metas, *, mesh: Mesh, axis: str = "data",
                           use_pallas: bool = True,
                           interpret: Optional[bool] = None):
    """Sharded twin of ``entropy_ops.decode_payloads`` (same outputs),
    for both stream versions (the per-mesh-shard twin is picked from the
    recorded ``version`` exactly like the single-device dispatch)."""
    D = int(mesh.shape[axis])
    # dummy shards decode against a degenerate-but-valid table (symbol 0
    # owns the whole range) so padded lanes cannot divide by zero or gather
    # out of range; n_valid = 0 masks their output anyway
    dummy_freq = jnp.zeros((256,), jnp.int32).at[0].set(PROB_SCALE)

    def core_fn(words, freq, states, n_valid, *, version: int, rows: int):
        core = _sharded_entropy_core(
            mesh, axis, True, use_pallas, use_interpret(interpret),
            version, rows,
        )
        S = words.shape[0]
        s_pad = -(-S // D) * D
        freq_p = jnp.concatenate(
            [freq] + [dummy_freq[None]] * (s_pad - S), axis=0
        ) if s_pad != S else freq
        out = core(
            _pad_shard_axis(words, s_pad),
            freq_p,
            _pad_shard_axis(states, s_pad),
            _pad_shard_axis(n_valid, s_pad),
        )
        return out[:S]

    return entropy_ops.decode_payloads(
        comps, metas, use_pallas=use_pallas, core_fn=core_fn
    )


def archive_stripe_sharded(
    codec_params,
    pub: rlwe.PublicKey,
    frames_list: List[jax.Array],
    key: jax.Array,
    cfg: ArchiveConfig = ArchiveConfig(),
    *,
    mesh: Mesh,
    axis: str = "data",
    use_pallas: bool = True,
) -> Tuple[StripeArchive, List[jax.Array]]:
    """``archive_stripe`` with the fused entropy+seal program
    shard_map'd over ``mesh``: each mesh shard entropy-codes, packs, seals
    and parity-folds its own slice of the stripe (the CSD-array mapping)
    in ONE local launch — codes -> rANS -> pack -> ChaCha20 -> parity with
    only the parity XOR reduce crossing devices.  (Host codecs ride the
    chained sharded seal instead.)

    Outputs (streams, sealed bodies, P, Q, manifests) are bit-identical to
    the single-device ``archive_stripe`` for every mesh shape — the KEM runs
    host-side in the same order, and the sharded launches differ only in
    where each shard's kernel executes.
    """
    return archive_stripe(
        codec_params, pub, frames_list, key, cfg, use_pallas=use_pallas,
        seal_fn=functools.partial(seal_stripe_sharded, mesh=mesh, axis=axis),
        entropy_fn=functools.partial(
            entropy_encode_sharded, mesh=mesh, axis=axis
        ),
        fused_fn=_sharded_fused_fn(mesh, axis),
    )


def restore_stripe_sharded(
    codec_params,
    s: jax.Array,
    stripe: StripeArchive,
    cfg: ArchiveConfig = ArchiveConfig(),
    *,
    mesh: Mesh,
    axis: str = "data",
    use_pallas: bool = True,
    verify_parity: bool = True,
    shards: Optional[List[int]] = None,
    manifests: Optional[List[Dict]] = None,
) -> List[jax.Array]:
    """``restore_stripe`` with the unseal + entropy-decode launches
    shard_map'd over ``mesh`` — including shard-subset retrieval reads
    (``shards``) and parity-based degraded reads (``manifests``; see
    ``restore_stripe_payloads``)."""
    return restore_stripe(
        codec_params, s, stripe, cfg, use_pallas=use_pallas,
        verify_parity=verify_parity, shards=shards, manifests=manifests,
        unseal_fn=functools.partial(
            unseal_stripe_sharded, mesh=mesh, axis=axis
        ),
        entropy_decode_fn=functools.partial(
            entropy_decode_sharded, mesh=mesh, axis=axis
        ),
    )


# ------------------------------------------------------ ingest coalescing
class PendingGOP(NamedTuple):
    """One encoded-but-unsealed GOP waiting for stripe-mates."""

    stream_id: int
    payload: jax.Array  # flat int8 codec payload
    manifest: Dict
    meta: Optional[Dict] = None  # caller tag (shard assignment, psnr, ...)


class CoalescedStripe(NamedTuple):
    """S GOPs bucketed into one stripe + the pow2 row bucket to pad to."""

    gops: List[PendingGOP]
    pad_rows: int


class StripeCoalescer:
    """Buckets ragged GOPs from N camera streams into full seal stripes.

    GOPs from interleaved streams are queued by their pow2 row bucket
    (``bucket_rows_for``); whenever a bucket holds ``n_shards`` GOPs they
    are emitted as one :class:`CoalescedStripe` — one fused seal launch per
    mesh shard instead of one launch per GOP.  Bucketing serves two jobs:

      * *trace bound*: the jit'd seal core specializes on the padded stripe
        shape, so pow2 buckets cap traces at log2(max_rows) for arbitrarily
        mixed GOP sizes;
      * *padding bound*: same-bucket GOPs differ by < 2x in padded height,
        so ragged-stripe padding waste stays < 2x worst-case.

    ``flush()`` force-drains leftovers (end of epoch / checkpoint) into
    possibly short stripes so no GOP is ever stranded unsealed;
    ``drain_expired(deadline_us)`` is the straggler-aware variant — it
    drains ONLY the buckets whose oldest GOP has waited past the deadline
    (oldest bucket first), so a cold bucket cannot hold its GOPs hostage
    and p99 GOP-to-commit stays bounded while hot buckets keep batching.

    Accounting lives on a ``repro.obs.Metrics`` registry (pass ``metrics``
    to share one with the owning ingest tier — ``ArchiveIngest`` does, so
    its ``stats()`` and the coalescer's are views of the SAME instruments
    instead of two hand-assembled dicts): ``ingest.gops`` /
    ``ingest.stripes_sealed`` counters plus the ``ingest.pending_gops``
    occupancy gauge.  ``add()`` stamps ``meta["_t_submit"]`` (monotonic ns)
    when the caller didn't, so latency and deadline accounting never need
    the caller's cooperation.
    """

    def __init__(self, n_shards: int, *, metrics: Optional[Metrics] = None):
        if n_shards < 1:
            raise ValueError("n_shards must be >= 1")
        self.n_shards = n_shards
        self._buckets: Dict[int, List[PendingGOP]] = {}
        self._pending_bytes = 0
        self.metrics = metrics if metrics is not None else Metrics()

    @property
    def n_gops(self) -> int:
        return int(self.metrics.get(obs_names.ING_GOPS))

    @property
    def n_stripes(self) -> int:
        return int(self.metrics.get(obs_names.ING_STRIPES))

    @staticmethod
    def _bucket_of(payload: jax.Array) -> int:
        n_words = -(-int(payload.shape[0]) // 4)
        return seal_ops.bucket_rows_for(n_words)

    def add(self, stream_id: int, payload, manifest: Dict,
            meta: Optional[Dict] = None) -> List[CoalescedStripe]:
        """Queue one GOP; returns the stripes it completed (usually 0 or 1)."""
        payload = jnp.asarray(payload).reshape(-1).astype(jnp.int8)
        meta = dict(meta) if meta else {}
        meta.setdefault("_t_submit", time.perf_counter_ns())
        r = self._bucket_of(payload)
        pending = self._buckets.setdefault(r, [])
        pending.append(PendingGOP(stream_id, payload, manifest, meta))
        self._pending_bytes += int(payload.shape[0])
        self.metrics.add(obs_names.ING_GOPS)
        out: List[CoalescedStripe] = []
        while len(pending) >= self.n_shards:
            out.append(CoalescedStripe(pending[: self.n_shards], r))
            del pending[: self.n_shards]
        return self._emitted(out)

    def _emitted(self, out: List[CoalescedStripe]) -> List[CoalescedStripe]:
        if out:
            self.metrics.add(obs_names.ING_STRIPES, len(out))
            self._pending_bytes -= sum(
                int(g.payload.shape[0]) for cs in out for g in cs.gops
            )
        self.metrics.set_gauge(obs_names.ING_PENDING, self.n_pending)
        return out

    def flush(self) -> List[CoalescedStripe]:
        """Drain leftovers into (possibly short) stripes, largest bucket last.

        Leftovers are grouped smallest-bucket-first so mixed-size stragglers
        pad to the smallest row count covering their group.
        """
        pending = [
            g for r in sorted(self._buckets) for g in self._buckets[r]
        ]
        self._buckets.clear()
        out: List[CoalescedStripe] = []
        for i in range(0, len(pending), self.n_shards):
            group = pending[i : i + self.n_shards]
            rows = max(self._bucket_of(g.payload) for g in group)
            out.append(CoalescedStripe(group, rows))
        return self._emitted(out)

    def drain_expired(self, deadline_us: float,
                      now_ns: Optional[int] = None) -> List[CoalescedStripe]:
        """Force-drain buckets whose OLDEST GOP has waited past the deadline.

        The straggler policy: a bucket that has not filled a stripe within
        ``deadline_us`` of its oldest GOP's submit stamp is drained into a
        (possibly short) stripe rather than holding its GOPs hostage —
        this is what bounds p99 GOP-to-commit on cold buckets.  Expired
        buckets drain oldest-first (and insertion order within a bucket is
        already oldest-first), so the longest-waiting GOPs always land in
        the first emitted stripe.  Fresh buckets are untouched and keep
        batching toward full stripes.
        """
        now = time.perf_counter_ns() if now_ns is None else int(now_ns)
        cutoff = now - int(float(deadline_us) * 1e3)
        aged = []
        for r, pending in self._buckets.items():
            if not pending:  # fully-drained bucket keys linger in the dict
                continue
            t_old = min(
                (g.meta or {}).get("_t_submit", now) for g in pending
            )
            if t_old <= cutoff:
                aged.append((t_old, r))
        if not aged:
            return []
        aged.sort()
        gops = [g for _, r in aged for g in self._buckets.pop(r)]
        out: List[CoalescedStripe] = []
        for i in range(0, len(gops), self.n_shards):
            group = gops[i : i + self.n_shards]
            rows = max(self._bucket_of(g.payload) for g in group)
            out.append(CoalescedStripe(group, rows))
        return self._emitted(out)

    @property
    def n_pending(self) -> int:
        return sum(len(v) for v in self._buckets.values())

    @property
    def queue_bytes(self) -> int:
        """Payload bytes currently queued (running counter, O(1))."""
        return self._pending_bytes

    def oldest_submit_ns(self) -> Optional[int]:
        """Submit stamp of the oldest pending GOP, or None when empty."""
        stamps = [
            (g.meta or {}).get("_t_submit")
            for v in self._buckets.values() for g in v
        ]
        stamps = [s for s in stamps if s is not None]
        return min(stamps) if stamps else None

    def stats(self) -> Dict[str, float]:
        """Launch accounting: naive ingest = one seal launch per GOP.

        A registry view — every value is read back from the shared
        ``Metrics`` instruments, never tracked twice.
        """
        n_gops, n_stripes = self.n_gops, self.n_stripes
        sealed_gops = n_gops - self.n_pending
        return {
            "n_gops": n_gops,
            "n_stripes": n_stripes,
            "n_pending": self.n_pending,
            "launch_reduction": (
                sealed_gops / n_stripes if n_stripes else float("nan")
            ),
        }


def seal_coalesced_stripe(
    pub: rlwe.PublicKey,
    cs: CoalescedStripe,
    key: jax.Array,
    cfg: ArchiveConfig = ArchiveConfig(),
    *,
    mesh: Optional[Mesh] = None,
    axis: str = "data",
    use_pallas: bool = True,
) -> StripeArchive:
    """Entropy-code + seal one coalesced stripe (sharded over ``mesh`` when
    given: the fused entropy+seal kernel runs once per mesh shard).

    The bucket's ``pad_rows`` flows into the launch so every stripe from the
    same bucket shares one jit trace (re-bucketed on the compressed sizes
    when an entropy stage runs — see ``seal_payload_stripe``).
    """
    seal_fn = None
    entropy_fn = None
    fused_fn = None
    if mesh is not None:
        seal_fn = functools.partial(seal_stripe_sharded, mesh=mesh, axis=axis)
        entropy_fn = functools.partial(
            entropy_encode_sharded, mesh=mesh, axis=axis
        )
        fused_fn = _sharded_fused_fn(mesh, axis)
    return seal_payload_stripe(
        pub,
        [g.payload for g in cs.gops],
        [g.manifest for g in cs.gops],
        key,
        cfg,
        use_pallas=use_pallas,
        pad_rows=cs.pad_rows,
        seal_fn=seal_fn,
        entropy_fn=entropy_fn,
        fused_fn=fused_fn,
    )


def seal_coalesced_stripes(
    pub: rlwe.PublicKey,
    batch: List[CoalescedStripe],
    keys: List[jax.Array],
    cfg: ArchiveConfig = ArchiveConfig(),
    *,
    mesh: Optional[Mesh] = None,
    axis: str = "data",
    use_pallas: bool = True,
) -> List[StripeArchive]:
    """Batched ``seal_coalesced_stripe``: K ready stripes, ONE fused launch
    per homogeneous (shard count, row bucket) group — multi-stream ingest's
    steady state, where a drained coalescer hands over several same-bucket
    stripes at once and per-launch dispatch amortizes K-fold.

    ``keys`` carries one stripe key per batch entry (the caller's sequence
    numbering — e.g. ``ArchiveIngest`` fold_in's its stripe counter), so
    session material is bit-identical to sealing the stripes one at a time.
    Host codecs fall back to per-stripe chained sealing.
    """
    if len(batch) != len(keys):
        raise ValueError(f"{len(batch)} stripes vs {len(keys)} keys")
    if not batch:
        return []
    return seal_coalesced_stripes_finalize(
        seal_coalesced_stripes_dispatch(
            pub, batch, keys, cfg, mesh=mesh, axis=axis,
            use_pallas=use_pallas,
        )
    )


def seal_coalesced_stripes_dispatch(
    pub: rlwe.PublicKey,
    batch: List[CoalescedStripe],
    keys: List[jax.Array],
    cfg: ArchiveConfig = ArchiveConfig(),
    *,
    mesh: Optional[Mesh] = None,
    axis: str = "data",
    use_pallas: bool = True,
) -> PendingStripeSeal:
    """Async half of ``seal_coalesced_stripes``: stage + launch the batch
    WITHOUT the device sync (see ``seal_payload_stripes_dispatch``).  The
    two-slot submit ring dispatches batch k+1's host prep between this and
    ``seal_coalesced_stripes_finalize``.  Non-rans codecs have no async
    seam and seal eagerly inside the returned handle.
    """
    if len(batch) != len(keys):
        raise ValueError(f"{len(batch)} stripes vs {len(keys)} keys")
    if not batch:
        return PendingStripeSeal(None, None, [], [], [])
    if cfg.codec_name != "rans":
        archives = [
            seal_coalesced_stripe(
                pub, cs, k, cfg, mesh=mesh, axis=axis, use_pallas=use_pallas
            )
            for cs, k in zip(batch, keys)
        ]
        return PendingStripeSeal(None, None, archives, [], [])
    return seal_payload_stripes_dispatch(
        pub,
        [[g.payload for g in cs.gops] for cs in batch],
        [[g.manifest for g in cs.gops] for cs in batch],
        list(keys),
        cfg,
        use_pallas=use_pallas,
        pad_rows=[cs.pad_rows for cs in batch],
        fused_dispatch_fn=(
            _sharded_fused_dispatch_fn(mesh, axis) if mesh is not None
            else None
        ),
    )


def seal_coalesced_stripes_finalize(
    pending: PendingStripeSeal,
) -> List[StripeArchive]:
    """Blocking half: redeem a dispatched coalesced batch (the single
    device→host fetch + archive assembly + ledger billing)."""
    return seal_payload_stripes_finalize(pending)


# ------------------------------------------------------------- CSD rebuild
class RebuildItem(NamedTuple):
    """One lost shard to reconstruct onto the replacement CSD."""

    stripe_id: str
    shard: int        # stripe shard index the dead CSD owned
    body_bytes: int   # sealed bytes the rebuild writes (the budget unit)
    salience: float   # priority: most-salient stripes come back first


class RebuildRound(NamedTuple):
    rebuilt: List[RebuildItem]    # completed this round, in priority order
    bytes_rebuilt: int            # strictly <= the round's budget
    remaining: List[RebuildItem]  # carry over to the next round


def plan_rebuild(
    catalog,
    dead_csd: int,
    centroids=None,
    *,
    owner_of=None,
) -> List[RebuildItem]:
    """Rebuild work-list for one dead CSD, most-salient stripes first.

    ``owner_of(entry) -> csd`` maps a catalog entry to the device that owns
    its shard; the default is the identity mapping the ingest tiers use
    (stripe shard s lives on CSD s).  Salience is scored against the
    caller's CURRENT ``centroids`` (same scoring as retrieval), so the
    shards replay is most likely to ask for are the first ones back — a
    degraded read window shrinks where it matters most.
    """
    owner_of = owner_of or (lambda e: e.shard)
    entries = catalog.entries
    nov = catalog.score(centroids)
    items = [
        RebuildItem(e.stripe_id, e.shard, e.body_bytes, float(nov[i]))
        for i, e in enumerate(entries)
        if owner_of(e) == dead_csd
    ]
    items.sort(key=lambda it: (-it.salience, it.stripe_id, it.shard))
    return items


def _rebuild_shard_body(
    stripe: StripeArchive,
    shard: int,
    manifests: List[Dict],
    *,
    mesh: Optional[Mesh] = None,
    axis: str = "data",
    use_pallas: bool = True,
):
    """Reconstruct one lost shard's sealed body from parity.

    Single loss rides the shard_mapped parity pass: the surviving bodies go
    through the unseal kernel with zero keys and ``parity="raid5"`` — the
    kernel's P accumulation IS the XOR fold of the survivors (cross-shard
    partials combined by ``_xor_allreduce`` on a mesh), and
    ``lost = P_stored ^ XOR(survivors)``.  Only parity-sized traffic
    crosses devices; bodies stay where they live.  A double loss (another
    shard of the same stripe already missing) falls back to the host
    GF(256) ``recover_stripe`` path.
    """
    from repro.core.archival.pipeline import (
        _u32_rows_to_u8,
        recover_stripe,
    )

    parity = stripe.parity
    if parity is None:
        raise ValueError(f"shard {shard} lost and the stripe has no parity")
    missing = [i for i, b in enumerate(stripe.blocks)
               if b is None or i == shard]
    meta = manifests[shard]
    n_words = int(meta["n_words"])
    if len(missing) > 1:
        blocks = [None if i in missing else b
                  for i, b in enumerate(stripe.blocks)]
        body_lens = [
            int(manifests[i]["n_words"]) if i in missing
            else int(stripe.blocks[i].sealed.n_valid_u32)
            for i in range(len(stripe.blocks))
        ]
        return recover_stripe(
            blocks, parity, missing, manifests, body_lens,
        )[shard]
    pad_to = int(parity["pad_to"])
    R = pad_to // 128
    survivors = [
        (i, b) for i, b in enumerate(stripe.blocks) if i != shard
    ]
    nw = tuple(int(b.sealed.n_valid_u32) for _, b in survivors)
    sealed = stack_rows([b.sealed.body for _, b in survivors], R)
    packed = SealedStripe(sealed, None, None, nw, nw)
    S = len(survivors)
    zero_k = jnp.zeros((S, 8), jnp.uint32)
    zero_n = jnp.zeros((S, 3), jnp.uint32)
    if mesh is not None:
        _, p, _ = unseal_stripe_sharded(
            packed, zero_k, zero_n, mesh=mesh, axis=axis, parity="raid5",
            use_pallas=use_pallas,
        )
    else:
        _, p, _ = seal_ops.unseal_stripe(
            packed, zero_k, zero_n, parity="raid5", use_pallas=use_pallas,
        )
    from repro.core.crypto.hybrid import SealedBlock
    from repro.core.archival.pipeline import ArchivedBlock

    lost = np.asarray(_u32_rows_to_u8(p)) ^ np.asarray(parity["p"], np.uint8)
    words = jnp.asarray(
        np.ascontiguousarray(lost[: pad_to * 4]).view(np.uint32)[:n_words]
    )
    sealed_blk = SealedBlock(
        meta["kem_c1"], meta["kem_c2"], meta["nonce"], words, n_words
    )
    return ArchivedBlock(sealed_blk, meta["manifest"])


def rebuild_csd_sharded(
    get_stripe,
    manifests_for,
    items: List[RebuildItem],
    *,
    budget_bytes: int,
    put_shard,
    mesh: Optional[Mesh] = None,
    axis: str = "data",
    use_pallas: bool = True,
) -> RebuildRound:
    """One budget-bounded rebuild round onto the replacement CSD.

    Processes ``items`` strictly in order (``plan_rebuild`` already sorted
    by salience) and STOPS at the first item that would overflow
    ``budget_bytes`` — the budget is a hard ceiling, never exceeded, so
    replay traffic keeps its share of the interconnect; skipping ahead to
    smaller items would subvert the salience priority, so the round ends
    instead and ``remaining`` carries over.  ``get_stripe(stripe_id)``
    reads the degraded stripe, ``manifests_for(stripe_id)`` its replicated
    metadata records (``stripe_manifests`` format — the lost shard's KEM
    polys/nonce/length), ``put_shard(stripe_id, shard, block)`` installs
    the reconstructed :class:`ArchivedBlock` on the replacement.
    """
    rebuilt: List[RebuildItem] = []
    remaining: List[RebuildItem] = []
    spent = 0
    items = list(items)
    t0 = time.perf_counter_ns() if OBS.enabled else 0
    with OBS.span(
        "rebuild.round", items=len(items), budget_bytes=budget_bytes
    ) as sp:
        for k, it in enumerate(items):
            if spent + it.body_bytes > budget_bytes:
                remaining = items[k:]
                break
            stripe = get_stripe(it.stripe_id)
            if OBS.enabled:
                # rebuild.read: every surviving body + both parity strips
                # feed the reconstruction; rebuild.write: the rebuilt body
                # landing on the replacement CSD
                nb = sum(
                    4 * int(b.sealed.n_valid_u32)
                    for b in stripe.blocks
                    if b is not None
                )
                if stripe.parity is not None:
                    nb += int(stripe.parity["p"].size)
                    q_strip = stripe.parity.get("q")
                    if q_strip is not None:
                        nb += int(q_strip.size)
                OBS.flow(EDGE_REBUILD_READ, nb)
                OBS.flow(EDGE_REBUILD_WRITE, it.body_bytes)
            blk = _rebuild_shard_body(
                stripe, it.shard, manifests_for(it.stripe_id),
                mesh=mesh, axis=axis, use_pallas=use_pallas,
            )
            put_shard(it.stripe_id, it.shard, blk)
            rebuilt.append(it)
            spent += it.body_bytes
        sp.set(rebuilt=len(rebuilt), bytes_rebuilt=spent)
    if OBS.enabled:
        OBS.count(obs_names.REBUILD_ROUNDS)
        OBS.count(obs_names.REBUILD_SHARDS, len(rebuilt))
        OBS.count(obs_names.REBUILD_BYTES, spent)
        OBS.gauge(obs_names.REBUILD_BUDGET, budget_bytes)
        OBS.observe(
            obs_names.REBUILD_ROUND_US, (time.perf_counter_ns() - t0) / 1e3
        )
    return RebuildRound(rebuilt, spent, remaining)
