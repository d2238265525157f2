"""Public wrappers for the fused entropy+seal write program: batching,
padding, dispatch, manifest reconstruction.

``entropy_seal_stripes`` takes a list of stripes (each a list of ragged
int8 shard payloads) plus per-stripe session material and returns, per
stripe, the exact ``(SealedStripe, entropy_metas)`` pair the chained
``entropy.encode_payloads`` -> ``seal.seal_stripe`` path would have
produced — every stored byte, parity word, manifest dict and row count
bit-identical — from ONE device dispatch per homogeneous batch (three
Pallas kernels inside, see ``entropy_seal.py``).

Batching: stripes are grouped by (shard count, padded lane rows); each
group launches once with K stripes on the batch axis, so the per-launch
dispatch overhead amortizes K-fold (``StripeCoalescer`` already pow2-
buckets GOPs, so production batches collapse to very few groups).  The
kernel returns fixed-capacity sealed rows; the host derives each shard's
compressed length from the returned rANS word count and slices every
stripe back to the chained path's row count (``bucket_rows_for`` of the
compressed sizes when the caller passed a pad_rows bucket — mirroring
``seal_payload_stripe``'s re-bucketing — else exact ``pad_rows_for``).
Words past a shard's stored length are zero by kernel masking, so the
slice is exact.

``core_fn`` overrides the fused launch itself — it is called with the
launch's codes as a host array (it stages them itself), the other arrays,
and the launch's static config as keyword arguments
(``n_shards``/``parity``/``use_pallas``/``interpret``, since
``n_shards`` varies per batch group); the sharded path
(``repro.distributed.archival``) passes a shard_map'd wrapper, exactly
like the ``core_fn`` seams of the entropy and seal ops.  A core may
return its sealed rows and word counts per stripe and shard, (K, S', ...)
with the shard axis split over a mesh: each body then goes back to its
shard's chip.

Pipelined submission: the wrapper is split at the single device→host
sync point (the rANS word-count fetch — the ``encode_payloads``
single-fetch pattern).  ``entropy_seal_stripes_dispatch`` does all host
prep, fires the jitted launches WITHOUT blocking — the returned
:class:`PendingSeal` holds lazy device arrays — and starts each launch's
sealed rows, P and Q on their way to the host;
``entropy_seal_stripes_finalize`` performs the blocking fetch plus the
host-side manifest tail, and cuts each stripe from those host copies.
The finalize enqueues no device program: one would queue behind the next
batch's launch, which a pipelined caller has already dispatched, and the
host blocks on it once the device's in-flight programs run out.
``entropy_seal_stripes`` is exactly ``finalize(dispatch(...))``, so a
caller that overlaps host prep for batch k+1 with batch k's in-flight
launch (``repro.serving.ingest``'s two-slot submit ring) produces
bit-identical archives by construction.
"""

from __future__ import annotations

import functools
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.archival.raid import gf_pow_gen
from repro.kernels import (
    as_payload_list,
    axis_devices,
    host_blocks,
    host_rows,
    use_interpret,
)
from repro.obs import OBS, names as obs_names
from repro.kernels.entropy.ops import HEADER_BYTES, MAX_ROWS, rows_for
from repro.kernels.entropy.rans import N_LANES, STREAM_VERSION
from repro.kernels.fused import ref as _ref
from repro.kernels.fused.entropy_seal import entropy_seal_pallas
from repro.kernels.seal.ops import (
    HostRows,
    SealedStripe,
    bucket_rows_for,
    pad_rows_for,
)

__all__ = [
    "entropy_seal_stripe",
    "entropy_seal_stripes",
    "entropy_seal_stripes_dispatch",
    "entropy_seal_stripes_finalize",
    "PendingSeal",
]


class _PendingGroup(NamedTuple):
    """One in-flight fused launch group: lazy device outputs + the host
    metadata the finalize tail needs to slice them back per stripe."""

    idxs: List[int]       # stripe indices (input order) in this group
    S: int                # shards per stripe
    T: int                # padded lane rows of the launch
    n_raw: List[int]      # raw payload bytes, group-flat (len(idxs) * S)
    # lazy outputs, already on their way to the host.  Sealed rows:
    # (len(idxs)*S, T', 128) on one device, or (len(idxs), S', T', 128)
    # with the shard axis split over a mesh
    sealed: jax.Array
    n_words_rans: jax.Array  # lazy per-shard rANS word counts, same layout
    p: Optional[jax.Array]
    q: Optional[jax.Array]


class PendingSeal(NamedTuple):
    """A dispatched-but-not-fetched ``entropy_seal_stripes`` batch.

    Every launch in ``groups`` is already in flight (jax dispatch is
    async), and so is the copy of its outputs to the host; the only
    remaining work is the device→host word-count fetch and the host-side
    cut, which ``entropy_seal_stripes_finalize`` performs.  Holding one of
    these while preparing the next batch is the whole double-buffering
    contract.
    """

    n_stripes: int
    pr_list: List
    groups: List[_PendingGroup]


@functools.partial(
    jax.jit,
    static_argnames=("n_shards", "parity", "use_pallas", "interpret"),
)
def _fused_core(codes, n_valid, keys, nonces, q_coef, *, n_shards: int,
                parity: str, use_pallas: bool, interpret: bool):
    if use_pallas:
        return entropy_seal_pallas(
            codes, n_valid, keys, nonces, q_coef, n_shards=n_shards,
            parity=parity, interpret=interpret,
        )
    return _ref.entropy_seal_ref(
        codes, n_valid, keys, nonces, q_coef, n_shards=n_shards,
        parity=parity,
    )


def _fused_launch(codes, *args, **kw):
    """The one-device launch: the host's codes in one transfer, then the
    fused program."""
    return _fused_core(jax.device_put(codes), *args, **kw)


def entropy_seal_stripes_dispatch(
    stripes: Sequence,
    keys: Sequence,
    nonces: Sequence,
    *,
    parity: str = "raid6",
    use_pallas: bool = True,
    interpret: Optional[bool] = None,
    pad_rows=None,
    core_fn=None,
) -> PendingSeal:
    """Host prep + async launch for a batch of stripes — NO device sync.

    Same inputs as ``entropy_seal_stripes``; returns a :class:`PendingSeal`
    whose launches are in flight.  The caller may do arbitrary host work
    (staging the NEXT batch) before calling
    ``entropy_seal_stripes_finalize``, which performs the single blocking
    word-count fetch and the slicing tail.
    """
    if not len(stripes):
        return PendingSeal(0, [], [])
    if not (len(stripes) == len(keys) == len(nonces)):
        raise ValueError(
            f"{len(stripes)} stripes vs {len(keys)} keys / "
            f"{len(nonces)} nonces"
        )
    interp = use_interpret(interpret)
    n_stripes = len(stripes)
    if isinstance(pad_rows, (list, tuple)):
        if len(pad_rows) != n_stripes:
            raise ValueError(
                f"{len(pad_rows)} pad_rows entries vs {n_stripes} stripes"
            )
        pr_list = list(pad_rows)
    else:
        pr_list = [pad_rows] * n_stripes
    plists = [as_payload_list(p) for p in stripes]
    for pl_ in plists:
        if not pl_:
            raise ValueError("stripe must contain at least one shard payload")

    # group into launches by (shard count, padded lane rows): one kernel
    # launch per group, stripes contiguous on the batch axis
    groups: Dict[Tuple[int, int], List[int]] = {}
    stripe_T = []
    for i, pl_ in enumerate(plists):
        T = rows_for(max(int(p.shape[0]) for p in pl_))
        if T > MAX_ROWS:
            raise ValueError(
                f"payload needs {T} lane rows (max {MAX_ROWS}); split it "
                f"across more stripe shards"
            )
        stripe_T.append(T)
        groups.setdefault((len(pl_), T), []).append(i)

    out_groups: List[_PendingGroup] = []
    for (S, T), idxs in groups.items():
        # one Pallas launch per homogeneous group; the telemetry counters
        # let the seal span report its exact launch amortization
        OBS.count(obs_names.FUSED_LAUNCHES)
        OBS.count(obs_names.FUSED_STRIPES, len(idxs))
        # host staging of the launch's inputs and the launch call itself
        with OBS.span("kernels.stage", rows=T, stripes=len(idxs)) as sp:
            flats = [p for i in idxs for p in plists[i]]
            n_raw = [int(f.shape[0]) for f in flats]
            codes = host_rows(flats, T, N_LANES, np.int8)
            n_valid = jnp.asarray(n_raw, jnp.int32).reshape(-1, 1)
            keys_a = jnp.concatenate([
                jnp.asarray(keys[i], jnp.uint32).reshape(S, 8) for i in idxs
            ])
            nonces_a = jnp.concatenate([
                jnp.asarray(nonces[i], jnp.uint32).reshape(S, 3) for i in idxs
            ])
            coefs = [gf_pow_gen(s) for s in range(S)]
            q_coef = jnp.asarray(coefs * len(idxs), jnp.uint32).reshape(-1, 1)
            fn = core_fn or _fused_launch
            sealed, n_words_rans, p, q = fn(
                codes, n_valid, keys_a, nonces_a, q_coef, n_shards=S,
                parity=parity, use_pallas=use_pallas, interpret=interp,
            )
            if OBS.enabled:
                sp.set(chips=len(sealed.sharding.device_set))
        # before anything else is enqueued behind this launch; on a mesh
        # each chip sends its own shards, and one chip P and Q
        for out in (sealed, p, q):
            if out is not None:
                out.copy_to_host_async()
                OBS.count(obs_names.ROWS_TO_HOST_BYTES, out.nbytes)
        out_groups.append(
            _PendingGroup(idxs, S, T, n_raw, sealed, n_words_rans, p, q)
        )
    return PendingSeal(n_stripes, pr_list, out_groups)


def entropy_seal_stripes_finalize(
    pending: PendingSeal,
) -> List[Tuple[SealedStripe, List[Dict]]]:
    """Blocking tail of a dispatched batch: fetch the rANS word counts
    (the ONLY wait on the launch) and cut every stripe and its parity,
    from the host copies of the launch's outputs that its dispatch
    started, to the chained path's row count.  Idempotence is not needed —
    call once."""
    results: List = [None] * pending.n_stripes
    pr_list = pending.pr_list
    for g in pending.groups:
        S, T, K = g.S, g.T, len(g.idxs)
        shard_axis = g.sealed.ndim - 3
        # the host waits here for the launch to finish, then for the copies
        # of its outputs: each chip's block of shards as (K, S'/chips, T',
        # 128), and one copy of P and Q
        with OBS.span("kernels.fetch", stripes=K):
            nw_host = np.asarray(g.n_words_rans).reshape(K, -1)
            blocks = [b.reshape((K, -1) + b.shape[-2:])
                      for b in host_blocks(g.sealed, shard_axis)]
            p, q = (None if a is None else np.asarray(a) for a in (g.p, g.q))
        owners = axis_devices(g.sealed, shard_axis)
        for j, i in enumerate(g.idxs):
            off = j * S
            metas, stored_words, stored_len = [], [], []
            for s in range(S):
                nr = g.n_raw[off + s]
                nc = HEADER_BYTES + 2 * int(nw_host[j, s])
                if nc >= nr:
                    metas.append(
                        {"codec": "rans", "version": STREAM_VERSION,
                         "raw": True, "n_raw": nr, "n_comp": nr, "rows": T}
                    )
                    nc = nr
                else:
                    metas.append(
                        {"codec": "rans", "version": STREAM_VERSION,
                         "n_raw": nr, "n_comp": nc, "rows": T}
                    )
                stored_len.append(nc)
                stored_words.append(-(-nc // 4))
            rows_of = bucket_rows_for if pr_list[i] is not None else pad_rows_for
            R = rows_of(max(stored_words))
            # one block is cut in place; a mesh's blocks are joined
            parts = [b[j, :, :R] for b in blocks]
            rows = parts[0] if len(parts) == 1 else np.concatenate(parts)
            stripe = SealedStripe(
                rows[:S].view(HostRows),
                None if p is None else p[j, :R].view(HostRows),
                None if q is None else q[j, :R].view(HostRows),
                tuple(stored_words),
                tuple(stored_len),
                tuple(owners[:S]) if owners else None,
                None if g.p is None else g.p.sharding,
            )
            results[i] = (stripe, metas)
    return results


def entropy_seal_stripes(
    stripes: Sequence,
    keys: Sequence,
    nonces: Sequence,
    *,
    parity: str = "raid6",
    use_pallas: bool = True,
    interpret: Optional[bool] = None,
    pad_rows=None,
    core_fn=None,
) -> List[Tuple[SealedStripe, List[Dict]]]:
    """Fused archival for a batch of stripes (one dispatch per group).

    stripes: per-stripe payload lists (ragged int8, or (S, N) arrays);
    keys / nonces: per-stripe (S, 8) / (S, 3) uint32 session material;
    pad_rows: None, an int, or a per-stripe sequence — a not-None entry
    requests the chained pipeline's pow2 re-bucketing of the sealed rows
    on the COMPRESSED sizes (the raw bucket value itself is superseded,
    exactly as ``seal_payload_stripe`` re-buckets before the chained
    seal); None requests the chained exact ``pad_rows_for`` padding.

    Returns ``[(SealedStripe, entropy_metas), ...]`` in input order,
    bit-identical to encode_payloads -> seal_stripe per stripe.  This is
    exactly ``finalize(dispatch(...))`` — the pipelined submit ring uses
    the two halves directly and stays bit-identical by construction.
    """
    return entropy_seal_stripes_finalize(
        entropy_seal_stripes_dispatch(
            stripes, keys, nonces, parity=parity, use_pallas=use_pallas,
            interpret=interpret, pad_rows=pad_rows, core_fn=core_fn,
        )
    )


def entropy_seal_stripe(
    payloads, keys, nonces, *, parity: str = "raid6",
    use_pallas: bool = True, interpret: Optional[bool] = None,
    pad_rows: Optional[int] = None, core_fn=None,
) -> Tuple[SealedStripe, List[Dict]]:
    """Single-stripe convenience twin of ``entropy_seal_stripes``."""
    return entropy_seal_stripes(
        [payloads], [keys], [nonces], parity=parity, use_pallas=use_pallas,
        interpret=interpret, pad_rows=[pad_rows], core_fn=core_fn,
    )[0]
