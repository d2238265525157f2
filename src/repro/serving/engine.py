"""The edge server's camera-facing archive ingest tier (``ArchiveIngest``).

The continuous-learning edge server *serves* N camera streams pushing
ragged GOPs.  GOPs are codec-encoded on arrival, coalesced across streams
into full parity stripes (``StripeCoalescer``), and each batch of completed
stripes is entropy-coded by the on-device interleaved-rANS kernel and
sealed, one fused launch per row bucket; with a mesh attached, each chip
codes and seals its own shards (the CSD-array mapping; see
``repro.distributed.archival``).  ``IngestConfig.archive.codec_name`` falls
back to the host zstd/zlib codec for compatibility.

The ingest tier also fronts the archive's READ side: every sealed stripe is
indexed into a :class:`StripeCatalog` with the per-GOP salience descriptors
callers pass to ``submit`` (feature vector + novelty — computed where the
frames were already hot), and ``query`` turns a trainer's centroids into a
budgeted :class:`ReadPlan` over the catalog without decoding anything.
``stats()`` reports the measured entropy ratio, host-side entropy bytes
(zero for the on-device coder), and the retrieval counters: cataloged GOPs/
bytes and how many bytes the plans served actually touched vs the no-index
full-restore baseline.

The ingest tier also hosts the durability loop over everything it sealed
(scrub -> rebuild -> retire; ``core/archival/scrub.py``): ``scrub_round``
parity-verifies retained stripes on a byte budget and repairs located
corruption, ``mark_csd_lost``/``rebuild_csd`` degrade and then reconstruct
a dead CSD's shards onto a replacement (budget-bounded, salience-priority),
and ``retire`` journals low-salience stripes out of existence before their
key material is recycled.  All of it shows up in ``stats()``.
"""

from __future__ import annotations

import functools
import time
from typing import Dict, List, NamedTuple, Optional

import jax
import numpy as np

from repro.core.archival.catalog import StripeCatalog, gop_descriptors
from repro.core.archival.pipeline import (
    ArchiveConfig,
    StripeArchive,
    encode_gop_payload,
    restore_stripe_payloads,
    stripe_manifests,
)
from repro.core.archival.scrub import StripeScrubber, retire_stripes
from repro.core.csd.retrieval import ReadPlan, plan_retrieval
from repro.distributed.archival import (
    StripeCoalescer,
    entropy_decode_sharded,
    plan_rebuild,
    rebuild_csd_sharded,
    seal_coalesced_stripes_dispatch,
    seal_coalesced_stripes_finalize,
    unseal_stripe_sharded,
)
from repro.obs import Metrics, OBS
from repro.obs import names as obs_names

__all__ = ["IngestConfig", "ArchiveIngest"]


class IngestConfig(NamedTuple):
    n_shards: int = 4  # GOPs per stripe == storage shards per parity group
    archive: ArchiveConfig = ArchiveConfig()
    feature_dim: int = 8  # salience descriptor width (zeros when not given)


class ArchiveIngest:
    """Multi-stream GOP ingest front-end for the edge server's storage tier.

    ``submit`` accepts one GOP from one camera stream: the clip is
    codec-encoded immediately (features are hot — same frames the serving/
    training tier just saw) and the flat payload joins the coalescer; the
    optional ``feature``/``novelty`` salience descriptor rides along and is
    catalog-indexed when the stripe seals.  The returned list holds every
    :class:`StripeArchive` whose stripe this GOP completed — sealed,
    parity-coded, ready for the journal/placement tier.  ``flush`` drains
    stragglers (end of epoch, shutdown) the same way.  ``query`` serves the
    retrieval side: centroids in, budgeted per-shard read plan out.
    """

    def __init__(
        self,
        codec_params,
        pub,
        cfg: IngestConfig = IngestConfig(),
        *,
        mesh=None,
        axis: str = "data",
        seed: int = 0,
        journal=None,
    ):
        self.codec_params = codec_params
        self.pub = pub
        self.cfg = cfg
        self.mesh = mesh
        self.axis = axis
        # one instrument registry for the whole ingest tier — the coalescer
        # shares it, so ``stats()`` / ``snapshot()`` are views of a single
        # set of counters instead of two hand-assembled dicts
        self.metrics = Metrics()
        self.coalescer = StripeCoalescer(cfg.n_shards, metrics=self.metrics)
        self.catalog = StripeCatalog(journal)
        if journal is not None:
            # a restart must see the old index AND resume the stripe id
            # sequence past it — otherwise new seals would overwrite old
            # catalog records (and reuse key material) under colliding ids
            self.catalog.load()
        self._key = jax.random.PRNGKey(seed * 9176 + 29)
        self._stripe_seq = max(
            (
                int(e.stripe_id[len("ingest_"):]) + 1
                for e in self.catalog.entries
                if e.stripe_id.startswith("ingest_")
            ),
            default=0,
        )
        # durability tier: retained sealed stripes + replicated manifests
        # (the in-memory stand-in for the CSD fleet's disks), the background
        # scrubber, and the lost-CSD set the rebuild path drains
        self._stripes: Dict[str, StripeArchive] = {}
        self._manifests: Dict[str, List[Dict]] = {}
        self._lost_csds: set = set()
        self._scrubber = StripeScrubber(
            self._stripes.__getitem__, self._stripes.__setitem__
        )

    def _seal_dispatch(self, ready):
        """Async half of ``_seal``: draw keys/ids in sequence order, stage
        the batch and dispatch the fused launch WITHOUT the device sync.
        Returns the slot the submit ring carries: ``(ready, stripe_ids,
        pending)``, redeemed by ``_seal_commit`` — which MUST run in
        dispatch order (stripe ids/keys are sequence-numbered)."""
        if not ready:
            return None
        # draw every stripe's key/id up front (sequence order fixed before
        # any sealing), then hand the whole batch to the fused path — same-
        # bucket stripes share ONE kernel launch instead of one per stripe
        keys, stripe_ids = [], []
        for _ in ready:
            keys.append(jax.random.fold_in(self._key, self._stripe_seq))
            stripe_ids.append(f"ingest_{self._stripe_seq:08d}")
            self._stripe_seq += 1
        if OBS.enabled:
            # how long each GOP waited, from its offer to this dispatch
            now = time.perf_counter_ns()
            stamps = [(g.meta or {}).get("_t_submit") for cs in ready
                      for g in cs.gops]
            waits = [now - t for t in stamps if t is not None]
            OBS.metrics.add(obs_names.ING_DISPATCH_WAIT_US, sum(waits) / 1e3)
            OBS.metrics.add(obs_names.ING_DISPATCHED_GOPS, len(waits))
        with OBS.span(
            "ingest.seal", stripes=len(ready),
            codec=self.cfg.archive.codec_name,
        ):
            pending = seal_coalesced_stripes_dispatch(
                self.pub, list(ready), keys, self.cfg.archive,
                mesh=self.mesh, axis=self.axis,
            )
        return (list(ready), stripe_ids, pending)

    def _seal_commit(self, slot) -> List[StripeArchive]:
        """Blocking half of ``_seal``: fetch the dispatched batch, then
        catalog/retain/meter every stripe exactly as the synchronous path
        always has (the commit stamp feeds the GOP latency histogram)."""
        if slot is None:
            return []
        ready, stripe_ids, pending = slot
        with OBS.span("ingest.commit", stripes=len(ready)):
            stripes = seal_coalesced_stripes_finalize(pending)
            t_commit = time.perf_counter_ns()
            # the journal commit: each stripe's catalog record, fsynced
            with OBS.span("ingest.journal", stripes=len(stripes)):
                for cs, stripe_id, stripe in zip(ready, stripe_ids, stripes):
                    self.catalog.add_stripe(
                        stripe_id,
                        stripe,
                        gop_descriptors(
                            cs.gops,
                            self.catalog.feature_dim or self.cfg.feature_dim,
                        ),
                    )
            for cs, stripe_id, stripe in zip(ready, stripe_ids, stripes):
                for b in stripe.blocks:
                    em = b.manifest.get("entropy")
                    if em and em.get("codec") != "none":
                        self.metrics.add(
                            obs_names.ING_ENTROPY_RAW, int(em["n_raw"])
                        )
                        self.metrics.add(
                            obs_names.ING_ENTROPY_COMP, int(em["n_comp"])
                        )
                for g in cs.gops:
                    t_sub = (g.meta or {}).get("_t_submit")
                    if t_sub is not None:
                        self.metrics.observe(
                            obs_names.ING_GOP_LATENCY_US,
                            (t_commit - t_sub) / 1e3,
                        )
                self._stripes[stripe_id] = stripe
                self._manifests[stripe_id] = stripe_manifests(stripe)
            self.metrics.set_gauge(obs_names.CAT_GOPS, len(self.catalog))
            self.metrics.set_gauge(
                obs_names.CAT_BYTES, self.catalog.bytes_indexed
            )
            self.metrics.set_gauge(
                obs_names.STRIPES_RETAINED, len(self._stripes)
            )
            return list(stripes)

    def _seal(self, ready) -> List[StripeArchive]:
        # the synchronous entry IS dispatch+commit back-to-back, so the
        # pipelined submit ring (``serving/ingest.py``) stays bit-identical
        # to this path by construction
        return self._seal_commit(self._seal_dispatch(ready))

    def submit(
        self,
        stream_id: int,
        frames: jax.Array,
        *,
        feature=None,
        novelty: float = 0.0,
    ) -> List[StripeArchive]:
        """frames: (T, B, H, W, 3) one GOP. Returns stripes it completed.

        ``feature``: (feature_dim,) pooled salience descriptor from the
        serving/training tier (the frames are hot there); ``novelty``: its
        score vs the current exemplar centroids.  Both are optional — GOPs
        without them are cataloged with zero descriptors and simply rank
        last in retrieval queries.
        """
        flat, manifest, _ = encode_gop_payload(
            self.codec_params, frames, self.cfg.archive
        )
        # the submit stamp feeds the GOP-submit -> journal-commit latency
        # histogram when this GOP's stripe seals (monotonic clock; the key
        # rides the coalescer meta, ignored by gop_descriptors)
        meta = {"novelty": float(novelty), "_t_submit": time.perf_counter_ns()}
        if feature is not None:
            meta["feature"] = np.asarray(feature, np.float32).reshape(-1)
        ready = self.coalescer.add(stream_id, flat, manifest, meta=meta)
        return self._seal(ready)

    def flush(self) -> List[StripeArchive]:
        """Seal all pending GOPs into (possibly short) stripes."""
        return self._seal(self.coalescer.flush())

    def query(
        self,
        centroids=None,
        *,
        budget_bytes: Optional[int] = None,
        k: Optional[int] = None,
        dead_shards=(),
    ) -> ReadPlan:
        """Plan a retrieval over everything this ingest tier has sealed:
        rank cataloged GOPs by novelty vs ``centroids``, price host-vs-CSD
        decode, and emit the per-stripe shard subsets to restore."""
        plan = plan_retrieval(
            self.catalog, centroids, budget_bytes, k=k,
            dead_shards=dead_shards,
            parity_shards={"raid6": 2, "raid5": 1, "none": 0}[
                self.cfg.archive.parity
            ],
        )
        self.metrics.add(obs_names.RETR_PLANS)
        self.metrics.add(obs_names.RETR_PLANNED_BYTES, plan.bytes_planned)
        self.metrics.add(obs_names.RETR_FULL_BYTES, plan.bytes_full_restore)
        self.metrics.add(obs_names.RETR_SKIPPED, plan.skipped)
        return plan

    def restore(self, s, stripe_id: str, shards=None):
        """Read a retained stripe back: unseal + entropy-decode the shard
        subset a plan names (``plan.shards_by_stripe[stripe_id]``; None =
        every shard, parity-verified) with the RLWE secret ``s``.  Lost
        shards are rebuilt from parity on the way.  With a mesh, the
        unseal and decode run shard_map'd over it.  Returns (codec
        payloads, their blocks) in ``shards`` order."""
        mesh_fns = {}
        if self.mesh is not None:
            mesh_fns = dict(
                unseal_fn=functools.partial(
                    unseal_stripe_sharded, mesh=self.mesh, axis=self.axis),
                entropy_decode_fn=functools.partial(
                    entropy_decode_sharded, mesh=self.mesh, axis=self.axis),
            )
        return restore_stripe_payloads(
            s, self._stripes[stripe_id], self.cfg.archive, shards=shards,
            manifests=self._manifests[stripe_id], **mesh_fns,
        )

    # ------------------------------------------------------ durability tier
    def scrub_round(self, budget_bytes: int):
        """One byte-budgeted background scrub pass over the retained
        stripes (parity syndromes through the fused unseal — zero keys
        move; see ``core/archival/scrub``).  Corrupt shards located by the
        P/Q syndrome are repaired in place.  Returns the ``ScrubRound``."""
        rnd = self._scrubber.scrub_round(
            sorted(self._stripes), budget_bytes
        )
        self.metrics.add(obs_names.SCRUB_ROUNDS)
        self.metrics.add(obs_names.SCRUB_STRIPES, rnd.stripes_checked)
        self.metrics.add(obs_names.SCRUB_BYTES, rnd.bytes_scrubbed)
        self.metrics.add(obs_names.SCRUB_FINDINGS, len(rnd.findings))
        self.metrics.add(
            obs_names.SCRUB_REPAIRED, sum(f.repaired for f in rnd.findings)
        )
        return rnd

    def mark_csd_lost(self, csd: int) -> int:
        """A CSD died (StragglerMonitor verdict): its shard of every
        retained stripe is gone until ``rebuild_csd`` restores it onto a
        replacement.  Returns how many stripe shards went degraded."""
        self._lost_csds.add(int(csd))
        self.metrics.set_gauge(obs_names.LOST_CSDS, len(self._lost_csds))
        n = 0
        for sid, stripe in self._stripes.items():
            if csd < len(stripe.blocks) and stripe.blocks[csd] is not None:
                blocks = list(stripe.blocks)
                blocks[csd] = None
                self._stripes[sid] = stripe._replace(blocks=blocks)
                n += 1
        return n

    def rebuild_csd(self, csd: int, budget_bytes: int, centroids=None):
        """One budget-bounded rebuild round for a lost CSD: reconstruct its
        shards onto the replacement via the sharded parity pass, most-
        salient stripes first.  Call repeatedly until ``remaining`` is
        empty — the CSD leaves the lost set only then."""
        items = [
            it for it in plan_rebuild(self.catalog, csd, centroids)
            if it.stripe_id in self._stripes
            and self._stripes[it.stripe_id].blocks[it.shard] is None
        ]

        def put_shard(sid, shard, blk):
            stripe = self._stripes[sid]
            blocks = list(stripe.blocks)
            blocks[shard] = blk
            self._stripes[sid] = stripe._replace(blocks=blocks)

        rnd = rebuild_csd_sharded(
            self._stripes.__getitem__, self._manifests.__getitem__, items,
            budget_bytes=budget_bytes, put_shard=put_shard,
            mesh=self.mesh, axis=self.axis,
        )
        self.metrics.add(obs_names.REBUILD_SHARDS, len(rnd.rebuilt))
        self.metrics.add(obs_names.REBUILD_BYTES, rnd.bytes_rebuilt)
        if not rnd.remaining:
            self._lost_csds.discard(int(csd))
        self.metrics.set_gauge(obs_names.LOST_CSDS, len(self._lost_csds))
        return rnd

    def retire(self, stripe_ids) -> int:
        """Retire stripes (lifecycle tier): journal the retirement, compact
        the catalog's journal, then drop bodies + key material — strictly
        in that order (see ``scrub.retire_stripes``).  Returns #retired."""
        report = retire_stripes(self.catalog, list(stripe_ids))
        for sid in report.keys_recyclable:
            # bodies (and the KEM material inside them) only after the
            # retirement is journaled
            self._stripes.pop(sid, None)
            self._manifests.pop(sid, None)
        self.metrics.add(obs_names.RETIRED_STRIPES, len(report.retired))
        self.metrics.set_gauge(
            obs_names.STRIPES_RETAINED, len(self._stripes)
        )
        self.metrics.set_gauge(obs_names.CAT_GOPS, len(self.catalog))
        self.metrics.set_gauge(
            obs_names.CAT_BYTES, self.catalog.bytes_indexed
        )
        return len(report.retired)

    def stats(self) -> Dict[str, float]:
        """Legacy stats view — every value read back from the shared
        ``Metrics`` registry (one set of instruments, see ``snapshot``
        for the windowed raw form)."""
        m = self.metrics
        s = self.coalescer.stats()
        raw = m.get(obs_names.ING_ENTROPY_RAW)
        comp = m.get(obs_names.ING_ENTROPY_COMP)
        s["entropy_ratio"] = raw / comp if comp else float("nan")
        # payload bytes the entropy stage moved over the host link: the
        # on-device coder ships none, the zstd/zlib fallback ships them all
        on_device = self.cfg.archive.codec_name in ("rans", "none")
        s["host_entropy_bytes"] = 0 if on_device else int(raw)
        # retrieval side: what the salience index is saving on reads
        s["catalog_gops"] = len(self.catalog)
        s["catalog_bytes"] = self.catalog.bytes_indexed
        s["plans_served"] = int(m.get(obs_names.RETR_PLANS))
        planned = int(m.get(obs_names.RETR_PLANNED_BYTES))
        full = int(m.get(obs_names.RETR_FULL_BYTES))
        s["planned_read_bytes"] = planned
        s["planned_full_bytes"] = full
        s["retrieval_bytes_ratio"] = planned / full if full else float("nan")
        # durability tier: is the archive being continuously verified?
        s["stripes_retained"] = len(self._stripes)
        s["lost_csds"] = len(self._lost_csds)
        s["scrub_rounds"] = int(m.get(obs_names.SCRUB_ROUNDS))
        s["scrub_bytes"] = int(m.get(obs_names.SCRUB_BYTES))
        s["scrub_findings"] = int(m.get(obs_names.SCRUB_FINDINGS))
        s["scrub_repaired"] = int(m.get(obs_names.SCRUB_REPAIRED))
        s["rebuilt_shards"] = int(m.get(obs_names.REBUILD_SHARDS))
        s["rebuilt_bytes"] = int(m.get(obs_names.REBUILD_BYTES))
        s["stripes_retired"] = int(m.get(obs_names.RETIRED_STRIPES))
        return s

    def snapshot(self, reset: bool = False) -> Dict[str, object]:
        """Raw registry snapshot (canonical ``repro.obs.names`` keys,
        histograms as summary dicts).  ``reset=True`` gives windowed
        semantics: counters and histograms zero after the read so the next
        snapshot reports per-interval activity; gauges (occupancy, catalog
        size) are levels and keep their value.  NOTE: ``stats()`` reads
        the same counters, so a windowed reset clears its cumulative
        totals too — pick one consumption style per instance.
        """
        return self.metrics.snapshot(reset=reset)
