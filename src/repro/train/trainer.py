"""Continuous-learning trainer with the full Salient Store archival loop.

Per step (Fig. 1's dual-stream dataflow, now closed into a cycle):
  1. ingest a clip batch per stream (placement engine decides which storage
     shard owns each stream — Table 2 load balancing);
  2. run the frozen backbone ONCE: its features feed both exemplar selection
     (k-means++ novelty -> train-or-archive) and the codec (compute reuse);
  3. REPLAY: every ``replay_every`` steps the trainer queries the salience
     catalog for the most-novel archived GOPs (``plan_retrieval`` against
     the current centroids, byte-budgeted), restores ONLY the planned shard
     subsets (degraded parity reads when a shard's CSD is flagged dead),
     and folds the decoded GOPs into the training batch — the archive
     participates in learning instead of being write-only;
  4. novel samples + replayed exemplars -> codec training step (Alg. 2);
  5. known samples -> archive ingest: layered-codec encode, then the GOP
     joins the multi-stream ``StripeCoalescer``; completed stripes are
     sealed + parity-coded, journal-committed (bodies, parity AND the
     replicated manifest record), and indexed into the ``StripeCatalog``
     with the GOP's pooled feature + novelty (descriptors are computed
     pre-seal, so later queries never decode a payload);
  6. heartbeat the straggler monitor; rebalance placement when flagged and
     remember dead shards so the next replay plans degraded reads;
  7. periodic checkpoint (pending stripes drain first; exemplar centroids
     ride in the checkpoint meta so novelty scoring survives a restart),
     then the stripe lifecycle tier retires archives past their TTL.

Durability interleave: every ``scrub_every`` steps a byte-budgeted
background scrub round (``core/archival/scrub.py``) re-reads journaled
stripes, parity-verifies them through the fused unseal (zero key material
moves), locates corrupt shards by P/Q syndrome and re-commits repaired
bodies — so silent corruption is found and fixed while training continues,
not discovered by a failed replay read months later.

Everything is pure JAX + the core modules.
"""

from __future__ import annotations

import json
import os
import re
import time
from typing import Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.archival.catalog import StripeCatalog, gop_descriptors
from repro.core.archival.exemplar import select_exemplars
from repro.core.archival.scrub import (
    ScrubRound,
    StripeScrubber,
    plan_retirement,
    retire_stripes,
)
from repro.core.archival.pipeline import (
    ArchiveConfig,
    ArchivedBlock,
    StripeArchive,
    encode_gop_payload,
    restore_stripe,
    stripe_manifests,
    stripe_manifests_from_json,
    stripe_manifests_to_json,
)
from repro.core.codec.feature_extractor import extract_features
from repro.core.codec.layered_codec import CodecConfig, init_codec, psnr
from repro.core.codec.training import (
    CodecTrainConfig,
    codec_train_step,
    init_codec_trainer,
)
from repro.core.crypto import rlwe
from repro.core.crypto.hybrid import SealedBlock
from repro.core.csd.failure import Journal, StragglerMonitor
from repro.core.csd.placement import Placement, balance_streams, rebalance
from repro.core.csd.retrieval import ReadPlan, plan_retrieval
from repro.data.video import VideoStream, render_clip
from repro.distributed.archival import (
    StripeCoalescer,
    seal_coalesced_stripes,
)
from repro.obs import OBS, enable as obs_enable
from repro.obs.export import commit_jsonl, write_chrome_trace, write_jsonl
from repro.train.checkpoint import (
    latest_step,
    load_checkpoint,
    load_checkpoint_meta,
    save_checkpoint,
)

__all__ = ["SalientTrainer", "TrainerConfig", "StepReport"]


class TrainerConfig(NamedTuple):
    codec: CodecConfig = CodecConfig(n_layers=2, latent_ch=4, feat_ch=16, mv_cond_ch=4)
    archive: Optional[ArchiveConfig] = None  # derived from codec if None
    n_shards: int = 4
    clip_len: int = 3
    exemplar_k: int = 4
    n_train_exemplars: int = 2
    checkpoint_every: int = 5
    parity: str = "raid6"
    # replay: every N steps pull the top-k most-novel archived GOPs (within
    # the byte budget) back through the retrieval planner into the batch;
    # replay_every=0 disables the stage
    replay_every: int = 2
    replay_k: int = 2
    replay_budget_bytes: int = 1 << 20
    # background scrub: every N steps parity-verify journaled stripes on a
    # byte budget, repairing located corruption in place (0 = off).  Scrub
    # rounds interleave with replay — both are budgeted so neither starves
    # the other
    scrub_every: int = 0
    scrub_budget_bytes: int = 1 << 20
    # stripe lifecycle: at checkpoint time retire stripes whose every GOP
    # was sealed >= ttl steps ago (0 = off) and whose novelty vs the
    # current centroids is at most retire_max_novelty (None = age alone)
    retire_ttl_steps: int = 0
    retire_max_novelty: Optional[float] = None
    # straggler drain: force-flush coalescer buckets whose oldest GOP has
    # waited longer than this many microseconds (0 = off, buckets only
    # drain at checkpoint) — bounds GOP-to-commit tail latency when a
    # cold bucket never fills a stripe
    archive_deadline_us: float = 0.0
    # telemetry: enable the process-global repro.obs tier (spans + metrics
    # + byte-flow ledger) for this trainer; each StepReport then carries a
    # per-step snapshot and ``export_telemetry`` writes a Perfetto trace +
    # fsync'd JSONL log.  Off by default: every instrumented site then
    # costs a single branch.
    telemetry: bool = False


class StepReport(NamedTuple):
    step: int
    codec_loss: float
    psnr: float
    archived_streams: int  # GOPs sealed to the journal this step
    archive_bytes: int
    novel_selected: int
    rebalanced: bool
    stripes_sealed: int = 0  # fused launches this step (coalesced stripes)
    pending_gops: int = 0  # encoded GOPs still waiting for stripe-mates
    replayed_gops: int = 0  # archived GOPs pulled back into the batch
    replay_read_bytes: int = 0  # sealed bytes the retrieval plan touched
    replay_full_bytes: int = 0  # no-index baseline (whole catalog restore)
    replay_degraded: int = 0  # replayed GOPs that needed a parity rebuild
    scrub_stripes: int = 0  # stripes parity-verified this step
    scrub_bytes: int = 0  # sealed bytes the scrub pass recomputed over
    scrub_findings: int = 0  # corruptions detected this step
    scrub_repaired: int = 0  # ... of which repaired in place + re-verified
    retired_stripes: int = 0  # stripes journaled as retired this step
    # per-step telemetry snapshot when TrainerConfig.telemetry is on:
    # {"stages": {span -> dur_us}, "metrics": registry snapshot,
    #  "ledger": byte-flow report} — None when telemetry is off
    telemetry: Optional[Dict] = None


class SalientTrainer:
    def __init__(
        self,
        streams: List[VideoStream],
        workdir: str,
        cfg: TrainerConfig = TrainerConfig(),
        seed: int = 0,
        mesh=None,
    ):
        """``mesh``: optional storage mesh — when given, stripe seals are
        shard_map'd over its ``data`` axis (one fused launch per mesh shard,
        cross-shard parity reduce) instead of running on one device."""
        self.cfg = cfg
        self.streams = streams
        self.workdir = workdir
        self.mesh = mesh
        if cfg.telemetry:
            obs_enable()
        key = jax.random.PRNGKey(seed)
        kc, kk = jax.random.split(key)
        self.codec_params = init_codec(kc, cfg.codec)
        self.train_cfg = CodecTrainConfig(codec=cfg.codec)
        self.trainable, self.frozen, self.opt_state = init_codec_trainer(
            self.codec_params, self.train_cfg
        )
        self.pub, self.secret = rlwe.keygen(kk)
        self.archive_cfg = cfg.archive or ArchiveConfig(
            codec=cfg.codec, parity=cfg.parity
        )
        self.placement: Placement = balance_streams(
            [s.fps for s in streams], cfg.n_shards
        )
        self.monitor = StragglerMonitor(cfg.n_shards)
        self.journal = Journal(workdir)
        self.coalescer = StripeCoalescer(cfg.n_shards)
        # salience index over every sealed stripe; rebuilt from the journal
        # on restart so old archives stay queryable
        self.catalog = StripeCatalog(self.journal)
        self.catalog.load()
        self._stripes: Dict[str, StripeArchive] = {}  # hot in-memory bodies
        self._dead_shards: List[int] = []  # monitor-flagged, for replay plans
        self._archive_key = jax.random.PRNGKey(seed * 31 + 7)
        # resume the stripe sequence from the journal: a restart must not
        # overwrite committed stripes or re-derive their key/nonce material
        self._stripe_seq = max(
            (
                int(m.group(1)) + 1
                for m in (
                    re.match(r"archive_(\d+)\.bin$", r["name"])
                    for r in self.journal.replay()
                )
                if m
            ),
            default=0,
        )
        # background scrubber over the journaled archive; the cursor lives
        # on the scrubber so successive rounds walk the whole archive even
        # when each round's budget covers a fraction of it
        self._scrub_recs: Dict[str, Dict] = {}
        self._scrubber = StripeScrubber(self._scrub_get, self._scrub_put)
        self._last_retired = 0
        self.step = 0
        self.known_centroids = None
        self._maybe_restore()

    # ------------------------------------------------------------- state
    def _params(self):
        return dict(self.frozen, **self.trainable)

    def _maybe_restore(self):
        st = latest_step(self.workdir)
        if st is None:
            return
        template = {
            "trainable": self.trainable,
            "opt": self.opt_state,
            "step": jnp.zeros((), jnp.int32),
        }
        _, state = load_checkpoint(self.workdir, template, st)
        self.trainable = state["trainable"]
        self.opt_state = state["opt"]
        self.step = int(state["step"])
        # exemplar centroids ride in the checkpoint meta: novelty scoring
        # (and catalog queries) resume against the learned distribution
        # instead of re-fitting it from scratch
        cents = load_checkpoint_meta(self.workdir, st).get("extra", {}).get(
            "centroids"
        )
        if cents is not None:
            self.known_centroids = jnp.asarray(cents, jnp.float32)

    def checkpoint(self):
        # drain pending ragged stripes first so a restart loses no GOP
        self._seal_and_commit(self.coalescer.flush())
        self._last_retired = self._retire_expired()
        extra = {}
        if self.known_centroids is not None:
            extra["centroids"] = np.asarray(
                self.known_centroids, np.float32
            ).tolist()
        save_checkpoint(
            self.workdir,
            self.step,
            {
                "trainable": self.trainable,
                "opt": self.opt_state,
                "step": jnp.asarray(self.step, jnp.int32),
            },
            n_shards=self.cfg.n_shards,
            parity=self.cfg.parity,
            extra_meta=extra,
        )

    # ----------------------------------------------------------- archival
    def _seal_and_commit(self, stripes) -> Tuple[int, int]:
        """Seal coalesced stripes (batched: same-bucket stripes share ONE
        fused launch, sharded over the storage mesh when attached),
        journal-commit bodies + parity + the replicated manifest record,
        and index the stripe into the salience catalog so retrieval plans
        can find its GOPs.

        Returns (GOPs sealed, sealed bytes).
        """
        stripes = list(stripes)
        n_gops, total_bytes = 0, 0
        if not stripes:
            return n_gops, total_bytes
        # draw every stripe's key/name up front (sequence order fixed
        # before any sealing — bit-identical to sealing one at a time),
        # then hand the whole batch to the fused path
        keys, rec_names = [], []
        for _ in stripes:
            keys.append(
                jax.random.fold_in(self._archive_key, self._stripe_seq)
            )
            rec_names.append(f"archive_{self._stripe_seq:08d}")
            self._stripe_seq += 1
        sealed = seal_coalesced_stripes(
            self.pub, stripes, keys, self.archive_cfg, mesh=self.mesh
        )
        for cs, rec_name, stripe in zip(stripes, rec_names, sealed):
            body = b"".join(
                np.asarray(b.sealed.body).astype("<u4").tobytes()
                for b in stripe.blocks
            )
            self.journal.commit(
                rec_name + ".bin",
                body,
                {
                    "step": self.step,
                    "streams": [g.stream_id for g in cs.gops],
                    "shards": [
                        (g.meta or {}).get("shard") for g in cs.gops
                    ],
                    "parity": self.archive_cfg.parity,
                    "body_words": [
                        int(b.sealed.body.size) for b in stripe.blocks
                    ],
                },
            )
            # replicated metadata tier: KEM polys, nonces and the packing
            # manifest, so a restarted trainer (or a degraded read) can
            # rebuild and decode this stripe from the journal alone
            self.journal.commit(
                rec_name + ".manifest.json",
                json.dumps(
                    stripe_manifests_to_json(stripe_manifests(stripe))
                ).encode(),
                {"step": self.step, "kind": "stripe_manifest"},
            )
            if stripe.parity is not None:
                # persist P/Q so shard loss in the .bin is actually recoverable
                p_u8 = np.asarray(stripe.parity["p"])
                q_u8 = stripe.parity.get("q")
                self.journal.commit(
                    rec_name + ".parity.bin",
                    p_u8.tobytes()
                    + (np.asarray(q_u8).tobytes() if q_u8 is not None else b""),
                    {
                        "step": self.step,
                        "pad_to": int(stripe.parity["pad_to"]),
                        "p_len": int(p_u8.size),
                        "has_q": q_u8 is not None,
                    },
                )
            # salience index: pooled feature + novelty recorded PRE-seal by
            # the exemplar stage rode along in the coalescer meta
            self.catalog.add_stripe(
                rec_name,
                stripe,
                gop_descriptors(cs.gops, self.catalog.feature_dim),
                sealed_step=self.step,
            )
            self._cache_stripe(rec_name, stripe)
            n_gops += len(stripe.blocks)
            total_bytes += sum(
                int(b.sealed.body.size) * 4 for b in stripe.blocks
            )
        return n_gops, total_bytes

    # ---------------------------------------------------------- retrieval
    # sealed bodies are already durable in the journal; the in-memory copy
    # is only a hot cache for replay, so it stays bounded
    STRIPE_CACHE_MAX = 16

    def _cache_stripe(self, rec_name: str, stripe: StripeArchive) -> None:
        self._stripes[rec_name] = stripe
        while len(self._stripes) > self.STRIPE_CACHE_MAX:
            self._stripes.pop(next(iter(self._stripes)))  # oldest first

    def _load_stripe(
        self, rec_name: str, recs: Optional[Dict[str, Dict]] = None
    ) -> StripeArchive:
        """Rebuild a sealed stripe from the journal (restart path): body
        words from the .bin record, KEM/nonce/manifest from the replicated
        manifest record, parity strips from the .parity.bin record.
        ``recs``: pre-scanned ``{name: record}`` journal map, so one replay
        round doing many loads scans the journal once."""
        if recs is None:
            recs = {r["name"]: r for r in self.journal.replay()}
        body_rec = recs.get(rec_name + ".bin")
        if body_rec is None:
            raise KeyError(f"stripe {rec_name} not in journal")
        mfs = stripe_manifests_from_json(
            json.loads(self.journal.read(rec_name + ".manifest.json"))
        )
        words = np.frombuffer(self.journal.read(rec_name + ".bin"), "<u4")
        blocks, off = [], 0
        for m, n in zip(mfs, body_rec["meta"]["body_words"]):
            blocks.append(
                ArchivedBlock(
                    SealedBlock(
                        m["kem_c1"], m["kem_c2"], m["nonce"],
                        jnp.asarray(words[off : off + n].copy()), int(n),
                    ),
                    m["manifest"],
                )
            )
            off += int(n)
        parity = None
        prec = recs.get(rec_name + ".parity.bin")
        if prec is not None:
            raw = np.frombuffer(
                self.journal.read(rec_name + ".parity.bin"), np.uint8
            )
            p_len = int(prec["meta"]["p_len"])
            parity = {
                "p": jnp.asarray(raw[:p_len]),
                "pad_to": int(prec["meta"]["pad_to"]),
            }
            if prec["meta"].get("has_q"):
                parity["q"] = jnp.asarray(raw[p_len:])
        return StripeArchive(blocks, parity)

    def _get_stripe(
        self, rec_name: str, recs: Optional[Dict[str, Dict]] = None
    ) -> StripeArchive:
        stripe = self._stripes.get(rec_name)
        if stripe is None:
            stripe = self._load_stripe(rec_name, recs)
            self._cache_stripe(rec_name, stripe)
        return stripe

    # --------------------------------------------------- scrub + lifecycle
    def _scrub_get(self, rec_name: str) -> StripeArchive:
        # journal truth, NOT the hot cache: disk corruption only shows up
        # when the bytes are re-read, and the scrub recs map is built with
        # verify_crc=False so known-corrupt bodies still load for repair
        return self._load_stripe(rec_name, self._scrub_recs)

    def _scrub_put(self, rec_name: str, stripe: StripeArchive) -> None:
        """Write a scrub-repaired stripe back: re-commit the body and
        parity records (the journal's newest record for a name wins on
        load, and the fresh crc32 re-arms silent-corruption detection)."""
        body = b"".join(
            np.asarray(b.sealed.body).astype("<u4").tobytes()
            for b in stripe.blocks
        )
        old = self._scrub_recs[rec_name + ".bin"]
        self.journal.commit(
            rec_name + ".bin", body,
            dict(old["meta"], scrub_repaired_step=self.step),
        )
        if stripe.parity is not None:
            p_u8 = np.asarray(stripe.parity["p"])
            q_u8 = stripe.parity.get("q")
            self.journal.commit(
                rec_name + ".parity.bin",
                p_u8.tobytes()
                + (np.asarray(q_u8).tobytes() if q_u8 is not None else b""),
                {
                    "step": self.step,
                    "pad_to": int(stripe.parity["pad_to"]),
                    "p_len": int(p_u8.size),
                    "has_q": q_u8 is not None,
                },
            )
        self._scrub_recs = {
            r["name"]: r for r in self.journal.replay(verify_crc=False)
        }
        self._stripes.pop(rec_name, None)  # drop any stale cached copy

    def _scrub_round(self) -> ScrubRound:
        """One byte-budgeted background scrub pass over the journaled
        archive (see ``core/archival/scrub``): parity syndromes through the
        fused unseal locate corrupt shards, repairs re-commit through the
        journal.  Interleaves with replay — both byte-budgeted."""
        self._scrub_recs = {
            r["name"]: r for r in self.journal.replay(verify_crc=False)
        }
        ids = sorted(
            m.group(0)[: -len(".bin")]
            for m in (
                re.match(r"archive_\d+\.bin$", n) for n in self._scrub_recs
            )
            if m
        )
        return self._scrubber.scrub_round(ids, self.cfg.scrub_budget_bytes)

    def _retire_expired(self) -> int:
        """Stripe lifecycle at checkpoint: retire stripes past the TTL (and
        below the novelty bar) in the crash-safe order — retirement record
        journaled first, then bodies/manifests/parity compact out of the
        journal, and only then is the key material gone.  Returns #retired."""
        if not self.cfg.retire_ttl_steps:
            return 0
        ids = plan_retirement(
            self.catalog,
            self.known_centroids,
            now_step=self.step,
            ttl_steps=self.cfg.retire_ttl_steps,
            max_novelty=self.cfg.retire_max_novelty,
        )
        if not ids:
            return 0
        report = retire_stripes(
            self.catalog, ids,
            records_for=lambda sid: [
                sid + ".bin", sid + ".manifest.json", sid + ".parity.bin",
            ],
        )
        for sid in report.retired:
            self._stripes.pop(sid, None)
        return len(report.retired)

    def _replay_from_archive(self) -> Tuple[List[jax.Array], Optional[ReadPlan]]:
        """Query the catalog for the most-novel archived GOPs and restore
        ONLY the shard subsets the plan names (degraded parity reads for
        shards whose CSD the monitor flagged dead)."""
        if not len(self.catalog):
            return [], None
        plan = plan_retrieval(
            self.catalog,
            self.known_centroids,
            budget_bytes=self.cfg.replay_budget_bytes,
            k=self.cfg.replay_k,
            dead_shards=self._dead_shards,
            parity_shards={"raid6": 2, "raid5": 1, "none": 0}[
                self.archive_cfg.parity
            ],
        )
        params = self._params()
        clips: List[jax.Array] = []
        recs = None
        if any(n not in self._stripes for n in plan.shards_by_stripe):
            # one journal scan shared by every cold stripe load this round
            recs = {r["name"]: r for r in self.journal.replay()}
        for rec_name in sorted(plan.shards_by_stripe):
            shard_ids = plan.shards_by_stripe[rec_name]
            try:
                stripe = self._get_stripe(rec_name, recs)
            except KeyError:
                # the stripe's journal record didn't survive replay (torn
                # mid-seal commit, or crc-failed awaiting scrub repair):
                # replay makes progress with what IS readable
                continue
            manifests = stripe_manifests(stripe)
            dead = [
                i for i in self._dead_shards if 0 <= i < len(stripe.blocks)
            ]
            if dead and stripe.parity is not None:
                # the flagged CSDs' bodies are unreachable: null them out so
                # the read is truly degraded.  The planner already refuses
                # degraded reads beyond the parity tolerance, so any WANTED
                # dead shard here is rebuildable; unwanted holes never
                # trigger a rebuild at all.
                holes = list(stripe.blocks)
                for i in dead:
                    holes[i] = None
                stripe = StripeArchive(holes, stripe.parity)
            clips.extend(
                restore_stripe(
                    params, self.secret, stripe, self.archive_cfg,
                    shards=shard_ids, manifests=manifests,
                )
            )
        return clips, plan

    # ----------------------------------------------------------- telemetry
    def _step_telemetry(self, ev0: int) -> Dict:
        """Per-step snapshot for ``StepReport.telemetry``: this step's
        span durations by stage, the metrics registry and the byte-flow
        ledger (both cumulative — the ledger is a conservation ledger)."""
        stages: Dict[str, float] = {}
        for ev in OBS.tracer.events[ev0:]:
            us = ev["dur_ns"] / 1e3
            stages[ev["name"]] = stages.get(ev["name"], 0.0) + us
        return {
            "stages": stages,
            "metrics": OBS.metrics.snapshot(),
            "ledger": OBS.ledger.report(),
        }

    def export_telemetry(self, basename: str = "telemetry") -> Dict[str, str]:
        """Write the telemetry captured so far: a Perfetto-loadable Chrome
        trace (``<workdir>/<basename>_trace.json``) plus the JSONL event
        log, committed through this trainer's journal (crc32 + fsync
        discipline — the log survives exactly like the archive does).
        Returns the paths written."""
        trace_path = os.path.join(self.workdir, f"{basename}_trace.json")
        write_chrome_trace(trace_path, OBS)
        jsonl_path = commit_jsonl(self.journal, OBS, f"{basename}.jsonl")
        return {"trace": trace_path, "jsonl": jsonl_path}

    # -------------------------------------------------------------- step
    def run_step(self, shard_times: Optional[List[float]] = None) -> StepReport:
        cfg = self.cfg
        step_key = jax.random.PRNGKey(self.step * 977 + 13)
        params = self._params()
        ev0 = len(OBS.tracer.events)

        with OBS.span("trainer.step", step=self.step):
            # 1. ingest one clip per stream
            with OBS.span("trainer.ingest_clips", streams=len(self.streams)):
                clips = {
                    s.stream_id: render_clip(
                        s, self.step * cfg.clip_len, cfg.clip_len
                    )
                    for s in self.streams
                }

            # 2. shared backbone features -> exemplar selection (per stream,
            #    pooled over space/time)
            with OBS.span("trainer.features"):
                feats = []
                for sid, clip in clips.items():
                    f = extract_features(params["extractor"], clip)
                    feats.append(f.mean(axis=(0, 1, 2)))
                fmat = jnp.stack(feats)  # (n_streams, C)
                split = select_exemplars(
                    step_key,
                    fmat,
                    k=min(cfg.exemplar_k, fmat.shape[0]),
                    n_train=min(cfg.n_train_exemplars, fmat.shape[0]),
                    known_centroids=self.known_centroids,
                )
                self.known_centroids = split.centroids
                train_ids = [int(i) for i in np.asarray(split.train_idx)]
                archive_ids = [int(i) for i in np.asarray(split.archive_idx)]

            # 3. replay: pull the most-novel archived GOPs (vs the CURRENT
            # centroids) back through the retrieval planner — only the
            # planned shard subsets are restored, so replay moves
            # catalog-priced bytes, not whole stripes
            replay_clips: List[jax.Array] = []
            plan = None
            if (
                cfg.replay_every
                and self.step % cfg.replay_every == cfg.replay_every - 1
            ):
                with OBS.span("trainer.replay"):
                    replay_clips, plan = self._replay_from_archive()

            # 3b. background scrub round (interleaves with replay; both are
            # byte-budgeted so recovery traffic never starves training reads)
            scrub = None
            if (
                cfg.scrub_every
                and self.step % cfg.scrub_every == cfg.scrub_every - 1
            ):
                with OBS.span("trainer.scrub"):
                    scrub = self._scrub_round()

            # 4. codec training on the novel clips + replayed exemplars
            with OBS.span("trainer.codec_train"):
                batch = [clips[self.streams[i].stream_id] for i in train_ids]
                want_shape = batch[0].shape if batch else None
                n_replayed = 0  # only GOPs that actually join the batch
                for g in replay_clips:
                    g = jnp.squeeze(g, axis=1)  # (T,1,H,W,3) -> (T,H,W,3)
                    # GOPs archived under a different clip geometry can't
                    # join this batch; they were still read, so the byte
                    # counters keep them
                    if want_shape is None or g.shape == want_shape:
                        batch.append(g)
                        n_replayed += 1
                train_clips = jnp.stack(batch, axis=1)  # (T, B, H, W, 3)
                self.trainable, self.opt_state, metrics = codec_train_step(
                    self.trainable, self.frozen, self.opt_state,
                    self.train_cfg, train_clips
                )

            # 5. archive ingest: codec-encode the known clips, coalesce
            # ragged GOPs across streams into full stripes; every completed
            # stripe is packed + sealed + parity-coded in ONE fused kernel
            # launch (per mesh shard when a storage mesh is attached) and
            # catalog-indexed with the exemplar stage's descriptors
            with OBS.span("trainer.archive", gops=len(archive_ids)):
                params = self._params()
                recon_psnrs = []
                ready = []
                for i in archive_ids:
                    sid = self.streams[i].stream_id
                    frames = clips[sid][:, None]  # (T, 1, H, W, 3)
                    flat, manifest, recons = encode_gop_payload(
                        params, frames, self.archive_cfg
                    )
                    recon_psnrs.append(float(psnr(recons, frames)))
                    ready += self.coalescer.add(
                        sid, flat, manifest,
                        meta={
                            "shard": self.placement.assignment[i],
                            "feature": np.asarray(fmat[i], np.float32),
                            "novelty": float(np.asarray(split.novelty)[i]),
                        },
                    )
                if cfg.archive_deadline_us > 0:
                    # straggler-aware drain: GOPs stuck past the deadline
                    # seal as (possibly short) stripes instead of waiting
                    # for stripe-mates that may never come
                    ready += self.coalescer.drain_expired(
                        cfg.archive_deadline_us
                    )
                n_sealed, total_bytes = self._seal_and_commit(ready)

            # 6. straggler handling (dead shards feed the next replay plan)
            rebalanced = False
            if shard_times is not None:
                status = self.monitor.update(shard_times)
                self._dead_shards = list(status.dead)
                if status.stragglers or status.dead:
                    self.placement = rebalance(
                        self.placement,
                        [s.fps for s in self.streams],
                        status.speed,
                    )
                    rebalanced = True

            # 7. checkpoint (drains stripes, then retires expired ones)
            self._last_retired = 0
            self.step += 1
            if self.step % cfg.checkpoint_every == 0:
                with OBS.span("trainer.checkpoint"):
                    self.checkpoint()

        return StepReport(
            step=self.step,
            codec_loss=float(metrics["loss"]),
            psnr=float(np.mean(recon_psnrs)) if recon_psnrs else float("nan"),
            archived_streams=n_sealed,
            archive_bytes=total_bytes,
            novel_selected=len(train_ids),
            rebalanced=rebalanced,
            stripes_sealed=len(ready),
            pending_gops=self.coalescer.n_pending,
            replayed_gops=n_replayed,
            replay_read_bytes=plan.bytes_planned if plan else 0,
            replay_full_bytes=plan.bytes_full_restore if plan else 0,
            replay_degraded=(
                sum(1 for r in plan.reads if r.degraded) if plan else 0
            ),
            scrub_stripes=scrub.stripes_checked if scrub else 0,
            scrub_bytes=scrub.bytes_scrubbed if scrub else 0,
            scrub_findings=len(scrub.findings) if scrub else 0,
            scrub_repaired=(
                sum(f.repaired for f in scrub.findings) if scrub else 0
            ),
            retired_stripes=self._last_retired,
            telemetry=self._step_telemetry(ev0) if OBS.enabled else None,
        )
