"""Streaming multi-stream ingest frontend: admission control + the
two-slot pipelined submit ring over the archive ingest tier.

``ArchiveIngest`` (``serving/engine.py``) is the storage tier's sealing
core, but by itself it is a single-caller toy: one synchronous
``submit -> coalesce -> seal`` chain with no notion of N concurrent
camera streams, no behavior when the coalescer falls behind, and no
overlap between host-side GOP staging and the device launch.  This
module is the edge server's camera-facing front door over it:

* **N bounded stream queues** — every stream gets its own session
  identity (a per-stream key derived by ``fold_in`` from the frontend
  seed) and per-stream sequence numbers, and a bounded GOP queue.  The
  identity material tags GOPs and shed records; stripe *seal* keys are
  untouched (still ``ArchiveIngest``'s sequence-numbered draw), so
  archives stay bit-identical to the synchronous path.
* **Admission control** — when a stream's queue is full, or aggregate
  queued bytes exceed ``queue_budget_bytes``, the LOWEST-novelty queued
  GOP is shed first (the retrieval tier would have ranked it last
  anyway).  A shed is never silent: each one appends a journal record
  (stream id, sequence number, novelty, bytes, reason), lands on the
  ``ingest.shed`` ledger edge (billed at exactly one call site,
  ``_shed``), and bumps the tier registry's ``ingest.shed_bytes``/
  ``ingest.shed_gops`` counters.
* **Two-slot submit ring** — ``pump()`` moves admitted GOPs into the
  coalescer and walks ready stripes through
  ``_seal_dispatch``/``_seal_commit`` (the split around the fused seal's
  single blocking device→host fetch): the batch-k launch runs on device
  while batch k+1's host prep (bucketing, payload staging, KEM) runs on
  the host, and slot k is fetched/committed only after k+1 has been
  dispatched.  Commits are strictly FIFO, so stripe ids/keys keep their
  sequence order and the ring is bit-identical to the synchronous path
  by construction (pinned by ``tests/test_ingest_scale.py``).
* **Straggler-aware drain** — each ``pump()`` also force-drains
  coalescer buckets whose oldest GOP has waited past ``deadline_us``
  (``StripeCoalescer.drain_expired``), so p99 GOP-to-commit is bounded
  even on cold buckets that never fill a stripe.

The 16/256/1024-stream ``ingest_scale`` bench
(``benchmarks/kernels_bench.py`` + ``benchmarks/ingest_workload.py``)
drives this frontend and gates stripes/s, p50/p99 GOP-to-commit, shed
fraction, and launches-per-stripe in ``run.py --check``.
"""

from __future__ import annotations

import time
from collections import deque
from typing import Dict, List, NamedTuple, Optional

import jax
import numpy as np

from repro.core.archival.pipeline import StripeArchive
from repro.obs import EDGE_INGEST_SHED, OBS
from repro.obs import names as obs_names
from repro.serving.engine import ArchiveIngest

__all__ = [
    "FrontendConfig",
    "QueuedGOP",
    "ShedRecord",
    "StreamIngestFrontend",
]

SHED_PREFIX = "shed_"


class FrontendConfig(NamedTuple):
    # per-stream bounded queue: GOPs a single camera may hold un-admitted
    max_stream_gops: int = 8
    # aggregate admission budget over every stream's queued payload bytes
    queue_budget_bytes: int = 8 << 20
    # ready stripes per submit-ring slot (one fused dispatch per slot)
    batch_stripes: int = 4
    # straggler deadline: a coalescer bucket whose oldest GOP has waited
    # longer than this is force-drained as a partial stripe
    deadline_us: float = 500_000.0


class QueuedGOP(NamedTuple):
    """One admitted-but-uncoalesced GOP in a stream's bounded queue."""

    stream_id: int
    seq: int            # per-stream sequence number
    payload: jax.Array  # flat int8 codec payload
    manifest: Dict
    meta: Dict          # novelty/feature/_t_submit (+ stream identity)
    nbytes: int


class ShedRecord(NamedTuple):
    """What admission control refused — journaled, never silently dropped."""

    stream_id: int
    seq: int
    nbytes: int
    novelty: float
    reason: str  # "stream_queue" | "byte_budget"


class _StreamState:
    __slots__ = ("key", "seq", "queue")

    def __init__(self, key):
        self.key = key
        self.seq = 0
        self.queue: deque = deque()


class StreamIngestFrontend:
    """Admission control + pipelined seal submission for N camera streams.

    ``offer`` admits one PRE-ENCODED GOP payload from one stream (the
    neural codec runs upstream where the frames are hot — the frontend
    moves flat int8 payloads, exactly what ``StripeCoalescer`` eats).
    ``pump`` advances the machine: queued GOPs -> coalescer -> ready
    stripes -> the two-slot submit ring.  ``drain`` force-flushes
    everything (partial stripes included) and empties the ring.
    """

    def __init__(
        self,
        ingest: ArchiveIngest,
        cfg: FrontendConfig = FrontendConfig(),
        *,
        seed: int = 0,
        journal=None,
    ):
        self.ingest = ingest
        self.cfg = cfg
        self.journal = journal
        self.metrics = ingest.metrics  # one registry for the whole tier
        self._root_key = jax.random.PRNGKey(seed * 6151 + 13)
        self._streams: Dict[int, _StreamState] = {}
        self._queued_bytes = 0
        self._inflight = None  # the ring's occupied slot (0 or 1 in flight)
        self._shed_seq = 0
        self.shed_log: List[ShedRecord] = []
        self.committed: int = 0  # stripes committed through the ring

    # ---------------------------------------------------------- admission
    def _stream(self, stream_id: int) -> _StreamState:
        st = self._streams.get(stream_id)
        if st is None:
            # per-stream session identity: derived once, rides in GOP meta
            # and shed records; stripe seal keys are NOT derived from it
            st = _StreamState(jax.random.fold_in(self._root_key, stream_id))
            self._streams[stream_id] = st
        return st

    def offer(
        self,
        stream_id: int,
        payload,
        manifest: Dict,
        *,
        novelty: float = 0.0,
        feature=None,
        now_ns: Optional[int] = None,
    ) -> bool:
        """Admit one GOP into its stream's bounded queue.

        Returns True if the offered GOP was admitted (it may still be shed
        LATER by the byte-budget pass if lower-novelty work is absent),
        False if admission shed it immediately.  Shedding always prefers
        the lowest-novelty GOP — offered or already queued.
        """
        st = self._stream(stream_id)
        seq = st.seq
        st.seq += 1
        payload = np.asarray(payload).reshape(-1).astype(np.int8)
        nbytes = int(payload.shape[0])
        meta = {
            "novelty": float(novelty),
            "stream_seq": seq,
            "_t_submit": time.perf_counter_ns() if now_ns is None
            else int(now_ns),
        }
        if feature is not None:
            meta["feature"] = np.asarray(feature, np.float32).reshape(-1)
        gop = QueuedGOP(stream_id, seq, payload, manifest, meta, nbytes)
        admitted = True
        if len(st.queue) >= self.cfg.max_stream_gops:
            # stream queue full: keep the higher-novelty of (offered,
            # lowest-novelty queued) — shed the other
            victim_i = min(
                range(len(st.queue)),
                key=lambda i: st.queue[i].meta["novelty"],
            )
            victim = st.queue[victim_i]
            if victim.meta["novelty"] < gop.meta["novelty"]:
                del st.queue[victim_i]
                self._queued_bytes -= victim.nbytes
                self._shed(victim, "stream_queue")
                st.queue.append(gop)
                self._queued_bytes += nbytes
            else:
                self._shed(gop, "stream_queue")
                admitted = False
        else:
            st.queue.append(gop)
            self._queued_bytes += nbytes
        self._enforce_budget()
        self.metrics.set_gauge(obs_names.ING_QUEUE_DEPTH, self.queue_bytes)
        return admitted

    def _enforce_budget(self) -> None:
        """Shed lowest-novelty queued GOPs until under the byte budget."""
        while self._queued_bytes > self.cfg.queue_budget_bytes:
            victim_st, victim_i = None, -1
            worst = None
            for st in self._streams.values():
                for i, g in enumerate(st.queue):
                    nov = g.meta["novelty"]
                    if worst is None or nov < worst:
                        worst, victim_st, victim_i = nov, st, i
            if victim_st is None:
                break  # nothing queued; budget must be < 0 — give up
            victim = victim_st.queue[victim_i]
            del victim_st.queue[victim_i]
            self._queued_bytes -= victim.nbytes
            self._shed(victim, "byte_budget")

    def _shed(self, gop: QueuedGOP, reason: str) -> None:
        """The ONE shed site: journal + ledger edge + counters.  Never a
        silent drop — the record survives a power loss if a journal is
        attached, and always lands in ``shed_log``."""
        rec = ShedRecord(
            gop.stream_id, gop.seq, gop.nbytes,
            float(gop.meta["novelty"]), reason,
        )
        self.shed_log.append(rec)
        if self.journal is not None:
            self.journal.commit(
                f"{SHED_PREFIX}{self._shed_seq:08d}.json",
                b"",
                meta={
                    "stream_id": rec.stream_id,
                    "seq": rec.seq,
                    "nbytes": rec.nbytes,
                    "novelty": rec.novelty,
                    "reason": rec.reason,
                },
            )
        self._shed_seq += 1
        OBS.flow(EDGE_INGEST_SHED, gop.nbytes)
        self.metrics.add(obs_names.ING_SHED_BYTES, gop.nbytes)
        self.metrics.add(obs_names.ING_SHED_GOPS)

    # ------------------------------------------------------------- pumping
    def _admit_to_coalescer(self) -> List:
        """Drain every stream queue into the coalescer, round-robin across
        streams in stream-id order so no camera can starve its peers."""
        ready = []
        queues = [
            (sid, st) for sid, st in sorted(self._streams.items())
            if st.queue
        ]
        while queues:
            next_round = []
            for sid, st in queues:
                g = st.queue.popleft()
                self._queued_bytes -= g.nbytes
                ready += self.ingest.coalescer.add(
                    g.stream_id, g.payload, g.manifest, meta=g.meta
                )
                if st.queue:
                    next_round.append((sid, st))
            queues = next_round
        return ready

    def _admit(self, *, flush: bool = False,
               now_ns: Optional[int] = None) -> List:
        """Queued GOPs into the coalescer, then its ready stripes out:
        those the GOPs filled, plus the deadline-expired buckets (or, with
        ``flush``, every bucket)."""
        if not OBS.enabled:
            return self._admit_ready(flush, now_ns)
        gops0 = self.metrics.get(obs_names.ING_GOPS)
        with OBS.span("ingest.admit") as sp:
            ready = self._admit_ready(flush, now_ns)
            sp.set(gops=int(self.metrics.get(obs_names.ING_GOPS) - gops0),
                   stripes=len(ready))
        return ready

    def _admit_ready(self, flush: bool, now_ns: Optional[int]) -> List:
        ready = self._admit_to_coalescer()
        if flush:
            return ready + self.ingest.coalescer.flush()
        return ready + self.ingest.coalescer.drain_expired(
            self.cfg.deadline_us, now_ns=now_ns
        )

    def pump(self, *, now_ns: Optional[int] = None) -> List[StripeArchive]:
        """Advance the machine one turn: admit queued GOPs, deadline-drain
        straggler buckets, and walk ready stripes through the two-slot
        submit ring.  Returns the stripes COMMITTED this turn (the ring
        may still hold one dispatched-but-unfetched slot — ``drain`` it).
        """
        ready = self._admit(now_ns=now_ns)
        committed: List[StripeArchive] = []
        B = max(1, int(self.cfg.batch_stripes))
        for i in range(0, len(ready), B):
            batch = ready[i : i + B]
            # dispatch k+1 (host prep + async launch), THEN fetch/commit
            # slot k — the fetch waits on a launch that has been running
            # the whole time the host was staging this batch
            slot = self.ingest._seal_dispatch(batch)
            if self._inflight is not None:
                committed += self.ingest._seal_commit(self._inflight)
            self._inflight = slot
        self.committed += len(committed)
        self.metrics.set_gauge(obs_names.ING_QUEUE_DEPTH, self.queue_bytes)
        return committed

    def drain(self) -> List[StripeArchive]:
        """Flush everything: queued GOPs, partial coalescer buckets, and
        the ring's in-flight slot.  The frontend is empty afterwards."""
        ready = self._admit(flush=True)
        committed: List[StripeArchive] = []
        if self._inflight is not None:
            committed += self.ingest._seal_commit(self._inflight)
            self._inflight = None
        if ready:
            committed += self.ingest._seal(ready)
        self.committed += len(committed)
        self.metrics.set_gauge(obs_names.ING_QUEUE_DEPTH, self.queue_bytes)
        return committed

    # ------------------------------------------------------------ querying
    @property
    def queue_bytes(self) -> int:
        """Aggregate queued payload bytes (streams + coalescer)."""
        return self._queued_bytes + self.ingest.coalescer.queue_bytes

    @property
    def n_streams(self) -> int:
        return len(self._streams)

    def stream_key(self, stream_id: int) -> jax.Array:
        """The stream's derived session identity key."""
        return self._stream(stream_id).key

    def stats(self) -> Dict[str, float]:
        m = self.metrics
        gops = int(m.get(obs_names.ING_GOPS))
        shed = int(m.get(obs_names.ING_SHED_GOPS))
        offered = gops + shed
        return {
            "n_streams": self.n_streams,
            "queue_bytes": self.queue_bytes,
            "stripes_committed": self.committed,
            "shed_gops": shed,
            "shed_bytes": int(m.get(obs_names.ING_SHED_BYTES)),
            "shed_frac": shed / offered if offered else 0.0,
        }
