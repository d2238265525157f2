"""ByteLedger: every byte crossing a boundary, attributed to a labeled edge.

The paper's Fig. 1 is a data-movement diagram; this module makes it a
queryable table.  Each edge label names one arrow of the stripe lifecycle,
billed at exactly ONE call site so totals conserve (bytes in == bytes
attributed — ``tests/test_obs.py`` pins it on a seal→scrub→restore
roundtrip):

==========================  ===================================================
edge                        billed by / meaning
==========================  ===================================================
``ingest.host_to_device``   raw codec payload bytes entering the fused seal
                            launch (``pipeline._assemble_stripe``) — the
                            pre-compression volume a host-codec design would
                            have shipped
``ingest.entropy_raw``      raw bytes through the entropy stage (shards whose
``ingest.entropy_comp``     manifest records a real codec) and the compressed
                            stream bytes they became — their ratio IS the
                            archive's rANS ``ratio``
``ingest.device_to_journal``sealed body bytes leaving the kernel for the
                            journal (compressed + sealed: the only payload
                            traffic the CSD design ships)
``ingest.shard_to_parity``  P/Q parity strip bytes per sealed stripe
``ingest.cross_chip``       bytes a mesh write launch moves between chips
                            (``distributed/archival.entropy_seal_sharded``):
                            the parity partials the XOR reduce gathers, and
                            inputs placed off the chip that held them
``ingest.shed``             payload bytes the streaming admission controller
                            refused under queue pressure
                            (``serving/ingest.StreamIngestFrontend._shed``,
                            journaled — never a silent drop)
``replay.planned``          bytes a retrieval plan promised to move
                            (``plan_retrieval``; virtual — billed at plan
                            time, compared against ``replay.read``)
``replay.full_baseline``    the no-index full-restore volume of the same
                            query (virtual) — ``planned / full_baseline`` IS
                            the catalog's ``bytes_moved_ratio``
``replay.read``             sealed body bytes a restore actually moved
                            (``restore_stripe_payloads``, present wanted
                            shards only)
``replay.parity``           degraded-read amplification: surviving unwanted
                            peer bodies + parity strips a rebuild had to read
``scrub.read``              sealed bytes a scrub round recomputed parity over
``scrub.syndrome``          P/Q strip bytes the scrub ships host-side
``rebuild.read``            surviving bodies + parity read per rebuilt shard
``rebuild.write``           reconstructed body bytes written to the
                            replacement CSD
==========================  ===================================================

``report()`` folds the table into the paper's headline ratios in one call:
``entropy_ratio`` (the rANS compression ratio recomputed from ledger edges
alone) and ``bytes_moved_ratio`` (planned subset reads vs the no-index
baseline) — the ~6.1x data-volume claim as a query, not a hand-assembled
stat.
"""

from __future__ import annotations

from typing import Dict

__all__ = [
    "ByteLedger",
    "EDGE_HOST_TO_DEVICE",
    "EDGE_ENTROPY_RAW",
    "EDGE_ENTROPY_COMP",
    "EDGE_DEVICE_TO_JOURNAL",
    "EDGE_SHARD_TO_PARITY",
    "EDGE_CROSS_CHIP",
    "EDGE_INGEST_SHED",
    "EDGE_REPLAY_PLANNED",
    "EDGE_REPLAY_FULL_BASELINE",
    "EDGE_REPLAY_READ",
    "EDGE_REPLAY_PARITY",
    "EDGE_SCRUB_READ",
    "EDGE_SCRUB_SYNDROME",
    "EDGE_REBUILD_READ",
    "EDGE_REBUILD_WRITE",
]

EDGE_HOST_TO_DEVICE = "ingest.host_to_device"
EDGE_ENTROPY_RAW = "ingest.entropy_raw"
EDGE_ENTROPY_COMP = "ingest.entropy_comp"
EDGE_DEVICE_TO_JOURNAL = "ingest.device_to_journal"
EDGE_SHARD_TO_PARITY = "ingest.shard_to_parity"
EDGE_CROSS_CHIP = "ingest.cross_chip"
EDGE_INGEST_SHED = "ingest.shed"
EDGE_REPLAY_PLANNED = "replay.planned"
EDGE_REPLAY_FULL_BASELINE = "replay.full_baseline"
EDGE_REPLAY_READ = "replay.read"
EDGE_REPLAY_PARITY = "replay.parity"
EDGE_SCRUB_READ = "scrub.read"
EDGE_SCRUB_SYNDROME = "scrub.syndrome"
EDGE_REBUILD_READ = "rebuild.read"
EDGE_REBUILD_WRITE = "rebuild.write"


class ByteLedger:
    """Per-edge byte totals + event counts.  Edges are created on first
    bill, so the totals only ever name flows that actually happened."""

    def __init__(self) -> None:
        self._bytes: Dict[str, int] = {}
        self._events: Dict[str, int] = {}

    def add(self, edge: str, nbytes: int, events: int = 1) -> None:
        self._bytes[edge] = self._bytes.get(edge, 0) + int(nbytes)
        self._events[edge] = self._events.get(edge, 0) + events

    def bytes(self, edge: str) -> int:
        return self._bytes.get(edge, 0)

    def events(self, edge: str) -> int:
        return self._events.get(edge, 0)

    def totals(self) -> Dict[str, int]:
        return dict(self._bytes)

    def _ratio(self, num: str, den: str) -> float:
        d = self._bytes.get(den, 0)
        return self._bytes.get(num, 0) / d if d else float("nan")

    def report(self) -> Dict[str, object]:
        """The one-call data-movement report: every edge's bytes/events
        plus the paper's derived ratios, computed from ledger edges alone."""
        return {
            "edges": {
                e: {"bytes": b, "events": self._events.get(e, 0)}
                for e, b in sorted(self._bytes.items())
            },
            # rANS compression ratio (raw / compressed through the coder)
            "entropy_ratio": self._ratio(EDGE_ENTROPY_RAW, EDGE_ENTROPY_COMP),
            # planned subset reads vs the no-index full-restore baseline —
            # the catalog's bytes_moved_ratio
            "bytes_moved_ratio": self._ratio(
                EDGE_REPLAY_PLANNED, EDGE_REPLAY_FULL_BASELINE
            ),
            # what restore actually moved vs what the plan promised (reads
            # of planned-but-retired stripes show up here, not as drift)
            "moved_vs_planned": self._ratio(
                EDGE_REPLAY_READ, EDGE_REPLAY_PLANNED
            ),
            # total ingest traffic the CSD design ships vs the raw volume a
            # host-codec design would have — the data-volume-reduction claim
            "ingest_volume_ratio": self._ratio(
                EDGE_DEVICE_TO_JOURNAL, EDGE_HOST_TO_DEVICE
            ),
        }

    def reset(self) -> None:
        self._bytes.clear()
        self._events.clear()
