"""Canonical instrument names, shared by every tier.

The serving (``serving/engine.py``) and distributed
(``distributed/archival.py``) stats surfaces used to hand-assemble their
own dicts, so a counter could be renamed on one side and silently stop
matching the other.  Both now register instruments under THESE constants
(one definition, two registries), so the names cannot drift — and the
exported snapshots stay joinable across tiers.
"""

from __future__ import annotations

# ---------------------------------------------------------------- ingest
ING_GOPS = "ingest.gops"                       # counter: GOPs submitted
ING_STRIPES = "ingest.stripes_sealed"          # counter: stripes sealed
ING_PENDING = "ingest.pending_gops"            # gauge: coalescer occupancy
ING_ENTROPY_RAW = "ingest.entropy_raw_bytes"   # counter
ING_ENTROPY_COMP = "ingest.entropy_comp_bytes"  # counter
ING_GOP_LATENCY_US = "ingest.gop_to_commit_us"  # histogram: submit->sealed
ING_QUEUE_DEPTH = "ingest.queue_depth"         # gauge: frontend queued bytes
ING_SHED_BYTES = "ingest.shed_bytes"           # counter: admission sheds
ING_SHED_GOPS = "ingest.shed_gops"             # counter: GOPs shed
# counters of OBS alone, added per seal batch while telemetry is on: the
# microseconds each GOP waited from its offer stamp to its stripe's
# dispatch, and the GOPs with a stamp they cover
ING_DISPATCH_WAIT_US = "ingest.dispatch_wait_us"
ING_DISPATCHED_GOPS = "ingest.dispatched_gops"

# ------------------------------------------------------------- retrieval
RETR_PLANS = "retrieval.plans_served"          # counter
RETR_PLANNED_BYTES = "retrieval.planned_bytes"  # counter
RETR_FULL_BYTES = "retrieval.full_restore_bytes"  # counter
RETR_SKIPPED = "retrieval.candidates_skipped"  # counter: budget rejections

# ------------------------------------------------------------- catalog
CAT_GOPS = "catalog.gops"                      # gauge
CAT_BYTES = "catalog.bytes_indexed"            # gauge

# ------------------------------------------------------------ durability
SCRUB_ROUNDS = "scrub.rounds"                  # counter
SCRUB_STRIPES = "scrub.stripes_checked"        # counter
SCRUB_BYTES = "scrub.bytes_scrubbed"           # counter
SCRUB_SYNDROME_HITS = "scrub.syndrome_hits"    # counter: nonzero syndromes
SCRUB_FINDINGS = "scrub.findings"              # counter
SCRUB_REPAIRED = "scrub.repaired"              # counter
SCRUB_ROUND_US = "scrub.round_us"              # histogram

REBUILD_ROUNDS = "rebuild.rounds"              # counter
REBUILD_SHARDS = "rebuild.shards"              # counter
REBUILD_BYTES = "rebuild.bytes_rebuilt"        # counter
REBUILD_BUDGET = "rebuild.budget_bytes"        # gauge: last round's budget
REBUILD_ROUND_US = "rebuild.round_us"          # histogram

RETIRED_STRIPES = "lifecycle.stripes_retired"  # counter
STRIPES_RETAINED = "lifecycle.stripes_retained"  # gauge
LOST_CSDS = "lifecycle.lost_csds"              # gauge

# --------------------------------------------------------------- kernels
FUSED_LAUNCHES = "kernels.fused_launches"      # counter: fused dispatch groups
FUSED_STRIPES = "kernels.fused_stripes"        # counter: stripes batched
# bytes of sealed rows and parity a fused launch sends to the host, the
# copies started at its dispatch (``kernels/fused/ops.py``, billed once per
# launch group)
ROWS_TO_HOST_BYTES = "kernels.rows_to_host_bytes"  # counter
# the seal dispatch's RLWE encapsulations, one jitted program a chunk of
# rows (``core/archival/pipeline.py``): programs launched, real sessions,
# and the dummy rows that pad a chunk to its fixed shape
KEM_LAUNCHES = "kem.launches"                  # counter
KEM_SESSIONS = "kem.sessions"                  # counter
KEM_PADDED = "kem.padded"                      # counter

# ------------------------------------------------------------------ mesh
# bytes a mesh write launch moves between chips: its inputs placed off the
# chip that held them, and the parity partials the reduce gathers
# (``distributed/archival.entropy_seal_sharded``, the one site; its ledger
# edge is ``ingest.cross_chip``)
MESH_CROSS_CHIP_BYTES = "mesh.cross_chip_bytes"  # counter
