"""Backlog upload on a four-CSD node: ``backlog.py``'s closed loop, with
the archive on a ``("data",)`` mesh of the cell's chips, chip d playing
data CSD d.

The window, the drain and the comparison with the reference are
``backlog.py``'s.  What differs is the program's set-up (every write shape
is warmed through the mesh seal, and the frontend's ``ArchiveIngest`` is
given the mesh) and one more number compared:

* ``shards_misplaced``: acknowledged stripes whose data-shard-d body is not
  held by chip d alone (limit 0); the GOPs of such stripes count as
  failed.

A program that does not keep each shard on its chip cannot run this
deployment: every warm-up launch is checked, and the first that misplaces
a body stops the run with a non-zero exit, before the window.

The warm-up's log line splits its wall time by JAX's monitoring events:
tracing to jaxprs, lowering to modules, building programs (compiling, or
loading from the persistent cache), and the rest (staging, running,
fetching and assembling the launches).
"""

from __future__ import annotations

import os
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))

import archive_ops  # noqa: E402
from harness import Check, load_module  # noqa: E402

backlog = load_module(HERE / "backlog.py")

window = backlog.window
finish = backlog.finish


def data_mesh(run):
    """The ``("data",)`` mesh over the cell's chips."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    return Mesh(np.asarray(jax.devices()[: run.cell.chips]), ("data",))


def misplaced(stripe, mesh) -> bool:
    """Whether some data shard's body is not held by its own chip alone:
    shard s on the mesh's chip s // ceil(S / chips)."""
    devices = list(mesh.devices.flat)
    per_chip = -(-len(stripe.blocks) // len(devices))
    for s, b in enumerate(stripe.blocks):
        held = getattr(b.sealed.body, "devices", lambda: set())()
        if held != {devices[s // per_chip]}:
            return True
    return False


# JAX's monitoring events of the stages that build a program
BUILD_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "tracing",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lowering",
    "/jax/core/compile/backend_compile_duration": "building",
}


class BuildClock:
    """While ``on``, the intervals of JAX's tracing, lowering and
    building events, by stage.  A trace nested in another reports its own
    interval too, so each stage's seconds are the union of its
    intervals."""

    def __init__(self):
        import jax

        self.on = False
        self.spans = {stage: [] for stage in BUILD_EVENTS.values()}

        def listen(event, duration, **_):
            stage = BUILD_EVENTS.get(event)
            if self.on and stage:
                end = time.perf_counter()
                self.spans[stage].append((end - duration, end))

        jax.monitoring.register_event_duration_secs_listener(listen)

    @staticmethod
    def union(spans) -> float:
        total, reach = 0.0, float("-inf")
        for a, b in sorted(spans):
            if b > reach:
                total += b - max(a, reach)
                reach = b
        return total

    def split(self, wall: float) -> str:
        parts = [f"{stage} {self.union(s):.3f} s"
                 for stage, s in self.spans.items()]
        every = [iv for s in self.spans.values() for iv in s]
        return ", ".join(parts + [f"the rest {wall - self.union(every):.3f} s"])


def warm_mesh_write_shapes(run, mesh) -> int:
    """``archive_ops.warm_write_shapes`` through the mesh seal: one batch
    of every (stripes per launch, shards, row bucket) the traffic can form,
    so that every mesh write program and every slice of its output is
    built before the window.  Returns the launches made."""
    import jax

    from repro.distributed.archival import (
        CoalescedStripe, PendingGOP, seal_coalesced_stripes)
    from repro.kernels.seal.ops import bucket_rows_for

    w = run.workload
    pub, _ = run.keys()
    cfg = run.ingest_config()
    S_full = int(run.cfg["data_shards"])
    K_max = int(run.cfg["frontend"]["batch_stripes"])
    combos = [(S_full, k) for k in range(1, K_max + 1)]
    combos += [(s, k) for s in range(1, S_full) for k in (1, 2)]
    pool = w.pool
    launches = 0

    def seal(r, n, S, K):
        batch = [
            CoalescedStripe(
                [PendingGOP(s, pool[(k * S + s) % pool.shape[0], :n],
                            archive_ops.manifest(-1, n), {})
                 for s in range(S)], r)
            for k in range(K)
        ]
        keys = [jax.random.fold_in(jax.random.PRNGKey(7), i)
                for i in range(K)]
        out = seal_coalesced_stripes(pub, batch, keys, cfg.archive, mesh=mesh)
        if any(misplaced(st, mesh) for st in out):
            raise SystemExit(
                "chipbench: shards_misplaced: the program's mesh seal does "
                "not keep data shard d's body on chip d; it cannot run "
                f"{run.cell.name}")
        return out[0].parity["pad_to"] if out[0].parity else None

    for r, (lo, hi) in sorted(archive_ops._extremes(
            w.sizes, lambda n: bucket_rows_for(-(-n // 4))).items()):
        stored = {}
        for S, K in combos:
            stored[S, K] = seal(r, lo, S, K)
            launches += 1
        if hi == lo:
            continue
        launches += 1
        if seal(r, hi, S_full, 1) == stored[S_full, 1]:
            continue
        for S, K in combos[1:]:
            seal(r, hi, S, K)
            launches += 1
    return launches


def make_frontend(run, mesh, name: str):
    """``Run.make_frontend`` with the archive on ``mesh``."""
    from repro.core.csd.failure import Journal
    from repro.serving.engine import ArchiveIngest
    from repro.serving.ingest import FrontendConfig, StreamIngestFrontend

    pub, _ = run.keys()
    jdir = os.path.join(run.tmp, name)
    journal = Journal(jdir)
    ingest = ArchiveIngest(None, pub, run.ingest_config(), mesh=mesh,
                           seed=run.prog_seed, journal=journal)
    front = StreamIngestFrontend(
        ingest, FrontendConfig(**run.cfg["frontend"]),
        seed=run.prog_seed, journal=journal)
    return front, jdir


def setup(run) -> None:
    mesh = data_mesh(run)
    clock = BuildClock()
    clock.on, t = True, time.perf_counter()
    n = warm_mesh_write_shapes(run, mesh)
    wall, clock.on = time.perf_counter() - t, False
    run.log(f"warm-up: {n} seal launches over every mesh write shape on "
            f"{mesh.size} chips in {wall:.3f} s ({clock.split(wall)})")
    front, jdir = make_frontend(run, mesh, "journal")
    run.state.update(front=front, jdir=jdir, acknowledged=[], ack_ns=[], g=0,
                     mesh=mesh)
    run.stamps["journal_dir"] = jdir


def check(run):
    failed, checks = backlog.check(run)
    g0, g1 = run.stamps["offered"]
    # the acknowledged stripes are the archive's retained ones, object for
    # object (``ArchiveIngest._seal_commit`` keeps what it returns)
    bad = [st for st in run.state["acknowledged"]
           if misplaced(st, run.state["mesh"])]
    checks.append(Check("shards_misplaced", len(bad), 0))
    return min(g1 - g0, failed + sum(len(st.blocks) for st in bad)), checks
