"""Telemetry in one screen: trace an ``ArchiveIngest`` session to Perfetto.

Enables the process-global ``repro.obs`` tier, pushes four camera GOPs
through the serving ingest engine (codec-encode -> stripe-coalesce ->
fused seal -> catalog), serves one budgeted retrieval plan, then dumps:

  * ``telemetry_trace.json`` — Chrome trace_event JSON; drag it onto
    https://ui.perfetto.dev and the whole stripe lifecycle (ingest.seal,
    archive.seal, retrieval.plan spans + per-edge byte counters) is one
    timeline;
  * ``telemetry_events.jsonl`` — the machine log: one JSON object per
    span, then the metrics snapshot and the byte-flow ledger report.

The ledger report at the end is the paper's data-movement claim computed
from edges alone — no counters hand-wired into the pipeline.

Every span also lands in the JAX profiler's trace as a ``TraceMe`` of the
same name: run this under ``jax.profiler.trace(dir)`` and the spans sit
beside the device operations in the ``.xplane.pb``, on their clock.

Run:  PYTHONPATH=src python examples/telemetry_trace.py
"""

import jax
import numpy as np

from repro import obs
from repro.core.archival.pipeline import ArchiveConfig
from repro.core.codec.layered_codec import CodecConfig, init_codec
from repro.core.crypto import rlwe
from repro.data.video import VideoStream, render_clip
from repro.obs.export import write_chrome_trace, write_jsonl
from repro.serving.engine import ArchiveIngest, IngestConfig


def main():
    obs.enable(reset=True)  # one switch; off by default everywhere

    ccfg = CodecConfig(n_layers=2, latent_ch=4, feat_ch=16, mv_cond_ch=4)
    icfg = IngestConfig(
        n_shards=4, archive=ArchiveConfig(codec=ccfg), feature_dim=8
    )
    pub, _ = rlwe.keygen(jax.random.PRNGKey(1))
    ing = ArchiveIngest(init_codec(jax.random.PRNGKey(0), ccfg), pub, icfg)

    rng = np.random.default_rng(0)
    print("== ingest 4 GOPs (one stripe) + 1 retrieval plan ==")
    for sid in range(4):
        frames = render_clip(
            VideoStream(sid, 1000 + sid, 32, 32, 30.0, 64), 0, 2
        )[:, None]
        ing.submit(
            sid, frames,
            feature=rng.normal(0, 1, 8),
            novelty=float(sid == 3),
        )
    ing.flush()
    plan = ing.query(np.zeros((1, 8), np.float32), k=2)
    print(f"plan: {len(plan.reads)} reads, {plan.bytes_planned} B "
          f"(full restore {plan.bytes_full_restore} B)")

    n_ev = write_chrome_trace("telemetry_trace.json", obs.OBS)
    n_ln = write_jsonl("telemetry_events.jsonl", obs.OBS)
    print(f"wrote telemetry_trace.json ({n_ev} events) -> ui.perfetto.dev")
    print(f"wrote telemetry_events.jsonl ({n_ln} records)")

    rep = obs.OBS.ledger.report()
    print("\n== byte-flow ledger (every byte attributed to an edge) ==")
    for edge, rec in rep["edges"].items():
        print(f"  {edge:28s} {rec['bytes']:>10d} B  ({rec['events']} events)")
    for k in ("entropy_ratio", "bytes_moved_ratio", "ingest_volume_ratio"):
        print(f"  {k:28s} {rep[k]:.4f}")
    obs.disable()


if __name__ == "__main__":
    main()
