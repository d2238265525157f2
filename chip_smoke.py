#!/usr/bin/env python3
"""Smoke run of the Salient Store archive on one TPU chip.

Deployment: a fixed-camera edge server.  16 cameras at 1280x720 (the shape
of BDD100K's 720p clips, arXiv:1805.04687 — only the shape is used, nothing
is downloaded), 8-frame GOPs (``CodecConfig.gop``), 4 data shards per
stripe with RAID-6 parity (``IngestConfig``/``ArchiveConfig`` defaults).
Frames, codec weights, keys and GOP payloads are all made from ``--seed``.

Phases, each raising on failure:

  (a) codec path — frames rendered by ``repro.data.video`` go through
      ``ArchiveIngest.submit`` (codec encode with the motion-search kernel,
      fused seal, catalog): 2 GOPs from each of 4 streams.  The motion
      kernel must match its jnp oracle on a 720p frame pair, and every
      sealed GOP must restore to its codec payload.
  (b) served ingest — 128 GOPs from 16 streams through
      ``StreamIngestFrontend.offer/pump/drain`` into an ``ArchiveIngest``
      whose catalog and shed records go to a ``csd.failure.Journal`` in a
      temporary directory.  GOP sizes come from
      ``benchmarks/ingest_workload.py`` around the size phase (a) measured
      (about 0.5 GiB of codes); the sealed stripes stay on the device.
  (c) read path — ``query()`` plans over the whole catalog and every
      planned GOP is restored; each must equal its offered payload byte
      for byte, and offered == sealed + shed.  One stripe's sealed bodies
      and P/Q must equal the ``kernels/fused/ref.py`` oracle.
  (d) durability — one ``scrub_round`` (no findings), one
      ``mark_csd_lost`` and ``rebuild_csd`` until done; the rebuilt shards
      must equal the originals.

With ``--chips 4`` only the sharded seal runs: phase (b)'s GOPs sealed by
an ``ArchiveIngest`` on a 4-device mesh and by one on a single device; the
stripes must be bit-identical.

Lines before the last are labelled ``smoke:``.  The last line of standard
output is the JSON verdict, printed only when every phase passed.  Without
a TPU the script exits non-zero and prints no verdict.

    python3 chip_smoke.py [--seed N] [--chips 4]
"""

from __future__ import annotations

import argparse
import json
import sys
import tempfile
import time
from pathlib import Path
from typing import Dict, List, NamedTuple, Optional, Tuple

ROOT = Path(__file__).resolve().parent


class Deployment(NamedTuple):
    height: int = 720
    width: int = 1280
    gop_frames: int = 8
    fps: int = 30
    n_shards: int = 4
    codec_streams: int = 4           # phase (a)
    codec_gops_per_stream: int = 2
    n_streams: int = 16              # phase (b)
    n_gops: int = 128
    # one shard of a stripe is one GOP; 4 MiB keeps every GOP inside the
    # 32768-row (4 MiB) coder bucket that a 720p GOP lands in
    max_gop_bytes: int = 4 << 20


# codes per pixel-frame of the default CodecConfig (measured at 64x64 and
# 64x128); used for the GOP size only where phase (a) does not run
NOMINAL_BYTES_PER_PIXEL_FRAME = 0.507


class SmokeFailure(RuntimeError):
    pass


def log(msg: str) -> None:
    print(f"smoke: {msg}", flush=True)


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


# ---------------------------------------------------------------- phases
def phase_codec(dep: Deployment, seed: int, ctx: Dict) -> int:
    """(a) Returns the mean GOP payload bytes the codec produced."""
    import jax
    import numpy as np

    from repro.core.archival.pipeline import encode_gop_payload
    from repro.data.video import make_streams, render_clip
    from repro.kernels.motion.ops import estimate_motion
    from repro.serving.engine import ArchiveIngest

    cfg = ctx["cfg"]
    streams = make_streams(
        dep.codec_streams, height=dep.height, width=dep.width, base_seed=seed
    )
    t = time.perf_counter()
    clip = render_clip(streams[0], 0, 2)
    mv_k, sad_k = estimate_motion(clip[1], clip[0])
    mv_r, sad_r = estimate_motion(clip[1], clip[0], use_kernel=False)
    check(
        np.array_equal(np.asarray(mv_k), np.asarray(mv_r))
        and np.array_equal(np.asarray(sad_k), np.asarray(sad_r)),
        "motion kernel differs from its oracle",
    )
    log(f"motion search equals its oracle on a {dep.height}x{dep.width} "
        f"frame pair ({time.perf_counter() - t:.1f} s)")

    ingest = ArchiveIngest(ctx["codec"], ctx["pub"], cfg, seed=seed)
    pending: List[np.ndarray] = []
    for g in range(dep.codec_gops_per_stream):
        for st in streams:
            frames = render_clip(st, g * dep.gop_frames, dep.gop_frames)
            frames = frames[:, None]                     # (T, B=1, H, W, 3)
            flat, _, _ = encode_gop_payload(ctx["codec"], frames, cfg.archive)
            pending.append(np.asarray(flat))
            ingest.submit(
                st.stream_id, frames, novelty=0.1 * (1 + st.stream_id + g)
            )
    ingest.flush()
    n_gops = len(pending)
    sids = sorted({e.stripe_id for e in ingest.catalog.entries})
    for sid in sids:
        got, _ = ingest.restore(ctx["sk"], sid)
        for payload in got:
            hit = [i for i, w in enumerate(pending)
                   if np.array_equal(np.asarray(payload), w)]
            check(hit, f"a GOP of {sid} does not restore to its codec payload")
            pending.pop(hit[0])
    check(not pending, f"{len(pending)} of {n_gops} codec GOPs not restored")
    total = sum(int(e.n_i8) for e in ingest.catalog.entries)
    pixel_frames = n_gops * dep.gop_frames * dep.height * dep.width
    log(f"codec: {n_gops} GOPs in {len(sids)} stripes, {total} code bytes, "
        f"{total / pixel_frames:.4f} bytes per pixel-frame, entropy ratio "
        f"{ingest.stats()['entropy_ratio']:.4f}")
    return total // n_gops


def run_ingest(dep: Deployment, seed: int, ctx: Dict, gop_bytes: int, *,
               mesh=None, journal=None):
    """(b) Offer the workload through the frontend.  Returns (ingest,
    frontend, committed stripes, offered payloads by (stream, seq))."""
    from benchmarks.ingest_workload import IngestWorkload, WorkloadConfig
    from repro.serving.engine import ArchiveIngest
    from repro.serving.ingest import FrontendConfig, StreamIngestFrontend

    wl = IngestWorkload(
        WorkloadConfig(
            n_streams=dep.n_streams, n_gops=dep.n_gops, seed=seed,
            min_bytes=gop_bytes // 2, median_bytes=gop_bytes, sigma=0.25,
            max_bytes=min(2 * gop_bytes, dep.max_gop_bytes),
        )
    )
    offered = {(a.stream_id, a.seq): wl.payload(a) for a in wl.arrivals}
    ingest = ArchiveIngest(
        None, ctx["pub"], ctx["cfg"], seed=seed, journal=journal, mesh=mesh
    )
    front = StreamIngestFrontend(
        ingest, FrontendConfig(queue_budget_bytes=64 << 20), seed=seed,
        journal=journal,
    )
    # arrivals on a fixed clock (16 cameras x 30 fps / 8-frame GOPs = 60
    # GOPs/s), so the straggler drains, and with them the stripes, do not
    # depend on how fast the host runs
    interval_ns = 10**9 * dep.gop_frames // (dep.n_streams * dep.fps)
    t0 = time.perf_counter_ns()
    committed = []
    for a in wl.arrivals:
        now = t0 + a.index * interval_ns
        manifest = dict(wl.manifest(a), stream=a.stream_id, seq=a.seq)
        front.offer(
            a.stream_id, offered[(a.stream_id, a.seq)], manifest,
            novelty=a.novelty, now_ns=now,
        )
        committed += front.pump(now_ns=now)
    committed += front.drain()
    check(committed, "no stripe was sealed")
    return ingest, front, committed, offered


def phase_ingest(dep: Deployment, seed: int, ctx: Dict, gop_bytes: int,
                 journal_dir: str):
    from repro.core.csd.failure import Journal

    journal = Journal(journal_dir)
    ingest, front, committed, offered = run_ingest(
        dep, seed, ctx, gop_bytes, journal=journal
    )
    sealed = sum(len(st.blocks) for st in committed)
    shed = len(front.shed_log)
    check(
        sealed + shed == dep.n_gops,
        f"offered {dep.n_gops} != sealed {sealed} + shed {shed}",
    )
    check(len(ingest.catalog) == sealed, "catalog misses sealed GOPs")
    raw = sum(int(b.manifest["n_i8"]) for st in committed for b in st.blocks)
    comp = sum(int(b.manifest["entropy"]["n_comp"])
               for st in committed for b in st.blocks)
    body = sum(4 * int(b.sealed.n_valid_u32)
               for st in committed for b in st.blocks)
    log(f"ingest: {dep.n_gops} GOPs offered, {sealed} sealed, {shed} shed, "
        f"{len(committed)} stripes; raw {raw} B, compressed {comp} B, "
        f"sealed bodies {body} B")
    return ingest, committed, offered


def _parity_equal(got_u8, want_u8) -> bool:
    import numpy as np

    got_u8, want_u8 = np.asarray(got_u8), np.asarray(want_u8)
    n = min(got_u8.size, want_u8.size)
    return bool(
        np.array_equal(got_u8[:n], want_u8[:n])
        and not got_u8[n:].any() and not want_u8[n:].any()
    )


def phase_read(dep: Deployment, ctx: Dict, ingest, committed, offered):
    """(c) Returns the blocks read, keyed by (stripe id, shard)."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core.archival.pipeline import _u32_rows_to_u8
    from repro.core.crypto import rlwe
    from repro.kernels.fused import ops as fused_ops

    plan = ingest.query()
    read: Dict[Tuple[str, int], object] = {}
    for sid, shards in plan.shards_by_stripe.items():
        got, blocks = ingest.restore(ctx["sk"], sid, shards=shards)
        for shard, payload, b in zip(shards, got, blocks):
            want = offered[(b.manifest["stream"], b.manifest["seq"])]
            check(np.array_equal(np.asarray(payload), want),
                  f"{sid} shard {shard} does not restore to its offered GOP")
            read[(sid, shard)] = b
    sealed = sum(len(st.blocks) for st in committed)
    check(len(read) == sealed, f"restored {len(read)} of {sealed} GOPs")

    # one full stripe against the staged jnp oracle, same session keys
    stripe = next(st for st in committed if len(st.blocks) == dep.n_shards)
    cfg = ctx["cfg"].archive
    keys = jnp.stack([
        rlwe.kem_decapsulate(
            ctx["sk"], rlwe.Ciphertext(b.sealed.kem_c1, b.sealed.kem_c2),
            cfg.rlwe,
        )
        for b in stripe.blocks
    ])
    nonces = jnp.stack([b.sealed.nonce for b in stripe.blocks])
    flats = [
        jnp.asarray(offered[(b.manifest["stream"], b.manifest["seq"])])
        for b in stripe.blocks
    ]
    ref, _ = fused_ops.entropy_seal_stripe(
        flats, keys, nonces, parity=cfg.parity, use_pallas=False
    )
    for s, b in enumerate(stripe.blocks):
        n = int(b.sealed.n_valid_u32)
        check(
            np.array_equal(np.asarray(ref.body(s)),
                           np.asarray(b.sealed.body)[:n]),
            f"sealed body of shard {s} differs from the fused oracle",
        )
    for name, arr in (("p", ref.p), ("q", ref.q)):
        check(_parity_equal(_u32_rows_to_u8(arr), stripe.parity[name]),
              f"parity {name.upper()} differs from the fused oracle")
    log(f"read: {len(plan.shards_by_stripe)} stripes planned, {len(read)} "
        f"GOPs restored byte-exact, {plan.bytes_planned} B planned; one "
        "stripe equals the fused oracle")
    return read


def phase_durability(dep: Deployment, ctx: Dict, ingest, read, offered):
    import numpy as np

    rnd = ingest.scrub_round(budget_bytes=1 << 40)
    check(rnd.stripes_checked == len({sid for sid, _ in read}),
          f"scrub checked {rnd.stripes_checked} stripes")
    check(not rnd.findings, f"scrub found {len(rnd.findings)} problems")
    csd = 1
    lost = ingest.mark_csd_lost(csd)
    check(lost, "no shard lived on the lost CSD")
    rounds, rebuilt = 0, 0
    while True:
        r = ingest.rebuild_csd(csd, budget_bytes=1 << 40)
        rounds += 1
        rebuilt += len(r.rebuilt)
        if not r.remaining:
            break
        check(rounds < 8, "rebuild does not converge")
    check(rebuilt == lost, f"rebuilt {rebuilt} of {lost} lost shards")
    for (sid, shard), orig in read.items():
        if shard != csd:
            continue
        got, blocks = ingest.restore(ctx["sk"], sid, shards=[csd])
        n = int(orig.sealed.n_valid_u32)
        check(
            np.array_equal(np.asarray(blocks[0].sealed.body)[:n],
                           np.asarray(orig.sealed.body)[:n]),
            f"rebuilt shard {csd} of {sid} differs from the original",
        )
        want = offered[(orig.manifest["stream"], orig.manifest["seq"])]
        check(np.array_equal(np.asarray(got[0]), want),
              f"rebuilt shard {csd} of {sid} does not restore")
    log(f"durability: scrub checked {rnd.stripes_checked} stripes, "
        f"{rnd.bytes_scrubbed} B, no findings; CSD {csd} lost {lost} "
        f"shards, rebuilt in {rounds} round(s), identical to the originals")


def _stripes_equal(a, b) -> bool:
    import numpy as np

    if len(a.blocks) != len(b.blocks):
        return False
    for x, y in zip(a.blocks, b.blocks):
        n = int(x.sealed.n_valid_u32)
        if n != int(y.sealed.n_valid_u32) or x.manifest != y.manifest:
            return False
        for f in ("kem_c1", "kem_c2", "nonce"):
            if not np.array_equal(np.asarray(getattr(x.sealed, f)),
                                  np.asarray(getattr(y.sealed, f))):
                return False
        if not np.array_equal(np.asarray(x.sealed.body)[:n],
                              np.asarray(y.sealed.body)[:n]):
            return False
    return all(
        _parity_equal(a.parity[k], b.parity[k])
        for k in ("p", "q") if k in a.parity or k in b.parity
    )


def phase_sharded(dep: Deployment, seed: int, ctx: Dict, gop_bytes: int):
    """--chips 4: the sealed stripes of a 4-device mesh equal one
    device's, and data shard s of each is kept on mesh device s."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    devices = jax.devices()
    check(len(devices) >= 4, f"--chips 4 needs 4 devices, have {len(devices)}")
    mesh = Mesh(np.asarray(devices[:4]), ("data",))
    t = time.perf_counter()
    _, _, on_mesh, _ = run_ingest(dep, seed, ctx, gop_bytes, mesh=mesh)
    t_mesh = time.perf_counter() - t
    t = time.perf_counter()
    _, _, on_one, _ = run_ingest(dep, seed, ctx, gop_bytes)
    t_one = time.perf_counter() - t
    check(len(on_mesh) == len(on_one),
          f"{len(on_mesh)} stripes on the mesh vs {len(on_one)} on one device")
    for i, (a, b) in enumerate(zip(on_mesh, on_one)):
        check(_stripes_equal(a, b), f"stripe {i} differs between the mesh "
              "and one device")
        # data shard s of a stripe is kept by mesh device s alone
        for s, blk in enumerate(a.blocks):
            check(blk.sealed.body.devices() == {devices[s]},
                  f"stripe {i}: shard {s}'s body is on "
                  f"{sorted(d.id for d in blk.sealed.body.devices())}, "
                  f"not on device {devices[s].id} alone")
    gops = sum(len(st.blocks) for st in on_one)
    log(f"sharded: {len(on_one)} stripes ({gops} GOPs) bit-identical on a "
        f"4-device mesh and on one device, each body on its shard's device; "
        f"wall {t_mesh:.1f} s (mesh) and {t_one:.1f} s (one device), "
        f"compilation included")


class PhaseClock:
    """Wall seconds of a phase and the part of them JAX spent compiling
    (summed from its backend-compile monitoring events)."""

    def __init__(self):
        import jax

        self.compile_s = 0.0
        self.compiles = 0

        def listen(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.compile_s += duration
                self.compiles += 1

        jax.monitoring.register_event_duration_secs_listener(listen)

    def phase(self, name: str, fn, *args):
        t, c0, n0 = time.perf_counter(), self.compile_s, self.compiles
        out = fn(*args)
        log(f"phase {name}: {time.perf_counter() - t:.1f} s wall, of which "
            f"{self.compile_s - c0:.1f} s compiling "
            f"{self.compiles - n0} programs")
        return out


# ------------------------------------------------------------------ main
def run(dep: Deployment, seed: int, chips: int) -> Dict:
    """All phases of the chosen mode; returns the device record."""
    import jax

    from repro.core.codec.layered_codec import init_codec
    from repro.core.crypto import rlwe
    from repro.serving.engine import IngestConfig

    cfg = IngestConfig(n_shards=dep.n_shards)
    pub, sk = rlwe.keygen(jax.random.PRNGKey(seed + 1), cfg.archive.rlwe)
    ctx = {"cfg": cfg, "pub": pub, "sk": sk}
    nominal = int(NOMINAL_BYTES_PER_PIXEL_FRAME * dep.gop_frames
                  * dep.height * dep.width)
    clock = PhaseClock()
    if chips == 4:
        clock.phase("sharded", phase_sharded, dep, seed, ctx,
                    nominal - nominal % 4)
    else:
        ctx["codec"] = jax.jit(init_codec, static_argnums=1)(
            jax.random.PRNGKey(seed), cfg.archive.codec
        )
        gop_bytes = clock.phase("a", phase_codec, dep, seed, ctx)
        with tempfile.TemporaryDirectory() as tmp:
            ingest, committed, offered = clock.phase(
                "b", phase_ingest, dep, seed, ctx, gop_bytes - gop_bytes % 4,
                tmp,
            )
            read = clock.phase(
                "c", phase_read, dep, ctx, ingest, committed, offered
            )
            clock.phase(
                "d", phase_durability, dep, ctx, ingest, read, offered
            )
    dev = jax.devices()[0]
    stats = dev.memory_stats() or {}
    if "peak_bytes_in_use" in stats:
        log(f"peak device memory {stats['peak_bytes_in_use']} B")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded seal on a 4-device mesh")
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"chip_smoke: no repro package under {ROOT / 'src'}",
              file=sys.stderr)
        return 2
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    import jax

    from repro.common.compile_cache import enable_compile_cache

    platform = jax.devices()[0].platform
    if platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX's first device is a "
              f"{platform!r} device); this smoke run needs a TPU",
              file=sys.stderr)
        return 1
    log(f"compile cache {enable_compile_cache()}")
    t0 = time.perf_counter()
    try:
        device = run(Deployment(), args.seed, args.chips)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    log(f"total {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
