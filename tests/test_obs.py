"""Telemetry tier tests: histogram accuracy, snapshot windowing, the
disabled fast path, byte-ledger conservation across the stripe lifecycle,
registry-backed engine stats, the trainer-level acceptance loop
(Perfetto trace + ledger report whose ratios recompute from edges alone),
spans in the JAX profiler's trace, and the served write path's spans and
counters.
"""

import glob
import json
import os
import time

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro import obs
from repro.core.archival.pipeline import (
    ArchiveConfig,
    restore_stripe_payloads,
    seal_payload_stripe,
    stripe_manifests,
)
from repro.core.archival.scrub import StripeScrubber
from repro.core.crypto import rlwe
from repro.obs import (
    EDGE_DEVICE_TO_JOURNAL,
    EDGE_ENTROPY_COMP,
    EDGE_ENTROPY_RAW,
    EDGE_HOST_TO_DEVICE,
    EDGE_REPLAY_FULL_BASELINE,
    EDGE_REPLAY_PARITY,
    EDGE_REPLAY_PLANNED,
    EDGE_REPLAY_READ,
    EDGE_SCRUB_READ,
    EDGE_SCRUB_SYNDROME,
    EDGE_SHARD_TO_PARITY,
    OBS,
    Metrics,
)
from repro.obs import names as obs_names


@pytest.fixture(autouse=True)
def _obs_clean():
    """Every test starts disabled with empty instruments and leaves the
    process-global singleton the same way (other test files rely on the
    off-by-default contract)."""
    obs.disable()
    obs.reset()
    yield
    obs.disable()
    obs.reset()


def _stripe(seed=3, n=8 * 1024, S=4, cfg=None):
    rng = np.random.default_rng(seed)
    cfg = cfg or ArchiveConfig()
    pub, sec = rlwe.keygen(jax.random.PRNGKey(seed + 1))
    flats = [
        jnp.asarray(
            np.clip(np.round(rng.normal(0, 2.0, n)), -128, 127), jnp.int8
        )
        for _ in range(S)
    ]
    mans = [{"n_i8": int(f.shape[0]), "spec": []} for f in flats]
    stripe = seal_payload_stripe(
        pub, flats, mans, jax.random.PRNGKey(seed + 2), cfg
    )
    return stripe, flats, sec, cfg


def _body_bytes(stripe, shards):
    return sum(
        4 * int(stripe.blocks[i].sealed.n_valid_u32)
        for i in shards
        if stripe.blocks[i] is not None
    )


# ----------------------------------------------------------- histograms
def test_histogram_percentiles_vs_numpy():
    rng = np.random.default_rng(0)
    samples = rng.lognormal(mean=5.0, sigma=2.0, size=20_000)
    m = Metrics()
    for x in samples:
        m.observe("lat", float(x))
    for q, key in ((50, "p50"), (95, "p95"), (99, "p99")):
        ref = float(np.percentile(samples, q))
        got = m.histogram("lat").summary()[key]
        # fixed geometric buckets (growth 2**0.125 => <=~9% bucket error)
        assert got == pytest.approx(ref, rel=0.12), (q, got, ref)
    s = m.histogram("lat").summary()
    assert s["count"] == samples.size
    assert s["min"] == pytest.approx(samples.min())
    assert s["max"] == pytest.approx(samples.max())
    assert s["sum"] == pytest.approx(samples.sum(), rel=1e-6)


def test_histogram_percentiles_clamped_to_observed_range():
    m = Metrics()
    m.observe("one", 42.0)
    s = m.histogram("one").summary()
    assert s["p50"] == s["p99"] == pytest.approx(42.0)


# ---------------------------------------------------- snapshot windowing
def test_snapshot_reset_windowing():
    m = Metrics()
    m.add("c", 5)
    m.set_gauge("g", 7.0)
    m.observe("h", 10.0)
    m.observe("h", 20.0)

    snap = m.snapshot(reset=True)  # window 1: read-and-zero
    assert snap["c"] == 5
    assert snap["g"] == 7.0
    assert snap["h"]["count"] == 2

    m.add("c", 2)
    snap2 = m.snapshot(reset=True)  # window 2 holds ONLY window-2 traffic
    assert snap2["c"] == 2
    assert snap2["h"]["count"] == 0
    assert snap2["g"] == 7.0  # gauges are levels, not flows: they persist

    assert m.snapshot()["c"] == 0  # plain snapshot does not consume


def test_engine_style_snapshot_delegates(tmp_path):
    # ArchiveIngest.snapshot(reset=...) is a thin view of its registry
    from repro.serving.engine import ArchiveIngest  # noqa: F401  (API exists)

    assert hasattr(ArchiveIngest, "snapshot")


# -------------------------------------------------- disabled fast path
def test_disabled_mode_records_nothing():
    assert not OBS.enabled
    stripe, flats, sec, cfg = _stripe(seed=11)
    scrubber = StripeScrubber({"s": stripe}.__getitem__, lambda k, v: None)
    scrubber.scrub_round(["s"], 1 << 30)
    restore_stripe_payloads(sec, stripe, cfg)
    assert OBS.tracer.events == []
    assert OBS.tracer.dropped == 0
    assert OBS.ledger.totals() == {}
    assert OBS.metrics.snapshot() == {}


def test_disabled_span_is_shared_null():
    sp = OBS.span("x", a=1)
    assert sp is OBS.span("y")  # one shared NullSpan, zero allocation


# ------------------------------------------------- ledger conservation
def test_ledger_conservation_seal_scrub_restore():
    with obs.enabled():
        stripe, flats, sec, cfg = _stripe(seed=5)
        S = len(stripe.blocks)
        led = OBS.ledger

        # ingest: journal edge == the sealed bodies, byte for byte
        d2j = _body_bytes(stripe, range(S))
        assert led.bytes(EDGE_DEVICE_TO_JOURNAL) == d2j
        assert led.bytes(EDGE_HOST_TO_DEVICE) == sum(
            int(f.shape[0]) for f in flats
        )
        # rans actually ran: raw == host payload bytes, comp is smaller
        assert led.bytes(EDGE_ENTROPY_RAW) == led.bytes(EDGE_HOST_TO_DEVICE)
        assert 0 < led.bytes(EDGE_ENTROPY_COMP) < led.bytes(EDGE_ENTROPY_RAW)
        par = int(stripe.parity["p"].size) + int(stripe.parity["q"].size)
        assert led.bytes(EDGE_SHARD_TO_PARITY) == par

        # scrub: the round's own accounting and the ledger agree exactly
        store = {"s": stripe}
        scrubber = StripeScrubber(store.__getitem__, store.__setitem__)
        sr = scrubber.scrub_round(["s"], 1 << 30)
        assert led.bytes(EDGE_SCRUB_READ) == sr.bytes_scrubbed == d2j
        assert led.bytes(EDGE_SCRUB_SYNDROME) == sr.syndrome_bytes == par

        # full restore: replay.read == every sealed body == journal edge
        restore_stripe_payloads(sec, stripe, cfg)
        assert led.bytes(EDGE_REPLAY_READ) == d2j
        assert led.bytes(EDGE_REPLAY_PARITY) == 0

        # degraded subset read: wanted [1, 2] with shard 1 lost.  The
        # present wanted body bills replay.read; the rebuild's extra
        # traffic (surviving peers OUTSIDE the subset + both parity
        # strips) bills replay.parity — nothing is double-billed.
        led.reset()
        mans = stripe_manifests(stripe)
        holes = list(stripe.blocks)
        holes[1] = None
        broken = stripe._replace(blocks=holes)
        out, _ = restore_stripe_payloads(
            sec, broken, cfg, shards=[1, 2], manifests=mans
        )
        np.testing.assert_array_equal(np.asarray(out[0]), np.asarray(flats[1]))
        assert led.bytes(EDGE_REPLAY_READ) == _body_bytes(broken, [1, 2])
        assert led.bytes(EDGE_REPLAY_PARITY) == (
            _body_bytes(broken, [0, 3]) + par
        )
        assert led.events(EDGE_REPLAY_PARITY) == 1  # one degraded shard
    assert not OBS.enabled  # context restored the prior flag


def test_ledger_report_ratios_recompute_from_edges():
    with obs.enabled():
        stripe, flats, sec, cfg = _stripe(seed=7)
        restore_stripe_payloads(sec, stripe, cfg)
        led = OBS.ledger
        rep = led.report()
        assert rep["entropy_ratio"] == pytest.approx(
            led.bytes(EDGE_ENTROPY_RAW) / led.bytes(EDGE_ENTROPY_COMP)
        )
        assert rep["ingest_volume_ratio"] == pytest.approx(
            led.bytes(EDGE_DEVICE_TO_JOURNAL) / led.bytes(EDGE_HOST_TO_DEVICE)
        )
        # no plan ran -> the planned-vs-baseline ratios are honest NaNs
        assert np.isnan(rep["bytes_moved_ratio"])
        for e, rec in rep["edges"].items():
            assert rec["bytes"] == led.bytes(e)
            assert rec["events"] == led.events(e)


# ------------------------------------------------------- engine registry
def test_engine_stats_are_registry_views(tmp_path):
    from repro.core.codec.layered_codec import CodecConfig, init_codec
    from repro.core.csd.failure import Journal
    from repro.data.video import VideoStream, render_clip
    from repro.serving.engine import ArchiveIngest, IngestConfig

    ccfg = CodecConfig(n_layers=2, latent_ch=4, feat_ch=16, mv_cond_ch=4)
    codec_params = init_codec(jax.random.PRNGKey(0), ccfg)
    pub, _ = rlwe.keygen(jax.random.PRNGKey(1))
    icfg = IngestConfig(
        n_shards=2, archive=ArchiveConfig(codec=ccfg), feature_dim=4
    )
    ing = ArchiveIngest(
        codec_params, pub, icfg, journal=Journal(str(tmp_path))
    )

    def _frames(i):
        return render_clip(
            VideoStream(i, 300 + i, 32, 32, 30.0, 64), 0, 2
        )[:, None]

    for i in range(4):
        ing.submit(i, _frames(i), feature=np.zeros(4), novelty=0.5)
    ing.flush()
    ing.query(np.zeros((1, 4), np.float32), k=1)

    s = ing.stats()
    snap = ing.snapshot()
    # stats() and the coalescer's stats() are views over ONE registry
    assert s["catalog_gops"] == snap[obs_names.CAT_GOPS] == 4
    assert s["plans_served"] == snap[obs_names.RETR_PLANS] == 1
    assert (
        ing.coalescer.stats()["n_gops"] == snap[obs_names.ING_GOPS] == 4
    )
    assert s["entropy_ratio"] == pytest.approx(
        snap[obs_names.ING_ENTROPY_RAW] / snap[obs_names.ING_ENTROPY_COMP]
    )
    # submit->commit latency histogram saw every sealed GOP
    assert snap[obs_names.ING_GOP_LATENCY_US]["count"] == 4
    assert snap[obs_names.ING_GOP_LATENCY_US]["p50"] > 0

    # windowed read: second window only carries new traffic
    ing.snapshot(reset=True)
    assert ing.snapshot()[obs_names.RETR_PLANS] == 0
    assert ing.snapshot()[obs_names.CAT_GOPS] == 4  # gauge: still the level
    ing.query(np.zeros((1, 4), np.float32), k=1)
    assert ing.snapshot()[obs_names.RETR_PLANS] == 1
    assert ing.stats()["catalog_gops"] == 4  # stats() unharmed by windows


# -------------------------------------------------- trainer acceptance
def test_trainer_telemetry_trace_and_ledger(tmp_path):
    from repro.data.video import make_streams
    from repro.train.trainer import SalientTrainer, TrainerConfig

    cfg = TrainerConfig(
        n_shards=2,
        checkpoint_every=2,
        replay_every=2,
        scrub_every=2,
        telemetry=True,
    )
    streams = make_streams(4, height=32, width=32)
    tr = SalientTrainer(streams, str(tmp_path), cfg)
    reports = [tr.run_step(shard_times=[1.0, 1.0]) for _ in range(4)]

    # every step carries a telemetry snapshot with stage timings
    for rep in reports:
        assert rep.telemetry is not None
        assert rep.telemetry["stages"].get("trainer.step", 0) > 0
        assert "archive.seal" in rep.telemetry["stages"]

    led = OBS.ledger
    rep = led.report()
    # the paper ratios recompute from ledger edges alone (within 1%)
    assert rep["entropy_ratio"] == pytest.approx(
        led.bytes(EDGE_ENTROPY_RAW) / led.bytes(EDGE_ENTROPY_COMP), rel=0.01
    )
    assert rep["bytes_moved_ratio"] == pytest.approx(
        led.bytes(EDGE_REPLAY_PLANNED) / led.bytes(EDGE_REPLAY_FULL_BASELINE),
        rel=0.01,
    )
    # ...and agree with the trainer's own per-step accounting (within 1%)
    planned = sum(r.replay_read_bytes for r in reports)
    baseline = sum(r.replay_full_bytes for r in reports)
    assert led.bytes(EDGE_REPLAY_PLANNED) == pytest.approx(planned, rel=0.01)
    assert led.bytes(EDGE_REPLAY_FULL_BASELINE) == pytest.approx(
        baseline, rel=0.01
    )
    moved = led.bytes(EDGE_REPLAY_READ) + led.bytes(EDGE_REPLAY_PARITY)
    assert moved == pytest.approx(planned, rel=0.01)

    # exporters: Perfetto-loadable Chrome trace + journaled JSONL log
    paths = tr.export_telemetry()
    with open(paths["trace"]) as f:
        trace = json.load(f)
    evs = trace["traceEvents"]
    assert any(
        e.get("ph") == "X" and e.get("name") == "trainer.step" for e in evs
    )
    assert any(
        e.get("ph") == "C" and e["name"].endswith(EDGE_DEVICE_TO_JOURNAL)
        for e in evs
    )
    assert all("ts" in e for e in evs if e.get("ph") == "X")
    assert os.path.exists(paths["jsonl"])
    with open(paths["jsonl"]) as f:
        kinds = [json.loads(ln)["kind"] for ln in f if ln.strip()]
    assert "span" in kinds and "metrics" in kinds and "ledger" in kinds


# ------------------------------------------------ the profiler's trace
def _host_events(logdir):
    """Every event on the host planes of the one ``.xplane.pb`` a
    profiler session wrote under ``logdir``."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                        recursive=True)
    pd = ProfileData.from_file(path)
    return [e for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events]


def test_enabled_span_is_a_trace_me_on_the_profiler_clock(tmp_path):
    """An enabled span lands in the profiler's trace under its own name,
    and the tracer's public epoch maps it onto the trace's clock from one
    anchor stamped on ``perf_counter_ns``."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs.enabled():
            t_anchor = time.perf_counter_ns()
            with jax.profiler.TraceAnnotation("test.anchor"):
                pass
            with OBS.span("test.traced", stripes=1):
                time.sleep(0.005)
    finally:
        jax.profiler.stop_trace()
    (ev,) = OBS.tracer.events
    host = _host_events(str(tmp_path))
    (anchor,) = [e for e in host if e.name == "test.anchor"]
    (copy,) = [e for e in host if e.name == "test.traced"]
    offset = int(anchor.start_ns) - t_anchor
    start = OBS.tracer.epoch_ns + ev["ts_ns"] + offset
    assert abs(start - int(copy.start_ns)) < 1_000_000
    assert abs(ev["dur_ns"] - int(copy.duration_ns)) < 1_000_000
    assert ev["dur_ns"] >= 5_000_000


def test_disabled_span_opens_no_trace_me(tmp_path):
    jax.profiler.start_trace(str(tmp_path))
    try:
        with OBS.span("test.untraced", stripes=1):
            time.sleep(0.001)
    finally:
        jax.profiler.stop_trace()
    assert OBS.tracer.events == []
    assert not [e for e in _host_events(str(tmp_path))
                if e.name == "test.untraced"]


# ------------------------------------------- the served write path's spans
# span -> the span it must sit under (None: any place)
WRITE_PATH_SPANS = {
    "ingest.admit": None,
    "ingest.seal": None,
    "ingest.kem": "ingest.seal",
    "archive.seal": "ingest.seal",
    "kernels.stage": "ingest.seal",
    "ingest.commit": None,
    "kernels.fetch": "ingest.commit",
    "kernels.slice": "ingest.commit",
    "ingest.journal": "ingest.commit",
}


@pytest.fixture(scope="module")
def served_backlog(tmp_path_factory):
    """A tiny backlog through ``StreamIngestFrontend`` with a journal,
    telemetry on: (the span events, the OBS counters, the committed
    stripes)."""
    from repro.core.csd.failure import Journal
    from repro.serving.engine import ArchiveIngest, IngestConfig
    from repro.serving.ingest import FrontendConfig, StreamIngestFrontend

    pub, _ = rlwe.keygen(jax.random.PRNGKey(7))
    journal = Journal(str(tmp_path_factory.mktemp("journal")))
    ing = ArchiveIngest(None, pub, IngestConfig(), seed=3, journal=journal)
    fe = StreamIngestFrontend(
        ing, FrontendConfig(max_stream_gops=64, queue_budget_bytes=1 << 30,
                            batch_stripes=2, deadline_us=1e15),
        seed=5, journal=journal)
    rng = np.random.default_rng(11)
    committed = []
    obs.disable()
    obs.reset()
    with obs.enabled():
        for g in range(22):
            n = int(rng.integers(1024, 3072))
            fe.offer(g % 4, rng.integers(-6, 7, n).astype(np.int8),
                     {"spec": [], "n_i8": n}, novelty=float(rng.random()))
            if g % 6 == 5:
                committed += fe.pump()
        committed += fe.pump()
        committed += fe.drain()
        events = list(OBS.tracer.events)
        counters = {k: OBS.metrics.get(k) for k in (
            obs_names.ING_DISPATCHED_GOPS, obs_names.ING_DISPATCH_WAIT_US,
            obs_names.KEM_LAUNCHES, obs_names.KEM_SESSIONS,
            obs_names.KEM_PADDED)}
    obs.reset()
    assert not fe.shed_log
    return events, counters, committed


def test_write_path_spans_nest(served_backlog):
    events, _, _ = served_backlog
    by_id = {e["id"]: e for e in events}

    def ancestors(e):
        while e["parent"]:
            e = by_id[e["parent"]]
            yield e["name"]

    names = {e["name"] for e in events}
    assert set(WRITE_PATH_SPANS) <= names
    for e in events:
        under = WRITE_PATH_SPANS.get(e["name"])
        if under is not None:
            assert under in set(ancestors(e)), e


@pytest.mark.parametrize("name", sorted(WRITE_PATH_SPANS))
def test_write_path_span_stripes_sum_to_committed(served_backlog, name):
    events, _, committed = served_backlog
    assert len(committed) >= 4
    spans = [e for e in events if e["name"] == name]
    assert sum(e["attrs"]["stripes"] for e in spans) == len(committed)


def test_dispatched_gops_count_the_gops_sealed(served_backlog):
    _, counters, committed = served_backlog
    sealed = sum(len(st.blocks) for st in committed)
    assert sealed == 22
    assert counters[obs_names.ING_DISPATCHED_GOPS] == sealed
    assert counters[obs_names.ING_DISPATCH_WAIT_US] > 0


def test_kem_counters_count_real_and_padded_rows(served_backlog):
    """One KEM program a dispatch (two stripes of up to four shards fit in
    one): its real rows are the GOPs sealed, the rest of its fixed rows
    padding."""
    from repro.core.archival.pipeline import KEM_ROWS

    events, counters, committed = served_backlog
    sealed = sum(len(st.blocks) for st in committed)
    launches = counters[obs_names.KEM_LAUNCHES]
    assert launches == len([e for e in events if e["name"] == "ingest.kem"])
    assert counters[obs_names.KEM_SESSIONS] == sealed == 22
    assert counters[obs_names.KEM_PADDED] == KEM_ROWS * launches - sealed
