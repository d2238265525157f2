"""The program's spans on the trace's clock (``program_spans.py``) and the
readers built on them, against hand counts and against a trace recorded
on a TPU v5e: one second of ``city720.backlog`` with its drain
(``data/backlog_spans_v5e.*``: the ``.xplane.pb``, the program's
telemetry as ``repro.obs.export.write_jsonl`` wrote it, and the run's
``window_ns`` stamp with the tracer's ``epoch_ns``)."""

import gzip
import json
import os
import shutil
from pathlib import Path
from types import SimpleNamespace

import pytest

import harness
import program_spans
from trace_reduce import Op, read_xplane, reduce_events

BENCH = Path(__file__).resolve().parents[1]
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
PREFIX = os.path.join(DATA, "backlog_spans_v5e")
SPAN_READERS = {
    "kem_host_ms.backlog": "ingest.kem",
    "stage_host_ms.backlog": "kernels.stage",
    "fetch_wait_ms.backlog": "kernels.fetch",
    "journal_commit_ms.backlog": "ingest.journal",
}
NEW_READERS = sorted(SPAN_READERS) + ["gop_wait_ms.backlog",
                                      "idle_in_seal_host_pct.backlog"]


def reader(name):
    return harness.load_module(BENCH / "metrics" / f"{name}.py")


class _Counters(dict):
    def get(self, name, default=0):
        return dict.get(self, name, default)


def _run(summary, events, counters, window_ns, epoch_ns):
    """What the readers see of a traced run."""
    tracer = SimpleNamespace(events=events, epoch_ns=epoch_ns)
    return SimpleNamespace(
        trace_summary=summary, stamps={"window_ns": window_ns},
        telemetry=SimpleNamespace(tracer=tracer, metrics=_Counters(counters)))


# ------------------------------------------------------------ hand counts
def _ev(name, ts, dur, parent=0, **attrs):
    return {"id": 0, "parent": parent, "name": name, "ts_ns": ts,
            "dur_ns": dur, "attrs": attrs}


def test_idle_by_innermost_span_hand_count():
    # window 1000..1100 on the trace; chip 0 busy 1010..1030 and 1060..1070
    device = [[Op("a", 1010, 20), Op("b", 1060, 10), Op("late", 1200, 5)]]
    summary = reduce_events(device, [Op("window", 1000, 100)])
    # program clock: window stamped at 500, epoch 400 -> offset 500
    events = [_ev("ingest.seal", 100, 60),          # 1000..1060
              _ev("ingest.kem", 100, 20),           # 1000..1020
              _ev("kernels.stage", 140, 15),        # 1040..1055
              _ev("ingest.commit", 175, 20),        # 1075..1095
              _ev("kernels.fetch", 175, 5)]         # 1075..1080
    run = _run(summary, events, {}, (500, 600), 400)
    spans = program_spans.events(run)
    assert [(s.start_ns, s.end_ns) for s in spans][:3] == [
        (1000, 1060), (1000, 1020), (1040, 1055)]
    # gaps: 1000..1010, 1030..1060, 1070..1100
    assert program_spans.idle_gaps(summary) == [
        (1000, 1010), (1030, 1060), (1070, 1100)]
    assert program_spans.idle_by_span(summary, spans) == {
        "ingest.kem": 10,            # 1000..1010
        "ingest.seal": 10 + 5,       # 1030..1040, 1055..1060
        "kernels.stage": 15,         # 1040..1055
        "kernels.fetch": 5,          # 1075..1080
        "ingest.commit": 15,         # 1080..1095
        "host:other": 5 + 5,         # 1070..1075, 1095..1100
    }
    # idle while the host is in either seal-host span: 10 + 15 of 100 ns
    assert reader("idle_in_seal_host_pct.backlog").read(run) == 25.0


def test_span_readers_hand_count():
    summary = reduce_events([[Op("a", 10, 5)]], [Op("window", 0, 100)])
    events = [_ev("ingest.kem", 0, 4_000_000, stripes=4),
              _ev("ingest.kem", 0, 2_000_000, stripes=2),
              _ev("ingest.journal", 0, 3_000_000, stripes=3)]
    counters = {"ingest.dispatch_wait_us": 9000.0,
                "ingest.dispatched_gops": 3}
    run = _run(summary, events, counters, (0, 100), 0)
    assert reader("kem_host_ms.backlog").read(run) == pytest.approx(1.0)
    assert reader("journal_commit_ms.backlog").read(run) == pytest.approx(1.0)
    assert reader("stage_host_ms.backlog").read(run) is None
    assert reader("gop_wait_ms.backlog").read(run) == pytest.approx(3.0)


@pytest.mark.parametrize("name", NEW_READERS)
def test_readers_find_nothing_without_device_operations(name):
    """A CPU run's trace holds no device operation: every new reader
    leaves its metric out, whatever the program recorded."""
    summary = reduce_events([[]], [Op("window", 0, 100)])
    events = [_ev(s, 0, 50, stripes=1) for s in SPAN_READERS.values()]
    counters = {"ingest.dispatch_wait_us": 1.0, "ingest.dispatched_gops": 1}
    run = _run(summary, events, counters, (0, 100), 0)
    assert reader(name).read(run) is None


def test_tracer_without_public_epoch_maps_nothing():
    """A program whose tracer keeps its epoch private (before the spans
    went onto the profiler's clock) gives the idle metric nothing."""
    summary = reduce_events([[Op("a", 10, 5)]], [Op("window", 0, 100)])
    run = _run(summary, [_ev("ingest.kem", 0, 50, stripes=1)], {}, (0, 100), 0)
    del run.telemetry.tracer.epoch_ns
    assert program_spans.events(run) == []
    assert reader("idle_in_seal_host_pct.backlog").read(run) is None


# ------------------------------------------------------- the recorded run
@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """(the run as the readers see it, the trace's host events)."""
    tmp = tmp_path_factory.mktemp("recorded")
    path = tmp / "spans.xplane.pb"
    with gzip.open(PREFIX + ".xplane.pb.gz", "rb") as src, \
            open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    with gzip.open(PREFIX + ".stamps.json.gz", "rt") as f:
        stamps = json.load(f)
    events, counters = [], {}
    with gzip.open(PREFIX + ".telemetry.jsonl.gz", "rt") as f:
        for line in f:
            rec = json.loads(line)
            kind = rec.pop("kind")
            if kind == "span":
                events.append(rec)
            elif kind == "metrics":
                counters = rec["snapshot"]
    device, host, modules = read_xplane(str(path), chips=1)
    summary = reduce_events(device, host, modules)
    run = _run(summary, events, counters, tuple(stamps["window_ns"]),
               stamps["epoch_ns"])
    run.stamps.update(stamps)

    from jax.profiler import ProfileData

    names = {e["name"] for e in events}
    copies = [Op(e.name, int(e.start_ns), int(e.duration_ns))
              for plane in ProfileData.from_file(str(path)).planes
              if plane.name.startswith("/host:")
              for line in plane.lines for e in line.events
              if e.name in names]
    return run, copies


def test_mapped_spans_match_their_trace_me_copies(recorded):
    run, copies = recorded
    spans = program_spans.events(run)
    assert len(spans) == len(run.telemetry.tracer.events) > 0
    for name in {s.name for s in spans}:
        mine = sorted((s for s in spans if s.name == name),
                      key=lambda s: s.start_ns)
        theirs = sorted((c for c in copies if c.name == name),
                        key=lambda c: c.start_ns)
        assert len(mine) == len(theirs), name
        for m, c in zip(mine, theirs):
            assert abs(m.start_ns - c.start_ns) < 100_000, (name, m, c)
            assert abs(m.end_ns - c.end_ns) < 100_000, (name, m, c)


def _innermost_by_hand(spans, t):
    """The span opened last among those open at ``t`` (of two opened at
    once, the shorter)."""
    open_ = [s for s in spans if s.start_ns <= t < s.end_ns]
    return max(open_, key=lambda s: (s.start_ns, -s.end_ns)).name \
        if open_ else "host:other"


def test_idle_attribution_on_the_recording(recorded):
    run, _ = recorded
    summary = run.trace_summary
    spans = program_spans.events(run)
    lo, hi = summary.window
    busy = summary.device_ops[0]
    cuts = sorted({lo, hi} | {min(hi, max(lo, x)) for o in busy + spans
                              for x in (o.start_ns, o.end_ns)})
    want = {}
    for a, b in zip(cuts, cuts[1:]):
        mid = (a + b) / 2
        if any(o.start_ns <= mid < o.end_ns for o in busy):
            continue
        name = _innermost_by_hand(spans, mid)
        want[name] = want.get(name, 0) + b - a
    got = program_spans.idle_by_span(summary, spans)
    assert got == want
    idle = summary.window_s - summary.busy_s
    assert sum(got.values()) / 1e9 == pytest.approx(idle, abs=1e-9)
    # the seal dispatch's host work and the wait for the chip are seen
    assert got["ingest.kem"] > 0 and got["kernels.stage"] > 0


def test_new_readers_on_the_recording(recorded):
    run, _ = recorded
    events = run.telemetry.tracer.events
    stripes = run.stamps["committed"] + run.stamps["drained"]
    for name, span in SPAN_READERS.items():
        mine = [e for e in events if e["name"] == span]
        assert sum(e["attrs"]["stripes"] for e in mine) == stripes, span
        want = sum(e["dur_ns"] for e in mine) / stripes / 1e6
        assert reader(name).read(run) == pytest.approx(want), name
    assert reader("kem_host_ms.backlog").read(run) > 0
    gops = run.telemetry.metrics.get("ingest.dispatched_gops")
    assert gops >= stripes
    assert reader("gop_wait_ms.backlog").read(run) == pytest.approx(
        run.telemetry.metrics.get("ingest.dispatch_wait_us") / gops / 1e3)
    # neither span holds another program span, so the innermost split of
    # all of them gives the same idle time under the two
    by_span = program_spans.idle_by_span(run.trace_summary,
                                         program_spans.events(run))
    lo, hi = run.trace_summary.window
    pct = reader("idle_in_seal_host_pct.backlog").read(run)
    assert pct == pytest.approx(100.0 * (
        by_span["ingest.kem"] + by_span["kernels.stage"]) / (hi - lo))
    assert 0 < pct < 100 * (1 - run.trace_summary.busy_s
                            / run.trace_summary.window_s)
