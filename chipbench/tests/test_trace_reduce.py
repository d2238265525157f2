"""The trace reduction against hand counts, and against a small trace
recorded on a TPU v5e (``data/backlog_v5e.xplane.pb.gz``: one second of
the backlog loop at 720p, with its drain)."""

import gzip
import json
import os
import shutil

import pytest

from trace_reduce import (HOST_SPANS, Op, read_xplane, reduce_events,
                          union_length)

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
RECORDED = os.path.join(DATA, "backlog_v5e.xplane.pb.gz")
KNOWN = os.path.join(DATA, "backlog_v5e.known.json")


def test_union_length_hand_count():
    iv = [(0, 10), (5, 15), (20, 30), (25, 26), (40, 50)]
    assert union_length(iv, 0, 100) == 15 + 10 + 10
    assert union_length(iv, 8, 45) == 7 + 10 + 5


def test_busy_idle_kernels_and_gaps_hand_count():
    # window 0..100 ns; ops cover 10..30 and 50..60 (overlapping pair)
    ops = [Op("%fusion.1 = u32[8] fusion()", 10, 15),
           Op("%rans_encode.2 = s32[8] custom-call()", 20, 10),
           Op("%seal_stripes.3 = u32[8] custom-call()", 50, 10),
           Op("%late = f32[] add()", 150, 10)]
    mods = [Op("jit__fused_core(1)", 10, 21), Op("jit__seal_core(2)", 50, 10),
            Op("jit_add(3)", 150, 10)]
    host = [Op("window", 0, 100), Op("pump", 0, 12), Op("offer", 30, 20),
            Op("drain", 60, 40), Op("unrelated", 0, 100)]
    t = reduce_events([ops], host, [mods])
    assert t.window == (0, 100)
    assert t.busy_s == pytest.approx(30e-9)
    assert t.window_s == pytest.approx(100e-9)
    assert t.idle_share() == pytest.approx(0.7)
    assert t.kernel_s(["jit__fused_core", "jit__seal_core"]) == pytest.approx(31e-9)
    # gaps: 0..10 (pump), 30..50 (offer), 60..100 (drain)
    assert t.idle_by_host() == {"pump": 10, "offer": 20, "drain": 40}
    b = t.breakdown()
    assert b["device_ops"][0] == ["jit__fused_core:fusion.1", 15e-9]
    assert ["jit__seal_core:seal_stripes.3", 10e-9] in b["device_ops"]
    assert [g[0] for g in b["idle_gaps"]] == ["drain", "offer", "pump"]


def test_idle_time_under_no_host_span_is_other():
    t = reduce_events([[Op("k", 40, 20)]],
                      [Op("window", 0, 100), Op("pump", 30, 20)])
    assert t.idle_by_host() == {"pump": 10, "host:other": 70}


def test_no_window_span_is_an_error():
    with pytest.raises(ValueError):
        reduce_events([[]], [Op("pump", 0, 5)])


def _brute_force(device, host):
    """Busy time by walking every boundary: the plain way."""
    w = max((s for s in host if s.name == "window"), key=lambda s: s.dur_ns)
    lo, hi = w.start_ns, w.end_ns
    cuts = sorted({lo, hi} | {max(lo, min(hi, x)) for o in device
                              for x in (o.start_ns, o.end_ns)})
    busy = 0
    for a, b in zip(cuts, cuts[1:]):
        mid = (a + b) / 2
        if any(o.start_ns <= mid < o.end_ns for o in device):
            busy += b - a
    return busy, hi - lo


def test_recorded_trace_reproduces_known_times(tmp_path):
    path = tmp_path / "backlog_v5e.xplane.pb"
    with gzip.open(RECORDED, "rb") as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    device, host, modules = read_xplane(str(path), chips=1)
    with open(KNOWN) as f:
        known = json.load(f)
    t = reduce_events(device, host, modules)
    busy, window = _brute_force(device[0], host)
    assert t.busy_s == pytest.approx(busy / 1e9, abs=1e-9)
    assert t.window_s == pytest.approx(window / 1e9, abs=1e-9)
    # the numbers read off this trace when it was recorded
    assert len(device[0]) == known["device_ops"]
    assert len(modules[0]) == known["program_executions"]
    assert t.busy_s == pytest.approx(known["busy_s"], rel=1e-9)
    assert t.window_s == pytest.approx(known["window_s"], rel=1e-9)
    for group, s in known["kernel_s"].items():
        names = known["kernels"][group]
        assert t.kernel_s(names) == pytest.approx(s, rel=1e-9)
        # the write program's executions, counted and summed by hand
        runs = [m for m in modules[0] if any(n in m.name for n in names)]
        assert len(runs) == known["kernel_count"][group]
        assert s == pytest.approx(sum(m.dur_ns for m in runs) / 1e9)
    spans = {n: sum(1 for s in host if s.name == n) for n in known["host_spans"]}
    assert spans == known["host_spans"]
    assert {s.name for s in host} <= set(HOST_SPANS) | {"window"}
    gaps = sum(t.idle_by_host().values()) / 1e9
    assert gaps == pytest.approx(t.window_s - t.busy_s, abs=1e-9)
