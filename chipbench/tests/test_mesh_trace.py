"""The readers of ``city720.sharded4`` (``mesh_trace.py``, the
``*.backlog4`` metrics, and the ``*.backlog`` metrics the cell shares with
``city720.backlog``) against hand counts, and against a trace recorded on
a 2x2 TPU v5e host: one second of ``city720.sharded4`` with its drain
(``data/sharded4_v5e.*``: the ``.xplane.pb``, the program's spans, and the
run's stamps with the counters it kept and the shard sizes of the stripes
it sealed; the journal's fsyncs and the dispatch-wait counters were not
kept, so ``journal_sync_ms`` and ``gop_wait_ms`` are not read here)."""

import gzip
import json
import os
import shutil
from pathlib import Path
from types import SimpleNamespace

import pytest

import harness
import kernels
import mesh_trace
from trace_reduce import Op, read_xplane, reduce_events

BENCH = Path(__file__).resolve().parents[1]
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
PREFIX = os.path.join(DATA, "sharded4_v5e")
CHIPS = 4
MESH_READERS = ["parity_reduce_ms.backlog4",
                "parity_reduce_roofline.backlog4"]
DEVICE_READERS = MESH_READERS + ["seal_device_ms.backlog",
                                 "seal_roofline.backlog",
                                 "device_idle_pct.backlog",
                                 "idle_in_seal_host_pct.backlog"]


def reader(name):
    return harness.load_module(BENCH / "metrics" / f"{name}.py")


class _Counters(dict):
    def get(self, name, default=0):
        return dict.get(self, name, default)


def _stripe(shards):
    """A stripe as the readers see it, from (n_raw, n_comp, rows, n_words)
    per shard."""
    return SimpleNamespace(blocks=[SimpleNamespace(
        manifest={"entropy": {"n_raw": s["n_raw"], "n_comp": s["n_comp"],
                              "rows": s["rows"]}},
        sealed=SimpleNamespace(n_valid_u32=s["n_words"])) for s in shards])


def _run(summary, stamps, events=(), counters=None):
    tracer = SimpleNamespace(events=list(events),
                             epoch_ns=stamps.get("epoch_ns"))
    return SimpleNamespace(
        trace_summary=summary, parity=stamps["parity"],
        device_kind=stamps["device_kind"],
        cell=SimpleNamespace(chips=stamps["chips"]),
        stamps={"window_ns": tuple(stamps["window_ns"]),
                "committed": [_stripe(s) for s in stamps["committed"]],
                "drained": [_stripe(s) for s in stamps["drained"]]},
        telemetry=SimpleNamespace(tracer=tracer,
                                  metrics=_Counters(counters or {})))


# ------------------------------------------------------------ hand counts
def test_program_and_collective_time_hand_count():
    # two chips; the mesh program runs 0..100 and 200..260 on each; an
    # all-gather inside it on both, one outside any execution of it.  The
    # write program's name in kernels.json finds the mesh program
    mods = [[Op("jit__fused_core_mesh(1)", 0, 100),
             Op("jit__fused_core_mesh(1)", 200, 60),
             Op("jit_other(2)", 300, 50)],
            [Op("jit__fused_core_mesh(1)", 5, 100),
             Op("jit__fused_core_mesh(1)", 205, 60)]]
    ops = [[Op("%all-gather.10 = u32[16,8,128] all-gather(%x)", 40, 10),
            Op("%all-gather.11 = u32[16,8,128] all-gather(%y)", 45, 10),
            Op("%fusion.9 = u32[4,8,128] fusion(%a)", 55, 20),
            Op("%all-gather-start.2 = (u32[8]) all-gather-start(%z)", 220, 5),
            Op("%all-gather.1 = u32[8] all-gather(%w)", 310, 30)],
           [Op("%all-gather.10 = u32[16,8,128] all-gather(%x)", 50, 30)]]
    tr = reduce_events(ops, [Op("window", 0, 400)], mods)
    assert tr.kernel_s(kernels.names("write")) == pytest.approx(320e-9)
    # chip 0: 40..55 and 220..225 (the one at 310 is another program's)
    assert mesh_trace.collective_s(tr) == pytest.approx([20e-9, 30e-9])


def test_parity_bytes_per_chip_hand_count():
    rows = lambda r: _stripe([{"n_raw": 1, "n_comp": 1, "rows": r,
                               "n_words": 1}] * 4)
    # raid6: 2 strips x 3 other chips x T x 128 bytes, per stripe
    assert mesh_trace.parity_bytes_per_chip(
        [rows(32768), rows(16384)], 4, "raid6") == 2 * 3 * 128 * (
            32768 + 16384)
    assert mesh_trace.parity_bytes_per_chip([rows(8)], 4, "raid5") == 3 * 1024
    assert mesh_trace.parity_bytes_per_chip([rows(8)], 1, "raid6") == 0


def test_readers_find_nothing_without_the_mesh_program():
    ops = [[Op("%rans_encode.1 = s32[8] custom-call()", 10, 20)]]
    mods = [[Op("jit__fused_core(1)", 10, 20)]]
    tr = reduce_events(ops, [Op("window", 0, 100)], mods)
    stamps = {"parity": "raid6", "device_kind": "TPU v5 lite", "chips": 1,
              "window_ns": [0, 100], "epoch_ns": 0,
              "committed": [[{"n_raw": 8, "n_comp": 8, "rows": 8,
                              "n_words": 2}]], "drained": []}
    run = _run(tr, stamps)
    for name in MESH_READERS:
        assert reader(name).read(run) is None, name


# ------------------------------------------------------- the recorded run
@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """The recorded run as the readers see it."""
    path = tmp_path_factory.mktemp("recorded") / "sharded4.xplane.pb"
    with gzip.open(PREFIX + ".xplane.pb.gz", "rb") as src, \
            open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    with gzip.open(PREFIX + ".stamps.json.gz", "rt") as f:
        stamps = json.load(f)
    with gzip.open(PREFIX + ".telemetry.jsonl.gz", "rt") as f:
        events = [json.loads(line) for line in f]
    summary = reduce_events(*read_xplane(str(path), chips=CHIPS))
    return _run(summary, stamps, events, stamps["counters"])


def test_every_chip_runs_the_program_once_a_launch(recorded):
    """Each launch the program counted is one execution of the mesh
    program on each of the four chips, with its collectives inside."""
    tr = recorded.trace_summary
    launches = recorded.telemetry.metrics.get("kernels.fused_launches")
    assert launches > 0 and len(tr.modules) == CHIPS
    for mods in tr.modules:
        mine = [m for m in mods if "jit__fused_core_mesh" in m.name]
        assert len(mine) == launches
        assert [m for m in mods
                if any(n in m.name for n in kernels.names("write"))] == mine
    assert all(t > 0 for t in mesh_trace.collective_s(tr))


def test_device_readers_on_the_recording(recorded):
    tr = recorded.trace_summary
    stripes = mesh_trace.stripes(recorded)
    got = {name: reader(name).read(recorded) for name in DEVICE_READERS}
    assert all(v is not None for v in got.values()), got
    # the mesh program's executions, summed over the four chips
    mesh_s = sum(m.dur_ns for mods in tr.modules for m in mods
                 if "jit__fused_core_mesh" in m.name) / 1e9
    assert got["seal_device_ms.backlog"] == pytest.approx(
        mesh_s / len(stripes) * 1e3)
    assert got["parity_reduce_ms.backlog4"] == pytest.approx(
        max(mesh_trace.collective_s(tr)) / len(stripes) * 1e3)
    # the reduce's time is part of the program's, on the busiest chip
    assert got["parity_reduce_ms.backlog4"] < got["seal_device_ms.backlog"]
    for name in ("seal_roofline.backlog", "parity_reduce_roofline.backlog4",
                 "idle_in_seal_host_pct.backlog"):
        assert 0 < got[name] <= 100, (name, got[name])
    idle = got["device_idle_pct.backlog"]
    lo, hi = tr.window
    busy = [sum(b - a for a, b in _union(ops, lo, hi)) for ops in tr.device_ops]
    assert idle == pytest.approx(100 * (1 - sum(busy) / CHIPS / (hi - lo)))


def _union(ops, lo, hi):
    """The busy intervals of one chip inside [lo, hi), merged by hand."""
    out = []
    for a, b in sorted((max(o.start_ns, lo), min(o.end_ns, hi)) for o in ops):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def test_counter_and_span_readers_on_the_recording(recorded):
    counters = recorded.telemetry.metrics
    stripes = counters.get("kernels.fused_stripes")
    assert stripes == len(mesh_trace.stripes(recorded))
    assert reader("cross_chip_mb_per_stripe.backlog4").read(recorded) == \
        pytest.approx(counters.get("mesh.cross_chip_bytes") / stripes / 1e6)
    for name, span in (("seal_host_ms.backlog", "ingest.seal"),
                       ("stage_host_ms.backlog", "kernels.stage"),
                       ("kem_host_ms.backlog", "ingest.kem"),
                       ("fetch_wait_ms.backlog", "kernels.fetch"),
                       ("journal_commit_ms.backlog", "ingest.journal")):
        mine = [e for e in recorded.telemetry.tracer.events
                if e["name"] == span]
        assert sum(e["attrs"]["stripes"] for e in mine) == stripes
        assert reader(name).read(recorded) == pytest.approx(
            sum(e["dur_ns"] for e in mine) / stripes / 1e6)
    launches = counters.get("kernels.fused_launches")
    assert reader("stripes_per_launch.backlog").read(recorded) == \
        pytest.approx(stripes / launches)
    assert reader("kem_sessions_per_launch.backlog").read(recorded) == \
        pytest.approx(counters.get("kem.sessions") / launches)
    committed = recorded.stamps["committed"]
    assert reader("gops_per_stripe.backlog").read(recorded) == \
        pytest.approx(sum(len(st.blocks) for st in committed) / len(committed))
    # every launch was staged to the four chips
    assert {e["attrs"]["chips"] for e in recorded.telemetry.tracer.events
            if e["name"] == "kernels.stage"} == {CHIPS}


def test_cross_chip_bytes_on_the_recording_are_the_parity_partials(recorded):
    """Per stripe, each chip received the other three chips' P and Q
    partials; the only other bytes are 52 a shard of small arrays."""
    sealed = mesh_trace.stripes(recorded)
    parity = CHIPS * mesh_trace.parity_bytes_per_chip(sealed, CHIPS, "raid6")
    shards = sum(len(st.blocks) for st in sealed)
    assert recorded.telemetry.metrics.get("mesh.cross_chip_bytes") == \
        parity + (CHIPS - 1) * 52 * shards
