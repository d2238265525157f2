"""The readers of ``slice_host_ms.backlog`` and
``rows_to_host_mb_per_stripe.backlog`` against hand counts: the host's
``kernels.slice`` time over the stripes the spans cut, and the bytes of
sealed rows and parity sent to the host over the stripes sealed; nothing
where a run has no device operations or the program has neither the span
nor the counter."""

from pathlib import Path
from types import SimpleNamespace

import pytest

import harness
from trace_reduce import Op, reduce_events

BENCH = Path(__file__).resolve().parents[1]


def _read(name, run):
    return harness.load_module(BENCH / "metrics" / f"{name}.py").read(run)


class _Counters(dict):
    def get(self, name, default=0):
        return dict.get(self, name, default)


def _span(name, dur_ns, **attrs):
    return {"name": name, "ts_ns": 0, "dur_ns": dur_ns, "attrs": attrs}


def _run(device, spans=(), counters=None):
    """What a reader sees of a traced run: chip 0's operations in a 100 ns
    window, the program's spans and its counters."""
    summary = reduce_events(device, [Op("window", 0, 100)])
    tracer = SimpleNamespace(events=list(spans), epoch_ns=0)
    return SimpleNamespace(
        trace_summary=summary, stamps={"window_ns": (0, 100)},
        telemetry=SimpleNamespace(tracer=tracer,
                                  metrics=_Counters(counters or {})))


ON_CHIP = [[Op("a", 10, 5)]]


def test_slice_host_ms_hand_count():
    spans = [_span("kernels.slice", 6_000_000, stripes=4),
             _span("kernels.slice", 3_000_000, stripes=2),
             _span("kernels.fetch", 50_000_000, stripes=6)]
    assert _read("slice_host_ms.backlog", _run(ON_CHIP, spans)) == \
        pytest.approx(9.0 / 6)


def test_rows_to_host_hand_count():
    """Two launches of the 32,768-row bucket, 4 and 3 stripes of 4 shards
    and P and Q at the coder's capacity of 8,192 rows: 25.17 MB a
    stripe."""
    per_stripe = (4 + 2) * 8192 * 128 * 4
    counters = {"kernels.rows_to_host_bytes": 7 * per_stripe,
                "kernels.fused_stripes": 7, "kernels.fused_launches": 2}
    got = _read("rows_to_host_mb_per_stripe.backlog",
                _run(ON_CHIP, (), counters))
    assert got == pytest.approx(25.165824)


@pytest.mark.parametrize("name", ["slice_host_ms.backlog",
                                  "rows_to_host_mb_per_stripe.backlog"])
@pytest.mark.parametrize("device, spans, counters", [
    (ON_CHIP, [_span("kernels.fetch", 10, stripes=1)],
     {"kernels.fused_stripes": 4}),
    ([[]], [_span("kernels.slice", 10, stripes=1)],
     {"kernels.rows_to_host_bytes": 100, "kernels.fused_stripes": 4}),
], ids=["program_without_span_or_counter", "no_device_operations"])
def test_finds_nothing(name, device, spans, counters):
    """The parent of the early host copy has neither the span nor the
    counter, and a CPU run has no device operations: either way the metric
    is left out."""
    assert _read(name, _run(device, spans, counters)) is None
