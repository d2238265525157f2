"""The reader of ``kem_sessions_per_launch.backlog`` against hand counts:
real RLWE sessions over launches of the seal dispatch's KEM program, and
nothing where a run has no device operations or the program keeps no
``kem.*`` counters."""

from pathlib import Path
from types import SimpleNamespace

import pytest

import harness
from trace_reduce import Op, reduce_events

BENCH = Path(__file__).resolve().parents[1]
NAME = "kem_sessions_per_launch.backlog"


def _read(run):
    return harness.load_module(BENCH / "metrics" / f"{NAME}.py").read(run)


class _Counters(dict):
    def get(self, name, default=0):
        return dict.get(self, name, default)


def _run(device, counters):
    """What the reader sees of a traced run: chip 0's operations in a
    100 ns window, and the program's counters."""
    summary = reduce_events(device, [Op("window", 0, 100)])
    tracer = SimpleNamespace(events=[], epoch_ns=0)
    return SimpleNamespace(
        trace_summary=summary, stamps={"window_ns": (0, 100)},
        telemetry=SimpleNamespace(tracer=tracer, metrics=_Counters(counters)))


def test_hand_count():
    counters = {"kem.launches": 3, "kem.sessions": 41, "kem.padded": 7}
    assert _read(_run([[Op("a", 10, 5)]], counters)) == pytest.approx(41 / 3)


@pytest.mark.parametrize("device, counters", [
    ([[Op("a", 10, 5)]], {}),
    ([[]], {"kem.launches": 1, "kem.sessions": 14, "kem.padded": 2}),
], ids=["program_without_counters", "no_device_operations"])
def test_finds_nothing(device, counters):
    """The parent of the batched KEM keeps no ``kem.*`` counters, and a CPU
    run has no device operations: either way the metric is left out."""
    assert _read(_run(device, counters)) is None
