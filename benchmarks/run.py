"""Benchmark harness: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV and records per-bench metrics
(GB/s, launch counts, device count) so the kernel perf trajectory is
machine-readable across PRs.  Fresh metrics are always written to a temp
file under the system tempdir; the committed ``BENCH_kernels.json`` at the
repo root is only replaced — atomically, via ``os.replace`` — on an
explicit ``--update`` run whose gates all pass.  Nothing is ever left
at the repo root otherwise (earlier revisions parked a stray
``BENCH_kernels.json.fresh`` there on gate failure).  Set BENCH_FULL=1
for the longer codec-training variant of the Fig. 8/9 rate-distortion
sweep.

``--check`` turns the committed BENCH_kernels.json into a regression gate:
the fresh run is diffed against it per bench and the process exits nonzero
if any ``us_per_call`` or ``us_decode`` regressed by more than
CHECK_THRESHOLD (2x — the timings are interpret-mode wall clock, so the
gate is deliberately coarse), or any ``gbps`` / ``gbps_decode`` fell below
1/CHECK_THRESHOLD of the committed value.  With today's fixed per-bench
byte counts the throughput floor mirrors the latency ceiling; it exists
so throughput stays gated if a future edit changes how many bytes a
bench pushes per call.
Benches that report ``bytes_moved_ratio`` (the retrieval bench's planned-
bytes / full-restore fraction) are additionally gated on it with the tight
BYTES_THRESHOLD: byte accounting is deterministic, so a retrieval plan that
starts moving more data than the committed baseline fails even when wall
clock looks fine.  ``ABS_GATES`` adds fixed (baseline-free) bounds on the
one-launch archival bench: a launch-count ceiling for its structural claim
and a ``vs_host_speed`` floor.  Gate rows carrying an ``"optional"`` flag
(the BENCH_FULL-only 1024-stream ingest point) gate when the metric is
present and skip — instead of failing — when the quick run did not
produce it.  When any gate fails, a consolidated
full-gate-state table (measured vs effective bound with signed margin,
passing rows included) is printed so the CI log alone answers "how close
was everything else".
"""

from __future__ import annotations

import json
import os
import sys

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_JSON_PATH = os.path.join(_REPO_ROOT, "BENCH_kernels.json")
CHECK_THRESHOLD = 2.0  # >2x slower us_per_call fails --check
BYTES_THRESHOLD = 1.1  # >10% more bytes_moved_ratio fails --check (exact metric)

# Absolute gates (fresh run vs a fixed bound, no committed baseline
# needed): the batched archival bench must KEEP its structural claim —
# three kernels (histogram, coder, seal) per K-stripe batch, whatever K
# is — and both entropy benches
# must hold the two-phase-encode win (PR 9) from both sides: wall-clock
# ceilings and exactness, plus vs-host floors set from measured
# CPU-interpret runs (entropy ~0.53-0.60, fused ~0.45-0.55, with +-15%
# machine noise), NOT at the >=1.0 TPU target: on a single-core interpret
# runner the bench is compute-bound on the shared rANS loop, so the
# dispatch/HBM savings the fusion buys cannot fully show up in wall clock
# (see the fused row's gap_note).
ABS_GATES = {
    # the standalone coder: >=1.5x over the pre-PR-9 24.7ms committed
    # baseline, holding >=0.5x of host zlib with bit-exact streams
    "entropy_fused": (
        ("us_per_call", "ceiling", 16500.0),
        ("gbps", "floor", 0.0158),
        ("vs_host_speed", "floor", 0.5),
        ("exact", "floor", 1.0),
        ("exact_recip", "floor", 1.0),
    ),
    "entropy_seal_fused": (
        ("launches", "ceiling", 3.0),
        ("launches_per_stripe", "ceiling", 1.0),
        ("us_per_stripe", "ceiling", 22000.0),
        ("vs_host_speed", "floor", 0.3),
    ),
    # Durability tier (scrub + rebuild under chaos): every injected
    # corruption must be detected (the crc/syndrome layers are exact, so
    # the floor is 1.0, not a tolerance), rebuild rounds must never
    # exceed their byte budget, and replay must keep progressing through
    # the chaos rounds.
    "scrub_rebuild": (
        ("detection_rate", "floor", 1.0),
        ("rebuild_budget_frac", "ceiling", 1.0),
        ("replay_progress_ratio", "floor", 0.5),
    ),
    # Telemetry tier: every hot-path obs call site is a single branch when
    # disabled, so enabling spans+ledger+histograms on the seal path may
    # cost at most 3% wall clock (interleaved A/B measurement).
    "obs_overhead": (
        ("overhead_frac", "ceiling", 0.03),
    ),
    # Streaming ingest tier: per stream-count point — a throughput floor,
    # GOP-to-commit latency ceilings (wall-clock on interpret-mode CPU,
    # so both carry 4-5x headroom over measured), an admission-shed
    # ceiling (the shed count is seed-deterministic: schedule and pump
    # cadence are fixed, so the bound is tight), and the structural
    # launches-per-stripe ceiling (<1: same-bucket stripes share a fused
    # launch).  The submit ring must hide at least half the fetch stall
    # (measured: >99% hidden).  The 1024-stream rows are BENCH_FULL-only
    # and marked "optional": absent metrics skip instead of fail.
    "ingest_scale": (
        ("stall_hidden_frac", "floor", 0.5),
        ("stripes_per_s_16", "floor", 0.6),
        ("p50_us_16", "ceiling", 6.0e6),
        ("p99_us_16", "ceiling", 36.0e6),
        ("shed_frac_16", "ceiling", 0.25),
        ("launches_per_stripe_16", "ceiling", 0.9),
        ("stripes_per_s_256", "floor", 1.2),
        ("p50_us_256", "ceiling", 6.0e6),
        ("p99_us_256", "ceiling", 36.0e6),
        ("shed_frac_256", "ceiling", 0.25),
        ("launches_per_stripe_256", "ceiling", 0.9),
        ("stripes_per_s_1024", "floor", 1.2, "optional"),
        ("p50_us_1024", "ceiling", 8.0e6, "optional"),
        ("p99_us_1024", "ceiling", 45.0e6, "optional"),
        ("shed_frac_1024", "ceiling", 0.25, "optional"),
        ("launches_per_stripe_1024", "ceiling", 0.9, "optional"),
    ),
}


def _force_multidevice_host() -> None:
    """Give the bench process an 8-device host platform (before jax init)
    so the sharded_seal bench can build 1/2/8-device storage meshes.

    (The legacy CPU runtime — ``--xla_cpu_use_thunk_runtime=false`` — was
    evaluated for the tiny-op-dominated coding loops and rejected: it
    miscompiles batched ``dot_general`` on forced multi-device hosts,
    returning garbage histogram sums.  Do not re-add it.)"""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()


def _dump_fresh(metrics: dict) -> str:
    """Write the fresh metrics to a temp file under the SYSTEM tempdir
    (never the repo root) and return its path.  This is the only copy a
    non-``--update`` run produces, so an aborted or gate-failed run cannot
    litter the checkout."""
    import tempfile

    import jax

    out = {
        "device_count": jax.device_count(),
        "backend": jax.default_backend(),
        "benches": metrics,
    }
    fd, path = tempfile.mkstemp(prefix="BENCH_kernels.", suffix=".json")
    with os.fdopen(fd, "w") as f:
        json.dump(out, f, indent=2, sort_keys=True)
        f.write("\n")
    return path


def _commit_kernels_json(fresh_path: str, n_benches: int) -> None:
    """Atomically replace the committed baseline with the fresh metrics:
    copy into a sibling temp file in the repo root, then ``os.replace`` so
    readers never observe a torn BENCH_kernels.json."""
    import shutil

    tmp = _JSON_PATH + ".tmp"
    shutil.copyfile(fresh_path, tmp)
    os.replace(tmp, _JSON_PATH)
    print(f"# wrote {_JSON_PATH} ({n_benches} benches)", flush=True)


def _load_committed() -> dict:
    if not os.path.exists(_JSON_PATH):
        return {}
    with open(_JSON_PATH) as f:
        return json.load(f).get("benches", {})


def _check_regressions(committed: dict, fresh: dict, gate_rows: list) -> int:
    """Print the per-bench delta table; return the number of regressions.

    Per bench (where both sides have the metric), ceilings AND floors:
    ``us_per_call`` and ``us_decode`` may not grow past the coarse
    CHECK_THRESHOLD (an unchecked decode made a decode regression
    invisible before this gate existed), throughput floors ``gbps`` /
    ``gbps_decode`` may not fall below 1/CHECK_THRESHOLD of the committed
    value (so a perf win, once committed, is locked in from both sides),
    and ``bytes_moved_ratio`` is gated against the tight BYTES_THRESHOLD —
    data-movement accounting is deterministic, so the retrieval plan
    growing its byte footprint is a real regression even at identical
    wall clock.
    """
    gates = [
        ("us_per_call", "ceiling", CHECK_THRESHOLD, "{:.1f}"),
        ("us_decode", "ceiling", CHECK_THRESHOLD, "{:.1f}"),
        ("gbps", "floor", CHECK_THRESHOLD, "{:.5f}"),
        ("gbps_decode", "floor", CHECK_THRESHOLD, "{:.5f}"),
        ("bytes_moved_ratio", "ceiling", BYTES_THRESHOLD, "{:.4f}"),
    ]
    print("\n# bench delta vs committed BENCH_kernels.json")
    print("name,metric,old,new,ratio,verdict")
    bad = 0
    for name in sorted(set(committed) & set(fresh)):
        for metric, kind, threshold, fmt in gates:
            old = committed[name].get(metric)
            new = fresh[name].get(metric)
            if not old or new is None or old != old or new != new:
                continue  # missing/NaN/zero baseline
            ratio = new / old
            verdict = "ok"
            bound = old * threshold if kind == "ceiling" else old / threshold
            if kind == "ceiling" and ratio > threshold:
                verdict = f"REGRESSION(>{threshold:g}x)"
                bad += 1
            if kind == "floor" and ratio < 1.0 / threshold:
                verdict = f"REGRESSION(<1/{threshold:g}x)"
                bad += 1
            gate_rows.append((name, metric, kind, new, bound, verdict))
            print(
                f"{name},{metric},{fmt.format(old)},{fmt.format(new)},"
                f"{ratio:.2f},{verdict}"
            )
    if bad:
        print(f"# {bad} bench metric(s) regressed past their threshold")
    return bad


def _check_abs_gates(fresh: dict, gate_rows: list) -> int:
    """Gate fresh metrics against the fixed ABS_GATES bounds; return the
    number of violations.  Unlike ``_check_regressions`` this does not need
    the metric in the committed baseline, so deleting a row from
    BENCH_kernels.json cannot silently disarm a structural claim."""
    print("\n# absolute gates")
    print("bench,metric,bound,value,verdict")
    bad = 0
    for bench, gates in sorted(ABS_GATES.items()):
        metrics = fresh.get(bench)
        for metric, kind, bound, *flags in gates:
            value = metrics.get(metric) if metrics else None
            verdict = "ok"
            if value is None or value != value:
                if "optional" in flags:
                    # BENCH_FULL-only rows (e.g. the 1024-stream ingest
                    # point) gate when present, skip when the quick run
                    # did not produce them
                    print(f"{bench},{metric},{kind}@{bound:g},nan,"
                          f"skip(absent)")
                    continue
                verdict = "FAIL(missing)"
                bad += 1
            elif kind == "ceiling" and value > bound:
                verdict = f"FAIL(>{bound:g})"
                bad += 1
            elif kind == "floor" and value < bound:
                verdict = f"FAIL(<{bound:g})"
                bad += 1
            shown = "nan" if value is None else f"{value:g}"
            gate_rows.append((bench, metric, kind, value, bound, verdict))
            print(f"{bench},{metric},{kind}@{bound:g},{shown},{verdict}")
    if bad:
        print(f"# {bad} absolute gate(s) failed")
    return bad


def _print_gate_state(gate_rows: list) -> None:
    """Consolidated gate-state table, printed when any gate failed.

    One row per evaluated gate — passing AND failing, relative AND
    absolute — with the measured value, the effective bound (for relative
    gates: committed value x threshold, i.e. the number the fresh run had
    to stay inside), and the signed margin as a fraction of the bound
    (positive = headroom, negative = by how much the gate was blown).  A
    failing CI run should need no further decoding: this table IS the
    full gate state.
    """
    print("\n# full gate state (measured vs bound, margin = headroom/bound)")
    print("bench,metric,kind,measured,bound,margin,verdict")
    for bench, metric, kind, measured, bound, verdict in gate_rows:
        if measured is None or measured != measured:
            meas_s, margin_s = "nan", "nan"
        else:
            meas_s = f"{measured:g}"
            if bound:
                head = (bound - measured) if kind == "ceiling" \
                    else (measured - bound)
                margin_s = f"{head / abs(bound):+.1%}"
            else:  # bound == 0: a ceiling at zero has no relative scale
                margin_s = "n/a" if measured else "+0.0%"
        print(f"{bench},{metric},{kind},{meas_s},{bound:g},{margin_s},"
              f"{verdict}")


def main() -> None:
    check = "--check" in sys.argv
    update = "--update" in sys.argv
    _force_multidevice_host()

    from benchmarks import kernels_bench, paper_tables
    from benchmarks.common import fmt_rows
    from repro.common.compile_cache import enable_compile_cache

    enable_compile_cache()

    quick = os.environ.get("BENCH_FULL", "0") != "1"
    suites = [
        ("table1", paper_tables.table1_resource),
        ("table2", paper_tables.table2_placement),
        ("fig4", paper_tables.fig4_workstation),
        ("fig5", paper_tables.fig5_consolidated),
        ("fig6", paper_tables.fig6_multinode),
        ("fig7", paper_tables.fig7_encryption),
        ("fig8/9", lambda: paper_tables.fig8_fig9_codec(quick=quick)),
        ("fig10", paper_tables.fig10_movement_scaling),
        ("fig11", paper_tables.fig11_csd_ratio),
        ("kernels/polymul", kernels_bench.polymul_kernel),
        ("kernels/motion", kernels_bench.motion_kernel),
        ("kernels/quantize", kernels_bench.quantize_kernel),
        ("kernels/entropy", kernels_bench.entropy_coder),
        ("kernels/fused", kernels_bench.entropy_seal_fused),
        ("kernels/seal", kernels_bench.seal_datapath),
        ("kernels/sharded_seal", kernels_bench.sharded_seal),
        ("kernels/retrieval", kernels_bench.retrieval),
        ("kernels/scrub_rebuild", kernels_bench.scrub_rebuild),
        ("kernels/obs_overhead", kernels_bench.obs_overhead),
        ("kernels/ingest_scale", kernels_bench.ingest_scale),
    ]
    committed = _load_committed() if check else {}
    print("name,us_per_call,derived")
    failures = 0
    for name, fn in suites:
        try:
            print(fmt_rows(fn()), flush=True)
        except Exception as e:  # noqa: BLE001
            failures += 1
            print(f"{name},nan,ERROR: {e!r}", flush=True)
    regressions = 0
    gate_rows: list = []
    if check:
        regressions = _check_regressions(
            committed, kernels_bench.JSON_METRICS, gate_rows
        )
        regressions += _check_abs_gates(kernels_bench.JSON_METRICS, gate_rows)
        if regressions:
            _print_gate_state(gate_rows)
    # fresh metrics always land in the system tempdir (CI can upload them
    # from there); the committed baseline is replaced only on an explicit
    # --update whose gates all passed, so a failed or exploratory run can
    # neither ratchet the baseline down nor leave debris at the repo root
    fresh_path = _dump_fresh(kernels_bench.JSON_METRICS)
    if regressions:
        print(f"# NOT touching {_JSON_PATH} (regression gate failed); "
              f"fresh metrics in {fresh_path}")
    elif update:
        _commit_kernels_json(fresh_path, len(kernels_bench.JSON_METRICS))
    else:
        print(f"# fresh metrics in {fresh_path} "
              f"(pass --update to commit them to {_JSON_PATH})")
    if failures or regressions:
        sys.exit(1)


if __name__ == "__main__":
    main()
