#!/usr/bin/env python3
"""Run one cell of the chip benchmark once.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json``'s ``workloads``.  The run sets
up, warms every shape its traffic uses, measures for ``--seconds``, drains,
and compares what the archive acknowledged and served with the plain
reference.  With ``--trace 0`` the result's metrics are the cell's
end-to-end metrics; with ``--trace 1`` the window runs under the profiler
and they are its per-layer metrics.  The numbers compared with the
reference are printed beside their limits as the last lines of standard
error, and the result as one JSON object on the last line of standard
output.  Without a TPU, or with fewer chips than the cell asks for, it
exits non-zero and prints no result.

``--control raid5`` runs the program with the configuration's RAID-6
parity lowered to RAID-5: the control run, which has to come out not
correct.  The benchmark's own runs never pass it.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def _fail(msg: str) -> int:
    print(f"chipbench: {msg}", file=sys.stderr, flush=True)
    return 2


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", choices=("raid5",), default=None)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        return _fail(f"the system under test is missing: no {ROOT / 'src' / 'repro'}")
    sys.path[:0] = [str(ROOT / "src"), str(BENCH)]
    # JAX's persistent cache: where the environment names one, JAX reads it
    # by itself; otherwise a fixed directory inside the checkout
    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT / ".jax_cache")
    import jax

    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    import harness

    cell = harness.load_cell(ROOT, args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        return _fail(f"no TPU: JAX's first device is a {devices[0].platform!r} "
                     "device; this benchmark measures a TPU")
    if len(devices) < cell.chips:
        return _fail(f"{cell.name} needs {cell.chips} chips, JAX finds "
                     f"{len(devices)}")
    fields, checks = harness.run_cell(
        ROOT, cell, args.seed, args.seconds, bool(args.trace),
        t_start=T_START, parity=args.control)
    device = harness.device_record(cell.chips)
    for c in checks:
        print(f"check {c.name}: {c.value} (limit {c.limit})",
              file=sys.stderr, flush=True)
    print(harness.result_line(fields, device, checks), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
