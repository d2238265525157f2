"""The benchmark's machinery, driven by ``BENCHMARK.json``.

A cell names a configuration (a deployment's file under
``chipbench/configs/``) and a traffic mix (``chipbench/traffic/<name>.json``,
whose ``loop`` key picks the closed loop in ``chipbench/loops/<loop>.py``).
Each per-layer metric is read by ``chipbench/metrics/<name>.py``.  Nothing
here names a cell, a configuration or a metric: a later cell is new files
and new entries in ``BENCHMARK.json``.

A run: set-up (keys, payload pool, the loop's warm-up of every shape it
will use, and whatever state its traffic needs), then the measured window
of ``seconds``, then the loop's drain and the comparison with the plain
reference (``reference.py``).  With ``trace`` the window runs under the
JAX profiler with the program's telemetry on, and the per-layer readers
take their numbers from the reduced trace (``trace_reduce.py``), the
program's counters and spans, and the loop's own stamps.
"""

from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path
from types import ModuleType
from typing import Callable, Dict, List, NamedTuple, Optional, Tuple

import numpy as np

BENCH = Path(__file__).resolve().parent
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

import reference  # noqa: E402
from workload import Workload  # noqa: E402

__all__ = ["Cell", "Check", "Run", "CompileCounter", "SyncLog", "load_cell",
           "load_module", "run_cell", "result_line"]

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class Check(NamedTuple):
    """One number compared with the reference, and its limit (pass when
    ``value <= limit``)."""

    name: str
    value: float
    limit: float


class Cell(NamedTuple):
    name: str
    chips: int
    config_name: str
    config: Dict
    traffic_name: str
    traffic: Dict
    end_to_end: List[Dict]
    per_layer: List[Dict]


def _load_json(path: Path) -> Dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path) -> ModuleType:
    """Import a file of the benchmark by its path (names may hold dots)."""
    spec = importlib.util.spec_from_file_location(
        "chipbench_" + path.stem.replace(".", "_"), path)
    if spec is None or spec.loader is None:
        raise ImportError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(root: Path, workload: str) -> Cell:
    """Resolve a cell of ``root/BENCHMARK.json`` into its files."""
    man = _load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in man["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    wl = cells[workload]
    cfgs = {c["name"]: c for c in man["configs"]}
    cfg = _load_json(root / cfgs[wl["config"]]["file"])
    traffic = _load_json(root / "chipbench" / "traffic" / f"{wl['traffic']}.json")
    e2e = [m for m in man["end_to_end"]
           if workload in m.get("workloads", [workload])]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [
        m for m in man["per_layer"]
        if (workload in m["workloads"] if "workloads" in m
            else m["moves"] in e2e_names)
    ]
    return Cell(workload, int(wl["chips"]), wl["config"], cfg,
                wl["traffic"], traffic, e2e, per_layer)


class CompileCounter:
    """Programs JAX built (compiled, or loaded from the persistent cache)
    and the seconds that took, from its monitoring events."""

    def __init__(self):
        import jax

        self.builds = 0
        self.build_s = 0.0
        self.cache_hits = 0

        def listen(event, duration, **_):
            if event == BACKEND_COMPILE_EVENT:
                self.builds += 1
                self.build_s += duration

        def count(event, **_):
            if event == CACHE_HIT_EVENT:
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(listen)
        jax.monitoring.register_event_listener(count)

    def mark(self) -> Tuple[int, float, int]:
        return self.builds, self.build_s, self.cache_hits


class SyncLog:
    """Every ``os.fsync`` the process makes while installed: when it
    returned (``time.perf_counter_ns``), what it synced (the path the file
    descriptor names), and how long it took.  The journal's durability is
    checked from it, and its time is the journal's commit cost."""

    def __init__(self):
        self.events: List[Tuple[int, str, int]] = []
        self._orig = None

    def install(self) -> None:
        orig = self._orig = os.fsync

        def fsync(fd):
            n = fd if isinstance(fd, int) else fd.fileno()
            t0 = time.perf_counter_ns()
            orig(n)
            t1 = time.perf_counter_ns()
            try:
                path = os.readlink(f"/proc/self/fd/{n}")
            except OSError:
                path = "?"
            self.events.append((t1, path, t1 - t0))

        os.fsync = fsync

    def uninstall(self) -> None:
        if self._orig is not None:
            os.fsync = self._orig
            self._orig = None

    def seconds(self, t0_ns: int, t1_ns: int, under: str = "/"
                ) -> Tuple[int, float]:
        """(fsyncs, seconds in them) that returned in [t0_ns, t1_ns], of
        paths under the directory ``under``."""
        root = os.path.join(os.path.realpath(under), "")
        ev = [d for t, path, d in self.events
              if t0_ns <= t <= t1_ns and os.path.join(path, "").startswith(root)]
        return len(ev), sum(ev) / 1e9


def _prog_seed(seed: int) -> int:
    """The seed handed to the program: its own key derivations multiply
    it, so it is kept small; drawn from the run's seed."""
    return int(np.random.default_rng([int(seed), 0x9E]).integers(1, 1 << 20))


class Run:
    """One run of a cell: what the loops and metric readers see."""

    def __init__(self, cell: Cell, seed: int, *, trace: bool,
                 parity: Optional[str] = None, log: Callable = None):
        self.cell = cell
        self.cfg = cell.config
        self.traffic = cell.traffic
        self.seed = int(seed)
        self.prog_seed = _prog_seed(seed)
        self.trace = bool(trace)
        # the parity the program is configured with; the reference always
        # holds it to what the configuration states
        self.parity = parity or self.cfg["parity"]
        self.log = log or (lambda msg: print(f"chipbench: {msg}",
                                             file=sys.stderr, flush=True))
        self.workload = Workload(self.cfg, self.traffic, self.seed)
        self.tmp = tempfile.mkdtemp(prefix="chipbench-")
        self.stamps: Dict = {}       # what the loop measured
        self.state: Dict = {}        # the loop's objects
        self.telemetry = None        # the program's counters/spans (trace)
        self.trace_summary = None    # the reduced device trace (trace)
        self.device_kind = None
        self.secret = None           # the reference's RLWE secret
        self.syncs = SyncLog()       # the journal's fsyncs

    # ------------------------------------------------------------ program
    def keys(self):
        """(the program's public key, its secret as the program's restore
        takes it).  The pair is the reference's own, made from the seed in
        numpy; the reference keeps the secret to open what was stored."""
        import jax.numpy as jnp

        from repro.core.crypto import rlwe

        if "keys" not in self.state:
            kem = self.cfg["kem"]
            a, b, s = reference.rlwe_keygen(
                self.prog_seed + 1, kem["ring_n"], kem["modulus_q"], kem["cbd_k"])
            self.secret = s
            self.state["keys"] = (
                rlwe.PublicKey(jnp.asarray(a, jnp.int32), jnp.asarray(b, jnp.int32)),
                jnp.asarray(s, jnp.int32))
        return self.state["keys"]

    def ingest_config(self):
        from repro.core.archival.pipeline import ArchiveConfig
        from repro.core.crypto.rlwe import RLWEParams
        from repro.serving.engine import IngestConfig

        kem = self.cfg["kem"]
        return IngestConfig(
            n_shards=int(self.cfg["data_shards"]),
            archive=ArchiveConfig(
                parity=self.parity, codec_name=self.cfg["codec"],
                rlwe=RLWEParams(kem["ring_n"], kem["modulus_q"], kem["cbd_k"])),
            feature_dim=int(self.cfg["feature_dim"]),
        )

    def make_frontend(self, name: str):
        """A fresh ``ArchiveIngest`` behind a ``StreamIngestFrontend``,
        with a journal of its own in the run's temporary directory."""
        from repro.core.csd.failure import Journal
        from repro.serving.engine import ArchiveIngest
        from repro.serving.ingest import FrontendConfig, StreamIngestFrontend

        pub, _ = self.keys()
        jdir = os.path.join(self.tmp, name)
        journal = Journal(jdir)
        ingest = ArchiveIngest(None, pub, self.ingest_config(),
                               seed=self.prog_seed, journal=journal)
        front = StreamIngestFrontend(
            ingest, FrontendConfig(**self.cfg["frontend"]),
            seed=self.prog_seed, journal=journal)
        return front, jdir

    def span(self, name: str):
        """A host span in the profiler's trace when tracing, else nothing."""
        if not self.trace:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)

    def close(self) -> None:
        self.syncs.uninstall()
        self.state.clear()
        shutil.rmtree(self.tmp, ignore_errors=True)


def device_record(chips: int) -> Dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak(chips: int) -> Optional[int]:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()[:chips]]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def run_cell(root: Path, cell: Cell, seed: int, seconds: float, trace: bool,
             *, t_start: float, parity: Optional[str] = None,
             log: Callable = None) -> Tuple[Dict, List[Check]]:
    """Set up, measure, drain and check one run of ``cell``.

    ``t_start`` is the process's start on ``time.perf_counter``, so that
    ``setup_s`` holds imports and the backend's start too.  Returns the
    result's fields (without ``device``) and the checks.
    """
    bench = root / "chipbench"
    loop = load_module(bench / "loops" / f"{cell.traffic['loop']}.py")
    run = Run(cell, seed, trace=trace, parity=parity, log=log)
    run.device_kind = device_record(cell.chips)["kind"]
    counter = CompileCounter()
    try:
        t = time.perf_counter()
        pool_bytes = run.workload.pool.nbytes
        run.log(f"payload pool of {pool_bytes} B made in "
                f"{time.perf_counter() - t:.3f} s")
        run.syncs.install()
        loop.setup(run)
        b0, s0, h0 = counter.mark()
        setup_s = time.perf_counter() - t_start
        run.log(f"set-up {setup_s:.3f} s, {b0} programs built "
                f"({s0:.3f} s), {h0} from the persistent cache")
        tracer = _Tracer(run) if trace else None
        if tracer:
            tracer.start()
        w0 = time.perf_counter_ns()
        with run.span("window"):
            measured = loop.window(run, seconds)
        run.stamps["window_ns"] = (w0, time.perf_counter_ns())
        b1, s1, _ = counter.mark()
        run.log(f"window: {b1 - b0} programs built inside it "
                f"({s1 - s0:.3f} s)")
        n_sync, sync_s = run.syncs.seconds(*run.stamps["window_ns"])
        run.log(f"window: {n_sync} fsyncs, {sync_s:.6f} s in them")
        peak = memory_peak(cell.chips)
        loop.finish(run)
        if tracer:
            tracer.stop()
        failed, checks = loop.check(run)
    finally:
        run.close()
    metrics: Dict[str, Dict] = {}
    if not trace:
        values = dict(measured["metrics"], setup_s=setup_s)
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    else:
        for m in cell.per_layer:
            reader = load_module(bench / "metrics" / f"{m['name']}.py")
            v = reader.read(run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    out = {
        "correct": all(c.value <= c.limit for c in checks),
        "attempted": int(measured["attempted"]),
        "failed": int(failed),
        "metrics": metrics,
        "memory_peak_bytes": peak,
    }
    if trace and run.trace_summary is not None:
        out["busy_s"] = run.trace_summary.busy_s
        out["window_s"] = run.trace_summary.window_s
        out["breakdown"] = run.trace_summary.breakdown()
    return out, checks


class _Tracer:
    """The profiler around the window, with the program's telemetry on."""

    def __init__(self, run: Run):
        self.run = run
        self.dir = os.path.join(run.tmp, "trace")

    def start(self) -> None:
        import jax

        from repro import obs

        obs.enable(reset=True)
        # host spans at TraceAnnotation's level; no Python call tracing,
        # which would slow the host and swell the trace
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.dir, profiler_options=opts)

    def stop(self) -> None:
        import jax

        from repro import obs

        jax.profiler.stop_trace()
        obs.disable()
        self.run.telemetry = obs.OBS
        from trace_reduce import reduce_trace

        self.run.trace_summary = reduce_trace(
            self.dir, chips=self.run.cell.chips)


def result_line(fields: Dict, device: Dict, checks: List[Check]) -> str:
    """The contract's last line: the result, with the compared numbers
    beside their limits under the last key."""
    dev = dict(device, memory_peak_bytes=fields["memory_peak_bytes"])
    for k in ("busy_s", "window_s"):
        if k in fields:
            dev[k] = fields[k]
    out = {k: fields[k] for k in ("correct", "attempted", "failed", "metrics")}
    out["device"] = dev
    if "breakdown" in fields:
        out["breakdown"] = fields["breakdown"]
    out["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                     for c in checks}
    return json.dumps(out)
