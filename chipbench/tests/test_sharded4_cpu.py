"""Whole runs of a tiny four-device copy of ``city720.sharded4`` on the CPU.

* A cell defined only by new files and new entries (a four-CSD
  configuration, a ``backlog4`` traffic mix, ``"chips": 4``) runs correct
  through ``harness.run_cell``, every number at 0 but ``stored_pct``.
* Faults planted in the program each fail their number: the served
  archive's bodies gathered to chip 0 (``shards_misplaced``), and one
  chip's partial left out of the parity reduce (``parity_mismatched``).
* A program whose mesh seal keeps no body on its chip cannot run the cell:
  the warm-up stops the run with a non-zero exit.

Four CPU devices exist only in a process started with
``--xla_force_host_platform_device_count=4``, so the runs happen in a child
process, this file run as a script, which reports what it saw.  Slow on
the CPU (Pallas interpret mode): a few minutes.
"""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
SEED = 2**33 + 29
SECONDS = 2.0
CHIPS = 4


def _copy(root: Path) -> Path:
    """A checkout copy whose tiny four-device cell is new files and new
    entries; no file of the benchmark is edited."""
    import shutil

    shutil.copytree(BENCH, root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    (root / "src").symlink_to(ROOT / "src")
    before = {p: p.read_bytes() for p in (root / "chipbench").rglob("*")
              if p.is_file()}

    cfg = json.loads(
        (BENCH / "configs" / "city_fleet_720p_csd4.json").read_text())
    cfg.update(name="tiny_fleet_csd4", cameras=4, height=32, width=64,
               gop_max_bytes=16384, pool_gops=4, max_stored_pct=75)
    cfg["frontend"] = dict(cfg["frontend"], queue_budget_bytes=65536)
    (root / "chipbench/configs/tiny_fleet_csd4.json").write_text(
        json.dumps(cfg))
    (root / "chipbench/traffic/tiny_backlog4.json").write_text(json.dumps(
        {"loop": "backlog4", "check_stripes": 3}))

    man = json.loads((root / "BENCHMARK.json").read_text())
    man["configs"].append({"name": "tiny_fleet_csd4", "source": "a test",
                           "file": "chipbench/configs/tiny_fleet_csd4.json",
                           "reduced": [], "why": "a test"})
    man["workloads"].append(
        {"name": "tiny.sharded4", "config": "tiny_fleet_csd4",
         "traffic": "tiny_backlog4", "chips": CHIPS, "why": "a test"})
    for m in man["end_to_end"] + man["per_layer"]:
        if "city720.sharded4" in m.get("workloads", []):
            m["workloads"].append("tiny.sharded4")
    (root / "BENCHMARK.json").write_text(json.dumps(man))
    for p, data in before.items():
        assert p.read_bytes() == data, p
    return root


# ------------------------------------------------------------ the faults
def _bodies_gathered_to_chip0():
    """The served archive keeps every body on chip 0 (the warm-up's
    seals are left as they are)."""
    import jax

    from repro.serving import engine

    orig = engine.seal_coalesced_stripes_finalize
    chip0 = jax.devices()[0]

    def finalize(pending):
        out = []
        for st in orig(pending):
            blocks = [b._replace(sealed=b.sealed._replace(
                body=jax.device_put(b.sealed.body, chip0)))
                for b in st.blocks]
            out.append(st._replace(blocks=blocks))
        return out

    engine.seal_coalesced_stripes_finalize = finalize


def _one_partial_left_out():
    """The XOR reduce folds every chip's parity partial but the last."""
    import jax

    from repro.distributed import archival

    def reduce(x, axis, D):
        g = jax.lax.all_gather(x, axis)
        acc = g[0]
        for i in range(1, D - 1):
            acc = acc ^ g[i]
        return acc

    archival._xor_allreduce = reduce
    archival._mesh_write_program.cache_clear()
    jax.clear_caches()


def _no_body_on_its_chip():
    """The mesh seal hands back no shard devices: every body goes to the
    default device."""
    from repro.kernels.fused import ops as fused_ops

    orig = fused_ops.entropy_seal_stripes_finalize

    def finalize(pending):
        return [(st._replace(devices=None), metas)
                for st, metas in orig(pending)]

    fused_ops.entropy_seal_stripes_finalize = finalize


FAULTS = {"none": None, "gathered": _bodies_gathered_to_chip0,
          "partial_left_out": _one_partial_left_out,
          "no_body_on_its_chip": _no_body_on_its_chip}


def _child(root: str, fault: str) -> dict:
    import time

    for p in (str(BENCH), str(ROOT / "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    import jax

    import harness

    assert jax.device_count() == CHIPS, jax.devices()
    if FAULTS[fault]:
        FAULTS[fault]()
    cell = harness.load_cell(Path(root), "tiny.sharded4")
    try:
        fields, checks = harness.run_cell(
            Path(root), cell, SEED, SECONDS, False,
            t_start=time.perf_counter())
    except SystemExit as e:
        return {"exit": str(e)}
    return {"fields": fields,
            "checks": {c.name: [c.value, c.limit] for c in checks}}


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    return _copy(tmp_path_factory.mktemp("checkout"))


def run(copy, fault):
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " --xla_force_host_"
                        f"platform_device_count={CHIPS}").strip()
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, __file__, str(copy), fault], env=env,
        capture_output=True, text=True, timeout=1200,
    )
    assert out.returncode == 0, out.stderr[-4000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_four_device_cell_from_new_files_runs_correct(copy):
    seen = run(copy, "none")
    fields, checks = seen["fields"], seen["checks"]
    assert fields["correct"], checks
    assert fields["attempted"] > 0 and fields["failed"] == 0
    assert set(fields["metrics"]) == {"setup_s", "ingest_mb_per_s"}
    assert checks["shards_misplaced"] == [0, 0]
    assert 20 < checks["stored_pct"][0] < 75
    assert all(v == 0 and lim == 0 for k, (v, lim) in checks.items()
               if k != "stored_pct")


@pytest.mark.parametrize("fault,number", [
    ("gathered", "shards_misplaced"),
    ("partial_left_out", "parity_mismatched"),
])
def test_fault_is_not_correct(copy, fault, number):
    seen = run(copy, fault)
    assert not seen["fields"]["correct"], seen["checks"]
    value, limit = seen["checks"][number]
    assert value > limit, seen["checks"]


def test_program_that_keeps_no_body_on_its_chip_cannot_run(copy):
    seen = run(copy, "no_body_on_its_chip")
    assert "shards_misplaced" in seen["exit"]


if __name__ == "__main__":
    print(json.dumps(_child(sys.argv[1], sys.argv[2])))
