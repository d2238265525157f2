"""Whole runs of the harness on the CPU at a tiny size.

* A cell defined only by new files (a configuration, a traffic mix) and
  new entries in a copy of ``BENCHMARK.json`` loads and runs, and comes
  out correct.
* The control (the program with RAID-6 lowered to RAID-5) and each fault
  a cell can have, planted in the program underneath the served path,
  come out not correct: a seal that leaves the archive unchanged, half of
  each batch left out, a sealed body altered where it is produced, a
  restored answer altered, bodies stored unencrypted (the keystream
  zeroed in both the seal and the unseal kernel, so the program's own
  round trip still holds), bodies stored without entropy coding, and a
  journal that acknowledges before it fsyncs.  (One chip: no exchange
  between chips to leave out.)

These drive ``harness.run_cell`` directly, past the look for a TPU in
``run.py``.  Slow on the CPU (Pallas interpret mode): a few minutes.
"""

import json
import os
import shutil
import time
from pathlib import Path

import numpy as np
import pytest

import harness

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
SEED = 2**33 + 17
SECONDS = 2.0


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    """A checkout copy whose tiny cells are new files and new entries."""
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(BENCH, root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    (root / "src").symlink_to(ROOT / "src")
    before = {p: p.read_bytes() for p in (root / "chipbench").rglob("*")
              if p.is_file()}
    before[root / "BENCHMARK.json"] = (root / "BENCHMARK.json").read_bytes()

    cfg = json.loads((BENCH / "configs" / "city_fleet_720p.json").read_text())
    cfg.update(name="tiny_fleet", cameras=4, height=32, width=64,
               gop_max_bytes=16384, pool_gops=4, max_stored_pct=75)
    cfg["frontend"] = dict(cfg["frontend"], queue_budget_bytes=65536)
    (root / "chipbench/configs/tiny_fleet.json").write_text(json.dumps(cfg))
    (root / "chipbench/traffic/tiny_backlog.json").write_text(json.dumps(
        {"loop": "backlog", "check_stripes": 3}))

    man = json.loads((root / "BENCHMARK.json").read_text())
    man["configs"].append({"name": "tiny_fleet", "source": "a test",
                           "file": "chipbench/configs/tiny_fleet.json",
                           "reduced": [], "why": "a test"})
    man["workloads"].append(
        {"name": "tiny.backlog", "config": "tiny_fleet",
         "traffic": "tiny_backlog", "chips": 1, "why": "a test"})
    for m in man["end_to_end"] + man["per_layer"]:
        if "city720.backlog" in m.get("workloads", []):
            m["workloads"].append("tiny.backlog")
    (root / "BENCHMARK.json").write_text(json.dumps(man))
    # only entries were added to BENCHMARK.json; no file was edited
    for p, data in before.items():
        if p.name != "BENCHMARK.json":
            assert p.read_bytes() == data, p
    return root


def run(root, name, **kw):
    cell = harness.load_cell(root, name)
    fields, checks = harness.run_cell(
        root, cell, SEED, SECONDS, False, t_start=time.perf_counter(), **kw)
    return fields, {c.name: c for c in checks}


def test_cell_from_new_files_runs_correct(copy):
    fields, checks = run(copy, "tiny.backlog")
    assert fields["correct"], checks
    assert fields["attempted"] > 0 and fields["failed"] == 0
    assert set(fields["metrics"]) == {"setup_s", "ingest_mb_per_s"}
    assert fields["metrics"]["ingest_mb_per_s"]["value"] > 0
    assert checks["stored_pct"].limit == 75
    assert 20 < checks["stored_pct"].value < 75
    assert all(c.limit == 0 for k, c in checks.items() if k != "stored_pct")


def test_control_raid5_is_not_correct(copy):
    fields, checks = run(copy, "tiny.backlog", parity="raid5")
    assert not fields["correct"]
    assert checks["parity_mismatched"].value > 0


def _seal_leaves_archive_unchanged(monkeypatch):
    from repro.serving.engine import ArchiveIngest

    monkeypatch.setattr(ArchiveIngest, "_seal_commit", lambda self, slot: [])


def _half_of_each_batch_left_out(monkeypatch):
    from repro.distributed.archival import StripeCoalescer

    orig, seen = StripeCoalescer.add, [0]

    def add(self, *a, **k):
        seen[0] += 1
        return [] if seen[0] % 2 else orig(self, *a, **k)

    monkeypatch.setattr(StripeCoalescer, "add", add)


def _sealed_body_altered(monkeypatch):
    from repro.kernels.fused import ops as fused_ops

    orig = fused_ops.entropy_seal_stripes_finalize

    def finalize(pending):
        return [(st._replace(sealed=st.sealed.at[0, 0, 0].add(1)), metas)
                for st, metas in orig(pending)]

    monkeypatch.setattr(fused_ops, "entropy_seal_stripes_finalize", finalize)


def _restored_answer_altered(monkeypatch):
    from repro.serving import engine

    orig = engine.restore_stripe_payloads

    def restore(*a, **k):
        payloads, blocks = orig(*a, **k)
        first = np.array(payloads[0], copy=True)
        first[0] ^= 1
        return [first] + list(payloads[1:]), blocks

    monkeypatch.setattr(engine, "restore_stripe_payloads", restore)


def _bodies_unencrypted(monkeypatch):
    import jax
    import jax.numpy as jnp

    from repro.kernels.seal import seal as seal_kernel

    def zero_keystream(key, nonce, row0, rows):
        return jnp.zeros((rows, seal_kernel.LANES), jnp.uint32)

    monkeypatch.setattr(seal_kernel, "_keystream_tile", zero_keystream)
    jax.clear_caches()


def _bodies_not_entropy_coded(monkeypatch):
    orig = harness.Run.ingest_config

    def ingest_config(self):
        cfg = orig(self)
        return cfg._replace(archive=cfg.archive._replace(codec_name="none"))

    monkeypatch.setattr(harness.Run, "ingest_config", ingest_config)


def _journal_acknowledges_before_fsync(monkeypatch):
    import json as json_
    import zlib

    from repro.core.csd.failure import Journal

    def commit(self, name, payload, meta=None):
        body_path = os.path.join(self.root, name)
        with open(body_path + ".tmp", "wb") as f:
            f.write(payload)
        os.replace(body_path + ".tmp", body_path)
        rec = {"name": name, "bytes": len(payload),
               "crc32": zlib.crc32(payload) & 0xFFFFFFFF, "ts": time.time(),
               "meta": meta or {}}
        with open(self.path, "a") as f:
            f.write(json_.dumps(rec) + "\n")
        return body_path

    monkeypatch.setattr(Journal, "commit", commit)


# fault -> the number that has to catch it
FAULTS = {
    "unchanged": (_seal_leaves_archive_unchanged, "gops_lost"),
    "half_batch": (_half_of_each_batch_left_out, "gops_lost"),
    "altered_seal": (_sealed_body_altered, "bodies_mismatched"),
    "altered_restore": (_restored_answer_altered, "gops_mismatched"),
    "unencrypted": (_bodies_unencrypted, "bodies_mismatched"),
    "not_entropy_coded": (_bodies_not_entropy_coded, "stored_pct"),
    "no_fsync": (_journal_acknowledges_before_fsync, "stripes_unsynced"),
}


@pytest.fixture
def clear_caches():
    import jax

    yield
    jax.clear_caches()


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_is_not_correct(copy, monkeypatch, clear_caches, fault):
    plant, number = FAULTS[fault]
    plant(monkeypatch)
    fields, checks = run(copy, "tiny.backlog")
    assert not fields["correct"], checks
    assert checks[number].value > checks[number].limit, checks


def test_traced_run_reads_the_host_side_layers(copy):
    """On the CPU the trace has no TPU plane: the device readers find
    nothing and leave their metrics out; the counters and spans read."""
    cell = harness.load_cell(copy, "tiny.backlog")
    fields, checks = harness.run_cell(copy, cell, SEED + 1, SECONDS, True,
                                      t_start=time.perf_counter())
    checks = {c.name: c for c in checks}
    assert fields["correct"]
    assert set(fields["metrics"]) == {"gops_per_stripe.backlog",
                                      "stripes_per_launch.backlog",
                                      "seal_host_ms.backlog",
                                      "journal_sync_ms.backlog"}
    assert fields["metrics"]["journal_sync_ms.backlog"]["value"] > 0
    assert 1 <= fields["metrics"]["gops_per_stripe.backlog"]["value"] <= 4
    assert fields["window_s"] >= SECONDS
    line = json.loads(harness.result_line(
        fields, harness.device_record(1), list(checks.values())))
    assert list(line)[-1] == "checks" and "breakdown" in line
