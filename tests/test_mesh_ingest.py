"""The four-CSD deployment on the served path: ``StreamIngestFrontend`` over
``ArchiveIngest(mesh=...)`` on four devices, mesh shard d playing data
CSD d.

* the stored bytes (bodies, KEM material, manifests, P and Q) equal one
  device's for the same seed, partial (padded) stripes included;
* each stripe's data-shard-d body is held by device d alone;
* ``restore`` reads every stripe back byte for byte, on the mesh;
* ``mesh.cross_chip_bytes`` (and its ledger edge ``ingest.cross_chip``)
  equals a hand count of what crosses: every chip receives the other three
  chips' P and Q partials of each stripe, and the small per-shard arrays
  (raw length, session key, nonce, Q coefficient: 52 B a shard) reach the
  three chips that did not make them.  No payload byte is counted.  The
  same count comes out of the programs the launches ran: every all-gather
  in their compiled HLO gathers a P or Q partial, and what they bring to
  the four devices is the parity term of the hand count.

The device count is fixed when JAX starts, and this suite's process has
one CPU device, so the ingest runs in a child process with four
(``--xla_force_host_platform_device_count=4``) and reports what it saw.
"""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(os.path.dirname(HERE), "src")
D = 4
# GOP sizes in two coder row buckets (2,048 and 4,096 rows); 10 GOPs make
# two full stripes and, at the drain, a 2-shard stripe padded to 4 shards
SIZES = [200_000, 210_000, 300_000, 310_000, 205_000, 215_000, 305_000,
         315_000, 220_000, 320_000]


def _ingest(mesh, payloads, pub):
    from repro.serving.engine import ArchiveIngest, IngestConfig
    from repro.serving.ingest import FrontendConfig, StreamIngestFrontend

    ingest = ArchiveIngest(None, pub, IngestConfig(n_shards=D), seed=3,
                           mesh=mesh)
    # no straggler deadline: the stripes do not depend on the host's speed
    front = StreamIngestFrontend(
        ingest, FrontendConfig(queue_budget_bytes=64 << 20, deadline_us=1e15),
        seed=3)
    committed = []
    for g, p in enumerate(payloads):
        front.offer(g % 3, p, {"spec": [], "n_i8": p.size, "g": g},
                    novelty=g / 10)
        committed += front.pump()
    committed += front.drain()
    return ingest, committed


def _child() -> dict:
    import jax
    import numpy as np
    from jax.sharding import Mesh

    import _hlo
    from repro import obs
    from repro.core.crypto import rlwe
    from repro.distributed import archival
    from repro.kernels.entropy.rans import N_LANES
    from repro.kernels.fused.entropy_seal import seal_rows_cap
    from repro.kernels.entropy.ops import rows_for

    devices = jax.devices()
    assert len(devices) == D, devices
    mesh = Mesh(np.asarray(devices), ("data",))
    pub, sk = rlwe.keygen(jax.random.PRNGKey(1))
    rng = np.random.default_rng(5)
    # a skewed byte histogram, so the coder compresses
    symbols = np.array([0, 1, 2, -1, -2, 3], np.int8)
    probs = np.array([0.5, 0.2, 0.1, 0.1, 0.05, 0.05])
    payloads = [rng.choice(symbols, n, p=probs) for n in SIZES]

    # each mesh launch's program, its arguments and its P partial's shape
    ran = []
    build = archival._mesh_write_program

    def recording(*a, **kw):
        program = build(*a, **kw)

        def launch(*args):
            outs = program(*args)
            ran.append((program, [jax.ShapeDtypeStruct(
                x.shape, x.dtype, sharding=x.sharding) for x in args],
                outs[2].shape))
            return outs
        return launch

    archival._mesh_write_program = recording
    with obs.enabled() as tel:
        ingest, on_mesh = _ingest(mesh, payloads, pub)
        counted = tel.metrics.get(obs.names.MESH_CROSS_CHIP_BYTES)
        edge = tel.ledger.bytes(obs.EDGE_CROSS_CHIP)
        launches = int(tel.metrics.get(obs.names.FUSED_LAUNCHES))
    _, on_one = _ingest(None, payloads, pub)

    # hand count, per committed stripe: each of the D chips receives the
    # other D - 1 chips' P and Q partials, (sealed-row capacity, 128) u32
    # each; the launch's stripes are padded to D shards a stripe
    parity = sum(2 * D * (D - 1) * seal_rows_cap(rows_for(max(
        int(b.manifest["entropy"]["n_raw"]) for b in st.blocks)))
        * N_LANES * 4 for st in on_mesh)
    small = sum((D - 1) * len(st.blocks) * (4 + 32 + 12 + 4)
                for st in on_mesh)
    # what the launches' compiled programs gather between the devices
    gathered, partials_only = 0, True
    for program, args, partial in ran:
        hlo = program.lower(*args).compile().as_text()
        gathered += _hlo.received_bytes(hlo, D)
        partials_only &= all(
            g.dtype == "u32" and _hlo.squeezed(g.operand)
            == _hlo.squeezed(partial) for g in _hlo.all_gathers(hlo))

    def same(a, b):
        return np.array_equal(np.asarray(a), np.asarray(b))

    identical = len(on_mesh) == len(on_one) and all(
        len(x.blocks) == len(y.blocks)
        and all(bx.manifest == by.manifest
                and all(same(getattr(bx.sealed, f), getattr(by.sealed, f))
                        for f in ("body", "kem_c1", "kem_c2", "nonce"))
                for bx, by in zip(x.blocks, y.blocks))
        and same(x.parity["p"], y.parity["p"])
        and same(x.parity["q"], y.parity["q"])
        for x, y in zip(on_mesh, on_one))
    placement = [[sorted(d.id for d in b.sealed.body.devices())
                  for b in st.blocks] for st in on_mesh]

    restored = 0
    for sid in sorted(ingest._stripes):
        got, blocks = ingest.restore(sk, sid)
        restored += sum(
            np.array_equal(np.asarray(p), payloads[int(b.manifest["g"])])
            for p, b in zip(got, blocks))
    return {
        "stripes": [len(st.blocks) for st in on_mesh],
        "launches": launches,
        "identical": bool(identical),
        "placement": placement,
        "device_ids": [d.id for d in devices],
        "restored": int(restored),
        "gops": len(payloads),
        "counted": counted,
        "edge": edge,
        "hand_count": parity + small,
        "parity": parity,
        "gathered": gathered,
        "partials_only": bool(partials_only),
        "programs_ran": len(ran),
    }


@pytest.fixture(scope="module")
def seen():
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " --xla_force_host_platform"
                        f"_device_count={D}").strip()
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        [SRC] + [p for p in env.get("PYTHONPATH", "").split(os.pathsep) if p])
    out = subprocess.run(
        [sys.executable, os.path.abspath(__file__)], env=env,
        capture_output=True, text=True, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-4000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_mesh_stores_what_one_device_stores(seen):
    assert seen["stripes"] == [4, 4, 2]  # the last one padded to 4 shards
    assert seen["identical"]


def test_each_data_shard_body_on_its_own_device(seen):
    ids = seen["device_ids"]
    for st in seen["placement"]:
        assert st == [[ids[s]] for s in range(len(st))]


def test_restore_on_the_mesh_is_byte_exact(seen):
    assert seen["restored"] == seen["gops"]


def test_cross_chip_bytes_are_the_parity_partials(seen):
    assert seen["launches"] >= 2
    assert seen["counted"] == seen["edge"] == seen["hand_count"]
    # the launches' own programs move the parity term, and only partials
    assert seen["programs_ran"] == seen["launches"]
    assert seen["partials_only"]
    assert seen["gathered"] == seen["parity"]


if __name__ == "__main__":
    print(json.dumps(_child()))
