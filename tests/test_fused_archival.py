"""One-launch fused archival tests: the ``kernels/fused`` entropy+seal
kernel must be bit-identical to the chained ``kernels/entropy`` ->
``kernels/seal`` path it replaces, at every layer it is wired into —
direct kernel launch (both multi-stripe schedules), batching wrappers,
the pipeline's default rANS dispatch, the shard_map'd mesh twin, and the
read side (full / subset / degraded restores of fused-written archives).

Mesh-shape cases beyond the host's device count skip; run under
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the CI
multi-device job does) to exercise all of {1, 2, 4, 8}.
"""

import functools
import json
import os
import subprocess
import sys

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh

from repro.core.archival import pipeline
from repro.core.archival.pipeline import (
    ArchiveConfig,
    StripeArchive,
    restore_stripe,
    restore_stripe_payloads,
    seal_payload_stripe,
    seal_payload_stripes,
    stripe_manifests,
)
from repro.core.archival.raid import gf_pow_gen
from repro.core.codec.layered_codec import CodecConfig, init_codec
from repro.core.crypto import rlwe
from repro.distributed.archival import (
    StripeCoalescer,
    entropy_seal_sharded,
    seal_coalesced_stripes,
)
from repro.kernels.entropy import ops as eops
from repro.kernels.entropy.ops import rows_for
from repro.kernels.entropy.rans import N_LANES
from repro.kernels.fused import ops as fops
from repro.kernels.fused.entropy_seal import entropy_seal_pallas, seal_rows_cap
from repro.kernels.seal import ops as sops

CFG = CodecConfig(n_layers=2, latent_ch=4, feat_ch=16, mv_cond_ch=4)
MESH_SIZES = [1, 2, 4, 8]


def _mesh(d: int) -> Mesh:
    if jax.device_count() < d:
        pytest.skip(
            f"need {d} devices, have {jax.device_count()} "
            "(run with XLA_FLAGS=--xla_force_host_platform_device_count=8)"
        )
    return Mesh(np.array(jax.devices()[:d]), ("data",))


def _eq(a, b):
    return np.array_equal(np.asarray(a), np.asarray(b))


def _payloads(seed, lens, raw_shards=()):
    """Ragged int8 shard payloads: low-entropy (compressible) by default,
    full-range uniform (incompressible -> raw-skip) for ``raw_shards``."""
    rng = np.random.default_rng(seed)
    out = []
    for s, n in enumerate(lens):
        if s in raw_shards:
            x = rng.integers(-128, 128, n)
        else:
            x = np.clip(np.rint(rng.normal(0.0, 2.0, n)), -128, 127)
        out.append(jnp.asarray(x, jnp.int8))
    return out


def _session(seed, S):
    rng = np.random.default_rng(1000 + seed)
    keys = jnp.asarray(rng.integers(0, 2**32, (S, 8), dtype=np.uint32))
    nonces = jnp.asarray(rng.integers(0, 2**32, (S, 3), dtype=np.uint32))
    return keys, nonces


def _chained(payloads, keys, nonces, parity):
    """The two-launch reference: entropy coder then seal kernel."""
    comps, metas = eops.encode_payloads(payloads)
    return sops.seal_stripe(comps, keys, nonces, parity=parity), metas


def _assert_stripes_equal(got, want):
    gs, gm = got
    ws, wm = want
    assert gm == wm
    assert _eq(gs.sealed, ws.sealed)
    assert gs.n_words == ws.n_words
    assert gs.n_i8 == ws.n_i8
    for a, b in ((gs.p, ws.p), (gs.q, ws.q)):
        assert (a is None) == (b is None)
        if a is not None:
            assert _eq(a, b)


# ------------------------------------------------ fused vs chained identity
@pytest.mark.parametrize("parity", ["raid6", "raid5", "none"])
def test_fused_bit_identical_to_chained(parity):
    """Acceptance: the one-launch kernel's sealed bodies, parity, metas and
    row counts match the chained entropy->seal path bit-for-bit, including
    a raw-skip (incompressible) shard mid-stripe."""
    lens = [5000, 4093, 4096, 777]
    payloads = _payloads(3, lens, raw_shards=(1,))
    keys, nonces = _session(3, len(lens))
    fused = fops.entropy_seal_stripe(payloads, keys, nonces, parity=parity)
    assert fused[1][1]["raw"] is True  # the high-entropy shard raw-skipped
    assert "raw" not in fused[1][0]
    _assert_stripes_equal(fused, _chained(payloads, keys, nonces, parity))


def test_fused_all_raw_stripe():
    """Every shard incompressible: the kernel raw-skips the whole stripe and
    still matches the chained path (stored bytes ARE the payloads)."""
    lens = [2048, 4096, 1023]
    payloads = _payloads(4, lens, raw_shards=range(len(lens)))
    keys, nonces = _session(4, len(lens))
    fused = fops.entropy_seal_stripe(payloads, keys, nonces)
    assert all(m["raw"] is True for m in fused[1])
    assert fused[0].n_i8 == tuple(lens)
    _assert_stripes_equal(fused, _chained(payloads, keys, nonces, "raid6"))


def test_fused_ref_matches_pallas():
    """The staged jnp oracle (use_pallas=False) is bit-identical to the
    kernel on a mixed compressible/raw stripe."""
    payloads = _payloads(5, [3000, 512, 4095], raw_shards=(2,))
    keys, nonces = _session(5, 3)
    _assert_stripes_equal(
        fops.entropy_seal_stripe(payloads, keys, nonces, use_pallas=False),
        fops.entropy_seal_stripe(payloads, keys, nonces, use_pallas=True),
    )


def test_batched_stripes_match_per_stripe():
    """K stripes through one batched call == K singular calls, across
    heterogeneous groups (different shard counts and row buckets)."""
    stripes = [
        _payloads(10, [4000, 4001]),
        _payloads(11, [3999, 100], raw_shards=(1,)),
        _payloads(12, [9000, 8888, 7000]),  # different (S, T) group
    ]
    mats = [_session(20 + i, len(p)) for i, p in enumerate(stripes)]
    keys = [m[0] for m in mats]
    nonces = [m[1] for m in mats]
    batched = fops.entropy_seal_stripes(stripes, keys, nonces)
    for got, p, k, n in zip(batched, stripes, keys, nonces):
        _assert_stripes_equal(got, fops.entropy_seal_stripe(p, k, n))


def test_grid_schedule_bit_identical_to_fat_block(monkeypatch):
    """The row-tile grid schedules of the write path (whole-bucket blocks
    vs minimal 8-row tiles in the coder and the seal) are pure
    scheduling: identical outputs."""
    from repro.kernels.entropy import rans
    from repro.kernels.seal import seal as seal_kernel

    S, K = 2, 3
    flats = [p for i in range(K) for p in _payloads(30 + i, [2500, 2501])]
    n_raw = [int(f.shape[0]) for f in flats]
    T = rows_for(max(n_raw))
    codes = jnp.stack(
        [jnp.pad(f, (0, T * N_LANES - n)).reshape(T, N_LANES)
         for f, n in zip(flats, n_raw)]
    )
    n_valid = jnp.asarray(n_raw, jnp.int32).reshape(-1, 1)
    keys, nonces = _session(30, K * S)
    q_coef = jnp.asarray(
        [gf_pow_gen(s) for s in range(S)] * K, jnp.uint32
    ).reshape(-1, 1)
    run = functools.partial(
        entropy_seal_pallas, codes, n_valid, keys, nonces, q_coef,
        n_shards=S, parity="raid6", interpret=True,
    )
    fat = run()
    monkeypatch.setattr(rans, "_TILE_ELEMS", 8 * 8 * N_LANES)
    monkeypatch.setattr(rans, "_HIST_TILE", 8)
    monkeypatch.setattr(seal_kernel, "_MAX_TILE", 8)
    grid = run()
    for a, b in zip(fat, grid):
        assert _eq(a, b)


# -------------------------------------------------- pipeline-level dispatch
def test_seal_payload_stripe_default_is_fused_and_identical_to_chained():
    """The default rANS path dispatches the fused launch (observed via the
    fused_fn seam) and its archive equals the explicit chained path."""
    cfg = ArchiveConfig(codec=CFG)
    pub, _ = rlwe.keygen(jax.random.PRNGKey(0))
    flats = _payloads(6, [4000, 123, 4096], raw_shards=(2,))
    manifests = [{"n_i8": int(f.shape[0])} for f in flats]
    key = jax.random.PRNGKey(42)

    calls = []

    def counting_fused(*a, **kw):
        calls.append(1)
        return fops.entropy_seal_stripes(*a, **kw)

    fused = seal_payload_stripe(
        pub, flats, manifests, key, cfg, fused_fn=counting_fused
    )
    assert len(calls) == 1
    default = seal_payload_stripe(pub, flats, manifests, key, cfg)
    chained = seal_payload_stripe(
        pub, flats, manifests, key, cfg,
        seal_fn=sops.seal_stripe, entropy_fn=eops.encode_payloads,
    )
    for got in (fused, default):
        for bg, bc in zip(got.blocks, chained.blocks):
            assert _eq(bg.sealed.body, bc.sealed.body)
            assert _eq(bg.sealed.kem_c1, bc.sealed.kem_c1)
            assert _eq(bg.sealed.nonce, bc.sealed.nonce)
            assert bg.manifest == bc.manifest
        assert _eq(got.parity["p"], chained.parity["p"])
        assert _eq(got.parity["q"], chained.parity["q"])


def test_seal_payload_stripes_matches_singular():
    """Batches of equal stripes, and of full and partial stripes mixed
    (their sessions share one KEM program), seal as each stripe alone."""
    cfg = ArchiveConfig(codec=CFG)
    pub, _ = rlwe.keygen(jax.random.PRNGKey(1))
    for layout in ([2, 2, 2], [4, 1, 3, 4, 2]):
        stripes = [
            _payloads(40 + i, [3000 + 7 * i + 5 * s for s in range(S)])
            for i, S in enumerate(layout)
        ]
        manifests = [
            [{"n_i8": int(f.shape[0])} for f in fl] for fl in stripes
        ]
        keys = [jax.random.PRNGKey(100 + i) for i in range(len(layout))]
        plural = seal_payload_stripes(pub, stripes, manifests, keys, cfg)
        for got, fl, mf, k in zip(plural, stripes, manifests, keys):
            want = seal_payload_stripe(pub, fl, mf, k, cfg)
            assert len(got.blocks) == len(want.blocks) == len(fl)
            for bg, bw in zip(got.blocks, want.blocks):
                assert _eq(bg.sealed.body, bw.sealed.body)
                assert _eq(bg.sealed.kem_c1, bw.sealed.kem_c1)
                assert _eq(bg.sealed.kem_c2, bw.sealed.kem_c2)
                assert _eq(bg.sealed.nonce, bw.sealed.nonce)
                assert bg.manifest == bw.manifest
            assert _eq(got.parity["p"], want.parity["p"])
            assert _eq(got.parity["q"], want.parity["q"])


def test_kem_program_built_once_for_every_dispatch_size():
    """Every session count a dispatch can hold (1 to 16: up to four
    stripes of up to four shards) runs the one KEM program, so the
    warm-up's launches build it and none is built in a measured window;
    the per-stripe key stacks vary only with the shard count."""
    cfg = ArchiveConfig(codec=CFG)
    pub, _ = rlwe.keygen(jax.random.PRNGKey(2))
    payload = jnp.zeros(64, jnp.int8)
    pipeline._encapsulate_rows._clear_cache()
    pipeline._stack._clear_cache()
    launched = []

    def no_launch(stripes, keys_a, nonces_a, **kw):
        launched.append([k.shape for k in keys_a])

    for V in range(1, 17):
        layout = [4] * (V // 4) + ([V % 4] if V % 4 else [])
        pipeline.seal_payload_stripes_dispatch(
            pub, [[payload] * S for S in layout],
            [[{"n_i8": 64}] * S for S in layout],
            [jax.random.PRNGKey(V * 10 + i) for i in range(len(layout))],
            cfg, fused_dispatch_fn=no_launch,
        )
        assert launched[-1] == [(S, 8) for S in layout]
    assert pipeline._encapsulate_rows._cache_size() == 1
    # sessions and nonces, each for 1, 2, 3 and 4 shards
    assert pipeline._stack._cache_size() == 8


# --------------------------------------------------- read side: fused-written
def test_restore_full_subset_degraded_through_fused_archive():
    """Fused-written archives decode through every read path: full stripe
    (with parity verification), shard-subset retrieval, and a parity-
    rebuilt degraded read of a lost shard."""
    cfg = ArchiveConfig(codec=CFG)
    pub, secret = rlwe.keygen(jax.random.PRNGKey(2))
    flats = _payloads(7, [5000, 4093, 64, 4096, 2500], raw_shards=(3,))
    manifests = [{"n_i8": int(f.shape[0])} for f in flats]
    archive = seal_payload_stripe(
        pub, flats, manifests, jax.random.PRNGKey(9), cfg
    )
    # full restore, parity recompute-and-compare on
    back, _ = restore_stripe_payloads(secret, archive, cfg)
    for got, want in zip(back, flats):
        assert _eq(got, want)
    # subset retrieval (raw-skip shard included)
    sub, blocks = restore_stripe_payloads(secret, archive, cfg, shards=[3, 1])
    assert _eq(sub[0], flats[3]) and _eq(sub[1], flats[1])
    assert blocks[0].manifest["entropy"]["raw"] is True
    # degraded read: lose a shard, rebuild from RAID parity + replicated meta
    recs = stripe_manifests(archive)
    holed = StripeArchive(
        [None if i == 2 else b for i, b in enumerate(archive.blocks)],
        archive.parity,
    )
    deg, _ = restore_stripe_payloads(
        secret, holed, cfg, shards=[2, 0], manifests=recs
    )
    assert _eq(deg[0], flats[2]) and _eq(deg[1], flats[0])


def test_golden_v0_fixture_unaffected_by_fused_write_path():
    """The fused kernel is write-side only: PR-4-era version-0 archives keep
    decoding, and fused re-encodes of the same payloads emit version-1
    streams bit-identical to the chained coder's."""
    import base64
    import json
    import os

    with open(os.path.join(os.path.dirname(__file__),
                           "data_rans_v0.json")) as f:
        g = json.load(f)
    comps = [
        jnp.asarray(np.frombuffer(base64.b64decode(b), np.int8))
        for b in g["streams_b64"]
    ]
    wants = [
        np.frombuffer(base64.b64decode(b), np.int8)
        for b in g["payloads_b64"]
    ]
    back = eops.decode_payloads(comps, g["metas"])
    for got, want in zip(back, wants):
        assert _eq(got, want)
    keys, nonces = _session(8, len(wants))
    fused = fops.entropy_seal_stripe(
        [jnp.asarray(w) for w in wants], keys, nonces
    )
    for m, m0 in zip(fused[1], g["metas"]):
        assert m["version"] == 1
        assert m["n_comp"] == m0["n_comp"]  # format moves words, adds none
    _assert_stripes_equal(
        fused, _chained([jnp.asarray(w) for w in wants], keys, nonces,
                        "raid6")
    )


# ------------------------------------------------------------- sharded twin
@pytest.mark.parametrize("d", MESH_SIZES)
def test_sharded_fused_core_bit_identical(d):
    """entropy_seal_sharded (shard_map'd local kernels + cross-shard XOR
    parity reduce) == the single-device fused launch on every mesh shape,
    including S % D != 0 (dummy zero-shard padding)."""
    mesh = _mesh(d)
    core = functools.partial(entropy_seal_sharded, mesh=mesh, axis="data")
    for seed, lens, raw in ((50, [4000, 3999, 4001, 128], (3,)),
                            (51, [2000, 1999, 2001], ())):  # S=3: padding
        payloads = _payloads(seed, lens, raw_shards=raw)
        keys, nonces = _session(seed, len(lens))
        _assert_stripes_equal(
            fops.entropy_seal_stripe(payloads, keys, nonces, core_fn=core),
            fops.entropy_seal_stripe(payloads, keys, nonces),
        )


@pytest.mark.parametrize("d", MESH_SIZES)
def test_seal_coalesced_stripes_sharded_end_to_end(d):
    """Coalescer -> batched sharded seal == batched local seal, and the
    fused-written stripes decode through the standard restore path."""
    mesh = _mesh(d)
    cfg = ArchiveConfig(codec=CFG)
    codec_params = init_codec(jax.random.PRNGKey(0), CFG)
    pub, secret = rlwe.keygen(jax.random.PRNGKey(1))
    from repro.core.archival.pipeline import encode_gop_payload

    coal = StripeCoalescer(n_shards=2)
    batch = []
    for i in range(4):
        f = jnp.clip(
            jax.random.uniform(jax.random.PRNGKey(70 + i), (2, 1, 32, 32, 3)),
            0.0, 1.0,
        )
        flat, manifest, _ = encode_gop_payload(codec_params, f, cfg)
        batch += coal.add(i % 3, flat, manifest)
    batch += coal.flush()
    assert batch and coal.n_pending == 0
    keys = [jax.random.PRNGKey(200 + i) for i in range(len(batch))]
    sharded = seal_coalesced_stripes(pub, batch, keys, cfg, mesh=mesh)
    local = seal_coalesced_stripes(pub, batch, keys, cfg)
    for gs, gl in zip(sharded, local):
        for bs, bl in zip(gs.blocks, gl.blocks):
            assert _eq(bs.sealed.body, bl.sealed.body)
            assert bs.manifest == bl.manifest
        assert _eq(gs.parity["p"], gl.parity["p"])
        assert _eq(gs.parity["q"], gl.parity["q"])
    out = restore_stripe(codec_params, secret, sharded[0], cfg)
    assert len(out) == len(sharded[0].blocks)


# ------------------------------------------------- the finalize's host cut
class _NoDeviceIndexing:
    """A launch's output that refuses any device-side indexing: the
    finalize may only read it whole, from the host copy its dispatch
    started."""

    def __init__(self, rows):
        self._rows = rows

    def __getitem__(self, index):
        raise AssertionError(f"device-side slice of a launch output: {index}")

    def __array__(self, dtype=None, copy=None):
        return np.asarray(self._rows, dtype)

    def __getattr__(self, name):
        return getattr(self._rows, name)


def _host_cut_seen(d: int) -> dict:
    """Three stripes of four shards (one launch group) through dispatch and
    a finalize whose pending rows and parity refuse device slices, on one
    device (``d == 1``) or split over a mesh of ``d``: what the finalize
    returned against the chained ``encode_payloads -> seal_stripe`` path,
    where the bodies and the stored parity went, the early copy's billed
    bytes against the hand count K * (S + 2) * R_cap * 128 * 4 (rows, P
    and Q), and the ``kernels.slice`` spans."""
    from repro import obs
    from repro.obs import OBS, names as obs_names

    devices = jax.devices()[:d]
    core = None if d == 1 else functools.partial(
        entropy_seal_sharded, mesh=Mesh(np.array(devices), ("data",)),
        axis="data")
    lens = [4000, 3999, 4001, 128]
    stripes = [_payloads(60 + k, lens, raw_shards=(3,)) for k in range(3)]
    keys, nonces = zip(*(_session(60 + k, len(lens)) for k in range(3)))
    obs.disable()
    obs.reset()
    with obs.enabled():
        pending = fops.entropy_seal_stripes_dispatch(
            stripes, keys, nonces, core_fn=core)
        counted = OBS.metrics.get(obs_names.ROWS_TO_HOST_BYTES)
        pending = pending._replace(groups=[
            g._replace(sealed=_NoDeviceIndexing(g.sealed),
                       p=_NoDeviceIndexing(g.p), q=_NoDeviceIndexing(g.q))
            for g in pending.groups])
        got = fops.entropy_seal_stripes_finalize(pending)
        bodies = [st.bodies() for st, _ in got]
        parity = [[pipeline._u32_rows_to_u8(x, st.parity_sharding)
                   for x in (st.p, st.q)] for st, _ in got]
        events = list(OBS.tracer.events)
    obs.reset()
    by_id = {e["id"]: e for e in events}

    def under_fetch(e):
        while e["parent"]:
            e = by_id[e["parent"]]
            if e["name"] == "kernels.fetch":
                return True
        return False

    identical = True
    for (st, metas), bs, pq, p, k, n in zip(got, bodies, parity, stripes,
                                            keys, nonces):
        want, want_metas = _chained(p, k, n, "raid6")
        identical &= (metas == want_metas and st.n_words == want.n_words
                      and _eq(st.sealed, want.sealed)
                      and _eq(st.p, want.p) and _eq(st.q, want.q)
                      and all(_eq(b, want.body(s)) for s, b in enumerate(bs))
                      and _eq(pq[0], pipeline._u32_rows_to_u8(want.p))
                      and _eq(pq[1], pipeline._u32_rows_to_u8(want.q)))
    T = rows_for(max(lens))
    slices = [e for e in events if e["name"] == "kernels.slice"]
    return {
        "groups": len(pending.groups),
        "identical": bool(identical),
        "placement": [[sorted(x.id for x in b.devices()) for b in bs]
                      for bs in bodies],
        "parity_placement": [list(ids) for ids in sorted(
            {tuple(sorted(x.id for x in a.devices()))
             for pq in parity for a in pq})],
        "device_ids": [x.id for x in devices],
        "counted": int(counted),
        "hand_count": (len(stripes) * (len(lens) + 2) * seal_rows_cap(T)
                       * 128 * 4),
        "slice_stripes": [e["attrs"].get("stripes") for e in slices],
        "slices_under_fetch": sum(under_fetch(e) for e in slices),
    }


@pytest.fixture(scope="module", params=["one_device", "mesh4"])
def host_cut(request):
    """``_host_cut_seen`` on one device here, or on a mesh of four in a
    child process with four CPU devices (the device count is fixed when
    JAX starts, and this process has one)."""
    if request.param == "one_device":
        return _host_cut_seen(1)
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " --xla_force_host_platform"
                        "_device_count=4").strip()
    env["JAX_PLATFORMS"] = "cpu"
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    env["PYTHONPATH"] = os.pathsep.join(
        [src] + [p for p in env.get("PYTHONPATH", "").split(os.pathsep) if p])
    out = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "4"], env=env,
        capture_output=True, text=True, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-4000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_finalize_cuts_stripes_on_the_host(host_cut):
    """The finalize slices no sealed rows or parity on the device, and
    still returns every stripe, body and stored parity bit-identical to the
    chained path; on the mesh each body is back on its shard's device, and
    P and Q on every device, as the launch left them."""
    seen = host_cut
    assert seen["groups"] == 1
    assert seen["identical"]
    ids = seen["device_ids"]
    for st in seen["placement"]:
        assert st == [[ids[s % len(ids)]] for s in range(len(st))]
    assert seen["parity_placement"] == [sorted(ids)]


def test_rows_to_host_bytes_and_slice_spans(host_cut):
    """The early copy bills K * (S + 2) * R_cap * 128 * 4 bytes for the
    launch (each chip's shards once on the mesh, and one copy of P and Q),
    and every ``kernels.slice`` span carries ``stripes=`` and sits outside
    the ``kernels.fetch`` wait."""
    seen = host_cut
    assert seen["counted"] == seen["hand_count"]
    assert seen["slice_stripes"] and all(seen["slice_stripes"])
    assert sum(seen["slice_stripes"]) == 3
    assert seen["slices_under_fetch"] == 0


# ------------------------------------------------------------------ hygiene
def test_hygiene_sweep_covers_fused_sources():
    """The TPU-hostile-construct bans apply to the fused kernel package:
    its sources must be inside the hygiene sweep's file set."""
    from test_kernel_hygiene import _kernel_sources

    srcs = _kernel_sources()
    for want in ("entropy_seal.py", "ref.py", "ops.py"):
        assert any(p.endswith("fused/" + want) for p in srcs), want


if __name__ == "__main__":
    print(json.dumps(_host_cut_seen(int(sys.argv[1]))))
