"""Interleaved-rANS entropy kernel tests: exactness vs oracle, roundtrip,
stream format, pipeline chaining (single-device and sharded)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh

from repro.core.archival.pipeline import (
    ArchiveConfig,
    archive_stripe,
    restore_stripe,
)
from repro.core.codec.layered_codec import CodecConfig, init_codec
from repro.core.crypto import rlwe
from repro.kernels.entropy import ops as eops
from repro.kernels.entropy.rans import (
    N_LANES,
    PROB_SCALE,
    STREAM_VERSION,
    build_freq_table,
)

CFG = CodecConfig(n_layers=2, latent_ch=4, feat_ch=16, mv_cond_ch=4)


def _eq(a, b):
    return np.array_equal(np.asarray(a), np.asarray(b))


def _latents(seed, n, sigma=2.0):
    """Peaked int8 distribution shaped like quantized codec latents."""
    rng = np.random.default_rng(seed)
    return jnp.asarray(
        np.clip(np.round(rng.normal(0.0, sigma, n)), -128, 127), jnp.int8
    )


# ------------------------------------------------------- kernel vs jnp oracle
def test_encode_matches_staged_oracle():
    payloads = [_latents(i, n) for i, n in enumerate([5000, 4093, 4096, 2500])]
    ck, mk = eops.encode_payloads(payloads, use_pallas=True)
    cr, mr = eops.encode_payloads(payloads, use_pallas=False)
    assert mk == mr
    for a, b in zip(ck, cr):
        assert _eq(a, b)  # streams bit-identical, header included


def test_roundtrip_bit_exact_both_paths():
    payloads = [_latents(7, 9000), _latents(8, 100)]
    comp, metas = eops.encode_payloads(payloads)
    for use_pallas in (True, False):
        back = eops.decode_payloads(comp, metas, use_pallas=use_pallas)
        for got, want in zip(back, payloads):
            assert _eq(got, want)


@pytest.mark.parametrize(
    "lens",
    [
        [1],                          # single byte
        [7, 1],                       # sub-lane shards
        [N_LANES * 8, 511],           # exactly one tile vs one byte short
        [4097, 13],                   # one word past a tile vs tiny
        [37, 37],                     # equal odd lengths
    ],
)
def test_odd_length_edges(lens):
    payloads = [_latents(sum(lens) + i, n) for i, n in enumerate(lens)]
    ck, mk = eops.encode_payloads(payloads, use_pallas=True)
    cr, mr = eops.encode_payloads(payloads, use_pallas=False)
    assert mk == mr
    for a, b in zip(ck, cr):
        assert _eq(a, b)
    back = eops.decode_payloads(ck, mk)
    for got, want in zip(back, payloads):
        assert _eq(got, want)


def test_degenerate_distributions_roundtrip():
    """Single-symbol (freq == PROB_SCALE), all-zero, and uniform-random
    (incompressible) payloads must all survive the coder exactly."""
    payloads = [
        jnp.full((4096,), -5, jnp.int8),
        jnp.zeros((300,), jnp.int8),
        jnp.asarray(
            np.random.default_rng(0).integers(-128, 128, 3000), jnp.int8
        ),
    ]
    comp, metas = eops.encode_payloads(payloads)
    comp_r, metas_r = eops.encode_payloads(payloads, use_pallas=False)
    assert metas == metas_r
    for a, b in zip(comp, comp_r):
        assert _eq(a, b)
    back = eops.decode_payloads(comp, metas)
    for got, want in zip(back, payloads):
        assert _eq(got, want)
    # single-symbol shard never renormalizes: stream is exactly the header
    assert metas[0]["n_comp"] == eops.HEADER_BYTES


def test_freq_table_exact_invariants():
    rng = np.random.default_rng(2)
    for counts in [
        rng.integers(0, 1000, 256),
        np.eye(256, dtype=np.int64)[3] * 10**9,      # huge single-symbol count
        np.full(256, 1 << 22),                       # huge uniform (downscale)
        np.zeros(256),                               # empty payload
    ]:
        f = np.asarray(build_freq_table(jnp.asarray(counts, jnp.int32)))
        assert f.sum() == PROB_SCALE, counts
        assert (f[counts > 0] >= 1).all()
        assert (f >= 0).all()


def test_compression_ratio_on_latents():
    """Acceptance shape: >= 2x on realistically peaked int8 latent codes."""
    payloads = [_latents(i, 65536) for i in range(4)]
    comp, metas = eops.encode_payloads(payloads)
    ratio = sum(m["n_raw"] for m in metas) / sum(m["n_comp"] for m in metas)
    assert ratio >= 2.0, ratio
    back = eops.decode_payloads(comp, metas)
    for got, want in zip(back, payloads):
        assert _eq(got, want)


def test_stream_is_self_contained():
    """Tables/lengths/states travel in the stream header; metas carry only
    lengths + row count + stream version (what the archive manifest
    stores)."""
    payloads = [_latents(0, 5000)]
    comp, metas = eops.encode_payloads(payloads)
    assert set(metas[0]) == {"codec", "version", "n_raw", "n_comp", "rows"}
    assert metas[0]["version"] == STREAM_VERSION == 1
    assert int(comp[0].shape[0]) == metas[0]["n_comp"] >= eops.HEADER_BYTES


def test_division_strategies_bit_identical():
    """All three per-symbol division strategies — hardware udiv, the
    error-repaired f32 reciprocal (what the Pallas kernel runs; Mosaic has
    no integer divide), and the Granlund-Montgomery mulhi — must produce
    identical streams bit-for-bit, in the oracle and in the kernel."""
    payloads = [_latents(3, 9000), _latents(4, 100)]
    ref_c, ref_m = eops.encode_payloads(payloads)
    for d in ("divide", "rcp32", "reciprocal"):
        c, m = eops.encode_payloads(payloads, use_pallas=False, division=d)
        assert m == ref_m, d
        for a, b in zip(c, ref_c):
            assert _eq(a, b), d


def test_row_and_tile_schedules_bit_identical(monkeypatch):
    """The row-tile grid schedule (rows per grid step: a whole bucket in
    one step, or 8-row tiles that carry the lane states, and the decode's
    stream window, from step to step) is pure scheduling — outputs must be
    identical."""
    from repro.kernels.entropy import rans

    n = 5000
    T = eops.rows_for(n)
    flat = _latents(9, n)
    codes = jnp.stack([jnp.pad(flat, (0, T * N_LANES - n)).reshape(T, N_LANES)])
    nv = jnp.asarray([[n]], jnp.int32)
    comp, metas = eops.encode_payloads([flat])
    stream, freq, states = eops._parse_streams(
        jnp.stack([jnp.pad(jnp.asarray(comp[0]).astype(jnp.uint8),
                           (0, (metas[0]["n_comp"] % 2)))])
    )

    def run():
        enc = rans.rans_encode_pallas(codes, nv, interpret=True)
        dec = rans.rans_decode_pallas(
            stream, freq, states, nv, rows=T, interpret=True
        )
        return enc, dec

    (enc_a, dec_a) = run()
    monkeypatch.setattr(rans, "_TILE_ELEMS", 8 * N_LANES)
    monkeypatch.setattr(rans, "_DEC_TILE", 8)
    (enc_b, dec_b) = run()
    for a, b in zip(enc_a, enc_b):
        assert _eq(a, b)
    # decode twin: both schedules reproduce the payload from the packed
    # version-1 stream
    for dec in (dec_a, dec_b):
        assert _eq(dec[0].reshape(-1)[:n], flat)


def _bucket_stripe(T, seed):
    """A stripe pinning bucket T's n_valid boundaries: an exactly-full
    shard, one byte short (last lane of the last row pads), the first
    byte of the last row, a sub-header tiny shard, and an incompressible
    raw-skip rider."""
    rng = np.random.default_rng(seed)
    n_full = T * N_LANES
    return [
        _latents(seed, n_full),
        _latents(seed + 1, n_full - 1),
        _latents(seed + 2, (T - 1) * N_LANES + 1),
        _latents(seed + 3, 5),
        jnp.asarray(rng.integers(-128, 128, n_full, dtype=np.int8)),
    ]


@pytest.mark.parametrize("T", [8, 16, 32, 64, 128, 256, 512])
def test_two_phase_bit_identity_every_bucket(T):
    """The batched two-phase encode (phase 1: full emission schedule as
    tensor ops; phase 2: one compaction pass) matches the staged scan
    oracle bit for bit in EVERY pow2 row bucket, with raw-skip shards and
    n_valid boundary rows riding in the same stripe."""
    payloads = _bucket_stripe(T, seed=40 + T)
    ck, mk = eops.encode_payloads(payloads, use_pallas=True)
    cr, mr = eops.encode_payloads(payloads, use_pallas=False)
    assert mk == mr
    assert all(m["rows"] == T for m in mk)
    assert mk[3]["raw"] and mk[4]["raw"]  # tiny + incompressible skip
    if T >= 32:  # smaller buckets can't amortize the 1536-byte header
        assert not mk[0].get("raw")
    for a, b in zip(ck, cr):
        assert _eq(a, b)
    back = eops.decode_payloads(ck, mk)
    for got, want in zip(back, payloads):
        assert _eq(got, want)


def test_two_phase_histogram_impls_bit_identical():
    """The MXU histogram kernel (one-hot nibble matmuls over row tiles)
    counts exactly what the jnp one-hot oracle counts, padding correction
    included — so both feed the coder identical tables."""
    from repro.kernels.entropy.rans import _histogram, byte_histogram

    payloads = _bucket_stripe(32, seed=77)
    T = 32
    codes = jnp.stack(
        [jnp.pad(p, (0, T * N_LANES - p.shape[0])).reshape(T, N_LANES)
         for p in payloads]
    )
    nv = jnp.asarray([p.shape[0] for p in payloads], jnp.int32)
    got = byte_histogram(codes, nv.reshape(-1, 1), interpret=True)
    want = jax.vmap(_histogram)((codes.astype(jnp.int32) & 0xFF), nv)
    assert _eq(got, want)
    assert _eq(got.sum(axis=1), nv)


@pytest.mark.parametrize("D", [1, 2, 4, 8])
@pytest.mark.parametrize("T", [8, 64])
def test_two_phase_sharded_buckets_bit_identical(T, D):
    """The shard_map'd twins inherit the two-phase schedule unchanged:
    mesh {1,2,4,8} encodes of boundary-row stripes match the single-device
    streams byte-for-byte and roundtrip."""
    if D > jax.device_count():
        pytest.skip(f"need {D} devices, have {jax.device_count()}")
    from repro.distributed.archival import (
        entropy_decode_sharded,
        entropy_encode_sharded,
    )

    payloads = _bucket_stripe(T, seed=60 + T)
    single_c, single_m = eops.encode_payloads(payloads)
    mesh = Mesh(np.array(jax.devices()[:D]), ("data",))
    c, m = entropy_encode_sharded(payloads, mesh=mesh)
    assert m == single_m
    for a, b in zip(c, single_c):
        assert _eq(a, b)
    back = entropy_decode_sharded(c, m, mesh=mesh)
    for got, want in zip(back, payloads):
        assert _eq(got, want)


def test_golden_v0_stream_decodes():
    """A PR-4-era version-0 (128-lane, lane-major words) stream captured at
    the old HEAD must keep decoding after the lane-group format change —
    on both the kernel and the staged-reference paths, and sharded."""
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
    assert "version" not in g["metas"][0]  # recorded before the field existed
    assert g["metas"][1].get("raw") is True  # raw-skip shard rides along
    for use_pallas in (True, False):
        back = eops.decode_payloads(comps, g["metas"], use_pallas=use_pallas)
        for got, want in zip(back, wants):
            assert np.array_equal(np.asarray(got), want)
    # re-encoding the same payload now yields a version-1 stream of the
    # same compressed size (the format change moves words, never adds any)
    comp1, metas1 = eops.encode_payloads(
        [jnp.asarray(w) for w in wants]
    )
    assert metas1[0]["version"] == STREAM_VERSION
    assert metas1[0]["n_comp"] == g["metas"][0]["n_comp"]
    from repro.distributed.archival import entropy_decode_sharded

    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    back = entropy_decode_sharded(comps, g["metas"], mesh=mesh)
    for got, want in zip(back, wants):
        assert np.array_equal(np.asarray(got), want)


def test_corrupt_meta_rejected():
    comp, metas = eops.encode_payloads([_latents(1, 1000)])
    bad = [dict(metas[0], n_comp=metas[0]["n_comp"] + 4)]
    with pytest.raises(ValueError, match="manifest says"):
        eops.decode_payloads(comp, bad)
    with pytest.raises(ValueError, match="share one padded row count"):
        eops.decode_payloads(
            comp + comp, [metas[0], dict(metas[0], rows=metas[0]["rows"] * 2)]
        )


# ------------------------------------------------------------ pipeline chain
def _clip(key, t=3, b=1, h=32, w=32):
    f = jax.random.uniform(key, (t, b, h, w, 3))
    k = jnp.ones((3, 3)) / 9.0
    from jax import lax

    f = lax.conv_general_dilated(
        f.reshape(t * b, h, w, 3),
        jnp.tile(k[:, :, None, None], (1, 1, 1, 3)).astype(f.dtype),
        (1, 1),
        "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=3,
    ).reshape(t, b, h, w, 3)
    return jnp.clip(f, 0.0, 1.0)


def test_archive_stripe_rans_roundtrip_and_bit_identity():
    """Acceptance: codec_name="rans" stripes roundtrip bit-exactly and the
    Pallas/staged-reference paths agree on every stored byte."""
    cfg = ArchiveConfig(codec=CFG, codec_name="rans")
    params = init_codec(jax.random.PRNGKey(0), CFG)
    pub, sec = rlwe.keygen(jax.random.PRNGKey(1))
    frames = [_clip(jax.random.PRNGKey(30 + i)) for i in range(3)]
    key = jax.random.PRNGKey(7)
    fused, rec = archive_stripe(params, pub, frames, key, cfg, use_pallas=True)
    staged, _ = archive_stripe(params, pub, frames, key, cfg, use_pallas=False)
    for bf, bs in zip(fused.blocks, staged.blocks):
        assert _eq(bf.sealed.body, bs.sealed.body)
        assert bf.manifest["entropy"] == bs.manifest["entropy"]
        assert bf.manifest["entropy"]["codec"] == "rans"
    assert _eq(fused.parity["p"], staged.parity["p"])
    assert _eq(fused.parity["q"], staged.parity["q"])
    for use_pallas in (True, False):
        out = restore_stripe(params, sec, fused, cfg, use_pallas=use_pallas)
        for got, want in zip(out, rec):
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(want), atol=1e-5
            )


def test_archive_stripe_host_codec_fallback():
    from repro.common import compress as host_entropy

    cfg = ArchiveConfig(codec=CFG, codec_name=host_entropy.CODEC_NAME)
    params = init_codec(jax.random.PRNGKey(0), CFG)
    pub, sec = rlwe.keygen(jax.random.PRNGKey(1))
    frames = [_clip(jax.random.PRNGKey(50 + i)) for i in range(2)]
    stripe, rec = archive_stripe(params, pub, frames, jax.random.PRNGKey(9), cfg)
    assert stripe.blocks[0].manifest["entropy"]["codec"] == host_entropy.CODEC_NAME
    out = restore_stripe(params, sec, stripe, cfg)
    for got, want in zip(out, rec):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_zlib_codec_always_available():
    """zlib is stdlib: a codec_name="zlib" stripe must write and restore on
    every host, whatever compressor the host prefers."""
    cfg = ArchiveConfig(codec=CFG, codec_name="zlib")
    params = init_codec(jax.random.PRNGKey(0), CFG)
    pub, sec = rlwe.keygen(jax.random.PRNGKey(1))
    stripe, rec = archive_stripe(
        params, pub, [_clip(jax.random.PRNGKey(61))], jax.random.PRNGKey(5), cfg
    )
    assert stripe.blocks[0].manifest["entropy"]["codec"] == "zlib"
    out = restore_stripe(params, sec, stripe, cfg)
    np.testing.assert_allclose(np.asarray(out[0]), np.asarray(rec[0]), atol=1e-5)


def test_missing_zstd_raises():
    from repro.common import compress as host_entropy

    if host_entropy.HAVE_ZSTD:
        pytest.skip("zstandard installed; nothing to be missing")
    cfg = ArchiveConfig(codec=CFG, codec_name="zstd")
    params = init_codec(jax.random.PRNGKey(0), CFG)
    pub, _ = rlwe.keygen(jax.random.PRNGKey(1))
    with pytest.raises(ValueError, match="requires the zstandard"):
        archive_stripe(
            params, pub, [_clip(jax.random.PRNGKey(60))],
            jax.random.PRNGKey(3), cfg,
        )


def test_restore_dispatches_on_manifest_not_cfg():
    """What was written wins: a rans stripe restores even if the caller's
    cfg says a host codec (and vice versa the manifest drives decode)."""
    cfg = ArchiveConfig(codec=CFG, codec_name="rans")
    params = init_codec(jax.random.PRNGKey(0), CFG)
    pub, sec = rlwe.keygen(jax.random.PRNGKey(1))
    stripe, rec = archive_stripe(
        params, pub, [_clip(jax.random.PRNGKey(70))], jax.random.PRNGKey(4), cfg
    )
    out = restore_stripe(
        params, sec, stripe, cfg._replace(codec_name="none")
    )
    np.testing.assert_allclose(np.asarray(out[0]), np.asarray(rec[0]), atol=1e-5)


# ------------------------------------------------------- checkpoint chaining
def test_checkpoint_codec_dispatch(tmp_path):
    """Checkpoints default to the on-device coder; the host codec stays a
    working fallback; an unavailable host codec fails loudly at save."""
    from repro.common import compress as host_entropy
    from repro.train.checkpoint import (
        CheckpointError,
        load_checkpoint,
        save_checkpoint,
    )

    state = {
        "w": jax.random.normal(jax.random.PRNGKey(0), (64, 64)),
        "step": jnp.asarray(3, jnp.int32),
    }
    meta = save_checkpoint(str(tmp_path), 3, state)  # codec_name="rans"
    assert meta["codec"] == "rans"
    assert [m["codec"] for m in meta["entropy"]] == ["rans"] * meta["n_shards"]
    _, back = load_checkpoint(str(tmp_path), state, 3)
    assert _eq(back["w"], state["w"])

    # zlib is stdlib: always a valid fallback, whatever the host prefers
    meta_h = save_checkpoint(str(tmp_path / "host"), 3, state, codec_name="zlib")
    assert meta_h["codec"] == "zlib"
    _, back_h = load_checkpoint(str(tmp_path / "host"), state, 3)
    assert _eq(back_h["w"], state["w"])

    if not host_entropy.HAVE_ZSTD:
        with pytest.raises(CheckpointError, match="host entropy codec"):
            save_checkpoint(str(tmp_path / "bad"), 3, state, codec_name="zstd")


# ------------------------------------------------------------- sharded coder
@pytest.mark.parametrize("D", [1, 2, 4, 8])
def test_sharded_coder_bit_identical(D):
    if D > jax.device_count():
        pytest.skip(f"need {D} devices, have {jax.device_count()}")
    from repro.distributed.archival import (
        entropy_decode_sharded,
        entropy_encode_sharded,
    )

    payloads = [
        _latents(i, n) for i, n in enumerate([5000, 4093, 4096, 2500, 9000])
    ]  # S=5: exercises dummy-shard padding for D in {2, 4, 8}
    single_c, single_m = eops.encode_payloads(payloads)
    mesh = Mesh(np.array(jax.devices()[:D]), ("data",))
    c, m = entropy_encode_sharded(payloads, mesh=mesh)
    assert m == single_m
    for a, b in zip(c, single_c):
        assert _eq(a, b)
    back = entropy_decode_sharded(c, m, mesh=mesh)
    for got, want in zip(back, payloads):
        assert _eq(got, want)


@pytest.mark.parametrize("D", [2, 8])
def test_archive_stripe_sharded_rans(D):
    """Acceptance: the 8-host-device sharded path roundtrips codec_name="rans"
    stripes bit-exactly and matches the single-device archive byte-for-byte."""
    if D > jax.device_count():
        pytest.skip(f"need {D} devices, have {jax.device_count()}")
    from repro.distributed.archival import (
        archive_stripe_sharded,
        restore_stripe_sharded,
    )

    cfg = ArchiveConfig(codec=CFG, codec_name="rans")
    params = init_codec(jax.random.PRNGKey(0), CFG)
    pub, sec = rlwe.keygen(jax.random.PRNGKey(1))
    frames = [_clip(jax.random.PRNGKey(80 + i)) for i in range(3)]
    key = jax.random.PRNGKey(11)
    mesh = Mesh(np.array(jax.devices()[:D]), ("data",))
    sharded, rec = archive_stripe_sharded(
        params, pub, frames, key, cfg, mesh=mesh
    )
    single, _ = archive_stripe(params, pub, frames, key, cfg)
    for bs, b1 in zip(sharded.blocks, single.blocks):
        assert _eq(bs.sealed.body, b1.sealed.body)
        assert bs.manifest["entropy"] == b1.manifest["entropy"]
    assert _eq(sharded.parity["p"], single.parity["p"])
    assert _eq(sharded.parity["q"], single.parity["q"])
    out = restore_stripe_sharded(params, sec, sharded, cfg, mesh=mesh)
    for got, want in zip(out, rec):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
