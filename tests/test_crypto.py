"""Crypto layer tests: polymul kernel vs oracle, R-LWE roundtrips, ChaCha20."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from _hyp import given, settings, st

from repro.kernels.polymul import ref as pref
from repro.kernels.polymul.ops import polymul, polymul_fixed
from repro.kernels.polymul.polymul import negacyclic_matmul_pallas
from repro.core.crypto import rlwe
from repro.core.crypto.chacha import chacha20_block, keystream, xor_stream
from repro.core.crypto.hybrid import (
    bytes_to_u32,
    encapsulate_session,
    encapsulate_sessions,
    seal,
    u32_to_bytes,
    unseal,
)
from repro.core.crypto.rsa_baseline import (
    rsa_decrypt_blocks,
    rsa_encrypt_blocks,
    rsa_keypair,
)

Q = 12289
N = 256


def np_negacyclic(a, b, q):
    """Independent numpy int64 oracle."""
    n = a.shape[-1]
    full = np.zeros(b.shape[:-1] + (2 * n,), dtype=np.int64)
    for i in range(n):
        full[..., i : i + n] += a[..., i, None].astype(np.int64) * b.astype(np.int64)
    return ((full[..., :n] - full[..., n : 2 * n]) % q).astype(np.int32)


# ---------------------------------------------------------------- polymul
@pytest.mark.parametrize("n", [8, 64, 128, 256, 512])
@pytest.mark.parametrize("batch", [1, 3, 256])
def test_polymul_kernel_matches_oracle_shapes(n, batch):
    rng = np.random.default_rng(n * 1000 + batch)
    a = rng.integers(0, Q, size=(n,), dtype=np.int32)
    b = rng.integers(0, Q, size=(batch, n), dtype=np.int32)
    expect = np_negacyclic(a, b, Q)
    got = np.asarray(polymul_fixed(jnp.asarray(a), jnp.asarray(b), Q))
    np.testing.assert_array_equal(got, expect)


@pytest.mark.parametrize("q", [257, 3329, 7681, 12289])
def test_polymul_kernel_moduli(q):
    rng = np.random.default_rng(q)
    a = rng.integers(0, q, size=(N,), dtype=np.int32)
    b = rng.integers(0, q, size=(4, N), dtype=np.int32)
    expect = np_negacyclic(a, b, q)
    got = np.asarray(polymul_fixed(jnp.asarray(a), jnp.asarray(b), q))
    np.testing.assert_array_equal(got, expect)


def test_polymul_large_q_falls_back_to_ref():
    q = 40961  # > 2^14: int8 limb path invalid, wrapper must fall back
    rng = np.random.default_rng(1)
    a = rng.integers(0, q, size=(N,), dtype=np.int32)
    b = rng.integers(0, q, size=(2, N), dtype=np.int32)
    expect = np_negacyclic(a, b, q)
    got = np.asarray(polymul_fixed(jnp.asarray(a), jnp.asarray(b), q))
    np.testing.assert_array_equal(got, expect)


def test_polymul_kernel_rejects_large_q():
    with pytest.raises(ValueError):
        negacyclic_matmul_pallas(
            jnp.zeros((N, N), jnp.int32), jnp.zeros((N, 8), jnp.int32), 1 << 14
        )


def test_polymul_general_batched():
    rng = np.random.default_rng(7)
    a = rng.integers(0, Q, size=(5, N), dtype=np.int32)
    b = rng.integers(0, Q, size=(5, N), dtype=np.int32)
    expect = np_negacyclic(a, b, Q)
    got = np.asarray(polymul(jnp.asarray(a), jnp.asarray(b), Q))
    np.testing.assert_array_equal(got, expect)


@settings(max_examples=25, deadline=None)
@given(
    seed=st.integers(0, 2**31 - 1),
    n=st.sampled_from([16, 64, 256]),
)
def test_polymul_ring_properties(seed, n):
    """Commutativity, x^n == -1, and distributivity in the quotient ring."""
    rng = np.random.default_rng(seed)
    a = jnp.asarray(rng.integers(0, Q, size=(n,), dtype=np.int32))
    b = jnp.asarray(rng.integers(0, Q, size=(n,), dtype=np.int32))
    c = jnp.asarray(rng.integers(0, Q, size=(n,), dtype=np.int32))
    ab = polymul(a, b, Q)
    ba = polymul(b, a, Q)
    np.testing.assert_array_equal(np.asarray(ab), np.asarray(ba))
    # multiplying by x n times negates: x^n = -1 in Z_q[x]/(x^n+1)
    x = jnp.zeros((n,), jnp.int32).at[1].set(1)
    out = a
    for _ in range(n):
        out = polymul(out, x, Q)
    np.testing.assert_array_equal(np.asarray(out), np.asarray((Q - a) % Q))
    # distributivity
    lhs = polymul(a, jnp.mod(b + c, Q), Q)
    rhs = jnp.mod(ab + polymul(a, c, Q), Q)
    np.testing.assert_array_equal(np.asarray(lhs), np.asarray(rhs))


# ---------------------------------------------------------------- R-LWE
def test_rlwe_roundtrip_batch():
    params = rlwe.RLWEParams()
    key = jax.random.PRNGKey(0)
    kk, km, ke = jax.random.split(key, 3)
    pub, s = rlwe.keygen(kk, params)
    m = jax.random.bernoulli(km, 0.5, (32, params.n)).astype(jnp.int32)
    ct = rlwe.encrypt_bits(pub, m, ke, params)
    dec = rlwe.decrypt_bits(s, ct, params)
    np.testing.assert_array_equal(np.asarray(dec), np.asarray(m))


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 2**31 - 1))
def test_rlwe_roundtrip_property(seed):
    params = rlwe.RLWEParams()
    key = jax.random.PRNGKey(seed)
    kk, km, ke = jax.random.split(key, 3)
    pub, s = rlwe.keygen(kk, params)
    m = jax.random.bernoulli(km, 0.5, (4, params.n)).astype(jnp.int32)
    ct = rlwe.encrypt_bits(pub, m, ke, params)
    dec = rlwe.decrypt_bits(s, ct, params)
    np.testing.assert_array_equal(np.asarray(dec), np.asarray(m))


def test_rlwe_ciphertext_differs_from_message():
    params = rlwe.RLWEParams()
    pub, s = rlwe.keygen(jax.random.PRNGKey(3), params)
    m = jnp.ones((1, params.n), jnp.int32)
    ct = rlwe.encrypt_bits(pub, m, jax.random.PRNGKey(4), params)
    # ciphertext coefficients should look uniform, not like the message
    assert np.asarray(ct.c2).std() > 1000


def test_kem_roundtrip():
    params = rlwe.RLWEParams()
    pub, s = rlwe.keygen(jax.random.PRNGKey(5), params)
    ct, shared = rlwe.kem_encapsulate(pub, jax.random.PRNGKey(6), params)
    shared2 = rlwe.kem_decapsulate(s, ct, params)
    np.testing.assert_array_equal(np.asarray(shared), np.asarray(shared2))
    assert shared.shape == (8,) and shared.dtype == jnp.uint32


def test_pack_unpack_bits():
    bits = jax.random.bernoulli(jax.random.PRNGKey(1), 0.5, (256,)).astype(jnp.int32)
    words = rlwe.pack_bits_u32(bits)
    back = rlwe.unpack_bits_u32(words, 256)
    np.testing.assert_array_equal(np.asarray(back), np.asarray(bits))


# ---------------------------------------------------------------- ChaCha20
def test_chacha_rfc8439_block():
    """RFC 8439 §2.3.2 test vector."""
    key = jnp.asarray(
        np.frombuffer(bytes(range(32)), dtype="<u4").copy(), jnp.uint32
    )
    nonce = jnp.asarray(
        np.frombuffer(bytes.fromhex("000000090000004a00000000"), dtype="<u4").copy(),
        jnp.uint32,
    )
    out = np.asarray(chacha20_block(key, jnp.uint32(1), nonce))[0]
    expect = np.array(
        [
            0xE4E7F110, 0x15593BD1, 0x1FDD0F50, 0xC47120A3,
            0xC7F4D1C7, 0x0368C033, 0x9AAA2204, 0x4E6CD4C3,
            0x466482D2, 0x09AA9F07, 0x05D7C214, 0xA2028BD9,
            0xD19C12B5, 0xB94E16DE, 0xE883D0CB, 0x4E3C50A2,
        ],
        dtype=np.uint32,
    )
    np.testing.assert_array_equal(out, expect)


def test_chacha_involution_and_determinism():
    key = jax.random.randint(jax.random.PRNGKey(0), (8,), 0, 2**31 - 1).astype(
        jnp.uint32
    )
    nonce = jnp.asarray([1, 2, 3], jnp.uint32)
    data = jax.random.randint(jax.random.PRNGKey(1), (1000,), 0, 2**31 - 1).astype(
        jnp.uint32
    )
    enc = xor_stream(key, nonce, data)
    dec = xor_stream(key, nonce, enc)
    np.testing.assert_array_equal(np.asarray(dec), np.asarray(data))
    assert not np.array_equal(np.asarray(enc), np.asarray(data))
    # different nonce -> different stream
    enc2 = xor_stream(key, jnp.asarray([9, 9, 9], jnp.uint32), data)
    assert not np.array_equal(np.asarray(enc), np.asarray(enc2))


def test_keystream_single_trace_across_mixed_sizes():
    """xor_stream buckets lengths to powers of two: one jit trace serves a
    whole bucket of mixed GOP sizes instead of retracing per length."""
    if not hasattr(keystream, "_cache_size"):
        pytest.skip("jit cache introspection unavailable")
    key = jnp.arange(8, dtype=jnp.uint32)
    nonce = jnp.ones(3, jnp.uint32)
    keystream._clear_cache()
    outs = {}
    for n in (513, 700, 901, 1024):  # all land in the 1024-word bucket
        data = jnp.arange(n, dtype=jnp.uint32)
        enc = xor_stream(key, nonce, data)
        np.testing.assert_array_equal(
            np.asarray(xor_stream(key, nonce, enc)), np.asarray(data)
        )
        outs[n] = enc
    assert keystream._cache_size() == 1
    # bucketing must not change the stream: same prefix for every length
    np.testing.assert_array_equal(
        np.asarray(outs[513]), np.asarray(outs[1024][:513])
    )


def test_hybrid_seal_mixed_gop_sizes_share_one_trace():
    if not hasattr(keystream, "_cache_size"):
        pytest.skip("jit cache introspection unavailable")
    pub, s = rlwe.keygen(jax.random.PRNGKey(11))
    keystream._clear_cache()
    for i, n_words in enumerate((525, 725, 925, 1024)):  # 1024-word bucket
        words = jnp.arange(n_words, dtype=jnp.uint32)
        block = seal(pub, words, jax.random.PRNGKey(20 + i))
        got = unseal(s, block)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(words))
    assert keystream._cache_size() == 1


def test_chacha_keystream_counter_continuity():
    key = jnp.arange(8, dtype=jnp.uint32)
    nonce = jnp.zeros(3, jnp.uint32)
    full = keystream(key, nonce, 64)
    tail = keystream(key, nonce, 32, counter0=2)
    np.testing.assert_array_equal(np.asarray(full[32:]), np.asarray(tail))


# ---------------------------------------------------------------- hybrid
def test_hybrid_seal_unseal_roundtrip():
    pub, s = rlwe.keygen(jax.random.PRNGKey(7))
    payload = b"salient store archival block" * 33
    words = bytes_to_u32(payload)
    block = seal(pub, words, jax.random.PRNGKey(8))
    got = unseal(s, block)
    assert u32_to_bytes(got, len(payload)) == payload
    assert not np.array_equal(np.asarray(block.body), np.asarray(words))


@settings(max_examples=10, deadline=None)
@given(data=st.binary(min_size=1, max_size=2048), seed=st.integers(0, 2**31 - 1))
def test_hybrid_roundtrip_property(data, seed):
    pub, s = rlwe.keygen(jax.random.PRNGKey(seed))
    words = bytes_to_u32(data)
    block = seal(pub, words, jax.random.PRNGKey(seed + 1))
    got = unseal(s, block)
    assert u32_to_bytes(got, len(data)) == data


# session counts V of one seal dispatch, as shards per stripe: one shard,
# one partial stripe, full and partial stripes mixed (14), one full
# program (16), and more than one program's rows (37, as a drain makes)
KEM_LAYOUTS = [[1], [3], [4, 4, 4, 2], [4, 4, 4, 4], [4] * 9 + [1]]


@pytest.mark.parametrize(
    "layout", KEM_LAYOUTS, ids=lambda l: f"V{sum(l)}"
)
def test_batched_kem_matches_singular(layout):
    """The seal dispatch's batched KEM (``encapsulate_sessions`` and the
    pipeline's chunked program over it) gives, shard for shard, what
    ``encapsulate_session`` gives for ``fold_in(stripe key, shard)``, and
    every shard's ciphertext decapsulates to its session key."""
    from repro.core.archival.pipeline import _encapsulate_stripes

    params = rlwe.RLWEParams()
    pub, s = rlwe.keygen(jax.random.PRNGKey(21), params)
    stripe_keys = [jax.random.fold_in(jax.random.PRNGKey(22), i)
                   for i in range(len(layout))]
    shard_keys = [jax.random.fold_in(k, sh)
                  for k, S in zip(stripe_keys, layout) for sh in range(S)]
    want = [encapsulate_session(pub, k, params) for k in shard_keys]
    rows = encapsulate_sessions(pub, jnp.stack(shard_keys), params)
    per_stripe = _encapsulate_stripes(pub, stripe_keys, layout, params)
    assert [len(ms) for ms in per_stripe] == layout
    flat = [m for ms in per_stripe for m in ms]
    assert len(flat) == len(want) == sum(layout)
    for v, (w, got) in enumerate(zip(want, flat)):
        for name in w._fields:
            a, b = np.asarray(getattr(got, name)), np.asarray(getattr(w, name))
            assert a.dtype == b.dtype and a.shape == b.shape, name
            np.testing.assert_array_equal(a, b, err_msg=name)
            np.testing.assert_array_equal(
                np.asarray(getattr(rows, name)[v]), b, err_msg=name
            )
        opened = rlwe.kem_decapsulate(
            s, rlwe.Ciphertext(got.kem_c1, got.kem_c2), params
        )
        np.testing.assert_array_equal(np.asarray(opened), np.asarray(w.session))


# ---------------------------------------------------------------- RSA baseline
def test_rsa_roundtrip():
    pub, priv = rsa_keypair()
    data = b"store now decrypt later" * 7
    blocks = rsa_encrypt_blocks(data, pub)
    assert rsa_decrypt_blocks(blocks, len(data), priv) == data
