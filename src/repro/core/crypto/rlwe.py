"""Ring-LWE public-key encryption / KEM (Salient Store §4, Alg. 3).

Paper-faithful parameters: ring dimension n = 256 (the HSPM services degree-256
polynomials with 128 MAC lanes), 13-bit modulus q = 12289 (the SDMM packs
13-bit "signed Gaussian" samples), centered-binomial error distribution
(psi_16, sigma ~= 2.83 — the signed-sampling trick of Liu et al. cited by the
paper).  The encryption equation is the paper's ``d = a.b + c`` dataflow:

    keygen:   b_pk = a o s + e
    encrypt:  C1 = a o r + e1,        (Alg. 3 line 4, "utilizing HSPM")
              C2 = b_pk o r + e2 + encode(m)   (line 5, "employing SDMM")
    decrypt:  m  = decode(C2 - C1 o s)

All polynomial products route through the Pallas MXU kernel
(``kernels/polymul``) in the bulk fixed-key layout.

This is a systems reproduction of the paper's accelerator, not an audited
cryptographic implementation (no CCA transform, no constant-time host code).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.kernels.polymul.ops import polymul_fixed

__all__ = [
    "RLWEParams",
    "PublicKey",
    "Ciphertext",
    "keygen",
    "encrypt_bits",
    "decrypt_bits",
    "kem_encapsulate",
    "kem_encapsulate_many",
    "kem_decapsulate",
    "pack_bits_u32",
    "unpack_bits_u32",
]


class RLWEParams(NamedTuple):
    n: int = 256  # ring dimension (x^n + 1)
    q: int = 12289  # 13-bit modulus (NewHope-style, matches paper's samples)
    cbd_k: int = 16  # centered binomial psi_k, sigma = sqrt(k/2)


class PublicKey(NamedTuple):
    a: jax.Array  # (n,) uniform public polynomial
    b: jax.Array  # (n,) a o s + e


class Ciphertext(NamedTuple):
    c1: jax.Array  # (B, n)
    c2: jax.Array  # (B, n)


def _sample_uniform(key, shape, q):
    return jax.random.randint(key, shape, 0, q, dtype=jnp.int32)


def _sample_cbd(key, shape, k, q):
    """Centered binomial psi_k in [0, q) (mod-q representation)."""
    bits = jax.random.bernoulli(key, 0.5, shape + (2 * k,)).astype(jnp.int32)
    e = bits[..., :k].sum(-1) - bits[..., k:].sum(-1)  # in [-k, k]
    return jnp.mod(e, q).astype(jnp.int32)


def keygen(key: jax.Array, params: RLWEParams = RLWEParams()):
    """Returns (PublicKey, secret s)."""
    n, q, k = params
    ka, ks, ke = jax.random.split(key, 3)
    a = _sample_uniform(ka, (n,), q)
    s = _sample_cbd(ks, (n,), k, q)
    e = _sample_cbd(ke, (n,), k, q)
    b = jnp.mod(polymul_fixed(a, s[None, :], q)[0] + e, q)
    return PublicKey(a, b), s


def _encrypt_draw(key: jax.Array, rows: int, params: RLWEParams):
    """The encryption's randomness for ``rows`` messages: r, e1, e2."""
    n, q, k = params
    kr, k1, k2 = jax.random.split(key, 3)
    return tuple(_sample_cbd(kk, (rows, n), k, q) for kk in (kr, k1, k2))


def _encrypt_rows(pub: PublicKey, m_bits, r, e1, e2, params: RLWEParams):
    """C1 = a o r + e1, C2 = b o r + e2 + encode(m), row by row: one
    ``polymul_fixed`` call for each of ``a`` and ``b`` over all rows."""
    q = params.q
    c1 = jnp.mod(polymul_fixed(pub.a, r, q) + e1, q)
    c2 = jnp.mod(
        polymul_fixed(pub.b, r, q) + e2 + m_bits.astype(jnp.int32) * (q // 2), q
    )
    return Ciphertext(c1, c2)


def encrypt_bits(
    pub: PublicKey, m_bits: jax.Array, key: jax.Array, params: RLWEParams = RLWEParams()
) -> Ciphertext:
    """Encrypt a batch of bit-vectors. m_bits: (B, n) in {0, 1}."""
    draw = _encrypt_draw(key, m_bits.shape[0], params)
    return _encrypt_rows(pub, m_bits, *draw, params)


def decrypt_bits(
    s: jax.Array, ct: Ciphertext, params: RLWEParams = RLWEParams()
) -> jax.Array:
    """Decrypt to (B, n) bits."""
    n, q, k = params
    d = jnp.mod(ct.c2 - polymul_fixed(s, ct.c1, q), q)
    # bit = 1 iff d is closer to q/2 than to 0 (mod q)
    return ((d > q // 4) & (d < 3 * q // 4)).astype(jnp.int32)


def pack_bits_u32(bits: jax.Array) -> jax.Array:
    """(..., 32*w) {0,1} -> (..., w) uint32, little-endian bit order."""
    *lead, nb = bits.shape
    assert nb % 32 == 0, nb
    b = bits.reshape(*lead, nb // 32, 32).astype(jnp.uint32)
    weights = (jnp.uint32(1) << jnp.arange(32, dtype=jnp.uint32)).astype(jnp.uint32)
    return (b * weights).sum(-1).astype(jnp.uint32)


def unpack_bits_u32(words: jax.Array, nbits: int) -> jax.Array:
    """(..., w) uint32 -> (..., nbits) {0,1}."""
    shifts = jnp.arange(32, dtype=jnp.uint32)
    bits = (words[..., None] >> shifts) & jnp.uint32(1)
    return bits.reshape(*words.shape[:-1], words.shape[-1] * 32)[..., :nbits].astype(
        jnp.int32
    )


def _kem_draw(key: jax.Array, params: RLWEParams):
    """One encapsulation's randomness: the message bits m and the
    encryption's r, e1, e2, each (1, n)."""
    kb, ke = jax.random.split(key)
    m = jax.random.bernoulli(kb, 0.5, (1, params.n)).astype(jnp.int32)
    return (m,) + _encrypt_draw(ke, 1, params)


def kem_encapsulate(pub: PublicKey, key: jax.Array, params: RLWEParams = RLWEParams()):
    """Returns (Ciphertext, shared_key (8,) uint32 = 256 bits)."""
    m, r, e1, e2 = _kem_draw(key, params)
    return _encrypt_rows(pub, m, r, e1, e2, params), pack_bits_u32(m[0])


def kem_encapsulate_many(
    pub: PublicKey, keys: jax.Array, params: RLWEParams = RLWEParams()
):
    """``kem_encapsulate`` for each of V keys, bit for bit: returns
    (Ciphertext of (V, 1, n) rows, shared keys (V, 8)).  Each key's draw
    is the singular one under ``vmap``; the ring products then run once
    over all V rows."""
    m, r, e1, e2 = (x[:, 0] for x in jax.vmap(
        lambda k: _kem_draw(k, params))(keys))
    ct = _encrypt_rows(pub, m, r, e1, e2, params)
    return Ciphertext(ct.c1[:, None], ct.c2[:, None]), pack_bits_u32(m)


def kem_decapsulate(
    s: jax.Array, ct: Ciphertext, params: RLWEParams = RLWEParams()
) -> jax.Array:
    m = decrypt_bits(s, ct, params)
    return pack_bits_u32(m[0])
