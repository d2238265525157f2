"""Hybrid archival encryption: R-LWE KEM + ChaCha20 bulk layer.

This is the quantum-safe archival path of Salient Store: every archived block
is encrypted under a fresh session key encapsulated with the lattice KEM, so
the store-now-decrypt-later adversary faces the R-LWE problem, while the bulk
bytes only pay a stream-cipher XOR (vectorized on the VPU, near-memory on the
"CSD" shard).  The design is programmable per the paper's requirement —
session keys rotate per block / per epoch by construction.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.crypto import rlwe
from repro.core.crypto.chacha import xor_stream

__all__ = [
    "SealedBlock",
    "SessionMaterial",
    "encapsulate_session",
    "encapsulate_sessions",
    "seal",
    "unseal",
    "bytes_to_u32",
    "u32_to_bytes",
]


class SealedBlock(NamedTuple):
    kem_c1: jax.Array  # (1, n) int32
    kem_c2: jax.Array  # (1, n) int32
    nonce: jax.Array  # (3,) uint32
    body: jax.Array  # uint32 payload, same shape as the input
    n_valid_u32: int  # logical length (payload may be padded by callers)


def bytes_to_u32(data: bytes) -> jax.Array:
    """Little-endian pack, zero-padded to a multiple of 4 bytes."""
    import numpy as np

    pad = (-len(data)) % 4
    buf = np.frombuffer(data + b"\0" * pad, dtype="<u4")
    return jnp.asarray(buf)


def u32_to_bytes(words: jax.Array, n_bytes: int) -> bytes:
    import numpy as np

    return np.asarray(words).astype("<u4").tobytes()[:n_bytes]


class SessionMaterial(NamedTuple):
    """One shard's bulk-encryption material: KEM ciphertext + symmetric key."""

    kem_c1: jax.Array  # (1, n) int32
    kem_c2: jax.Array  # (1, n) int32
    session: jax.Array  # (8,) uint32 ChaCha key (never stored)
    nonce: jax.Array  # (3,) uint32


def _split_session_key(key: jax.Array):
    """A shard key's KEM key and its (3,) uint32 nonce."""
    k_kem, k_nonce = jax.random.split(key)
    nonce = jax.random.randint(
        k_nonce, (3,), 0, jnp.iinfo(jnp.int32).max, dtype=jnp.int32
    ).astype(jnp.uint32)
    return k_kem, nonce


def encapsulate_session(
    pub: rlwe.PublicKey,
    key: jax.Array,
    params: rlwe.RLWEParams = rlwe.RLWEParams(),
) -> SessionMaterial:
    """Fresh session key + nonce under the lattice KEM.

    Split out of ``seal`` so batched paths can hand all S session keys to
    one kernel launch for the bulk bytes; the fused stripe seal makes them
    with ``encapsulate_sessions``.
    """
    k_kem, nonce = _split_session_key(key)
    ct, session = rlwe.kem_encapsulate(pub, k_kem, params)
    return SessionMaterial(ct.c1, ct.c2, session, nonce)


def encapsulate_sessions(
    pub: rlwe.PublicKey,
    keys: jax.Array,
    params: rlwe.RLWEParams = rlwe.RLWEParams(),
) -> SessionMaterial:
    """``encapsulate_session`` for each of V stacked keys, bit for bit:
    fields of V rows (``kem_c1``/``kem_c2`` (V, 1, n), ``session`` (V, 8),
    ``nonce`` (V, 3)).  Under ``jit`` the V encapsulations are one device
    program rather than dozens of eager ones each."""
    k_kem, nonce = jax.vmap(_split_session_key)(keys)
    ct, session = rlwe.kem_encapsulate_many(pub, k_kem, params)
    return SessionMaterial(ct.c1, ct.c2, session, nonce)


def seal(
    pub: rlwe.PublicKey,
    payload_u32: jax.Array,
    key: jax.Array,
    params: rlwe.RLWEParams = rlwe.RLWEParams(),
) -> SealedBlock:
    """Encrypt a uint32 payload under a fresh encapsulated session key."""
    sm = encapsulate_session(pub, key, params)
    body = xor_stream(sm.session, sm.nonce, payload_u32)
    return SealedBlock(sm.kem_c1, sm.kem_c2, sm.nonce, body, int(payload_u32.size))


def unseal(
    s: jax.Array,
    block: SealedBlock,
    params: rlwe.RLWEParams = rlwe.RLWEParams(),
) -> jax.Array:
    session = rlwe.kem_decapsulate(
        s, rlwe.Ciphertext(block.kem_c1, block.kem_c2), params
    )
    return xor_stream(session, block.nonce, block.body)
