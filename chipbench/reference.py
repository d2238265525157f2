"""The plain reference the benchmark holds the archive to.

An archive's semantics are simple to state, and this module states them
in numpy and plain Python:

* every acknowledged GOP is stored sealed: its stripe shard's body,
  XORed with the ChaCha20 keystream (RFC 8439) of the session key that
  the shard's Ring-LWE KEM ciphertext carries, is an interleaved-rANS
  stream (128 lanes, 12-bit frequency tables, 16-bit renormalisation)
  that decodes to exactly the bytes that were offered.  The secret key
  that opens the KEM is the reference's own (``rlwe_keygen``); the
  program only ever gets its public half;
* each stripe's RAID-6 parity is the XOR (P) and the generator-2
  Reed-Solomon sum over GF(2^8), polynomial 0x11D (Q), of its stored
  shard bodies, as Linux md RAID-6 defines them;
* every acknowledged stripe has its catalog record in the journal.

It imports nothing of the program and takes nothing from it but what it
stored: the bodies, KEM ciphertexts, nonces and parity a stripe holds,
and the journal directory's files.
"""

from __future__ import annotations

import json
import os
from typing import Dict, List, Optional, Tuple

import numpy as np

__all__ = ["xtime", "raid6_parity", "parity_mismatch", "journal_gops",
           "rlwe_keygen", "kem_decapsulate", "chacha20_keystream",
           "rans_decode", "open_bodies"]


def xtime(x: np.ndarray) -> np.ndarray:
    """Multiply uint8 bytes by 2 in GF(2^8) with polynomial 0x11D."""
    x = x.astype(np.uint8)
    hi = (x & 0x80) != 0
    return ((x << 1) & 0xFF).astype(np.uint8) ^ np.where(hi, 0x1D, 0).astype(
        np.uint8)


def raid6_parity(bodies: List[np.ndarray], nbytes: int
                 ) -> Tuple[np.ndarray, np.ndarray]:
    """(P, Q) over the shards' bytes, each zero-padded to ``nbytes``:
    P = XOR of D_s, Q = sum over s of 2^s * D_s (Horner from the last
    shard down: Q = (...(D_{S-1} * 2 + D_{S-2}) * 2 ...) + D_0)."""
    padded = []
    for b in bodies:
        b = np.asarray(b).reshape(-1).view(np.uint8)
        if b.size > nbytes:
            raise ValueError(f"body of {b.size} B exceeds the parity's {nbytes}")
        row = np.zeros(nbytes, np.uint8)
        row[: b.size] = b
        padded.append(row)
    p = np.zeros(nbytes, np.uint8)
    q = np.zeros(nbytes, np.uint8)
    for row in padded:
        p ^= row
    for row in reversed(padded):
        q = xtime(q) ^ row
    return p, q


def parity_mismatch(bodies: List[np.ndarray], stored: Optional[Dict],
                    mode: str) -> int:
    """1 when the stripe's stored parity is not the reference's (or is
    missing parts the configuration's ``mode`` promises), else 0."""
    if mode not in ("raid5", "raid6"):
        raise ValueError(f"unknown parity mode {mode!r}")
    if stored is None or "p" not in stored:
        return 1
    if mode == "raid6" and "q" not in stored:
        return 1
    p_st = np.asarray(stored["p"]).reshape(-1).view(np.uint8)
    p, q = raid6_parity(bodies, p_st.size)
    if not np.array_equal(p, p_st):
        return 1
    if mode == "raid6":
        q_st = np.asarray(stored["q"]).reshape(-1).view(np.uint8)
        if not np.array_equal(q, q_st):
            return 1
    return 0


def journal_gops(root: str) -> Dict[str, List[Tuple[int, float, int]]]:
    """The catalog records the journal directory holds, read as plain
    files: ``{stripe id: [(stream, novelty, payload bytes) per GOP]}`` for
    every record line whose payload file exists with the committed length.
    Novelty is rounded to 9 decimals, so a GOP is named the same way from
    the traffic's side."""
    path = os.path.join(root, "journal.jsonl")
    out: Dict[str, List[Tuple[int, float, int]]] = {}
    if not os.path.exists(path):
        return out
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            rec = json.loads(line)
            name = rec.get("name", "")
            if not (name.startswith("catalog_") and name.endswith(".json")):
                continue
            body = os.path.join(root, name)
            if not os.path.exists(body) or os.path.getsize(body) != rec["bytes"]:
                continue
            with open(body) as bf:
                gops = json.load(bf)
            out[name[len("catalog_"):-len(".json")]] = [
                (int(r["stream_id"]), round(float(r["novelty"]), 9),
                 int(r["n_i8"]))
                for r in gops
            ]
    return out


# ---------------------------------------------------------------- sealing
def _cbd(rng: np.random.Generator, shape, k: int) -> np.ndarray:
    """Centered binomial psi_k samples in [-k, k]."""
    bits = rng.integers(0, 2, size=tuple(shape) + (2 * k,), dtype=np.int64)
    return bits[..., :k].sum(-1) - bits[..., k:].sum(-1)


def negacyclic_mul(a: np.ndarray, b: np.ndarray, q: int) -> np.ndarray:
    """a * b in Z_q[x] / (x^n + 1)."""
    n = a.shape[-1]
    full = np.convolve(np.asarray(a, np.int64) % q, np.asarray(b, np.int64) % q)
    out = full[:n].copy()
    out[: n - 1] -= full[n:]
    return out % q


def rlwe_keygen(seed: int, n: int, q: int, k: int
                ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """A Ring-LWE key pair from the seed: public (a, b = a*s + e) and
    secret s, each (n,) int64 in [0, q); s and e centered binomial psi_k."""
    rng = np.random.default_rng([int(seed), 0x41E7])
    a = rng.integers(0, q, size=n, dtype=np.int64)
    s = _cbd(rng, (n,), k) % q
    e = _cbd(rng, (n,), k) % q
    return a, (negacyclic_mul(a, s, q) + e) % q, s


def kem_decapsulate(s: np.ndarray, c1: np.ndarray, c2: np.ndarray,
                    q: int) -> np.ndarray:
    """The 256-bit session key a KEM ciphertext carries, as (8,) uint32:
    m = round(2 (c2 - c1*s) / q) bitwise, packed little-endian."""
    d = (np.asarray(c2, np.int64).reshape(-1)
         - negacyclic_mul(np.asarray(c1, np.int64).reshape(-1), s, q)) % q
    bits = ((d > q // 4) & (d < 3 * q // 4)).astype(np.uint64)
    w = (bits.reshape(-1, 32) << np.arange(32, dtype=np.uint64)).sum(1)
    return w.astype(np.uint32)


_SIGMA = np.array([0x61707865, 0x3320646E, 0x79622D32, 0x6B206574], np.uint32)


def _rotl(x: np.ndarray, r: int) -> np.ndarray:
    return (x << np.uint32(r)) | (x >> np.uint32(32 - r))


def chacha20_keystream(key: np.ndarray, nonce: np.ndarray, n_words: int,
                       counter0: int = 0) -> np.ndarray:
    """(n_words,) uint32 ChaCha20 keystream (RFC 8439): word i is word
    i % 16 of the block with counter counter0 + i // 16."""
    n_blocks = -(-int(n_words) // 16)
    ctr = (np.arange(n_blocks, dtype=np.uint64) + counter0).astype(np.uint32)
    key = np.asarray(key, np.uint32).reshape(8)
    nonce = np.asarray(nonce, np.uint32).reshape(3)
    init = ([np.full(n_blocks, c, np.uint32) for c in _SIGMA]
            + [np.full(n_blocks, k, np.uint32) for k in key] + [ctr]
            + [np.full(n_blocks, v, np.uint32) for v in nonce])
    x = [v.copy() for v in init]

    def qr(a, b, c, d):
        x[a] += x[b]; x[d] = _rotl(x[d] ^ x[a], 16)
        x[c] += x[d]; x[b] = _rotl(x[b] ^ x[c], 12)
        x[a] += x[b]; x[d] = _rotl(x[d] ^ x[a], 8)
        x[c] += x[d]; x[b] = _rotl(x[b] ^ x[c], 7)

    for _ in range(10):
        qr(0, 4, 8, 12); qr(1, 5, 9, 13); qr(2, 6, 10, 14); qr(3, 7, 11, 15)
        qr(0, 5, 10, 15); qr(1, 6, 11, 12); qr(2, 7, 8, 13); qr(3, 4, 9, 14)
    out = np.stack([xi + si for xi, si in zip(x, init)], axis=1)
    return out.reshape(-1)[:n_words]


# ------------------------------------------------------------ entropy code
RANS_LANES = 128
RANS_PROB_BITS = 12
RANS_L = 1 << 16
RANS_HEADER = 2 * 256 + 4 * RANS_LANES + 4 * RANS_LANES


def rans_decode(streams: List[np.ndarray], n_out: List[int]
                ) -> List[Optional[np.ndarray]]:
    """Decode interleaved-rANS streams, all at once, to ``n_out[i]`` bytes
    each, or None where a stream is not a sound one.

    A stream is a header (256 u16 symbol frequencies summing to 2^12, 128
    u32 per-lane word counts, 128 u32 final encoder states) and then the
    16-bit renormalisation words in the order the decoder reads them.
    Byte i of the payload belongs to lane i % 128, row i // 128; the
    decoder walks the rows forward, and within a row the lanes that need a
    word take the next ones in lane order.  A sound stream uses exactly
    its words and leaves every lane at the encoder's initial state 2^16.
    """
    B, L = len(streams), RANS_LANES
    if not B:
        return []
    rows = max([-(-int(n) // L) for n in n_out] + [1])
    freq = np.zeros((B, 256), np.int64)
    states = np.full((B, L), RANS_L, np.int64)
    n_words = np.zeros(B, np.int64)
    ok = np.ones(B, bool)
    words = []
    for i, st in enumerate(streams):
        st = np.asarray(st, np.uint8).reshape(-1)
        if st.size < RANS_HEADER:
            ok[i] = False
            words.append(np.zeros(0, np.int64))
            continue
        f = st[:512].view("<u2").astype(np.int64)
        lens = st[512:1024].view("<u4").astype(np.int64)
        nw = int(lens.sum())
        if f.sum() != 1 << RANS_PROB_BITS or RANS_HEADER + 2 * nw > st.size:
            ok[i] = False
            words.append(np.zeros(0, np.int64))
            continue
        freq[i] = f
        states[i] = st[1024:RANS_HEADER].view("<u4").astype(np.int64)
        n_words[i] = nw
        words.append(st[RANS_HEADER: RANS_HEADER + 2 * nw].view("<u2")
                     .astype(np.int64))
    W = max([w.size for w in words] + [1])
    wmat = np.zeros((B, W), np.int64)
    for i, w in enumerate(words):
        wmat[i, : w.size] = w
    freq[~ok] = np.eye(256, dtype=np.int64)[0] << RANS_PROB_BITS
    cum = np.cumsum(freq, axis=1) - freq
    slot2sym = np.stack([np.repeat(np.arange(256), f) for f in freq])
    n_out_a = np.asarray(n_out, np.int64).reshape(B, 1)
    lane = np.arange(L, dtype=np.int64)[None, :]
    bidx = np.arange(B)[:, None]
    x = states.copy()
    base = np.zeros(B, np.int64)
    out = np.zeros((B, rows, L), np.uint8)
    for r in range(rows):
        valid = r * L + lane < n_out_a
        slot = x & ((1 << RANS_PROB_BITS) - 1)
        s = slot2sym[bidx, slot]
        x2 = freq[bidx, s] * (x >> RANS_PROB_BITS) + slot - cum[bidx, s]
        need = (x2 < RANS_L) & valid
        csum = np.cumsum(need, axis=1)
        pos = np.minimum(base[:, None] + csum - need, W - 1)
        x2 = np.where(need, (x2 << 16) | wmat[bidx, pos], x2)
        x = np.where(valid, x2, x)
        base += csum[:, -1]
        out[:, r] = np.where(valid, s, 0)
    sound = ok & (base == n_words) & (x == RANS_L).all(axis=1)
    flat = out.reshape(B, rows * L)
    return [flat[i, : int(n_out[i])] if sound[i] else None for i in range(B)]


def open_bodies(shards: List[Dict], s: np.ndarray, q: int
                ) -> List[Optional[np.ndarray]]:
    """The payload bytes each stored shard holds, opened as the
    configuration states it is sealed, or None where it does not open.

    Each shard is ``{"body": uint32 words, "c1", "c2": KEM ciphertext,
    "nonce": (3,) uint32, "n_bytes": payload bytes offered}``: the KEM
    gives the session key, its ChaCha20 keystream turns the body back into
    the rANS stream, and the stream decodes to the payload."""
    streams = []
    for sh in shards:
        body = np.asarray(sh["body"], np.uint32).reshape(-1)
        key = kem_decapsulate(s, sh["c1"], sh["c2"], q)
        ks = chacha20_keystream(key, sh["nonce"], body.size)
        streams.append((body ^ ks).astype("<u4").view(np.uint8))
    return rans_decode(streams, [int(sh["n_bytes"]) for sh in shards])
