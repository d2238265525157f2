"""The reference's own parts against published vectors and hand-made
inputs: ChaCha20 (RFC 8439), the Ring-LWE KEM, and the rANS decoder."""

import numpy as np
import pytest

import reference


def test_chacha20_rfc8439_block_vector():
    # RFC 8439 section 2.3.2: key 00..1f, nonce 00:00:00:09:00:00:00:4a:
    # 00:00:00:00, block counter 1
    key = np.frombuffer(bytes(range(32)), "<u4")
    nonce = np.frombuffer(bytes.fromhex("000000090000004a00000000"), "<u4")
    want = [0xE4E7F110, 0x15593BD1, 0x1FDD0F50, 0xC47120A3, 0xC7F4D1C7,
            0x0368C033, 0x9AAA2204, 0x4E6CD4C3, 0x466482D2, 0x09AA9F07,
            0x05D7C214, 0xA2028BD9, 0xD19C12B5, 0xB94E16DE, 0xE883D0CB,
            0x4E3C50A2]
    assert reference.chacha20_keystream(key, nonce, 16, counter0=1).tolist() == want
    # word i is word i % 16 of block i // 16
    ks = reference.chacha20_keystream(key, nonce, 40)
    assert ks[16:32].tolist() == want


def test_kem_opens_what_its_public_key_sealed():
    n, q, k = 256, 12289, 16
    a, b, s = reference.rlwe_keygen(2**35 + 3, n, q, k)
    assert np.array_equal(b, reference.rlwe_keygen(2**35 + 3, n, q, k)[1])
    rng = np.random.default_rng(1)
    for _ in range(4):
        m = rng.integers(0, 2, n)
        r, e1, e2 = (reference._cbd(rng, (n,), k) for _ in range(3))
        c1 = (reference.negacyclic_mul(a, r, q) + e1) % q
        c2 = (reference.negacyclic_mul(b, r, q) + e2 + m * (q // 2)) % q
        words = reference.kem_decapsulate(s, c1, c2, q)
        bits = (words[:, None] >> np.arange(32, dtype=np.uint32)) & 1
        assert np.array_equal(bits.reshape(-1), m)


def test_negacyclic_mul_wraps_with_a_sign():
    q = 97
    a = np.zeros(4, np.int64); a[3] = 1          # x^3
    b = np.zeros(4, np.int64); b[2] = 5          # 5 x^2
    # x^3 * 5 x^2 = 5 x^5 = -5 x  (mod x^4 + 1)
    assert reference.negacyclic_mul(a, b, q).tolist() == [0, q - 5, 0, 0]


def _encode(data: np.ndarray) -> np.ndarray:
    """A plain interleaved-rANS encoder of the stream layout the decoder
    reads (written here, independently of the program's kernels)."""
    L, bits = reference.RANS_LANES, reference.RANS_PROB_BITS
    counts = np.bincount(data, minlength=256).astype(np.int64)
    freq = np.where(counts > 0, 1, 0)
    freq += (counts * ((1 << bits) - freq.sum())) // counts.sum()
    freq[np.argmax(counts)] += (1 << bits) - freq.sum()
    cum = np.cumsum(freq) - freq
    rows = -(-data.size // L)
    x = np.full(L, reference.RANS_L, np.int64)
    emitted = np.full((rows, L), -1, np.int64)
    for r in reversed(range(rows)):
        for lane in range(L):
            i = r * L + lane
            if i >= data.size:
                continue
            f, c = freq[data[i]], cum[data[i]]
            if (x[lane] >> 20) >= f:
                emitted[r, lane] = x[lane] & 0xFFFF
                x[lane] >>= 16
            x[lane] = ((x[lane] // f) << bits) + x[lane] % f + c
    words = emitted[emitted >= 0].astype("<u2")
    lens = (emitted >= 0).sum(0).astype("<u4")
    head = freq.astype("<u2").tobytes() + lens.tobytes() + x.astype("<u4").tobytes()
    return np.frombuffer(head + words.tobytes(), np.uint8)


def test_rans_decode_inverts_a_plain_encoder():
    rng = np.random.default_rng(7)
    a = rng.choice(256, size=5000, p=np.r_[0.7, np.full(255, 0.3 / 255)])
    b = rng.integers(0, 3, size=300)
    sa, sb = _encode(a.astype(np.uint8)), _encode(b.astype(np.uint8))
    got = reference.rans_decode([sa, sb], [a.size, b.size])
    assert np.array_equal(got[0], a) and np.array_equal(got[1], b)
    assert sa.size < a.size  # it compresses


@pytest.mark.parametrize("spoil", ["freq", "word", "short", "raw"])
def test_rans_decode_refuses_what_is_not_a_sound_stream(spoil):
    data = np.random.default_rng(8).integers(0, 4, size=3000).astype(np.uint8)
    st = _encode(data).copy()
    if spoil == "freq":
        st[0] ^= 1
    elif spoil == "word":
        st[reference.RANS_HEADER + 10] ^= 0x40
    elif spoil == "short":
        st = st[:-2]
    else:
        st = data
    got = reference.rans_decode([st], [data.size])[0]
    assert got is None or not np.array_equal(got, data)
