"""Edge-case and property tests for the rANS table builders.

``build_freq_table`` invariants (integer-exact normalization): the sum is
exactly PROB_SCALE, every present symbol keeps freq >= 1, and the >= 2^19
downscale path stays exact.  ``build_enc_tables`` reciprocals: both the
Granlund-Montgomery (mprime, shift) fixed-point pair and the
error-repaired f32 reciprocal must reproduce the hardware quotient for
every reachable (x, f) — brute-checked against u64 ground truth here so
the hot loop's division strategies stay interchangeable bit-for-bit.
"""

import numpy as np
import jax.numpy as jnp
import pytest

from _hyp import HAVE_HYPOTHESIS, given, settings, st  # noqa: F401
from repro.kernels.entropy.ops import MAX_ROWS
from repro.kernels.entropy.rans import (
    N_LANES,
    PROB_BITS,
    PROB_SCALE,
    build_enc_tables,
    build_freq_table,
    slot_to_symbol,
)

_SYM_MASK = 0x1FFF


def _check_invariants(counts):
    f = np.asarray(build_freq_table(jnp.asarray(counts, jnp.int32)))
    assert f.sum() == PROB_SCALE
    assert (f[np.asarray(counts) > 0] >= 1).all()
    assert (f >= 0).all()
    return f


# ----------------------------------------------------- deterministic edges
def test_single_symbol_shard():
    counts = np.zeros(256, np.int64)
    counts[42] = 12345
    f = _check_invariants(counts)
    assert f[42] == PROB_SCALE  # sole symbol owns the whole range


def test_all_256_symbols_present():
    f = _check_invariants(np.full(256, 7))
    assert (f >= 1).all()  # every present symbol survives normalization


@pytest.mark.parametrize(
    "total_exp", [19, 20, 25, 30]
)
def test_large_total_shift_path(total_exp):
    """Totals >= 2^19 take the downscale-then-allocate path; the result
    must still be exact (the shift exists so count*budget < 2^31)."""
    counts = np.zeros(256, np.int64)
    counts[: 4] = (1 << total_exp) // 4
    assert counts.sum() >= 1 << 19
    f = _check_invariants(counts)
    # equal counts, no other symbols: equal freqs modulo the remainder
    assert f[:4].min() >= PROB_SCALE // 4 - 1


def test_huge_single_count_int32_safe():
    counts = np.zeros(256, np.int64)
    counts[3] = 10**9  # near int32 max: the shift keeps products in range
    counts[7] = 1
    f = _check_invariants(counts)
    assert f[3] > f[7] >= 1


def test_empty_payload_degenerate_table():
    f = _check_invariants(np.zeros(256, np.int64))
    assert f[0] == PROB_SCALE  # symbol 0 owns everything; still decodable


def test_slot_table_matches_searchsorted_oracle():
    """The cumulative-bucket fill must agree with the searchsorted
    semantics it replaced, including zero-frequency symbols."""
    rng = np.random.default_rng(3)
    for _ in range(20):
        counts = rng.integers(0, 50, 256) * rng.integers(0, 2, 256)
        f = np.asarray(build_freq_table(jnp.asarray(counts, jnp.int32)))
        got = np.asarray(slot_to_symbol(jnp.asarray(f)))
        want = np.searchsorted(
            np.cumsum(f), np.arange(PROB_SCALE), side="right"
        )
        assert np.array_equal(got, want)


# ----------------------------------------------------- reciprocal exactness
def _table_quotients(f_val, xs):
    """Quotients for symbol-frequency ``f_val`` over u32 samples ``xs``,
    via both precomputed-reciprocal strategies from build_enc_tables."""
    freq = np.zeros(256, np.int64)
    freq[1] = f_val
    freq[0] = PROB_SCALE - f_val
    packed, mprime, rcp = (
        np.asarray(a) for a in build_enc_tables(jnp.asarray(freq, jnp.int32))
    )
    p, m, r = int(packed[1]), int(mprime[1]), np.float32(rcp[1])
    s1 = (p >> 13) & 0x3F
    x = xs.astype(np.uint64)
    # Granlund-Montgomery: t = mulhi(x, mprime); q = (t + (x-t)//2) >> s1
    t = (x * np.uint64(m)) >> np.uint64(32)
    q_gm = (t + ((x - t) >> np.uint64(1))) >> np.uint64(s1)
    if f_val <= 1:
        q_gm = x
    # error-repaired f32 reciprocal
    qh = (xs.astype(np.float32) * r).astype(np.int64)
    rem = xs.astype(np.int64) - qh * f_val
    q_f32 = qh + (rem >= f_val) - (rem < 0)
    return q_gm.astype(np.int64), q_f32


@pytest.mark.parametrize("f_val", [1, 2, 3, 5, 7, 255, 641, 2048, 2731,
                                   4095, 4096])
def test_reciprocal_exact_adversarial(f_val):
    rng = np.random.default_rng(f_val)
    # GM must hold for every x < 2^32; the f32 repair for x < f * 2^20
    # (the renorm invariant bounds post-renorm states by exactly that)
    lim32 = 1 << 32
    lim_f = f_val << 20
    xs = {0, 1, f_val - 1, f_val, f_val + 1, lim_f - 1, lim32 - 1}
    for k in (1, 2, (lim32 - 1) // f_val, (lim_f - 1) // f_val):
        for d in (-1, 0, 1):
            v = k * f_val + d
            if 0 <= v < lim32:
                xs.add(v)
    xs |= {int(v) for v in rng.integers(0, lim32, 300)}
    xs = np.asarray(sorted(xs), np.uint32)
    q_gm, q_f32 = _table_quotients(f_val, xs)
    truth = xs.astype(np.uint64) // np.uint64(f_val)
    assert np.array_equal(q_gm, truth.astype(np.int64))
    in_range = xs < lim_f
    assert np.array_equal(q_f32[in_range], truth.astype(np.int64)[in_range])


# ------------------------------------------------------ hypothesis sweeps
@settings(max_examples=200, deadline=None)
@given(
    st.lists(
        st.integers(0, MAX_ROWS * N_LANES // 256), min_size=256, max_size=256
    )
)
def test_freq_table_invariants_property(counts):
    # totals a shard can reach: at most MAX_ROWS * 128 bytes (2^24), well
    # inside build_freq_table's total < 2^31 precondition
    _check_invariants(np.asarray(counts, np.int64))


@settings(max_examples=100, deadline=None)
@given(st.integers(1, PROB_SCALE), st.integers(0, (1 << 32) - 1))
def test_reciprocal_exact_property(f_val, x):
    q_gm, q_f32 = _table_quotients(f_val, np.asarray([x], np.uint32))
    assert q_gm[0] == x // f_val
    if x < (f_val << 20):
        assert q_f32[0] == x // f_val
