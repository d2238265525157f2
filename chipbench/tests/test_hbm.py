import hbm
import pytest


def test_seal_bytes_hand_count():
    # one stripe of two shards: codes 1000 + 600 in, streams 200 + 150
    # written and read, bodies 51 + 38 words out, P and Q of 51 words each
    stripe = [(1000, 200, 51), (600, 150, 38)]
    want = (1000 + 400 + 204) + (600 + 300 + 152) + 2 * 204
    assert hbm.seal_bytes([stripe]) == want
    assert hbm.seal_bytes([stripe], parity_strips=1) == want - 204


def test_peaks_are_keyed_by_device_kind():
    assert hbm.hbm_peak("TPU v5 lite") == 819e9
    with pytest.raises(KeyError):
        hbm.hbm_peak("TPU v9 imaginary")
