import json
import os

import numpy as np

import archive_ops
from workload import SIZE_STRATA, Workload, gop_median_bytes, symbol_table

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _cfg(name="city_fleet_720p"):
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        return json.load(f)


TRAFFIC = {"loop": "backlog"}


def test_same_seed_same_traffic():
    cfg = _cfg()
    a = Workload(dict(cfg, pool_gops=2, gop_max_bytes=4096), TRAFFIC, 2**33 + 1)
    b = Workload(dict(cfg, pool_gops=2, gop_max_bytes=4096), TRAFFIC, 2**33 + 1)
    assert np.array_equal(a.sizes, b.sizes)
    assert np.array_equal(a.novelty, b.novelty)
    assert np.array_equal(a.features, b.features)
    assert np.array_equal(a.pool, b.pool)
    assert [a.gop(g) for g in range(50)] == [b.gop(g) for g in range(50)]


def test_seeds_permute_one_size_multiset():
    cfg = _cfg()
    a = Workload(cfg, TRAFFIC, 1)
    b = Workload(cfg, TRAFFIC, 2)
    assert not np.array_equal(a.sizes, b.sizes)
    assert np.array_equal(np.sort(a.sizes[:SIZE_STRATA]),
                          np.sort(b.sizes[:SIZE_STRATA]))
    med = gop_median_bytes(cfg)
    assert med == 3736532
    assert a.sizes.min() >= med // 2 - 3 and a.sizes.max() <= cfg["gop_max_bytes"]
    assert (a.sizes % 4 == 0).all()
    assert abs(np.median(a.sizes) - med) < 0.01 * med


def test_seeds_order_the_same_gops():
    cfg = _cfg()
    a, b = Workload(cfg, TRAFFIC, 1), Workload(cfg, TRAFFIC, 2)
    pairs = lambda w: sorted(
        (int(n), tuple(f.tolist()))
        for n, f in zip(w.sizes[:SIZE_STRATA], w.features[:SIZE_STRATA]))
    assert pairs(a) == pairs(b)
    # the journal names a GOP by (stream, novelty, size): no two alike
    keys = {(a.gop(g).stream, a.gop(g).novelty, a.gop(g).nbytes)
            for g in range(4 * SIZE_STRATA)}
    assert len(keys) == 4 * SIZE_STRATA


def test_round_robin_capture_order():
    w = Workload(_cfg(), TRAFFIC, 3)
    gops = [w.gop(g) for g in range(48)]
    assert [x.stream for x in gops] == [g % 16 for g in range(48)]
    assert [x.seq for x in gops] == [g // 16 for g in range(48)]


def test_symbol_table_follows_the_histogram():
    counts = {"0": 3, "255": 1}
    t = symbol_table(counts)
    assert set(np.unique(t).tolist()) == {0, 255}
    assert abs((t == 0).mean() - 0.75) < 1e-4


def test_payload_statistics_match_the_config():
    cfg = dict(_cfg(), pool_gops=1, gop_max_bytes=1 << 20)
    w = Workload(cfg, TRAFFIC, 5)
    got = np.bincount(w.pool[0].view(np.uint8), minlength=256) / (1 << 20)
    want = np.zeros(256)
    for k, v in cfg["symbol_counts"].items():
        want[int(k)] = v
    want /= want.sum()
    assert np.abs(got - want).max() < 0.005


class _Front:
    """The frontend's admission contract: queued bytes over the budget
    are shed; a pump moves everything queued on."""

    def __init__(self, budget):
        self.budget, self.queue_bytes, self.shed, self.pumps = budget, 0, 0, 0

    def offer(self, stream, payload, manifest, **_):
        self.queue_bytes += int(payload.shape[0])
        if self.queue_bytes > self.budget:
            self.shed += 1

    def pump(self):
        self.queue_bytes = 0
        self.pumps += 1
        return []


class _Run:
    def __init__(self, w):
        self.workload = w

    def span(self, name):
        import contextlib
        return contextlib.nullcontext()


def test_backpressure_loop_never_sheds():
    cfg = dict(_cfg(), pool_gops=2)
    w = Workload(cfg, TRAFFIC, 6)
    budget = cfg["frontend"]["queue_budget_bytes"]
    front, g = _Front(budget), 0
    while g < 500:
        g, _ = archive_ops.offer_or_pump(_Run(w), front, g, budget)
        assert front.queue_bytes <= budget
    assert front.shed == 0 and front.pumps > 0
