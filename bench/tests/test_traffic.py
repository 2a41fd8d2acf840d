"""Mixes are drawn from the seed alone, from a finite set of lengths, and
give every seed the same work in its own order."""
import numpy as np
import pytest

from bench import traffic
from bench.registry import BENCH, Registry

REG = Registry({"workloads": [], "end_to_end": [], "per_layer": []}, (BENCH,))
CASES = [("wearable-sync", "mbiotracker", "respiration")]
BIG = 2 ** 31 + 12345


def _build(mix, config, signal, seed, seconds=6.0):
    m = REG.mix(mix)
    m["pool_s"] = min(m["pool_s"], 600)
    return traffic.build(m, REG.config(config), seed, seconds,
                         REG.module("signals", signal).make,
                         REG.module("arrivals", m["arrivals"]))


@pytest.mark.parametrize("mix,config,signal", CASES)
def test_same_seed_same_schedule_and_data(mix, config, signal):
    a = _build(mix, config, signal, BIG)
    b = _build(mix, config, signal, BIG)
    assert a.uploads == b.uploads
    np.testing.assert_array_equal(a.pool[0], b.pool[0])
    assert all(np.array_equal(a.samples(u), b.samples(u))
               for u in a.uploads[:20])


@pytest.mark.parametrize("mix,config,signal", CASES)
def test_every_seed_gets_the_same_work(mix, config, signal):
    a = _build(mix, config, signal, BIG)
    b = _build(mix, config, signal, 7)
    assert sorted(u.n_samples for u in a.uploads) == \
        sorted(u.n_samples for u in b.uploads)
    assert [(u.due_s, u.tenant) for u in a.uploads] != \
        [(u.due_s, u.tenant) for u in b.uploads]
    assert not np.array_equal(a.pool[0][:1000], b.pool[0][:1000])


@pytest.mark.parametrize("mix,config,signal", CASES)
def test_lengths_come_from_the_warmed_set(mix, config, signal):
    tr = _build(mix, config, signal, BIG)
    assert {u.n_samples for u in tr.uploads} <= set(tr.lengths)
    assert all(0 <= u.due_s < 6.0 for u in tr.uploads)
    assert all(u.offset + u.n_samples <= tr.pool[u.item].shape[0]
               for u in tr.uploads)


def test_sync_uploads_hold_period_plus_overlap():
    m = REG.mix("wearable-sync")
    tr = _build("wearable-sync", "mbiotracker", "respiration", BIG, 20.0)
    assert tr.lengths == tuple(sorted(p * 64 + 1536 for p in m["periods_s"]))
    # period/8 windows of 2048 at hop 512 per upload
    windows = {1 + (n - 2048) // 512 for n in tr.lengths}
    assert windows == {p // 8 for p in m["periods_s"]}
    rate = len(tr.uploads) / 20.0
    assert abs(rate - m["rate_per_s"]) / m["rate_per_s"] < 0.1


def test_backlog_cycles_its_pool():
    m = {"arrivals": "backlog", "recording_s": 100, "pool": 3}
    tr = traffic.build(m, REG.config("mbiotracker"), BIG, 5.0,
                       REG.module("signals", "respiration").make,
                       REG.module("arrivals", "backlog"))
    ups = [u for _, u in zip(range(7), tr.backlog())]
    assert [u.item for u in ups] == [0, 1, 2, 0, 1, 2, 0]
    assert tr.lengths == (6400,)
    assert all(u.due_s == 0.0 for u in ups)
