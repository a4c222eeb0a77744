"""The traffic generator: deterministic in the seed, on its stated
distributions, and the same work for every seed in another order."""

import numpy as np
import pytest

from perfbench import manifest, stats, traffic

BIG_SEED = 2**31 + 12345


def mix(name):
    return traffic.load(manifest.traffic_path(name))


@pytest.mark.parametrize("name", ["flood", "steady"])
def test_serve_requests_are_deterministic_in_the_seed(name):
    a = traffic.serve_requests(mix(name), 32768, BIG_SEED, 20.0)
    b = traffic.serve_requests(mix(name), 32768, BIG_SEED, 20.0)
    c = traffic.serve_requests(mix(name), 32768, BIG_SEED + 1, 20.0)
    assert a == b and a != c


@pytest.mark.parametrize("name, order", [
    ("flood", "seeded"), ("steady", "seeded"), ("steady", "fixed")])
def test_every_seed_offers_the_same_work(name, order):
    m = dict(mix(name), order=order)
    a = traffic.serve_requests(m, 32768, 11, 26.0)
    b = traffic.serve_requests(m, 32768, 12, 26.0)
    size = lambda r: (len(r["prompt"]), r["max_new"])      # noqa: E731
    assert sorted(map(size, a)) == sorted(map(size, b))
    assert a[0]["prompt"] != b[0]["prompt"]         # its own token ids
    # in another order, or replaying the one schedule
    assert (list(map(size, a)) == list(map(size, b))) == (order == "fixed")
    if name == "steady":
        assert a[-1]["due_s"] == pytest.approx(b[-1]["due_s"], rel=1e-9)
        assert ([r["due_s"] for r in a] == [r["due_s"] for r in b]) \
            == (order == "fixed")
    assert mix("steady")["order"] == "fixed" and "order" not in mix("flood")


def test_lengths_hit_their_stated_distribution():
    m = mix("flood")
    pop = traffic.population(m, 8192)
    plen, new = pop["prompt_len"], pop["max_new"]
    assert plen.min() >= 4 and plen.max() <= 256
    assert 26 <= np.median(plen) <= 30                 # lognormal, median 28
    assert 0.55 <= np.std(np.log(plen)) <= 0.65        # sigma 0.6
    ratio = new / plen
    assert new.min() >= 4 and new.max() <= 256
    assert 0.75 <= np.percentile(ratio[plen > 20], 1) and \
        np.percentile(ratio[plen > 20], 99) <= 1.25


def test_open_loop_arrivals_keep_their_rate_and_bursts_keep_the_mean():
    m = mix("steady")
    reqs = traffic.serve_requests(m, 32768, 5, 200.0)
    due = np.array([r["due_s"] for r in reqs])
    assert len(reqs) == round(m["rate_per_s"] * 200.0)
    assert np.all(np.diff(due) > 0)
    assert len(due) / due[-1] == pytest.approx(m["rate_per_s"], rel=0.05)
    gaps = np.diff(due)
    assert np.std(gaps) / np.mean(gaps) == pytest.approx(1.0, abs=0.1)
    bursty = dict(m, burst={"factor": 4, "every_s": 5, "for_s": 1})
    bd = np.array([r["due_s"] for r in
                   traffic.serve_requests(bursty, 32768, 5, 200.0)])
    assert len(bd) / bd[-1] == pytest.approx(m["rate_per_s"], rel=0.06)
    in_burst = np.sum((bd % 5) < 1) / len(bd)
    assert in_burst == pytest.approx(4 / 8, abs=0.05)   # 4x rate, 1 s in 5


def test_closed_loop_deals_requests_to_its_clients():
    m = mix("flood")
    reqs = traffic.serve_requests(m, 32768, 9, 30.0)
    assert len(reqs) == m["population"]
    assert {r["client"] for r in reqs} == set(range(m["clients"]))
    assert all(2 <= t < 32768 for r in reqs[:50] for t in r["prompt"])


def test_train_batches_are_seeded_and_every_row_differs():
    m = dict(mix("train-s256"), batch=8, seq_len=16)
    a = traffic.train_batches(m, 32768, BIG_SEED)
    b = traffic.train_batches(m, 32768, BIG_SEED)
    assert len(a) == m["pool_batches"]
    for x, y in zip(a, b):
        for k in traffic.TRAIN_FEEDS:
            assert np.array_equal(x[k], y[k])
    rows = np.concatenate([x["src_word"] for x in a])
    assert len({r.tobytes() for r in rows}) == len(rows)
    assert a[0]["src_word"].dtype == np.int32 and a[0]["src_word"].min() >= 1
    assert not np.array_equal(
        a[0]["src_word"],
        traffic.train_batches(m, 32768, BIG_SEED + 1)[0]["src_word"])


def test_percentiles_state_their_sample_count():
    xs = list(range(1, 101))
    assert stats.percentile(xs, 50) == 50.5
    assert stats.percentile(xs, 95) == pytest.approx(np.percentile(xs, 95))
    s = stats.summary(xs, "ms")
    assert s["n"] == 100 and s["unit"] == "ms" and not s["p95_supported"]
    assert stats.summary(list(range(400)))["p95_supported"]
    assert stats.summary([]) == {"n": 0, "unit": ""}
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_spread_is_the_interquartile_share_of_the_median():
    import statistics

    xs = [10.0, 10.2, 9.9, 10.1, 10.4, 9.8]
    q1, _, q3 = statistics.quantiles(xs, n=4)
    assert stats.iqr_share(xs) == pytest.approx(
        (q3 - q1) / statistics.median(xs))
