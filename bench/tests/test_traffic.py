"""The generator is a function of the mix and the seed alone."""
import numpy as np

import traffic
import tiny

SEED = 2 ** 45 + 12345


def summary(reqs):
    return [(r.rid, r.prompt.tolist(), r.max_new, r.arrival_s, r.client)
            for r in reqs]


def test_same_seed_same_requests():
    for mix in (tiny.OPEN, tiny.CLOSED):
        a = traffic.generate(mix, SEED, 10.0, 256)
        b = traffic.generate(mix, SEED, 10.0, 256)
        assert summary(a) == summary(b)


def test_seeds_share_sizes_and_gaps_in_another_order():
    a = traffic.generate(tiny.OPEN, SEED, 10.0, 256)
    b = traffic.generate(tiny.OPEN, SEED + 1, 10.0, 256)
    assert summary(a) != summary(b)
    for key in (lambda r: len(r.prompt), lambda r: r.max_new):
        assert sorted(map(key, a)) == sorted(map(key, b))
    gaps = lambda reqs: sorted(np.diff([0.0] + [r.arrival_s for r in reqs]).round(9))
    assert gaps(a) == gaps(b)


def test_open_loop_arrives_inside_the_window_at_the_rate():
    reqs = traffic.generate(tiny.OPEN, SEED, 10.0, 256)
    t = [r.arrival_s for r in reqs]
    assert t == sorted(t) and 0 < t[0] and t[-1] < 10.0
    assert len(reqs) == round(tiny.OPEN["rate_per_s"] * 10.0)


def test_lengths_keep_to_the_mix():
    mix = tiny.OPEN
    for r in traffic.generate(mix, SEED, 50.0, 256):
        assert mix["prompt_len"]["min"] <= len(r.prompt) <= mix["prompt_len"]["max"]
        assert mix["output_len"]["min"] <= r.max_new <= mix["output_len"]["max"]
        assert r.prompt.dtype == np.int32 and r.prompt.max() < 256


def test_closed_loop_clients_have_the_same_turns_for_every_seed():
    def turns(seed):
        by = {}
        for r in traffic.generate(tiny.CLOSED, seed, 10.0, 256):
            by.setdefault(r.client, []).append((len(r.prompt), r.max_new))
        return by
    a, b = turns(SEED), turns(SEED + 1)
    clients = tiny.CLOSED["clients"]
    assert set(a) == set(range(clients))
    assert all(len(v) == tiny.CLOSED["requests_per_client"] for v in a.values())
    assert sorted(a.values()) == sorted(b.values()) and a != b


def test_stratified_quantiles_follow_the_distribution():
    spec = {"median": 600, "sigma": 0.7, "min": 64, "max": 2048}
    q = traffic.lognormal_quantiles(spec, 1001)
    assert q == sorted(q) and q[500] == 600
    gaps = traffic.exponential_gaps(5.0, 10000)
    assert abs(np.mean(gaps) - 0.2) < 0.01


def test_bursts_keep_the_count_and_arrive_only_while_on():
    mix = dict(tiny.OPEN, bursts={"period_s": 4.0, "on_share": 0.25})
    a = traffic.generate(mix, SEED, 20.0, 256)
    b = traffic.generate(mix, SEED + 1, 20.0, 256)
    t = [r.arrival_s for r in a]
    assert len(a) == round(tiny.OPEN["rate_per_s"] * 20.0)
    assert t == sorted(t) and 0 < t[0] and t[-1] < 20.0
    assert all(x % 4.0 <= 1.0 + 1e-9 for x in t)
    assert len({int(x // 4.0) for x in t}) == 5      # every period has some
    assert sorted(len(r.prompt) for r in a) == sorted(len(r.prompt) for r in b)
