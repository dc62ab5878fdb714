"""The traffic generator: deterministic per seed, the same work for
every seed, lengths inside their clips, Poisson gaps of the right mean."""
import numpy as np
import pytest

from benchmarks.chip import cells, traffic

MIXES = ["long-output", "completion", "chat"]
SEEDS = [0, 2**31 + 12345, 2**40 + 7]


def _lengths(specs):
    return (sorted(len(s.prompt) for s in specs),
            sorted(s.max_new for s in specs))


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_requests(name):
    mix = cells.load_mix(name)
    a = traffic.make_requests(mix, seed=SEEDS[1], seconds=30, vocab=1000)
    b = traffic.make_requests(mix, seed=SEEDS[1], seconds=30, vocab=1000)
    assert [(s.rid, s.max_new, s.due_s, s.client) for s in a] == \
        [(s.rid, s.max_new, s.due_s, s.client) for s in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_gets_the_same_work(name):
    mix = cells.load_mix(name)
    runs = [traffic.make_requests(mix, seed=s, seconds=30, vocab=1000)
            for s in SEEDS]
    assert all(_lengths(r) == _lengths(runs[0]) for r in runs)
    gaps = [np.sort(np.diff([0.0] + sorted(s.due_s for s in r)))
            for r in runs]
    assert all(g == pytest.approx(gaps[0]) for g in gaps)
    # an open loop in another order; a closed loop in its deal's order,
    # the same for every seed; never with the same tokens
    same_order = [[s.max_new for s in r] == [s.max_new for s in runs[0]]
                  for r in runs[1:]]
    assert all(same_order) if mix["loop"] == "closed" else \
        not any(same_order)
    assert not np.array_equal(runs[0][0].prompt[:8], runs[1][0].prompt[:8])


@pytest.mark.parametrize("name", MIXES)
def test_lengths_inside_their_clips(name):
    mix = cells.load_mix(name)
    specs = traffic.make_requests(mix, seed=3, seconds=51, vocab=1000)
    p, o = mix["prompt"], mix["output"]
    assert all(p["min"] <= len(s.prompt) <= p["max"] for s in specs)
    assert all(o["min"] <= s.max_new <= o["max"] for s in specs)
    assert all(traffic.FIRST_TOKEN_ID <= s.prompt.min()
               and s.prompt.max() < 1000 for s in specs)
    # the median of the quantile lengths is the distribution's
    assert np.median(traffic.quantile_lengths(p, 401)) == p["median"]


@pytest.mark.parametrize("rate", [0.5, 2.0, 7.3])
def test_poisson_gaps(rate):
    mix = {"loop": "open", "rate_per_s": rate,
           "prompt": {"median": 8, "sigma": 0.5, "min": 1, "max": 64},
           "output": {"median": 8, "sigma": 0.5, "min": 1, "max": 64}}
    seconds = 400 / rate
    specs = traffic.make_requests(mix, seed=1, seconds=seconds, vocab=100)
    due = np.array(sorted(s.due_s for s in specs))
    gaps = np.diff(np.concatenate([[0.0], due]))
    assert gaps.mean() == pytest.approx(1 / rate, rel=0.03)
    # exponential: the standard deviation equals the mean
    assert gaps.std() == pytest.approx(1 / rate, rel=0.1)
    assert due[-1] < seconds
    assert len(specs) == traffic.n_open_requests(mix, seconds)


def test_closed_loop_clients_share_the_pool():
    mix = cells.load_mix("long-output")
    specs = traffic.make_requests(mix, seed=5, seconds=30, vocab=1000)
    assert len(specs) == mix["requests"]
    clients = [s.client for s in specs]
    assert set(clients) == set(range(mix["clients"]))
    assert max(np.bincount(clients)) - min(np.bincount(clients)) <= 1
    # every round of one request per client holds the same lengths
    c = mix["clients"]
    other = traffic.make_requests(mix, seed=6, seconds=30, vocab=1000)
    for r in range(0, len(specs), c):
        assert sorted(s.max_new for s in specs[r:r + c]) == \
            sorted(s.max_new for s in other[r:r + c])
        assert sorted(len(s.prompt) for s in specs[r:r + c]) == \
            sorted(len(s.prompt) for s in specs[:c])


def test_closed_loop_order_comes_from_the_deal():
    mix = cells.load_mix("long-output")

    def order(m, seed):
        return [(s.rid, s.client, len(s.prompt), s.max_new)
                for s in traffic.make_requests(m, seed=seed, seconds=51,
                                               vocab=1000)]

    first = order(mix, SEEDS[0])
    assert all(order(mix, s) == first for s in SEEDS[1:])
    # another deal: the same lengths in another order
    other = order({**mix, "deal": mix["deal"] + 1}, SEEDS[0])
    assert other != first
    assert sorted(o[3] for o in other) == sorted(o[3] for o in first)


def test_closed_mix_without_a_deal_raises():
    mix = {k: v for k, v in cells.load_mix("long-output").items()
           if k != "deal"}
    with pytest.raises(ValueError, match="deal"):
        traffic.make_requests(mix, seed=1, seconds=51, vocab=1000)


def test_base_mix_is_merged(tmp_path):
    (tmp_path / "a.json").write_text(
        '{"loop": "open", "rate_per_s": 1.0, "prompt": 1, "output": 2}')
    (tmp_path / "b.json").write_text('{"base": "a", "rate_per_s": 4.0}')
    mix = cells.load_mix("b", tmp_path)
    assert mix == {"loop": "open", "rate_per_s": 4.0, "prompt": 1,
                   "output": 2}
