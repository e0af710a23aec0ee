"""perfbench.loadgen: deterministic per seed, honours the clipped
distributions, offers a fixed amount of work, general over data files."""

import json
import os

import numpy as np
import pytest

from perfbench import loadgen

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def mix(name):
    with open(os.path.join(ROOT, "perfbench", "traffic", name + ".json")) as f:
        return json.load(f)


def open_mix(**over):
    m = mix("chat-poisson")
    m.pop("schedule_seed", None)
    m.update(over)
    return m


def traffic(m, seed, seconds=50.0, **params):
    return loadgen.Traffic(m, params, 32768, seed, seconds)


def lengths(reqs):
    return [(len(r.prompt), r.max_new_tokens) for r in reqs]


def test_same_seed_same_requests_other_seed_other_requests():
    a = traffic(open_mix(), 3, rate_rps=1.0).schedule()
    b = traffic(open_mix(), 3, rate_rps=1.0).schedule()
    c = traffic(open_mix(), 4, rate_rps=1.0).schedule()
    assert lengths(a) == lengths(b)
    assert [r.due_s for r in a] == [r.due_s for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    assert lengths(a) != lengths(c)
    assert [r.due_s for r in a] != [r.due_s for r in c]


def test_schedule_seed_replays_the_schedule_and_redraws_the_tokens():
    m = open_mix(schedule_seed=22)
    a = traffic(m, 1, rate_rps=1.0).schedule()
    b = traffic(m, 2, rate_rps=1.0).schedule()
    assert lengths(a) == lengths(b)
    assert [r.due_s for r in a] == [r.due_s for r in b]
    assert not all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


def test_lengths_stay_inside_the_clip_and_tokens_inside_the_vocabulary():
    m = open_mix()
    reqs = traffic(m, 0, rate_rps=8.0).schedule()
    p, o = m["prompt_tokens"], m["output_tokens"]
    assert len(reqs) > 400
    assert all(p["min"] <= len(r.prompt) <= p["max"] for r in reqs)
    assert all(o["min"] <= r.max_new_tokens <= o["max"] for r in reqs)
    assert all(r.prompt.dtype == np.int32 and r.prompt.min() >= 1
               and r.prompt.max() < 32768 for r in reqs)
    med = np.median([len(r.prompt) for r in reqs])
    assert 0.9 * p["median"] < med < 1.1 * p["median"]


def test_stratified_lengths_carry_the_same_work_for_every_seed():
    def tokens(sampling):
        m = open_mix(length_sampling=sampling)
        return [sum(len(r.prompt) for r in traffic(m, s, rate_rps=2.0)
                    .schedule()) for s in range(12)]
    iid = np.std(tokens({"kind": "iid"}))
    strat = np.std(tokens({"kind": "stratified", "block": 16}))
    assert strat < 0.35 * iid


def test_quantile_hand_worked():
    u = np.array([0.5, 1e-15, 1 - 1e-15])
    spec = {"dist": "lognormal", "median": 256, "sigma": 1.0,
            "min": 16, "max": 2048}
    assert loadgen.quantile(spec, u).tolist() == [256, 16, 2048]
    spec = {"dist": "uniform", "min": 10, "max": 20}
    assert loadgen.quantile(spec, np.array([0.0, 0.5, 1.0])).tolist() \
        == [10, 15, 20]
    assert loadgen.quantile({"dist": "fixed", "value": 16},
                            np.array([0.1, 0.9])).tolist() == [16, 16]


@pytest.mark.parametrize("process", ["poisson", "regular"])
def test_fixed_count_arrivals(process):
    m = open_mix(arrivals={"process": process, "rate": {"param": "r"},
                           "count": "fixed"}, warmup_s=10)
    for seed in range(5):
        due = [r.due_s for r in traffic(m, seed, 40.0, r=0.7).schedule()]
        assert due == sorted(due)
        assert sum(d < 0 for d in due) == 7          # round(0.7 * 10)
        assert sum(d >= 0 for d in due) == 28        # round(0.7 * 40)
        assert -10 <= due[0] and due[-1] < 40


def test_random_count_is_poisson():
    m = open_mix(arrivals={"process": "poisson", "rate": 2.0,
                           "count": "random"}, warmup_s=0)
    counts = [len(traffic(m, s, 50.0).schedule()) for s in range(40)]
    assert 90 < np.mean(counts) < 110 and 5 < np.std(counts) < 16


def test_rate_profile_puts_the_arrivals_into_the_bursts():
    rng = np.random.default_rng(0)
    spec = {"process": "poisson", "count": "fixed",
            "rate_profile": [[2.0, 9.0], [8.0, 1.0]]}   # 10 s period
    t = loadgen.arrival_times(spec, 10.0, -20.0, 40.0, rng)
    assert len(t) == 600 and (np.diff(t) >= 0).all()
    on = ((t % 10.0) < 2.0).mean()
    assert abs(on - 18.0 / 26.0) < 0.05              # 2*9 / (2*9 + 8*1)


def test_shared_prefix_share_and_groups():
    m = open_mix(shared_prefix={"tokens": 64, "groups": 2, "share": 0.5})
    tr = traffic(m, 5, rate_rps=4.0)
    reqs = tr.schedule()
    heads = {tuple(r.prompt[:64]) for r in reqs}
    shared = [r for r in reqs
              if any(np.array_equal(r.prompt[:64], p) for p in tr._prefixes)]
    assert 0.35 < len(shared) / len(reqs) < 0.65
    assert len({tuple(r.prompt[:64]) for r in shared}) == 2
    assert len(heads) > 2


def test_batch_is_sized_by_the_window_and_seeded():
    m = mix("chat-batch")
    m.pop("schedule_seed")
    tr = traffic(m, 9, 50.0, clients=4, batch_rps=0.5)
    warm, batch = tr.warmup(), tr.batch()
    again = traffic(m, 9, 50.0, clients=4, batch_rps=0.5)
    assert len(warm) == m["warmup_requests"] and len(batch) == 25
    assert lengths(warm + batch) == lengths(again.warmup() + again.batch())
    assert [r.index for r in warm] == list(range(-len(warm), 0))
    assert [r.index for r in batch] == list(range(25))
    assert all(r.due_s is None for r in batch)
    other = traffic(m, 10, 50.0, clients=4, batch_rps=0.5).batch()
    assert lengths(batch) != lengths(other)
    assert tr.clients == 4
    assert len(traffic(m, 9, 1.0, clients=4, batch_rps=0.1).batch()) == 1
    # warm-up requests have streams of their own: asking for more of them
    # does not move the batch
    more = traffic(dict(m, warmup_requests=9), 9, 50.0, clients=4,
                   batch_rps=0.5)
    assert lengths(more.batch()) == lengths(batch)
    assert len(traffic(open_mix(), 9, rate_rps=1.0).warmup()) == 1


def test_missing_cell_parameter_is_an_error():
    with pytest.raises(KeyError, match="rate_rps"):
        traffic(open_mix(), 0).schedule()
