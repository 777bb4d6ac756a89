"""The traffic generator: seeded, in range, and the same work per seed."""
from collections import Counter

import pytest

import smoke  # noqa: F401  (puts chipbench/ on the path)
from cb.traffic import Traffic, prompt_ids, quantile_levels

TRAFFIC_DIR = smoke.BENCH_DIR / "traffic"
MIXES = sorted(p.stem for p in TRAFFIC_DIR.glob("*.json"))


@pytest.mark.parametrize("mix", MIXES)
def test_levels_stay_in_range(mix):
    t = Traffic.from_file(TRAFFIC_DIR / f"{mix}.json")
    for spec, levels in ((t.prompt, t.prompt_levels()),
                         (t.output, t.output_levels())):
        assert len(levels) == t.levels
        assert all(spec["min"] <= v <= spec["max"] for v in levels)
        assert levels == sorted(levels)
    assert max(t.prompt_levels()) + max(t.output_levels()) <= t.max_ctx


@pytest.mark.parametrize("mix", MIXES)
def test_same_seed_same_schedule_other_seed_same_work(mix):
    t = Traffic.from_file(TRAFFIC_DIR / f"{mix}.json")

    def make(seed):
        if t.loop == "open":
            return [(a.model, a.prompt_len, a.output_len, round(a.due, 9))
                    for a in t.open_schedule(seed, 40.0)]
        return [(a.model, a.prompt_len, a.output_len)
                for q in t.closed_queues(seed, 64).values() for a in q]

    a, b, c = make(2**31 + 17), make(2**31 + 17), make(5)
    assert a == b
    assert a != c
    # another seed orders the same sizes (and gaps) differently
    assert Counter(x[1] for x in a) == Counter(x[1] for x in c)
    assert Counter(x[2] for x in a) == Counter(x[2] for x in c)
    assert Counter(x[0] for x in a) == Counter(x[0] for x in c)


def test_open_schedule_fills_the_window_at_the_rate():
    t = Traffic.from_file(TRAFFIC_DIR / "chat.json")
    s = t.open_schedule(123, 40.0)
    assert len(s) == round(t.rate_per_s * 40.0)
    dues = [a.due for a in s]
    assert dues == sorted(dues) and dues[0] == 0.0 and dues[-1] < 40.0
    share = sum(a.model == "moonshot-v1-16b-a3b" for a in s) / len(s)
    assert abs(share - t.models["moonshot-v1-16b-a3b"]["share"]) < 0.02


def test_quantile_levels_by_hand():
    assert quantile_levels({"dist": "uniform", "min": 0, "max": 100},
                           4) == [12, 38, 62, 88]
    med = quantile_levels({"dist": "lognormal", "median": 256,
                           "sigma": 0.5, "min": 1, "max": 10**6}, 3)
    assert med[1] == 256 and med[0] < 256 < med[2]


def test_prompt_ids_from_the_seed_alone():
    a = prompt_ids(2**31 + 5, 3, 50, 1000)
    assert (a == prompt_ids(2**31 + 5, 3, 50, 1000)).all()
    assert not (a == prompt_ids(2**31 + 5, 4, 50, 1000)).all()
    assert a.min() >= 0 and a.max() < 1000 and len(a) == 50
