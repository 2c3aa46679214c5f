"""The traffic generator: seeds, clips, shares, and the mixes found by name."""
import json
import os

import numpy as np
import pytest

import bench_paths  # noqa: F401
import traffic

MIXES = sorted(f[:-5] for f in os.listdir(os.path.join(bench_paths.CHIP, "traffic"))
               if f.endswith(".json"))


def _sig(reqs):
    return [(r.prompt.tobytes(), r.max_new_tokens, r.tier, r.key.tobytes(), r.due)
            for r in reqs]


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_requests(name):
    mix = traffic.load_mix(name)
    a = traffic.generate(mix, 2**31 + 7, 10.0, 49152)
    b = traffic.generate(mix, 2**31 + 7, 10.0, 49152)
    c = traffic.generate(mix, 2**31 + 8, 10.0, 49152)
    assert _sig(a) == _sig(b)
    assert _sig(a) != _sig(c)


@pytest.mark.parametrize("name", MIXES)
def test_seeds_share_one_multiset_of_work(name):
    mix = traffic.load_mix(name)
    a = traffic.generate(mix, 1, 10.0, 49152)
    b = traffic.generate(mix, 2, 10.0, 49152)
    for field in ("max_new_tokens", "tier"):
        assert sorted(getattr(r, field) for r in a) == sorted(getattr(r, field) for r in b)
    assert sorted(r.prompt.size for r in a) == sorted(r.prompt.size for r in b)
    if mix["loop"] == "open":
        # the same gaps in another order, every request due inside the window
        ga, gb = np.diff([r.due for r in a]), np.diff([r.due for r in b])
        assert len(set(np.round(ga, 9)) ^ set(np.round(gb, 9))) <= 2
        assert max(r.due for r in a) < 10.0 and max(r.due for r in b) < 10.0


@pytest.mark.parametrize("name", MIXES)
def test_seeds_share_one_schedule(name):
    # every seed sends the same lengths and tiers at the same times; only
    # the prompt tokens and the noise keys differ
    mix = traffic.load_mix(name)
    a = traffic.generate(mix, 2**31 + 11, 10.0, 49152)
    b = traffic.generate(mix, 2**32 + 5, 10.0, 49152)
    sched = [[(r.prompt.size, r.max_new_tokens, r.tier, r.due) for r in x] for x in (a, b)]
    assert sched[0] == sched[1]
    assert all(x.key.tobytes() != y.key.tobytes() for x, y in zip(a, b))
    other = traffic.generate(dict(mix, schedule_seed=mix.get("schedule_seed", 0) + 1),
                             2**31 + 11, 10.0, 49152)
    assert [(r.prompt.size, r.due) for r in other] != [(r.prompt.size, r.due) for r in a]


@pytest.mark.parametrize("name", MIXES)
def test_lengths_within_clips_and_buckets(name):
    mix = traffic.load_mix(name)
    reqs = traffic.generate(mix, 3, 10.0, 49152)
    p, o, e = mix["prompt"], mix["output"], mix["engine"]
    for r in reqs:
        assert p["min"] <= r.prompt.size <= p["max"]
        assert o["min"] <= r.max_new_tokens <= o["max"] <= e["max_gen"]
        assert traffic.bucket(r.prompt.size, e["seq_buckets"]) is not None
        assert r.prompt.dtype == np.int32 and r.prompt.max() < 49152
        assert r.key.dtype == np.uint32 and r.key.shape == (2,)


@pytest.mark.parametrize("name", MIXES)
def test_tier_shares_exact(name):
    mix = traffic.load_mix(name)
    reqs = traffic.generate(mix, 4, 10.0, 49152)
    n = len(reqs)
    for tier, spec in mix["tiers"].items():
        got = sum(r.tier == tier for r in reqs)
        assert abs(got - spec["share"] * n) <= 1


def test_open_loop_rate():
    mix = dict(traffic.load_mix("chat"), rate_per_s=5.0)
    reqs = traffic.generate(mix, 5, 40.0, 100)
    assert len(reqs) == 200
    dues = [r.due for r in reqs]
    assert dues[0] == 0.0 and all(b >= a for a, b in zip(dues, dues[1:]))
    assert 35.0 < dues[-1] < 42.0


def test_mix_files_are_json_data():
    for name in MIXES:
        with open(os.path.join(bench_paths.CHIP, "traffic", f"{name}.json")) as f:
            json.load(f)
