"""The traffic generator: same seed, same schedule; a seed draws the
token ids and never the work, its order or its timing."""
import json
import os

import numpy as np
import pytest

import appended
from appended import BENCHES
from benchmark import traffic

MIX = {"arrivals": {"gaps": "exponential", "rate_per_s": 6.0},
       "prompt_tokens": {"dist": "lognormal", "mean": 350, "sigma": 0.8,
                         "min": 32, "max": 1024},
       "output_tokens": {"dist": "lognormal", "mean": 120, "sigma": 0.7,
                         "min": 16, "max": 256},
       "lead_s": 5.0, "base_seed": 1}
BIG = 2 ** 31 + 12345


def _sched(seed, seconds=30, mix=MIX):
    return traffic.serve_schedule(mix, seed, seconds, 50304)


def _shape(r, shift=0.0):
    return (round(r["due"] - shift, 9), len(r["prompt"]),
            r["max_new_tokens"])


def test_same_seed_same_schedule():
    a, b = _sched(BIG), _sched(BIG)
    assert [r["due"] for r in a] == [r["due"] for r in b]
    assert all((x["prompt"] == y["prompt"]).all() for x, y in zip(a, b))
    assert [r["max_new_tokens"] for r in a] == \
        [r["max_new_tokens"] for r in b]


def test_another_seed_same_work_other_tokens():
    a, b = _sched(1), _sched(BIG)
    assert [_shape(r) for r in a] == [_shape(r) for r in b]
    assert sum(1 for r in a if 0 <= r["due"] < 30) == 180
    assert not (a[0]["prompt"][:8] == b[0]["prompt"][:8]).all()


def test_lengths_stay_inside_their_clips():
    s = _sched(3, seconds=60)
    assert all(32 <= len(r["prompt"]) <= 1024 for r in s)
    assert all(16 <= r["max_new_tokens"] <= 256 for r in s)
    assert all(0 <= int(r["prompt"].min()) and int(r["prompt"].max()) < 50304
               for r in s)


def test_rate_and_window_are_honoured():
    s = _sched(7, seconds=30)
    due = [r["due"] for r in s]
    assert due == sorted(due)
    assert -5.0 <= due[0] and due[-1] < 30.0
    assert sum(1 for d in due if d >= 0) == 180       # 6/s x 30 s
    assert 15 <= sum(1 for d in due if d < 0) <= 50   # ~6/s x 5 s lead-in


@pytest.mark.parametrize("seed", [1, 7, BIG])
def test_lead_in_and_lead_out_are_the_cycle_itself(seed):
    s = _sched(seed, seconds=30, mix=dict(MIX, lead_s=40.0, tail_s=12.0))
    window = [_shape(r) for r in s if 0 <= r["due"] < 30]
    before = [_shape(r, -30.0) for r in s if -30 <= r["due"] < 0]
    after = [_shape(r, 30.0) for r in s if r["due"] >= 30]
    assert s[0]["due"] >= -40 and s[-1]["due"] < 42
    assert before == window                 # one whole cycle before it
    assert after and after == window[:len(after)]
    earlier = [_shape(r, -60.0) for r in s if r["due"] < -30]
    assert earlier and earlier == window[-len(earlier):]


@pytest.mark.parametrize("rate", [2.0, 7.5])
def test_rate_scales_the_count(rate):
    mix = dict(MIX, arrivals={"gaps": "exponential", "rate_per_s": rate})
    s = _sched(11, seconds=20, mix=mix)
    assert sum(1 for r in s if r["due"] >= 0) == round(rate * 20)


def test_mean_is_that_of_the_unclipped_lognormal():
    spec = {"dist": "lognormal", "mean": 161.31, "sigma": 0.93,
            "min": 1, "max": 10 ** 9}
    x = traffic._lengths(spec, 200000, np.random.default_rng(0))
    assert abs(x.mean() / 161.31 - 1) < 0.02
    assert abs(np.median(x) / (161.31 / np.exp(0.93 ** 2 / 2)) - 1) < 0.02


def test_unknown_distribution_is_refused():
    with pytest.raises(ValueError):
        _sched(1, mix=dict(MIX, prompt_tokens={"dist": "zipf"}))
    with pytest.raises(ValueError):
        _sched(1, mix=dict(MIX, arrivals={"gaps": "x", "rate_per_s": 1}))


def test_train_rows_all_differ_and_repeat_by_seed():
    mix = {"batch": 4, "seq": 128}
    a = traffic.train_tokens(mix, BIG, 50304)
    assert a.shape == (64, 4, 129) and a.dtype == np.int32
    assert (a == traffic.train_tokens(mix, BIG, 50304)).all()
    assert not (a == traffic.train_tokens(mix, 1, 50304)).all()
    rows = a.reshape(-1, 129)
    assert len({r.tobytes() for r in rows}) == len(rows)
    assert 0 <= a.min() and a.max() < 50304


@pytest.mark.parametrize("which", list(BENCHES))
def test_every_committed_mix_generates(which, roots):
    """Each mix against the configurations whose cells use it: their
    vocabulary, their positions.  `which` is the committed benchmark or
    the copy a later PR appended to (tests/benchmark/appended.py)."""
    bench, root = BENCHES[which], roots[which]
    files = {c["name"]: c["file"] for c in bench["configs"]}
    folder = os.path.join(root, "benchmark", "traffic")
    for name in sorted(os.listdir(folder)):
        mix = json.load(open(os.path.join(folder, name)))
        assert mix["kind"] in ("train", "serve"), name
        users = [json.load(open(os.path.join(root, files[w["config"]])))
                 for w in bench["workloads"]
                 if w["traffic"] + ".json" == name]
        assert users, f"no cell uses {name}"
        if mix["kind"] == "train":
            assert traffic.train_tokens(mix, 1, 100, pool=2).shape == \
                (2, mix["batch"], mix["seq"] + 1)
            continue
        assert mix["source"]["lengths"] and mix["source"]["arrivals"]
        for cfg in users:
            vocab = cfg["vocab_size"]
            s = traffic.serve_schedule(mix, BIG, 10, vocab)
            assert s and all(r["due"] < 10 + mix.get("tail_s", 0)
                             for r in s)
            assert max(int(r["prompt"].max()) for r in s) < vocab
            assert mix["prompt_tokens"]["max"] + \
                mix["output_tokens"]["max"] <= cfg["max_position_embeddings"]
        # every seed sees the same requests: the pool holds the
        # max_running longest of a whole run (lead-in and window) at
        # once, so it cannot run out whatever the order (lengths do not
        # depend on the vocabulary)
        run = traffic.serve_schedule(mix, BIG, bench["run_seconds"],
                                     users[0]["vocab_size"])
        eng = mix["engine"]
        blocks = sorted(-(-(len(r["prompt"]) + r["max_new_tokens"])
                          // eng["block_size"]) for r in run)
        assert sum(blocks[-eng["max_running"]:]) <= eng["num_blocks"]


def test_the_appended_mix_is_beyond_the_first_configuration():
    """What the committed numbers (50304, 2048) would have refused."""
    mix = appended.MIX
    assert mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"] == 8192
    s = traffic.serve_schedule(mix, BIG, 10, appended.CONFIG["vocab_size"])
    assert max(int(r["prompt"].max()) for r in s) >= 50304
    assert max(len(r["prompt"]) for r in s) > 2048
