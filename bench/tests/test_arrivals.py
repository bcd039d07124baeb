import numpy as np
import pytest

import arrivals

CHAT = arrivals.load(arrivals.Path(__file__).parents[1] / "traffic" /
                     "chat.json")
# long prompts, short uniform answers
RAG = dict(CHAT, prompt={"dist": "lognormal", "median": 700, "sigma": 0.2,
                         "min": 512, "max": 960},
           output={"dist": "uniform", "min": 8, "max": 32})


@pytest.mark.parametrize("mix", [CHAT, RAG], ids=["chat", "rag"])
def test_same_seed_same_arrivals_and_prompts(mix):
    seed = 2**31 + 12345
    a = arrivals.schedule(mix, 51, seed, 49155)
    b = arrivals.schedule(mix, 51, seed, 49155)
    assert [x.t for x in a] == [x.t for x in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    assert [x.max_new_tokens for x in a] == [x.max_new_tokens for x in b]
    c = arrivals.schedule(mix, 51, seed + 1, 49155)
    assert [x.t for x in a] != [x.t for x in c]


@pytest.mark.parametrize("mix", [CHAT, RAG], ids=["chat", "rag"])
def test_every_seed_offers_the_same_work(mix):
    lead, window = mix["lead_in_s"], 51
    a = arrivals.schedule(mix, window, 3, 49155)
    b = arrivals.schedule(mix, window, 4, 49155)
    # the lead-in and the window apart: the same sizes and gaps in another
    # order, every arrival inside its span
    for t0, span in ((0.0, lead), (lead, window)):
        pa, pb = ([x for x in s if t0 <= x.t < t0 + span] for s in (a, b))
        assert len(pa) == len(pb) == int(np.ceil(mix["rate_rps"] * span))
        assert sorted(len(x.prompt) for x in pa) == \
            sorted(len(x.prompt) for x in pb)
        assert sorted(x.max_new_tokens for x in pa) == \
            sorted(x.max_new_tokens for x in pb)
        gaps = [sorted(np.diff([t0] + [x.t for x in p])) for p in (pa, pb)]
        assert np.allclose(gaps[0], gaps[1])
    assert len(a) == len(b)
    for x in a:
        assert mix["prompt"]["min"] <= len(x.prompt) <= mix["prompt"]["max"]
        assert mix["output"]["min"] <= x.max_new_tokens \
            <= mix["output"]["max"]
        assert x.prompt.max() < 49155


def test_arrivals_cluster_as_poisson_arrivals_do():
    # the span of 8 consecutive Poisson arrivals is Gamma(8): coefficient
    # of variation 1/sqrt(8) = 0.35; an evenly spread schedule has ~0
    spans = []
    for seed in range(40):
        t = np.array([x.t for x in arrivals.schedule(CHAT, 51, seed, 100)])
        spans += list(t[8:] - t[:-8])
    cv = np.std(spans) / np.mean(spans)
    assert 0.28 < cv < 0.42


def test_rate_and_lengths_follow_the_mix():
    a = arrivals.schedule(CHAT, 51, 9, 49155)
    horizon = CHAT["lead_in_s"] + 51
    assert 0 <= len(a) - CHAT["rate_rps"] * horizon < 2
    assert abs(a[-1].t - horizon) / horizon < 0.1
    med = np.median([len(x.prompt) for x in a])
    assert abs(med - CHAT["prompt"]["median"]) / CHAT["prompt"]["median"] \
        < 0.1


def test_bursty_matches_the_repository_generator():
    from repro.serving.requests import bursty_diurnal_trace
    mix = dict(CHAT, generator="bursty_diurnal", base_rps=0.5,
               peak_rps=1.2, period_s=40.0, burst_factor=2.0,
               burst_every_s=10.0, burst_len_s=2.0)
    ours = arrivals._thinned_times(np.random.default_rng(4), mix, 60.0)
    theirs = bursty_diurnal_trace(0.5, 1.2, 60.0, 40.0, seed=4,
                                  burst_factor=2.0, burst_every_s=10.0,
                                  burst_len_s=2.0)
    assert len(ours) > 10
    assert np.allclose(ours, [a.t for a in theirs])
    assert len(arrivals.schedule(mix, 50, 4, 100)) > 10


def test_buckets_reached():
    b = (16, 32, 64, 128, 256, 512, 1024)
    assert arrivals.buckets_reached(CHAT, b) == [32, 64, 128, 256, 512,
                                                  1024]
    assert arrivals.buckets_reached(RAG, b) == [512, 1024]
