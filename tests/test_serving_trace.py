"""Serving engine spans: the ``serving.*`` trace annotations, their
nesting and their counters, read back from a profiler trace; and the
public ``step()`` against ``run()``."""

import glob

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.models import build_model, get_config
from repro.serving import Request, ServingEngine
from repro.serving.queues import bucket_for

SPANS = ("serving.step", "serving.admit", "serving.prefill",
         "serving.scatter", "serving.decode", "serving.readback",
         "serving.bookkeep")
# the span each one nests in, on the calling thread
PARENT = {"serving.step": None, "serving.admit": "serving.step",
          "serving.prefill": "serving.admit",
          "serving.scatter": "serving.admit",
          "serving.decode": "serving.step",
          "serving.readback": "serving.step",
          "serving.bookkeep": "serving.step"}
# bytes of K/V a position of a slot of ``small_model``: two layers, K and
# V, two heads of 16, bfloat16
POSITION = 2 * 2 * 2 * 16 * 2


@pytest.fixture(scope="module")
def small_model():
    cfg = get_config("granite-8b").replace(
        n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
        d_ff=128, vocab=128, remat=False, q_chunk=32, loss_seq_chunk=None)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _requests(cfg, lens=(5, 9, 20), new=(3, 4, 5)):
    rng = np.random.default_rng(7)
    return [Request(rid=i, prompt=rng.integers(0, cfg.vocab, n),
                    max_new_tokens=k)
            for i, (n, k) in enumerate(zip(lens, new))]


def _spans(path):
    """Every ``serving.*`` event of the trace under ``path``, in start
    order, as dicts of name, start, end, stats, thread and parent."""
    f = sorted(glob.glob(f"{path}/**/*.xplane.pb", recursive=True))[-1]
    out = []
    for plane in ProfileData.from_file(f).planes:
        for i, line in enumerate(plane.lines):
            evs = sorted((e for e in line.events
                          if e.name.startswith("serving.")),
                         key=lambda e: (e.start_ns, -e.duration_ns))
            for e in evs:
                s, t = e.start_ns, e.start_ns + e.duration_ns
                cover = [o for o in out if o["thread"] == (plane.name, i)
                         and o["start"] <= s and t <= o["end"]]
                out.append({"name": e.name, "start": s, "end": t,
                            "stats": dict(e.stats),
                            "thread": (plane.name, i),
                            "parent": cover[-1]["name"] if cover else None})
    return out


def _stepped(eng):
    """Step ``eng`` to completion.  Returns the finished requests and,
    per step, the rows that got a token and the requests finished."""
    grown, done = [], []
    while eng.queue or eng.active:
        before = {r.rid: len(r.tokens)
                  for r in list(eng.queue) + list(eng.active.values())}
        reqs = list(eng.queue) + list(eng.active.values())
        fin = eng.step()
        grown.append(sum(len(r.tokens) > before[r.rid] for r in reqs))
        done.append(fin)
    return [r for f in done for r in f], grown, [len(f) for f in done]


@pytest.fixture(scope="module")
def traced(small_model, tmp_path_factory):
    cfg, model, params = small_model
    eng = ServingEngine(model, params, width=4, max_len=64)
    reqs = _requests(cfg)
    for r in reqs:
        eng.submit(r)
    eng.warmup()
    path = str(tmp_path_factory.mktemp("trace"))
    jax.profiler.start_trace(path)
    try:
        _, grown, fin = _stepped(eng)
    finally:
        jax.profiler.stop_trace()
    return eng, reqs, _spans(path), grown, fin


def test_every_span_appears_and_nests(traced):
    _, _, spans, grown, _ = traced
    assert {s["name"] for s in spans} == set(SPANS)
    for s in spans:
        assert s["parent"] == PARENT[s["name"]], s
    steps = [s for s in spans if s["name"] == "serving.step"]
    assert len(steps) == len(grown)
    # one admission, in the first step: the queue's three requests
    first = steps[0]
    # after admission the pool holds the three prompts less their last
    # tokens, of a width-4 pool of 64 positions a slot and no state
    assert first["stats"] == {"queued": 3, "active": 0,
                              "kv_used": POSITION * (4 + 8 + 19),
                              "state_used": 0, "reserved": 4 * 64 * POSITION}
    assert all(s["stats"]["queued"] == 0 for s in steps[1:])
    admits = [s for s in spans if s["name"] == "serving.admit"]
    assert len(admits) == 1
    assert first["start"] <= admits[0]["start"] <= first["end"]
    assert admits[0]["stats"]["rows"] == 3
    assert str(admits[0]["stats"]["rids"]).split() == ["0", "1", "2"]


def test_prefill_counters_are_exact(traced):
    _, reqs, spans, _, _ = traced
    (pre,) = [s for s in spans if s["name"] == "serving.prefill"]
    lens = [len(r.prompt) for r in reqs]
    assert pre["stats"] == {
        "rows": 3, "width": 4,
        "bucket": bucket_for(max(lens) - 1, (16, 32, 64)),
        "real_tokens": sum(n - 1 for n in lens)}
    assert pre["stats"]["bucket"] == 32
    (sc,) = [s for s in spans if s["name"] == "serving.scatter"]
    assert sc["stats"] == {"rows": 3, "state_bytes": 3 * 64 * POSITION}


def test_one_row_prefills_one_row(small_model, tmp_path):
    """A lone admission to a width-4 engine computes a row bucket of one:
    ``width`` is the rows computed, not the engine's width."""
    cfg, model, params = small_model
    eng = ServingEngine(model, params, width=4, max_len=64)
    (req,) = _requests(cfg, lens=(9,), new=(3,))
    eng.submit(req)
    eng.warmup()
    jax.profiler.start_trace(str(tmp_path))
    try:
        _stepped(eng)
    finally:
        jax.profiler.stop_trace()
    (pre,) = [s["stats"] for s in _spans(str(tmp_path))
              if s["name"] == "serving.prefill"]
    assert pre == {"rows": 1, "width": 1, "bucket": 16, "real_tokens": 8}


def test_decode_rows_are_the_active_slots(traced):
    _, _, spans, grown, fin = traced
    dec = [s for s in spans if s["name"] == "serving.decode"]
    assert [s["stats"]["rows"] for s in dec] == grown
    assert grown[0] == 3 and grown[-1] >= 1
    steps = [s for s in spans if s["name"] == "serving.step"]
    # a step's ``active`` at entry is the rows of the step before, less
    # those that finished in it
    assert [s["stats"]["active"] for s in steps[1:]] == \
        [g - f for g, f in zip(grown, fin)][:-1]
    book = [s for s in spans if s["name"] == "serving.bookkeep"]
    assert [s["stats"]["finished"] for s in book] == fin
    assert sum(fin) == 3
    assert not any(s["stats"] for s in spans
                   if s["name"] == "serving.readback")


def test_each_exact_prefill_is_a_span(small_model, tmp_path):
    cfg, model, params = small_model
    eng = ServingEngine(model, params, width=4, max_len=64,
                        prompt_buckets=None)
    reqs = _requests(cfg)
    for r in reqs:
        eng.submit(r)
    jax.profiler.start_trace(str(tmp_path))
    try:
        _stepped(eng)
    finally:
        jax.profiler.stop_trace()
    spans = _spans(str(tmp_path))
    pre = [s["stats"] for s in spans if s["name"] == "serving.prefill"]
    assert pre == [{"rows": 1, "width": 1, "bucket": len(r.prompt),
                    "real_tokens": len(r.prompt)} for r in reqs]
    assert [s["stats"] for s in spans if s["name"] == "serving.scatter"] \
        == [{"rows": 1, "state_bytes": 64 * POSITION}] * 3
    for s in spans:
        assert s["parent"] == PARENT[s["name"]], s


def test_step_matches_run_with_and_without_a_profiler(small_model,
                                                      tmp_path):
    cfg, model, params = small_model

    def served(drive):
        eng = ServingEngine(model, params, width=2, max_len=64)
        for r in _requests(cfg):
            eng.submit(r)
        return {r.rid: r.tokens for r in drive(eng)}

    want = served(lambda eng: eng.run())
    assert sorted(want) == [0, 1, 2]
    assert served(lambda eng: _stepped(eng)[0]) == want
    jax.profiler.start_trace(str(tmp_path))
    try:
        got = served(lambda eng: _stepped(eng)[0])
    finally:
        jax.profiler.stop_trace()
    assert got == want


def test_step_on_an_idle_engine_does_nothing(small_model):
    _, model, params = small_model
    eng = ServingEngine(model, params, width=2, max_len=32)
    assert eng.step() == []
    assert not eng.queue and not eng.active
