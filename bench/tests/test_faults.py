"""A run with the timed path broken underneath comes out not correct:
the serving engine's compiled steps are wrapped so that each fault a
served cell can have happens where the program produces it."""

import time

import jax
import pytest

import tiny
from drivers import lm_serving
import repro.serving.engine as engine_mod


def altered_token(make):
    def factory(*a):
        step = make(*a)

        def decode(params, cache, token, cache_len):
            nxt, logits, cache = step(params, cache, token, cache_len)
            return (nxt + 1) % tiny.MODEL["vocab"], logits, cache
        return decode
    return factory


def state_unchanged(make):
    def factory(*a):
        step = make(*a)

        def decode(params, cache, token, cache_len):
            nxt, logits, _ = step(params, cache, token, cache_len)
            return nxt, logits, cache
        return decode
    return factory


def half_batch_left_out(make):
    """The decode step serves the first half of its rows; the rest keep
    their cache and get their input token back."""
    def factory(*a):
        step = make(*a)

        def decode(params, cache, token, cache_len):
            nxt, logits, out = step(params, cache, token, cache_len)
            half = token.shape[0] // 2
            out = jax.tree.map(lambda o, c: o.at[:, half:].set(c[:, half:]),
                               out, cache)
            return nxt.at[half:].set(token[half:]), logits, out
        return decode
    return factory


@pytest.mark.parametrize("conf", [tiny.CONF, tiny.DENSE_CONF],
                         ids=["moe", "dense"])
@pytest.mark.parametrize("name,fault", [
    ("make_decode_step", altered_token),
    ("make_decode_step", state_unchanged),
    ("make_decode_step", half_batch_left_out)],
    ids=["token_altered", "state_unchanged", "half_batch_left_out"])
def test_fault_is_not_correct(name, fault, conf, monkeypatch, tmp_path):
    monkeypatch.setattr(engine_mod, name, fault(getattr(engine_mod, name)))
    res = lm_serving.run(conf, tiny.MIX, 3, 2.0, False,
                         time.perf_counter(), str(tmp_path),
                         jax.devices()[:1])
    assert not res.correct
    assert any(res.checks[k]["value"] > lim
               for k, lim in conf["limits"].items())
