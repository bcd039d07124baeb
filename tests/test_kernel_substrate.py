"""Kernel substrate layer: compiler params, cost normalizer, pad-and-mask
parity on uneven shapes, and the block-size autotuner."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (AnalyticProvider, BenchmarkDB, Resource,
                        TimingProvider, benchmark_model, fuse_blocks,
                        linear_graph)
from repro.core.resources import CLOUD_VM
from repro.kernels import substrate
from repro.kernels.ops import (decode_attention, flash_attention,
                               flash_attention_node, ssd_scan, ssd_scan_node)
from repro.kernels.ref import (decode_attention_ref, flash_attention_ref,
                               ssd_ref)
from repro.kernels.substrate import (KernelAutotuner, TuneRecord,
                                     normalize_cost_analysis, pad_axis_to,
                                     round_up, tpu_compiler_params)

TOL32 = dict(rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# compiler params
# ---------------------------------------------------------------------------

class TestCompilerParamsShim:
    def test_resolves_on_installed_jax(self):
        from jax.experimental.pallas import tpu as pltpu
        assert isinstance(tpu_compiler_params(), pltpu.CompilerParams)

    def test_constructs_with_dimension_semantics(self):
        params = tpu_compiler_params(
            dimension_semantics=("parallel", "arbitrary"))
        assert tuple(params.dimension_semantics) == ("parallel", "arbitrary")

    def test_unknown_kwargs_dropped(self):
        """An unknown field is refused by name, never silently dropped."""
        with pytest.raises(TypeError, match="kwarg_from_a_future"):
            tpu_compiler_params(dimension_semantics=("parallel",),
                                kwarg_from_a_future_jax_version=42)


# ---------------------------------------------------------------------------
# cost-analysis normalizer
# ---------------------------------------------------------------------------

class TestNormalizeCostAnalysis:
    def test_dict_passthrough(self):
        got = normalize_cost_analysis({"flops": 10, "bytes accessed": 3.5})
        assert got == {"flops": 10.0, "bytes accessed": 3.5}

    def test_none_and_junk(self):
        assert normalize_cost_analysis({"flops": 1.0, "note": "x"}) == \
            {"flops": 1.0}
        for junk in (None, "nope", [{"flops": 1.0}]):
            with pytest.raises(TypeError):
                normalize_cost_analysis(junk)

    def test_real_compiled_artifact(self):
        lowered = jax.jit(lambda x: jnp.tanh(x @ x)).lower(
            jax.ShapeDtypeStruct((8, 8), jnp.float32))
        cost = normalize_cost_analysis(lowered.compile().cost_analysis())
        assert cost.get("flops", 0.0) >= 2 * 8 * 8 * 8


# ---------------------------------------------------------------------------
# pad helpers
# ---------------------------------------------------------------------------

class TestPadHelpers:
    def test_round_up(self):
        assert round_up(200, 128) == 256
        assert round_up(256, 128) == 256
        assert round_up(1, 128) == 128
        with pytest.raises(ValueError):
            round_up(5, 0)

    def test_pad_axis_to(self):
        x = jnp.ones((2, 5, 3))
        y = pad_axis_to(x, 1, 8)
        assert y.shape == (2, 8, 3)
        np.testing.assert_array_equal(np.asarray(y[:, 5:]), 0.0)
        assert pad_axis_to(x, 1, 5) is x
        with pytest.raises(ValueError):
            pad_axis_to(x, 1, 4)


# ---------------------------------------------------------------------------
# uneven-shape parity vs reference kernels (CPU interpret mode)
# ---------------------------------------------------------------------------

class TestUnevenShapeParity:
    @pytest.mark.parametrize("causal", [True, False])
    @pytest.mark.parametrize("Sq,Sk", [(200, 200), (384, 200), (130, 257)])
    def test_flash_uneven(self, Sq, Sk, causal):
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(ks[0], (1, Sq, 4, 64))
        k = jax.random.normal(ks[1], (1, Sk, 2, 64))
        v = jax.random.normal(ks[2], (1, Sk, 2, 64))
        got = flash_attention(q, k, v, causal=causal, block_q=128,
                              block_k=128, interpret=True)
        want = flash_attention_ref(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL32)

    def test_flash_uneven_window_softcap(self):
        ks = jax.random.split(jax.random.PRNGKey(1), 3)
        q = jax.random.normal(ks[0], (1, 300, 4, 64))
        k = jax.random.normal(ks[1], (1, 300, 2, 64))
        v = jax.random.normal(ks[2], (1, 300, 2, 64))
        got = flash_attention(q, k, v, causal=True, window=70, softcap=30.0,
                              block_q=128, block_k=128, interpret=True)
        want = flash_attention_ref(q, k, v, causal=True, window=70,
                                   softcap=30.0)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL32)

    def test_decode_uneven_cache(self):
        ks = jax.random.split(jax.random.PRNGKey(2), 4)
        B, Smax, H, Hk, hd = 2, 300, 4, 2, 64
        q = jax.random.normal(ks[0], (B, H, hd))
        k = jax.random.normal(ks[1], (B, Smax, Hk, hd))
        v = jax.random.normal(ks[2], (B, Smax, Hk, hd))
        lengths = jnp.array([300, 123], jnp.int32)
        got = decode_attention(q, k, v, lengths, block_k=256, interpret=True)
        want = decode_attention_ref(q, k, v, lengths)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL32)

    def test_decode_padding_never_leaks(self):
        """Values in the padded tail must not affect the output."""
        ks = jax.random.split(jax.random.PRNGKey(3), 3)
        B, Smax, H, hd = 1, 200, 2, 64
        q = jax.random.normal(ks[0], (B, H, hd))
        k = jax.random.normal(ks[1], (B, Smax, H, hd))
        v = jax.random.normal(ks[2], (B, Smax, H, hd))
        lengths = jnp.array([Smax], jnp.int32)
        got = decode_attention(q, k, v, lengths, block_k=256, interpret=True)
        want = decode_attention_ref(q, k, v, lengths)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL32)

    @pytest.mark.parametrize("S,chunk", [(200, 128), (130, 64), (257, 128)])
    def test_ssd_uneven(self, S, chunk):
        ks = jax.random.split(jax.random.PRNGKey(4), 4)
        B, H, P, N = 1, 2, 32, 16
        x = jax.random.normal(ks[0], (B, S, H, P))
        log_a = -jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
        b = jax.random.normal(ks[2], (B, S, H, N))
        c = jax.random.normal(ks[3], (B, S, H, N))
        y, fin = ssd_scan(x, log_a, b, c, chunk=chunk, interpret=True)
        y_ref, fin_ref = ssd_ref(x, log_a, b, c)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(np.asarray(fin), np.asarray(fin_ref),
                                   rtol=1e-3, atol=1e-3)


# ---------------------------------------------------------------------------
# autotuner
# ---------------------------------------------------------------------------

def _fake_measure(best_params):
    """Deterministic measurement: ``best_params`` wins, everything else is
    slower in proportion to its distance from it."""
    best_key = json.dumps(best_params, sort_keys=True)

    def measure(fn, args):
        params = getattr(fn, "_params", None)
        if params is None:
            return 1.0
        return 0.1 if json.dumps(params, sort_keys=True) == best_key else 1.0
    return measure


def _tagged_factory(params):
    def fn(x):
        return x
    fn._params = dict(params)
    return fn


class TestKernelAutotuner:
    def test_picks_winner_and_caches(self):
        tuner = KernelAutotuner(measure=_fake_measure({"block_q": 64,
                                                       "block_k": 64}))
        args = (jnp.zeros((1, 8)),)
        rec = tuner.tune("flash_attention", _tagged_factory, args,
                         resource="cloud")
        assert rec.params == {"block_q": 64, "block_k": 64}
        assert rec.changed_default        # default is (128, 128)
        assert rec.default_time_s > rec.time_s
        # cached: same key returns the same record object
        assert tuner.tune("flash_attention", _tagged_factory, args,
                          resource="cloud") is rec
        # different resource -> separate sweep
        rec2 = tuner.tune("flash_attention", _tagged_factory, args,
                          resource="device")
        assert rec2 is not rec

    def test_trials_shared_across_resources(self):
        """Per-resource records, but the (host wall-clock) trial table is
        measured once — not once per resource."""
        calls = []

        def counting_measure(fn, args):
            calls.append(fn._params)
            return 1.0

        tuner = KernelAutotuner(measure=counting_measure)
        args = (jnp.zeros((1, 4)),)
        tuner.tune("ssd_scan", _tagged_factory, args, resource="edge1")
        n = len(calls)
        assert n > 0
        tuner.tune("ssd_scan", _tagged_factory, args, resource="cloud")
        assert len(calls) == n      # second resource reused the trials

    def test_config_key_separates_same_shape_nodes(self):
        """Same input shapes, different kernel options -> separate sweeps."""
        tuner = KernelAutotuner(measure=lambda fn, args: 1.0)
        args = (jnp.zeros((1, 4)),)
        r1 = tuner.tune("ssd_scan", _tagged_factory, args,
                        config_key='{"causal": true}')
        r2 = tuner.tune("ssd_scan", _tagged_factory, args,
                        config_key='{"causal": false}')
        assert r1 is not r2
        assert r1.shape_key != r2.shape_key

    def test_failed_candidates_skipped(self):
        def factory(params):
            if params.get("chunk") != 64:
                raise ValueError("unsupported block shape")
            return _tagged_factory(params)

        tuner = KernelAutotuner(measure=lambda fn, args: 0.5)
        rec = tuner.tune("ssd_scan", factory, (jnp.zeros((1, 4)),))
        assert rec.params == {"chunk": 64}
        # every refusal is recorded with its reason, none skipped unseen
        assert set(rec.refused) == set(rec.trials) ^ {
            json.dumps(p, sort_keys=True)
            for p in tuner.candidates["ssd_scan"]}
        assert all("unsupported block shape" in why
                   for why in rec.refused.values())

    def test_json_roundtrip(self):
        tuner = KernelAutotuner(measure=_fake_measure({"chunk": 32}))
        tuner.tune("ssd_scan", _tagged_factory, (jnp.zeros((2, 2)),))
        back = KernelAutotuner.from_json(tuner.to_json())
        assert len(back.records) == 1
        rec = next(iter(back.records.values()))
        assert isinstance(rec, TuneRecord)
        assert rec.params == {"chunk": 32}

    def test_wall_clock_tune_real_kernel(self):
        """End-to-end wall-clock sweep of the real flash kernel (small shape,
        two candidates) — must pick *some* candidate and rewrite the node."""
        node = flash_attention_node(interpret=True)
        g = linear_graph("attn-toy",
                         jax.ShapeDtypeStruct((1, 96, 2, 32), jnp.float32),
                         [node])
        tuner = KernelAutotuner(
            candidates={"flash_attention": [{"block_q": 32, "block_k": 32},
                                            {"block_q": 96, "block_k": 96}]},
            runs=1)
        blocks = fuse_blocks(g)
        recs = tuner.tune_block(blocks[-1], resource="cloud")
        assert len(recs) == 1
        assert recs[0].params in ({"block_q": 32, "block_k": 32},
                                  {"block_q": 96, "block_k": 96},
                                  {"block_q": 128, "block_k": 128})
        assert node.kernel_params == recs[0].params


class TestTunedTimingsFlowIntoDB:
    def test_benchmark_records_carry_tuned_params(self):
        node = ssd_scan_node(state_dim=8, interpret=True)
        g = linear_graph("ssd-toy",
                         jax.ShapeDtypeStruct((1, 64, 1, 16), jnp.float32),
                         [node])
        res = [Resource("cloud", "cloud", CLOUD_VM, speed_factor=1.0)]
        tuner = KernelAutotuner(
            candidates={"ssd_scan": [{"chunk": 16}, {"chunk": 64}]}, runs=1)
        db = benchmark_model(g, res, TimingProvider(tuner=tuner), runs=1)
        recs = [r for r in db.records["cloud"] if r.tuned_params]
        assert recs, "no benchmark record carries tuned block sizes"
        tuned = next(iter(recs[0].tuned_params.values()))
        assert "chunk" in tuned
        # tuned params survive the DB's JSON round-trip (offline contract)
        db2 = BenchmarkDB.from_json(db.to_json())
        recs2 = [r for r in db2.records["cloud"] if r.tuned_params]
        assert recs2 and recs2[0].tuned_params == recs[0].tuned_params

    def test_untuned_provider_keeps_empty_params(self):
        node = ssd_scan_node(state_dim=8, interpret=True)
        g = linear_graph("ssd-toy2",
                         jax.ShapeDtypeStruct((1, 64, 1, 16), jnp.float32),
                         [node])
        res = [Resource("cloud", "cloud", CLOUD_VM, speed_factor=1.0)]
        db = benchmark_model(g, res, AnalyticProvider(), runs=1)
        assert all(not r.tuned_params for r in db.records["cloud"])
