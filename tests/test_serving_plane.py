"""Serving plane: arrival traces, router admission/shedding, metrics,
bucketed prefill, warmup, queue-wait stats, and live re-plan swaps."""

import dataclasses
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (AnalyticProvider, Query, Resource, Scission,
                        THROUGHPUT, paper_network, FOUR_G)
from repro.core.partition import PartitionConfig, Segment
from repro.core.resources import CLOUD_VM, EDGE_BOX_1, RPI4
from repro.models import build_model, cnn_zoo, get_config
from repro.runtime.elastic import ElasticController
from repro.serving import (ExecutorBackend, PROMPT_BUCKETS, Request, Router,
                           ServingEngine, StageQueue, VirtualBackend,
                           bucket_for, bursty_diurnal_trace, empirical_rate,
                           mean, percentile, poisson_trace, row_bucket)
from repro.serving.router import stage_layout


def _point(batch=2, replicas=(1, 1)):
    return PartitionConfig(
        model="m", segments=(Segment("edge1", 0, 3), Segment("cloud", 3, 8)),
        latency_s=0.12, compute_s={"edge1": 0.04, "cloud": 0.05},
        comm_s=0.02, transfer_bytes=1e5, input_comm_s=0.01,
        stage_compute_s=(0.04, 0.05), stage_comm_s=(0.02,),
        batch_size=batch, replicas=replicas)


# ---------------------------------------------------------------------------
# arrival traces
# ---------------------------------------------------------------------------

class TestTraces:
    def test_poisson_deterministic(self):
        a = poisson_trace(rate_rps=10, horizon_s=20, seed=7)
        b = poisson_trace(rate_rps=10, horizon_s=20, seed=7)
        assert a == b
        c = poisson_trace(rate_rps=10, horizon_s=20, seed=8)
        assert a != c

    def test_poisson_empirical_rate(self):
        tr = poisson_trace(rate_rps=50, horizon_s=60, seed=0)
        # ~3000 arrivals: the empirical rate concentrates near nominal
        assert empirical_rate(tr) == pytest.approx(50, rel=0.10)
        assert all(0 <= a.t < 60 for a in tr)
        assert [a.t for a in tr] == sorted(a.t for a in tr)
        assert [a.rid for a in tr] == list(range(len(tr)))

    def test_poisson_prompt_len_range(self):
        tr = poisson_trace(rate_rps=20, horizon_s=20, seed=1,
                           prompt_len=(4, 9), max_new_tokens=3)
        assert all(4 <= a.prompt_len <= 9 for a in tr)
        assert all(a.max_new_tokens == 3 for a in tr)
        assert len({a.prompt_len for a in tr}) > 1

    def test_poisson_validation(self):
        with pytest.raises(ValueError, match="rate"):
            poisson_trace(rate_rps=0, horizon_s=10)
        with pytest.raises(ValueError, match="horizon"):
            poisson_trace(rate_rps=1, horizon_s=0)

    def test_bursty_deterministic_and_bounded(self):
        kw = dict(base_rps=5, peak_rps=40, horizon_s=40, period_s=20,
                  seed=3, burst_factor=2.0, burst_every_s=10, burst_len_s=1)
        a = bursty_diurnal_trace(**kw)
        assert a == bursty_diurnal_trace(**kw)
        r = empirical_rate(a)
        # diurnal mean is (base+peak)/2; bursts only add — stay in band
        assert 5 < r < 80

    def test_bursty_peak_exceeds_base_rate(self):
        """The diurnal envelope is visible: mid-period windows (sin^2 near
        1) are denser than start-of-period windows (sin^2 near 0)."""
        tr = bursty_diurnal_trace(base_rps=2, peak_rps=50, horizon_s=40,
                                  period_s=40, seed=0)
        early = sum(a.t < 8 for a in tr)           # sin^2 < 0.35
        mid = sum(16 <= a.t < 24 for a in tr)      # sin^2 > 0.9
        assert mid > 3 * early

    def test_bursty_validation(self):
        with pytest.raises(ValueError, match="base_rps"):
            bursty_diurnal_trace(base_rps=5, peak_rps=2, horizon_s=10,
                                 period_s=5)
        with pytest.raises(ValueError, match="burst_factor"):
            bursty_diurnal_trace(base_rps=1, peak_rps=2, horizon_s=10,
                                 period_s=5, burst_factor=0.5)


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------

class TestPercentile:
    def test_nearest_rank_is_a_sample(self):
        xs = [5.0, 1.0, 9.0, 3.0, 7.0]
        assert percentile(xs, 50) == 5.0           # median of odd length
        assert percentile(xs, 100) == 9.0
        assert percentile(xs, 1) == 1.0
        for p in (10, 25, 50, 75, 90, 99):
            assert percentile(xs, p) in xs

    def test_exact_rank_boundaries(self):
        assert percentile([1, 2, 3, 4], 50) == 2   # rank ceil(2.0) = 2
        assert percentile([1, 2, 3, 4], 75) == 3
        assert percentile([1, 2, 3, 4], 76) == 4
        # p99 of 10 samples is the max (rank ceil(9.9) = 10)
        assert percentile(list(range(10)), 99) == 9

    def test_empty_and_validation(self):
        assert percentile([], 50) == 0.0
        assert mean([]) == 0.0
        assert mean([1.0, 3.0]) == 2.0
        with pytest.raises(ValueError, match="percentile"):
            percentile([1], 0)
        with pytest.raises(ValueError, match="percentile"):
            percentile([1], 101)


class TestStageQueue:
    def test_bounded_push(self):
        q = StageQueue(limit=2)
        assert q.push("a") and q.push("b")
        assert not q.push("c")
        assert q.offered == 3 and q.rejected == 1
        assert q.pop() == "a" and len(q) == 1
        assert q.peak_depth == 2
        assert q.depth_histogram == {0: 1, 1: 1, 2: 1}

    def test_bucket_for(self):
        assert bucket_for(1, PROMPT_BUCKETS) == 16
        assert bucket_for(16, PROMPT_BUCKETS) == 16
        assert bucket_for(17, PROMPT_BUCKETS) == 32
        assert bucket_for(5000, PROMPT_BUCKETS) == 5000   # escape hatch
        with pytest.raises(ValueError):
            bucket_for(0, PROMPT_BUCKETS)

    @pytest.mark.parametrize("width", [1, 5, 8, 16])
    def test_row_bucket(self, width):
        got = [row_bucket(k, width) for k in range(1, width + 1)]
        for k, r in enumerate(got, 1):
            # covers k, within the width, and the least power of two that
            # does unless the width caps it
            assert k <= r <= width
            assert r == width or (r & (r - 1) == 0 and r // 2 < k)
        assert got == sorted(got) and got[-1] == width
        for k in (0, width + 1):
            with pytest.raises(ValueError):
                row_bucket(k, width)

    def test_row_bucket_examples(self):
        assert [row_bucket(k, 16) for k in (1, 2, 3, 5, 9, 16)] == \
            [1, 2, 4, 8, 16, 16]
        assert [row_bucket(k, 5) for k in range(1, 6)] == [1, 2, 4, 4, 5]


# ---------------------------------------------------------------------------
# router
# ---------------------------------------------------------------------------

class TestRouter:
    def test_under_capacity_completes_everything(self):
        point = _point()
        tr = poisson_trace(rate_rps=0.4 * point.throughput_rps,
                           horizon_s=60, seed=0)
        rep = Router(point, slo_s=2.0).serve(tr)
        assert rep.arrivals == len(tr)
        assert rep.shed == 0 and rep.completed == rep.arrivals
        assert rep.goodput_rps == pytest.approx(rep.offered_rps, rel=0.15)
        assert rep.latency_p50_s <= rep.latency_p99_s
        assert rep.slo_violations == 0

    def test_saturated_goodput_tracks_prediction(self):
        point = _point()
        pred = point.throughput_rps
        tr = poisson_trace(rate_rps=1.3 * pred, horizon_s=120, seed=1)
        rep = Router(point, slo_s=None).serve(tr)
        assert rep.goodput_rps == pytest.approx(pred, rel=0.10)
        assert rep.arrivals == rep.completed + rep.shed

    def test_replicas_scale_capacity(self):
        """Doubling the bottleneck stage's replicas roughly doubles the
        sustained rate (comm hops become the new bottleneck)."""
        lo = Router(_point()).serve(
            poisson_trace(rate_rps=120, horizon_s=60, seed=2))
        hi = Router(_point(replicas=(2, 2))).serve(
            poisson_trace(rate_rps=120, horizon_s=60, seed=2))
        assert hi.goodput_rps > 1.5 * lo.goodput_rps

    def test_queue_full_sheds(self):
        point = _point()
        tr = poisson_trace(rate_rps=5 * point.throughput_rps,
                           horizon_s=60, seed=3)
        rep = Router(point, queue_limit=4).serve(tr)
        assert rep.shed > 0
        assert rep.shed_reasons.get("queue-full", 0) > 0
        assert rep.arrivals == rep.completed + rep.shed

    def test_slo_sheds_at_front_door(self):
        point = _point()
        tr = poisson_trace(rate_rps=3 * point.throughput_rps,
                           horizon_s=60, seed=4)
        slo = 4 * point.latency_s
        rep = Router(point, slo_s=slo, queue_limit=None).serve(tr)
        assert rep.shed_reasons.get("slo", 0) > 0
        assert rep.arrivals == rep.completed + rep.shed
        # admission control did its job: completions honor the SLO (the
        # shadow estimate is exact for full batches; partial-batch age-out
        # may add bounded extra wait)
        assert rep.slo_violations <= 0.1 * rep.completed

    def test_arrivals_must_be_ordered(self):
        r = Router(_point())
        from repro.serving import Arrival
        r.offer(Arrival(t=1.0, rid=0))
        with pytest.raises(ValueError, match="time order"):
            r.offer(Arrival(t=0.5, rid=1))

    def test_queue_depth_histogram_sampled(self):
        point = _point()
        tr = poisson_trace(rate_rps=2 * point.throughput_rps,
                           horizon_s=30, seed=5)
        rep = Router(point).serve(tr)
        assert sum(rep.queue_depth_hist.values()) == rep.arrivals
        assert rep.queue_wait_p99_s >= rep.queue_wait_mean_s >= 0

    def test_live_swap_drops_nothing(self):
        point = _point()
        tr = poisson_trace(rate_rps=1.5 * point.throughput_rps,
                           horizon_s=60, seed=6)
        r = Router(point)
        for a in tr:
            if a.t >= 30 and not r.swaps:
                drained = r.set_operating_point(
                    dataclasses.replace(point, replicas=(2, 2)))
                assert drained >= 30
            r.offer(a)
        r.flush()
        rep = r.report()
        assert rep.swaps == 1
        assert rep.arrivals == rep.completed + rep.shed
        assert rep.completed > 0

    def test_on_plan_adapter(self):
        r = Router(_point(batch=2))
        new = _point(batch=4)
        r.on_plan(SimpleNamespace(config=new))
        assert r.point is new and r.width == 4
        assert len(r.swaps) == 1

    def test_whole_model_point_single_stage(self):
        """A point evaluated without per-stage times serves as one stage
        at its end-to-end latency."""
        point = PartitionConfig(
            model="m", segments=(Segment("cloud", 0, 8),), latency_s=0.2,
            compute_s={"cloud": 0.2}, comm_s=0.0, transfer_bytes=0.0)
        assert stage_layout(point) == [("compute", 0.2, 1)]
        rep = Router(point).serve(poisson_trace(2, 20, seed=0))
        assert rep.completed == rep.arrivals


# ---------------------------------------------------------------------------
# elastic controller -> router wiring
# ---------------------------------------------------------------------------

class TestElasticWiring:
    def _scission(self):
        res = [Resource("device", "device", RPI4),
               Resource("edge1", "edge", EDGE_BOX_1),
               Resource("cloud", "cloud", CLOUD_VM)]
        net = paper_network(FOUR_G, edges=("edge1",), clouds=("cloud",))
        return Scission(resources=res, network=net, source="device",
                        provider=AnalyticProvider(), runs=1)

    def test_replan_swaps_router_live(self):
        s = self._scission()
        s.benchmark(cnn_zoo.build("MobileNet"))
        ctl = ElasticController(s, "MobileNet",
                                query=Query(objective=THROUGHPUT))
        router = Router(ctl.current)
        ctl.add_listener(router.on_plan)
        tr = poisson_trace(rate_rps=1.2 * ctl.current.throughput_rps,
                           horizon_s=20, seed=0)
        half = len(tr) // 2
        for a in tr[:half]:
            router.offer(a)
        lost = next(r for r in ctl.current.resources if r != "device")
        ctl.on_resource_lost(lost)
        assert len(router.swaps) == 1          # listener fired
        assert router.point is ctl.current
        for a in tr[half:]:
            router.offer(a)
        router.flush()
        rep = router.report()
        assert rep.arrivals == rep.completed + rep.shed
        assert rep.swaps == 1

    def test_listeners_not_called_for_prior_plans(self):
        s = self._scission()
        s.benchmark(cnn_zoo.build("MobileNet"))
        ctl = ElasticController(s, "MobileNet")
        seen = []
        ctl.add_listener(seen.append)
        assert seen == []                      # initial plan predates it
        ev = ctl.on_network_change(paper_network(
            FOUR_G, edges=("edge1",), clouds=("cloud",)))
        assert seen == [ev]


# ---------------------------------------------------------------------------
# executor backend (runtime pipeline as the plane's substrate)
# ---------------------------------------------------------------------------

class TestExecutorBackend:
    def test_measured_stage_times(self):
        g = cnn_zoo.build("MobileNet")
        res = [Resource("device", "device", RPI4),
               Resource("edge1", "edge", EDGE_BOX_1),
               Resource("cloud", "cloud", CLOUD_VM)]
        net = paper_network(FOUR_G, edges=("edge1",), clouds=("cloud",))
        s = Scission(resources=res, network=net, source="device",
                     provider=AnalyticProvider(), runs=1)
        s.benchmark(g)
        best = s.query(g.name, Query(top_n=1, must_use=("device", "edge1")),
                       input_bytes=150e3).best

        def make_input(batch):
            return jnp.zeros(g.input_spec.shape, g.input_spec.dtype)

        backend = ExecutorBackend(g, make_input, network=s.network,
                                  source="device", runs=2)
        router = Router(best, backend=backend)
        times = backend.stage_times()
        assert len(times) == len(stage_layout(best))
        assert all(t >= 0 for t in times)
        # measured compute replaces predicted; hops keep modeled times
        kinds = [k for k, _, _ in stage_layout(best)]
        assert sum(times[i] for i, k in enumerate(kinds)
                   if k == "compute") > 0
        rep = router.serve(poisson_trace(rate_rps=5, horizon_s=5, seed=0))
        assert rep.completed == rep.arrivals


# ---------------------------------------------------------------------------
# serving engine: bucketed prefill, warmup, queue-wait stats
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def small_model():
    cfg = get_config("granite-8b").replace(
        n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
        d_ff=128, vocab=128, remat=False, q_chunk=32, loss_seq_chunk=None)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _greedy_reference(model, params, prompt, n_new, max_len=64):
    cache = model.init_cache(batch=1, max_len=max_len)
    logits, cache = jax.jit(model.prefill)(
        params, jnp.asarray(prompt, jnp.int32)[None], cache)
    toks = [int(jnp.argmax(logits[0, -1]))]
    clen = len(prompt)
    step = jax.jit(model.decode_step)
    for _ in range(n_new - 1):
        logits, cache = step(params, jnp.asarray([[toks[-1]]], jnp.int32),
                             cache, jnp.int32(clen))
        toks.append(int(jnp.argmax(logits[0, -1])))
        clen += 1
    return toks


def _serves_greedy(small_model, lens, width, seed):
    """Requests of prompt lengths ``lens``, admitted in one tick of a
    ``width``-slot engine, decode exactly like per-request greedy."""
    cfg, model, params = small_model
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab, n) for n in lens]
    n_new = 4
    want = [_greedy_reference(model, params, p, n_new) for p in prompts]
    eng = ServingEngine(model, params, width=width, max_len=64)
    assert eng.prompt_buckets is not None      # attn model: auto on
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=i, prompt=p, max_new_tokens=n_new))
    done = sorted(eng.run(), key=lambda r: r.rid)
    assert len(done) == len(prompts)
    for r, w in zip(done, want):
        assert r.tokens == w, (r.rid, r.tokens, w)


class TestEnginePlane:
    def test_bucketed_prefill_matches_greedy_mixed_lengths(self, small_model):
        """Same-tick admissions across bucket boundaries (lengths 3..21,
        buckets 16/32/64) must decode exactly like per-request greedy."""
        _serves_greedy(small_model, (3, 7, 16, 17, 21), width=5, seed=9)

    @pytest.mark.parametrize("lens", [(7,), (3, 17), (5, 16, 21)],
                             ids=["1row", "2rows", "3rows"])
    def test_few_rows_of_a_wide_engine_match_greedy(self, small_model,
                                                    lens):
        """One, two and three admissions prefill over row buckets 1, 2
        and 4 of a width-8 engine and decode exactly like greedy."""
        _serves_greedy(small_model, lens, width=8, seed=13)

    def test_warmup_compiles_every_row_bucket(self, small_model):
        """After warmup(), an admission of any k up to the width compiles
        no new prefill program: one per row bucket and length bucket."""
        cfg, model, params = small_model
        rng = np.random.default_rng(14)
        compiled = []

        def listen(event, secs, fun_name="", **_):
            if event == "/jax/core/compile/backend_compile_duration" \
                    and "prefill_step" in fun_name:
                compiled.append(fun_name)

        def prompt():
            return rng.integers(0, cfg.vocab, int(rng.integers(2, 18)))

        jax.monitoring.register_event_duration_secs_listener(listen)
        try:
            eng = ServingEngine(model, params, width=5, max_len=32)
            eng.submit(Request(rid=0, prompt=prompt(), max_new_tokens=2))
            eng.warmup()
            assert len(compiled) == 4               # rows 1, 2, 4, 5 at 16
            eng.run()
            for k in range(1, eng.width + 1):
                for i in range(k):
                    eng.submit(Request(rid=i, prompt=prompt(),
                                       max_new_tokens=2))
                assert len(eng.run()) == k
        finally:
            jax.monitoring.unregister_event_duration_listener(listen)
        assert len(compiled) == 4

    def test_exact_path_still_available(self, small_model):
        cfg, model, params = small_model
        rng = np.random.default_rng(10)
        prompt = rng.integers(0, cfg.vocab, 5)
        want = _greedy_reference(model, params, prompt, 3)
        eng = ServingEngine(model, params, width=2, max_len=64,
                            prompt_buckets=None)
        eng.submit(Request(rid=0, prompt=prompt, max_new_tokens=3))
        (done,) = eng.run()
        assert done.tokens == want

    def test_single_token_prompt(self, small_model):
        cfg, model, params = small_model
        eng = ServingEngine(model, params, width=1, max_len=32)
        eng.submit(Request(rid=0, prompt=np.array([7]), max_new_tokens=2))
        (done,) = eng.run()
        assert len(done.tokens) == 2

    def test_warmup_precompiles(self, small_model):
        cfg, model, params = small_model
        rng = np.random.default_rng(11)
        reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab, 6),
                        max_new_tokens=3) for i in range(3)]
        eng = ServingEngine(model, params, width=2, max_len=32)
        for r in reqs:
            eng.submit(r)
        assert eng.warmup() is eng                 # chains; idempotent
        eng.warmup()
        done = eng.run()
        assert len(done) == 3
        # warmup left the engine untouched: nothing admitted, pool empty
        eng2 = ServingEngine(model, params, width=2, max_len=32).warmup()
        assert len(eng2.pool.free) == 2 and not eng2.active

    def test_queue_wait_stats(self, small_model):
        cfg, model, params = small_model
        rng = np.random.default_rng(12)
        # width 1 + 4 requests: later requests measurably wait for a slot
        eng = ServingEngine(model, params, width=1, max_len=32)
        for i in range(4):
            eng.submit(Request(rid=i, prompt=rng.integers(0, cfg.vocab, 4),
                               max_new_tokens=3))
        done = eng.run()
        assert all(r.admitted_at is not None for r in done)
        assert all(r.queue_wait_s >= 0 for r in done)
        assert eng.stats.queue_wait_p99_s >= eng.stats.queue_wait_mean_s > 0

    def test_prompt_too_long_rejected(self, small_model):
        cfg, model, params = small_model
        eng = ServingEngine(model, params, width=1, max_len=16)
        with pytest.raises(ValueError, match="max_len"):
            eng.submit(Request(rid=0, prompt=np.zeros(16, np.int32)))


class TestCompatShim:
    def test_old_engine_imports_still_work(self):
        from repro.serving.engine import (KVCachePool, Request,
                                          ServingEngine, ServingStats,
                                          simulate_pipeline_throughput)
        assert callable(simulate_pipeline_throughput)
        assert ServingStats().requests_per_s == 0.0
