"""scission-lint v2: cost-model soundness (SCN4xx), jaxpr dataflow lint
(SCN5xx), TPU tiling analysis (SCN204-207), and their wiring.

Each new diagnostic code has a minimal triggering fixture; clean inputs
must produce zero findings (the soundness direction).  The tiling pass is
additionally exercised through the autotuner (misaligned candidates are
pruned before measurement, winners unchanged) and the serving registry
(``adopt_tuned_params`` changes the actual chunking of the model-zoo
attention/SSD paths, observable in their jaxprs).
"""

import json
import math
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.cost_lint import (lint_cost, lint_cost_db,
                                      lint_cost_model, lint_network)
from repro.analysis.diagnostics import ERROR, WARNING
from repro.analysis.jaxpr_lint import lint_block, lint_blocks
from repro.analysis.tiling import (analyze_tiling, lint_tiling, min_tile,
                                   misaligned_candidates)
from repro.core import (Link, NetworkModel, Query, QueryEngine, Resource,
                        linear_graph)
from repro.core.bench import (AnalyticProvider, BenchmarkDB, BlockBenchmark,
                              benchmark_model)
from repro.core.graph import LayerNode, fuse_blocks
from repro.core.partition import CostModel
from repro.core.resources import CLOUD_VM, EDGE_BOX_1, RPI4
from repro.kernels.substrate import (DEFAULT_PARAMS, KernelAutotuner,
                                     adopt_tuned_params, clear_tuned_params,
                                     kernel_for_params, serving_param)

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                      # container has no hypothesis
    HAVE_HYPOTHESIS = False

ROOT = pathlib.Path(__file__).resolve().parents[1]


def _codes(diags):
    return {d.code for d in diags}


def _db(n_blocks=3, resources=("edge", "cloud"), batches=(1, 4)):
    """A clean v2 DB: positive times, monotone profiles, full coverage."""
    db = BenchmarkDB(model="lint", n_blocks=n_blocks)
    for k, r in enumerate(resources):
        recs = []
        for i in range(n_blocks):
            t = 0.001 * (i + 1) * (k + 1)
            profile = {b: (t * b * (1.0 + 0.1 * b), 1024 * (i + 1) * b)
                       for b in batches}
            profile[1] = (t, 1024 * (i + 1))
            recs.append(BlockBenchmark(
                block=i, resource=r, mean_time_s=t, std_time_s=0.0,
                output_bytes=1024 * (i + 1), runs=1,
                batch_profile=profile))
        db.records[r] = recs
    return db


def _fleet():
    return [Resource("edge", "edge", EDGE_BOX_1),
            Resource("cloud", "cloud", CLOUD_VM)]


# ---------------------------------------------------------------------------
# SCN401-403: BenchmarkDB soundness
# ---------------------------------------------------------------------------

class TestCostDbLint:
    def test_clean_db_zero_findings(self):
        assert lint_cost_db(_db()) == []

    def test_scn401_negative_time(self):
        db = _db()
        db.records["edge"][0].mean_time_s = -0.5
        diags = lint_cost_db(db)
        (d,) = [x for x in diags if x.code == "SCN401"]
        assert d.severity == ERROR and d.subject == "edge/block0"
        assert "dominance" in d.message      # names the voided guarantee

    def test_scn401_nan_bytes_and_profile(self):
        db = _db()
        db.records["cloud"][1].output_bytes = float("nan")
        db.records["cloud"][2].batch_profile[4] = (float("inf"), 4096)
        diags = [d for d in lint_cost_db(db) if d.code == "SCN401"]
        assert {d.subject for d in diags} == {"cloud/block1", "cloud/block2"}

    def test_scn402_non_monotone_profile(self):
        db = _db()
        db.records["edge"][1].batch_profile[4] = (0.0001, 8192)
        diags = [d for d in lint_cost_db(db) if d.code == "SCN402"]
        assert len(diags) == 1 and diags[0].severity == WARNING
        assert diags[0].subject == "edge/block1"

    def test_scn402_skipped_when_non_finite(self):
        # a NaN profile point is SCN401's finding, not a bogus SCN402
        db = _db()
        db.records["edge"][0].batch_profile[4] = (float("nan"), 8192)
        codes = _codes(lint_cost_db(db))
        assert "SCN401" in codes and "SCN402" not in codes

    def test_scn403_batch_coverage_gap(self):
        db = _db()
        for rec in db.records["edge"]:
            rec.batch_profile.pop(4)
        diags = [d for d in lint_cost_db(db) if d.code == "SCN403"]
        assert len(diags) == 1 and diags[0].subject == "edge"
        assert "[4]" in diags[0].message

    def test_resources_filter_ignores_stale_records(self):
        db = _db()
        db.records["gone"] = [BlockBenchmark(
            block=0, resource="gone", mean_time_s=-1.0, std_time_s=0.0,
            output_bytes=1, runs=1)]
        assert lint_cost_db(db, resources=["edge", "cloud"]) == []

    def test_seeded_random_monotone_dbs_are_clean(self):
        # soundness property: any DB with positive, batch-monotone
        # profiles and full coverage yields zero findings
        rng = np.random.default_rng(0)
        for _ in range(25):
            db = BenchmarkDB(model="rnd", n_blocks=3)
            batches = (1, 2, 8)
            for r in ("a", "b", "c"):
                recs = []
                for i in range(3):
                    t = float(rng.uniform(1e-5, 1e-2))
                    prof, cur = {}, t
                    for b in batches:
                        cur = cur * b * float(rng.uniform(1.0, 1.5)) \
                            if b > 1 else t
                        prof[b] = (cur, 128 * b)
                    recs.append(BlockBenchmark(
                        block=i, resource=r, mean_time_s=t, std_time_s=0.0,
                        output_bytes=128, runs=1, batch_profile=prof))
                db.records[r] = recs
            assert lint_cost_db(db) == []

    if HAVE_HYPOTHESIS:
        @settings(max_examples=50, deadline=None)
        @given(st.lists(st.floats(1e-6, 1.0), min_size=1, max_size=4),
               st.lists(st.floats(1.0, 2.0), min_size=3, max_size=3))
        def test_hypothesis_monotone_profiles_are_clean(self, times, growth):
            db = BenchmarkDB(model="hyp", n_blocks=len(times))
            recs = []
            for i, t in enumerate(times):
                prof, cur, b = {1: (t, 64)}, t, 1
                for g in growth:
                    b *= 2
                    cur = cur * 2 * g
                    prof[b] = (cur, 64 * b)
                recs.append(BlockBenchmark(
                    block=i, resource="r", mean_time_s=t, std_time_s=0.0,
                    output_bytes=64, runs=1, batch_profile=prof))
            db.records["r"] = recs
            assert lint_cost_db(db) == []


# ---------------------------------------------------------------------------
# SCN404-406: NetworkModel soundness
# ---------------------------------------------------------------------------

class TestNetworkLint:
    def test_clean_network(self):
        net = NetworkModel(default=Link("wired", 0.005, 1e8))
        net.connect("edge", "cloud", Link("wan", 0.02, 1e7))
        assert lint_network(net) == []

    def test_scn404_negative_latency_default(self):
        net = NetworkModel(default=Link("bad", -0.01, 1e8))
        diags = [d for d in lint_network(net) if d.code == "SCN404"]
        assert len(diags) == 1 and diags[0].severity == ERROR
        assert diags[0].subject == "default"

    def test_scn404_nonpositive_bandwidth_link(self):
        net = NetworkModel(default=Link("wired", 0.005, 1e8))
        net.connect("a", "b", Link("dead", 0.01, 0.0), symmetric=False)
        diags = [d for d in lint_network(net) if d.code == "SCN404"]
        assert len(diags) == 1 and diags[0].subject == "a->b"

    def test_infinite_bandwidth_is_fine(self):
        net = NetworkModel(default=Link("instant", 0.0, float("inf")))
        assert lint_network(net) == []

    def test_scn405_asymmetric_explicit_pair(self):
        net = NetworkModel(default=Link("wired", 0.005, 1e8))
        net.connect("a", "b", Link("up", 0.01, 1e6), symmetric=False)
        net.connect("b", "a", Link("down", 0.01, 1e8), symmetric=False)
        diags = [d for d in lint_network(net) if d.code == "SCN405"]
        assert len(diags) == 1 and diags[0].subject == "a<->b"

    def test_symmetric_pair_is_clean(self):
        net = NetworkModel(default=Link("wired", 0.005, 1e8))
        net.connect("a", "b", Link("wan", 0.02, 1e6), symmetric=True)
        assert [d for d in lint_network(net) if d.code == "SCN405"] == []

    def test_scn406_costly_self_link(self):
        net = NetworkModel(default=Link("wired", 0.005, 1e8))
        net.connect("a", "a", Link("slow-self", 1.0, 1e3), symmetric=False)
        diags = [d for d in lint_network(net) if d.code == "SCN406"]
        assert len(diags) == 1 and diags[0].subject == "a->a"


# ---------------------------------------------------------------------------
# SCN407: cost-model composition
# ---------------------------------------------------------------------------

def _cost(db=None, batch=1):
    return CostModel(db=db or _db(), resources=_fleet(),
                     network=NetworkModel(default=Link("wired", 0.005, 1e8)),
                     source="edge", input_bytes=4096.0, batch_size=batch)


class TestCostModelLint:
    def test_clean_cost_model(self):
        assert lint_cost_model(_cost()) == []

    def test_clean_cost_model_batched(self):
        assert lint_cost_model(_cost(batch=4)) == []

    def test_scn407_broken_segment_time(self):
        class Broken(CostModel):
            def segment_time(self, r, s, e):
                return super().segment_time(r, s, e) * 1.5

        broken = Broken(db=_db(), resources=_fleet(),
                        network=NetworkModel(
                            default=Link("wired", 0.005, 1e8)),
                        source="edge", input_bytes=4096.0, batch_size=1)
        diags = [d for d in lint_cost_model(broken) if d.code == "SCN407"]
        assert diags and all(d.severity == ERROR for d in diags)
        assert any("additive" in d.message for d in diags)

    def test_skips_resources_scn401_owns(self):
        db = _db()
        for rec in db.records["edge"]:
            rec.mean_time_s = float("nan")
        # the composition pass must not crash or double-report; the full
        # pass still carries the SCN401s
        assert lint_cost_model(_cost(db)) == []
        codes = _codes(lint_cost(db, cost=_cost(db)))
        assert "SCN401" in codes and "SCN407" not in codes


# ---------------------------------------------------------------------------
# SCN5xx: jaxpr dataflow lint
# ---------------------------------------------------------------------------

def _dense_node(name, d=8, apply=None):
    w = jnp.eye(d) * 0.5
    return LayerNode(name=name, kind="dense",
                     apply=apply or (lambda x, w=w: jnp.tanh(x @ w)),
                     flops=2.0 * d * d)


def _graph_of(*nodes, d=8):
    return linear_graph("jx", jax.ShapeDtypeStruct((1, 4, d), jnp.float32),
                        list(nodes))


class TestJaxprLint:
    def test_clean_graph_zero_findings(self):
        g = _graph_of(_dense_node("a"), _dense_node("b"))
        assert lint_blocks(fuse_blocks(g)) == []

    def test_scn501_float64_leakage(self):
        with jax.enable_x64(True):
            g = _graph_of(_dense_node(
                "f64", apply=lambda x: (x.astype(jnp.float64) * 2.0)
                .astype(jnp.float32)))
            diags = lint_blocks(fuse_blocks(g))
        d = next(x for x in diags if x.code == "SCN501")
        assert d.severity == WARNING and "float64" in d.message

    def test_scn502_db_byte_disagreement(self):
        g = _graph_of(_dense_node("a"))
        blocks = list(fuse_blocks(g))
        db = benchmark_model(g, [Resource("cloud", "cloud", CLOUD_VM)],
                             AnalyticProvider(), runs=1, blocks=blocks)
        assert lint_block(blocks[0], db=db) == []
        db.records["cloud"][0].output_bytes += 64       # tamper
        diags = lint_block(blocks[0], db=db)
        d = next(x for x in diags if x.code == "SCN502")
        assert "BenchmarkDB.output_bytes" in d.message

    def test_scn503_host_callback(self):
        def apply(x):
            jax.debug.callback(lambda v: None, x.sum())
            return x * 2.0

        g = _graph_of(_dense_node("cb", apply=apply))
        diags = lint_blocks(fuse_blocks(g))
        d = next(x for x in diags if x.code == "SCN503")
        assert d.severity == ERROR and "debug_callback" in d.message

    def test_scn503_untraceable_block(self):
        # a node whose apply was swapped post-trace for a host-concretizing
        # one: graph.trace() never saw it, only the block lint can
        g = _graph_of(_dense_node("a"))
        g.nodes[1].apply = lambda x: jnp.asarray(np.asarray(x) + 1.0)
        diags = lint_blocks(fuse_blocks(g))
        assert [d.code for d in diags] == ["SCN503"]
        assert "abstract tracing" in diags[0].message

    def test_scn504_subf32_accumulation_on_kernel_block(self):
        w = jnp.eye(8, dtype=jnp.bfloat16)

        def apply(x):
            y = x.astype(jnp.bfloat16) @ w              # bf16 dot_general
            return y.astype(jnp.float32)

        node = LayerNode(name="k", kind="attention", apply=apply,
                         kernel="flash_attention")
        g = _graph_of(node)
        diags = lint_blocks(fuse_blocks(g))
        assert "SCN504" in _codes(diags)
        # same dataflow without the kernel marker is plain mixed precision
        g2 = _graph_of(LayerNode(name="nk", kind="dense", apply=apply))
        assert "SCN504" not in _codes(lint_blocks(fuse_blocks(g2)))

    def test_kernel_demo_graph_is_clean(self):
        from repro.kernels.ops import flash_attention_node, ssd_scan_node
        g = linear_graph(
            "demo", jax.ShapeDtypeStruct((1, 128, 2, 32), jnp.float32),
            [flash_attention_node("attn", interpret=True),
             ssd_scan_node("ssd", state_dim=16, interpret=True)])
        assert lint_blocks(fuse_blocks(g)) == []


# ---------------------------------------------------------------------------
# SCN204-207: tiling analysis + autotuner pruning
# ---------------------------------------------------------------------------

_F32ARG = (jax.ShapeDtypeStruct((1, 256, 2, 64), jnp.float32),)
_BF16ARG = (jax.ShapeDtypeStruct((1, 256, 2, 64), jnp.bfloat16),)


class TestTiling:
    def test_min_tile_table(self):
        assert min_tile(jnp.float32) == (8, 128)
        assert min_tile(jnp.bfloat16) == (16, 128)
        assert min_tile(jnp.int8) == (32, 128)
        assert min_tile(jnp.float64) == (8, 128)        # fallback

    def test_aligned_candidate(self):
        ta = analyze_tiling("flash_attention",
                            {"block_q": 128, "block_k": 128}, _F32ARG, {})
        assert ta.is_aligned and ta.grid_waste == {}
        assert ta.lane_padded                           # hd=64 < 128 lanes

    def test_misaligned_and_waste(self):
        ta = analyze_tiling("flash_attention",
                            {"block_q": 100, "block_k": 64}, _F32ARG, {})
        assert not ta.is_aligned and "q" in ta.misaligned
        assert ta.misaligned["q"] == (100, 8)
        # 256 rounds up to 300 under block 100: ~15% padded away
        assert math.isclose(ta.waste_fraction, 1 - 256 / 300, abs_tol=1e-9)

    def test_bf16_tightens_sublane(self):
        params = {"block_q": 8, "block_k": 8}
        assert analyze_tiling("flash_attention", params, _F32ARG,
                              {}).is_aligned
        assert not analyze_tiling("flash_attention", params, _BF16ARG,
                                  {}).is_aligned

    def test_lint_tiling_scn204_205_207(self):
        cands = [{"block_q": 128, "block_k": 128},
                 {"block_q": 100, "block_k": 64}]
        kept, flagged, diags = lint_tiling("flash_attention", cands,
                                           _F32ARG, subject="flash")
        assert kept == [cands[0]] and len(flagged) == 1
        codes = [d.code for d in diags]
        assert "SCN204" in codes and "SCN205" in codes \
            and codes.count("SCN207") == 1
        scn204 = next(d for d in diags if d.code == "SCN204")
        assert scn204.severity == WARNING

    def test_scn206_all_misaligned(self):
        cands = [{"block_q": 100, "block_k": 100},
                 {"block_q": 12, "block_k": 12}]
        kept, flagged, diags = lint_tiling("flash_attention", cands,
                                           _F32ARG)
        assert kept == [] and len(flagged) == 2
        d = next(x for x in diags if x.code == "SCN206")
        assert d.severity == ERROR

    def test_unknown_kernel_flags_nothing(self):
        assert misaligned_candidates("not_a_kernel", [{"x": 3}],
                                     _F32ARG) == {}
        kept, flagged, diags = lint_tiling("not_a_kernel", [{"x": 3}],
                                           _F32ARG)
        assert kept == [{"x": 3}] and not flagged and not diags

    def test_default_candidate_grids_are_aligned(self):
        # default-on pruning must never touch the committed sweeps (at the
        # representative shapes the CLI kernels/tiling targets use)
        from repro.analysis.cli import _KERNEL_SHAPES
        from repro.kernels.substrate import DEFAULT_CANDIDATES
        for kernel, cands in sorted(DEFAULT_CANDIDATES.items()):
            args, options = _KERNEL_SHAPES[kernel]
            assert misaligned_candidates(kernel, cands, args,
                                         options) == {}


class TestAutotunerTilePruning:
    def _tuner(self, candidates, tile_check):
        seen = []

        def factory(params):
            def fn(x):
                return x
            fn.params = dict(params)
            return fn

        def measure(fn, args):
            seen.append(dict(fn.params))
            return float(sum(fn.params.values()))

        tuner = KernelAutotuner(candidates={"flash_attention": candidates},
                                measure=measure, tile_check=tile_check)
        return tuner, factory, seen

    def test_misaligned_pruned_before_measurement(self):
        cands = [{"block_q": 128, "block_k": 128},
                 {"block_q": 64, "block_k": 100}]
        tuner, factory, seen = self._tuner(cands, tile_check=True)
        rec = tuner.tune("flash_attention", factory, _F32ARG,
                         resource="host")
        assert len(rec.tile_pruned) == 1
        assert {"block_q": 64, "block_k": 100} not in seen
        assert rec.params == {"block_q": 128, "block_k": 128}

    def test_tile_check_off_measures_everything(self):
        cands = [{"block_q": 128, "block_k": 128},
                 {"block_q": 64, "block_k": 100}]
        tuner, factory, seen = self._tuner(cands, tile_check=False)
        rec = tuner.tune("flash_attention", factory, _F32ARG,
                         resource="host")
        assert rec.tile_pruned == {} and len(seen) == 2

    def test_never_empties_the_sweep(self):
        # when every candidate is misaligned, measure them anyway
        cands = [{"block_q": 100, "block_k": 100}]
        tuner, factory, seen = self._tuner(cands, tile_check=True)
        rec = tuner.tune("flash_attention", factory, _F32ARG,
                         resource="host", defaults=cands[0])
        assert rec.params == cands[0] and rec.tile_pruned == {}
        assert len(seen) == 1

    def test_tune_record_json_roundtrip(self):
        cands = [{"block_q": 128, "block_k": 128},
                 {"block_q": 64, "block_k": 100}]
        tuner, factory, _ = self._tuner(cands, tile_check=True)
        tuner.tune("flash_attention", factory, _F32ARG, resource="host")
        back = KernelAutotuner.from_json(tuner.to_json())
        rec = next(iter(back.records.values()))
        assert len(rec.tile_pruned) == 1
        key = json.dumps({"block_q": 64, "block_k": 100}, sort_keys=True)
        assert "sublane-misaligned" in rec.tile_pruned[key]

    def test_v1_tune_record_payload_still_loads(self):
        # persisted records predating tile_pruned must keep loading
        tuner, factory, _ = self._tuner([{"block_q": 64, "block_k": 64}],
                                        tile_check=False)
        tuner.tune("flash_attention", factory, _F32ARG, resource="host")
        payload = json.loads(tuner.to_json())
        for rec in payload:
            rec.pop("tile_pruned")
        back = KernelAutotuner.from_json(json.dumps(payload))
        assert next(iter(back.records.values())).tile_pruned == {}


# ---------------------------------------------------------------------------
# serving-path adoption of tuned params
# ---------------------------------------------------------------------------

class TestServingParams:
    def _tuned_db(self, flash=None, ssd=None):
        db = _db(n_blocks=2, resources=("cloud",), batches=(1,))
        db.records["cloud"][0].tuned_params = {
            "attn": flash or {"block_q": 64, "block_k": 64}}
        db.records["cloud"][1].tuned_params = {
            "ssd": ssd or {"chunk": 32}}
        return db

    def test_kernel_for_params(self):
        assert kernel_for_params({"block_q": 1, "block_k": 1}) \
            == "flash_attention"
        assert kernel_for_params({"chunk": 1}) == "ssd_scan"
        assert kernel_for_params({"block_k": 1}) == "decode_attention"
        assert kernel_for_params({"zap": 1}) is None

    def test_adopt_and_serve(self):
        try:
            adopted = adopt_tuned_params(self._tuned_db())
            assert adopted["flash_attention"] == {"block_q": 64,
                                                  "block_k": 64}
            assert serving_param("flash_attention", "block_q", 512) == 64
            assert serving_param("ssd_scan", "chunk", 128) == 32
        finally:
            clear_tuned_params()
        assert serving_param("flash_attention", "block_q", 512) == 512

    def test_misaligned_tuned_params_rejected(self):
        try:
            adopted = adopt_tuned_params(
                self._tuned_db(flash={"block_q": 60, "block_k": 64}))
            assert "flash_attention" not in adopted
            assert serving_param("flash_attention", "block_q", 512) == 512
        finally:
            clear_tuned_params()

    def test_sdpa_chunks_at_adopted_block_q(self):
        from repro.models.layers import sdpa
        S, H, hd = 128, 2, 16
        q = jnp.zeros((1, S, H, hd))
        pos = jnp.arange(S)

        def scan_lengths():
            # a fresh closure per trace: jax caches traces on fn identity,
            # which would mask the registry change
            jaxpr = jax.make_jaxpr(
                lambda q: sdpa(q, q, q, q_pos=pos, k_pos=pos))(q)
            return [int(e.params["length"]) for e in jaxpr.eqns
                    if e.primitive.name == "scan"]

        assert scan_lengths() == []          # fallback q_chunk=512 >= S
        try:
            adopt_tuned_params(self._tuned_db())           # block_q=64
            assert scan_lengths() == [S // 64]
        finally:
            clear_tuned_params()
        assert scan_lengths() == []

    def test_ssd_chunks_at_adopted_chunk(self):
        from repro.models.ssm import ssd
        S, H, P, N = 128, 2, 16, 8
        x = jnp.zeros((1, S, H, P))
        log_a = jnp.zeros((1, S, H))
        b = jnp.zeros((1, S, 1, N))

        def nc():
            jaxpr = jax.make_jaxpr(lambda x: ssd(x, log_a, b, b)[0])(x)
            return [int(e.params["length"]) for e in jaxpr.eqns
                    if e.primitive.name == "scan"]

        assert nc() == [S // 128]            # fallback chunk=128
        try:
            adopt_tuned_params(self._tuned_db())           # chunk=32
            assert nc() == [S // 32]
        finally:
            clear_tuned_params()


# ---------------------------------------------------------------------------
# engine wiring + CLI
# ---------------------------------------------------------------------------

class TestEngineWiring:
    def _engine(self, db):
        net = NetworkModel(default=Link("wired", 0.005, 1e8))
        return QueryEngine(db, _fleet(), net, source="edge",
                           input_bytes=4096.0)

    def test_clean_engine_clean_result(self):
        r = self._engine(_db()).run(Query())
        assert r.configs and r.diagnostics == []

    def test_corrupted_db_surfaces_on_results(self):
        db = _db()
        db.records["cloud"][1].batch_profile[4] = (1e-7, 8192)  # SCN402
        r = self._engine(db).run(Query())
        assert r.configs
        d = next(x for x in r.diagnostics if x.code == "SCN402")
        assert d.subject == "cloud/block1"

    def test_error_findings_attach_too(self):
        db = _db()
        db.records["edge"][2].output_bytes = -5
        r = self._engine(db).run(Query())
        assert "SCN401" in {d.code for d in r.diagnostics}


class TestCli:
    def _main(self, *argv):
        from repro.analysis.cli import main
        return main(list(argv))

    def test_clean_db_passes_strict(self, capsys):
        assert self._main("--strict", "cost",
                          str(ROOT / "examples/dbs/edge_cloud_db.json")) == 0
        assert "clean" in capsys.readouterr().out

    def test_corrupted_db_fails_strict(self, capsys):
        path = str(ROOT / "examples/dbs/corrupted_db.json")
        assert self._main("--strict", "cost", path) == 1
        out = capsys.readouterr().out
        assert "SCN401" in out and "SCN402" in out and "SCN403" in out
        # non-strict reports but exits 0
        assert self._main("cost", path) == 0

    def test_allow_waives_codes(self):
        path = str(ROOT / "examples/dbs/corrupted_db.json")
        assert self._main("--strict", "--allow", "SCN401", "--allow",
                          "SCN402", "--allow", "SCN403", "cost", path) == 0
        # waiving only the warnings still fails on the error
        assert self._main("--strict", "--allow", "SCN402", "--allow",
                          "SCN403", "cost", path) == 1

    def test_tiling_target_is_strict_clean(self):
        assert self._main("--strict", "tiling") == 0

    def test_cost_keyword_requires_path(self):
        with pytest.raises(SystemExit):
            self._main("cost")
