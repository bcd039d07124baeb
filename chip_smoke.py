"""Smoke run of the repository's main paths on one TPU chip.

    python chip_smoke.py               # one chip: kernels, Scission, serving
    python chip_smoke.py --four-chips  # sharded train step: 2x2 vs 1 chip

Phases, in the order they run (one process, all on the chip):

* ``kernels`` — the three Pallas kernels compiled for the chip
  (``interpret=False``) at config widths, against ``kernels/ref.py``.
* ``scission`` — ResNet50 (224x224, full width) through Steps 1-3
  (``Scission.benchmark``, ``TimingProvider`` measuring on the chip, nothing
  read from disk) and Steps 4-6 (``query(top_n=3)``, ``frontier()``), then
  the best configuration and a split of at least two stages executed by
  ``PipelineExecutor`` and compared with the unsplit graph on the chip and
  with a float32 CPU reference.
* ``serving`` — ``ServingEngine`` on granite-moe-3b-a800m at its published
  widths and depth (random seeded weights), built and driven through
  ``launch/serve.py``; every request must complete with its token count, and
  prefill + first decode step must give the logits of ``model.forward``.

Each check prints one JSON line; the last line of a passing run is
``{"ok": true, "device": {...}}``.  The script exits non-zero without that
line when JAX finds no TPU, when a phase raises, or when a comparison fails.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

# ResNet50 has no biases or normalisation, so its logits scale linearly with
# the input; at this scale the random-weight softmax is not saturated (top
# probability ~0.5), so the comparisons below see every class, not a one-hot.
CNN_INPUT_SCALE = 1e-3
# Split against unsplit, both on the chip: the same ops at the same
# precision; only fusion across a stage boundary differs, which moves f32
# rounding and at most flips a rare bf16 operand rounding.
TOL_SPLIT = 5e-3
# Chip against the float32 CPU reference: the TPU rounds f32 conv/matmul
# operands to bf16 by default.  Emulating that rounding on the CPU moves
# this input's softmax by 2.6e-2 (relative L2); the bound leaves 4x.
TOL_CNN_CPU = 1e-1
# Engine logits (bucketed prefill, then one decode step against the KV
# cache) against model.forward over the same tokens: bf16 weights and
# activations through 32 layers, attention and the expert combine reduced
# in another order.  With the routing used below, the CPU gives 2.3e-2 at
# 32 layers of width 384; a cache or position error gives O(1).
TOL_LOGITS = 1e-1
# Kernels against kernels/ref.py evaluated at "highest" precision: inputs
# and outputs are bf16, so the bound is a few bf16 ulps (2^-8 = 3.9e-3).
TOL_KERNEL = 2e-2
# Sharded train step against the same step on one chip: bf16 parameters,
# reductions split across devices in a different order.
TOL_SPMD = 1e-2


def emit(phase: str, **kv) -> None:
    print(json.dumps({"phase": phase, **kv}, default=float), flush=True)


def rel_err(got, want) -> float:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def check(phase: str, name: str, err: float, tol: float) -> None:
    emit(phase, check=name, rel_l2=err, tol=tol, ok=bool(err <= tol))
    if not err <= tol:
        raise AssertionError(f"{phase}/{name}: relative L2 error {err:.3e} "
                             f"exceeds {tol:.1e}")


class CompileClock:
    """Backend compile seconds and persistent-cache hits, from JAX's own
    monitoring events, so each phase can report what it compiled."""

    def __init__(self):
        self.compile_s = 0.0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self) -> tuple[float, int, int]:
        return self.compile_s, self.hits, self.misses


def run_phase(clock: CompileClock, name: str, fn, *args) -> None:
    c0, h0, m0 = clock.snapshot()
    t0 = time.perf_counter()
    fn(*args)
    c1, h1, m1 = clock.snapshot()
    emit(name, done=True, wall_s=time.perf_counter() - t0,
         compile_s=c1 - c0, cache_hits=h1 - h0, cache_misses=m1 - m0)


# ---------------------------------------------------------------------------
# Scission: benchmark -> query/frontier -> partitioned execution
# ---------------------------------------------------------------------------

def phase_scission(model: str = "ResNet50", n_inputs: int = 3) -> None:
    from benchmarks.common import scission_for
    from repro.core import Query, fuse_blocks
    from repro.models import cnn_zoo
    from repro.runtime.pipeline import PipelineExecutor

    graph = cnn_zoo.build(model)
    blocks = fuse_blocks(graph)
    s = scission_for("4g")
    t0 = time.perf_counter()
    db = s.benchmark(graph)                         # Steps 1-3, measured now
    times = np.array([[r.mean_time_s for r in db.records[res.name]]
                      for res in s.resources])
    emit("scission", step="benchmark", model=model, blocks=db.n_blocks,
         resources=[r.name for r in s.resources],
         wall_s=time.perf_counter() - t0,
         block_s_min=times.min(), block_s_max=times.max())
    if times.shape != (len(s.resources), len(blocks)) or \
            not np.all(np.isfinite(times) & (times > 0)):
        raise AssertionError(f"benchmark DB is incomplete or not positive: "
                             f"{times.shape}")

    top = s.query(model, Query(top_n=3))
    front = s.frontier(model)
    emit("scission", step="query", configs=[c.describe() for c in top.configs],
         query_ms=top.query_time_s * 1e3, frontier=len(front.configs))
    if not top.configs or not front.configs:
        raise AssertionError("query or frontier returned no configuration")

    split = next((c for c in (*top.configs, *front.configs)
                  if len(c.segments) >= 2), None)
    if split is None:           # every ranked point is native: ask for a cut
        split = s.query(model, Query(top_n=1,
                                     must_use=("device", "cloud"))).best
    if len(split.segments) < 2:
        raise AssertionError(f"no split configuration: {split.describe()}")

    def unsplit(x):
        for b in blocks:
            x = b.make_callable()(x)
        return x

    on_chip = jax.jit(unsplit)
    cpu = jax.devices("cpu")[0]
    spec = blocks[0].in_specs[0]
    executors = {"best": PipelineExecutor(graph, top.best, s.network),
                 "split": PipelineExecutor(graph, split, s.network)}
    for name, ex in executors.items():
        emit("scission", step="execute", config=name,
             partition=ex.config.describe(), stages=len(ex.stages))
    for seed in range(n_inputs):
        x = jax.random.uniform(jax.random.PRNGKey(seed), spec.shape,
                               spec.dtype) * CNN_INPUT_SCALE
        want_chip = on_chip(x)
        with jax.default_device(cpu), jax.default_matmul_precision("highest"):
            want_cpu = jax.jit(unsplit)(jax.device_put(x, cpu))
        emit("scission", input=seed, top_prob=float(jnp.max(want_chip)))
        check("scission", f"unsplit_vs_cpu_f32[{seed}]",
              rel_err(want_chip, want_cpu), TOL_CNN_CPU)
        for name, ex in executors.items():
            got, _ = ex.run(x)
            check("scission", f"{name}_vs_unsplit[{seed}]",
                  rel_err(got, want_chip), TOL_SPLIT)
            check("scission", f"{name}_vs_cpu_f32[{seed}]",
                  rel_err(got, want_cpu), TOL_CNN_CPU)


# ---------------------------------------------------------------------------
# LM serving through launch/serve.py
# ---------------------------------------------------------------------------

def phase_serving(arch: str = "granite-moe-3b-a800m", *, tiny: bool = False,
                  width: int = 8, max_len: int = 1024,
                  prompt_lens: tuple[int, int] = (64, 513),
                  n_requests: int = 12, max_new: int = 16) -> None:
    from repro.launch.serve import build_engine, make_requests, serve
    from repro.models import build_model, layers as L
    from repro.models.moe import pad_experts
    from repro.serving import ServingEngine
    from repro.serving.queues import bucket_for

    eng = build_engine(arch, tiny=tiny, width=width, max_len=max_len)
    cfg, model = eng.cfg, eng.model
    n_params = sum(int(x.size) for x in jax.tree.leaves(eng.params))
    emit("serving", arch=cfg.name, n_layers=cfg.n_layers,
         d_model=cfg.d_model, params=n_params, width=width, max_len=max_len)

    reqs = make_requests(cfg.vocab, n_requests, prompt_lens=prompt_lens,
                         max_new=max_new, seed=1)
    t0 = time.perf_counter()
    done = serve(eng, reqs, warmup=True)
    wall = time.perf_counter() - t0
    bad = [r.rid for r in done
           if len(r.tokens) != max_new or not all(0 <= t < cfg.vocab
                                                  for t in r.tokens)]
    emit("serving", requests=len(done), tokens=sum(len(r.tokens)
                                                   for r in done),
         prompt_lens=[len(r.prompt) for r in reqs], wall_s=wall,
         host_run_s=eng.stats.wall_s, incomplete=bad)
    if len(done) != len(reqs) or bad:
        raise AssertionError(f"{len(done)}/{len(reqs)} requests finished; "
                             f"wrong token counts: {bad}")

    # Logits of the engine's prefill + first decode step for one request,
    # against a full forward pass over the same tokens, on a copy of the
    # config that routes every token to every expert with room for all.
    # The published routing is unfit for a logit comparison: expert
    # capacity drops tokens by how they are grouped (forward groups the
    # whole prompt, decode routes one token per row), and top-8-of-40 picks
    # of random routers are near-ties that any change of evaluation order
    # flips in bf16, compounding over the layers (CPU, 32 layers of width
    # 384: 0.15 relative L2, where a dense model of those widths gives 9e-3
    # and full routing 2.3e-2).
    if cfg.moe_experts:
        cfg = cfg.replace(moe_top_k=cfg.moe_experts,
                          moe_capacity_factor=pad_experts(cfg.moe_experts)
                          / cfg.moe_experts)
        model = build_model(cfg)
        eng = ServingEngine(model, eng.params, width=width, max_len=max_len)
    prompt = np.asarray(reqs[0].prompt, np.int32)
    n = len(prompt) - 1
    toks = np.zeros((width, bucket_for(n, eng.prompt_buckets)), np.int32)
    toks[0, :n] = prompt[:-1]
    _, cache = eng._prefill(eng.params, {"tokens": jnp.asarray(toks)})
    last = np.zeros((width, 1), np.int32)
    last[0, 0] = prompt[-1]
    lengths = np.zeros((width,), np.int32)
    lengths[0] = n
    _, logits, _ = eng._decode(eng.params, cache, jnp.asarray(last),
                               jnp.asarray(lengths))

    @jax.jit
    def forward_logits(params, tokens):
        hidden, _ = model.forward(params, tokens)
        return L.unembed(params["embed"], hidden[:, -1:],
                         softcap=cfg.final_softcap)

    ref = forward_logits(eng.params, jnp.asarray(prompt)[None])
    got, want = np.asarray(logits[0, -1], np.float32), \
        np.asarray(ref[0, -1], np.float32)
    emit("serving", check="argmax", prompt_len=len(prompt),
         engine=int(got.argmax()), forward=int(want.argmax()),
         max_abs=float(np.abs(got - want).max()))
    check("serving", "engine_logits_vs_forward", rel_err(got, want),
          TOL_LOGITS)


# ---------------------------------------------------------------------------
# Pallas kernels compiled for the chip
# ---------------------------------------------------------------------------

def phase_kernels() -> None:
    from repro.kernels import decode_attention, flash_attention, ssd_scan
    from repro.kernels.ref import (decode_attention_ref, flash_attention_ref,
                                   ssd_ref)

    ks = jax.random.split(jax.random.PRNGKey(3), 12)

    def normal(k, shape, dtype=jnp.bfloat16):
        return jax.random.normal(k, shape, jnp.float32).astype(dtype)

    def run(name, kernel, ref, args, pick=lambda o: o):
        fn = jax.jit(lambda *a: kernel(*a, interpret=False))
        text = fn.lower(*args).compile().as_text()
        if "tpu_custom_call" not in text:
            raise AssertionError(f"{name}: no tpu_custom_call in the "
                                 "compiled program")
        got = pick(fn(*args))
        with jax.default_matmul_precision("highest"):
            want = pick(jax.jit(ref)(*args))
        emit("kernels", kernel=name, tpu_custom_call=True,
             shapes=[list(a.shape) for a in args])
        check("kernels", name, rel_err(got, want), TOL_KERNEL)

    # granite-moe-3b-a800m attention: 24 heads, 8 KV heads, head_dim 64
    q = normal(ks[0], (1, 2048, 24, 64))
    k = normal(ks[1], (1, 2048, 8, 64))
    v = normal(ks[2], (1, 2048, 8, 64))
    run("flash_attention", flash_attention, flash_attention_ref, (q, k, v))

    qd = normal(ks[3], (8, 24, 64))
    kc = normal(ks[4], (8, 4096, 8, 64))
    vc = normal(ks[5], (8, 4096, 8, 64))
    lengths = jax.random.randint(ks[6], (8,), 1, 4097, jnp.int32)
    run("decode_attention", decode_attention, decode_attention_ref,
        (qd, kc, vc, lengths))

    # zamba2-2.7b SSD: 80 heads of ssm_head_dim 64, state 64, chunk 128
    x = normal(ks[7], (1, 2048, 80, 64))
    log_a = -jax.nn.softplus(jax.random.normal(ks[8], (1, 2048, 80)))
    b = normal(ks[9], (1, 2048, 80, 64))
    c = normal(ks[10], (1, 2048, 80, 64))
    run("ssd_scan", lambda *a, **kw: ssd_scan(*a, chunk=128, **kw), ssd_ref,
        (x, log_a, b, c), pick=lambda o: o[0])


# ---------------------------------------------------------------------------
# four chips: the sharded train step against one chip
# ---------------------------------------------------------------------------

def phase_four_chips(cfg=None, *, batch: int = 8, seq: int = 512) -> None:
    from repro.configs.base import ShapeConfig
    from repro.launch.mesh import make_mesh
    from repro.launch.steps import (input_specs, make_train_step, rules_for,
                                    shardings_for)
    from repro.models import build_model, get_config
    from repro.optim import AdamWConfig, init_state

    if cfg is None:             # published widths, depth cut to one period
        full = get_config("granite-moe-3b-a800m")
        cfg = full.replace(n_layers=len(full.pattern))
    devices = jax.devices()
    if len(devices) != 4:
        raise AssertionError(f"--four-chips needs 4 devices, found "
                             f"{len(devices)}")
    model = build_model(cfg)
    shape = ShapeConfig("smoke_train", seq_len=seq, global_batch=batch,
                        kind="train")
    params = model.init(jax.random.PRNGKey(0))
    opt = init_state(params)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (batch, seq), 0,
                                cfg.vocab)
    data = {"tokens": tokens, "labels": jnp.roll(tokens, -1, axis=1)}

    one = jax.jit(make_train_step(model, AdamWConfig(), None, None))
    _, _, m1 = one(params, opt, data)

    mesh = make_mesh((2, 2), ("data", "model"), devices=devices)
    rules = rules_for(shape, multi_pod=False)
    sh = shardings_for(cfg, shape, mesh, rules, input_specs(cfg, shape))
    with mesh:
        step = jax.jit(make_train_step(model, AdamWConfig(), rules, mesh),
                       in_shardings=(sh["params"], sh["opt_state"],
                                     sh["batch"]))
        args = jax.device_put((params, opt, data),
                              (sh["params"], sh["opt_state"], sh["batch"]))
        text = step.lower(*args).compile().as_text()
        _, _, m4 = step(*args)
    collectives = [op for op in ("all-reduce", "all-gather",
                                 "reduce-scatter", "all-to-all")
                   if op in text]
    emit("four_chips", arch=cfg.name, n_layers=cfg.n_layers,
         mesh={"data": 2, "model": 2}, batch=batch, seq=seq,
         collectives=collectives,
         loss_one=float(m1["loss"]), loss_mesh=float(m4["loss"]),
         grad_norm_one=float(m1["grad_norm"]),
         grad_norm_mesh=float(m4["grad_norm"]))
    if not collectives:
        raise AssertionError("the 2x2 step compiled without collectives")
    check("four_chips", "loss_mesh_vs_one_chip",
          rel_err(m4["loss"], m1["loss"]), TOL_SPMD)
    check("four_chips", "grad_norm_mesh_vs_one_chip",
          rel_err(m4["grad_norm"], m1["grad_norm"]), TOL_SPMD)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded train step on a 2x2 mesh "
                         "against the same step on one chip")
    args = ap.parse_args()

    # the float32 reference runs on the host's CPU backend next to the chip
    platforms = jax.config.jax_platforms
    if platforms and "cpu" not in platforms.split(","):
        jax.config.update("jax_platforms", platforms + ",cpu")
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r}); "
              "refusing to run on another backend", file=sys.stderr)
        return 2

    from repro.launch.cache import enable_compile_cache

    emit("setup", compile_cache=enable_compile_cache(),
         platform=dev.platform, kind=dev.device_kind,
         count=len(jax.devices()))
    clock = CompileClock()
    t0 = time.perf_counter()
    if args.four_chips:
        run_phase(clock, "four_chips", phase_four_chips)
    else:
        run_phase(clock, "kernels", phase_kernels)
        run_phase(clock, "scission", phase_scission)
        run_phase(clock, "serving", phase_serving)
    emit("total", wall_s=time.perf_counter() - t0, compile_s=clock.compile_s,
         cache_hits=clock.hits, cache_misses=clock.misses)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
