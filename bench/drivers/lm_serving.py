"""Driver for a served decoder-only LM: ``ServingEngine`` stepped open loop
in wall-clock time.

Set-up (timed as ``setup_s``, from process start to the window's start):
build the program's model from the configuration, draw the weights from
the seed on the device, construct the engine, compile every shape the
mix can reach (one warm-up request per reachable prompt bucket through
``launch/serve.py:serve(warmup=True)``, then one admission of k requests
for every k up to the width, which is every shape of the slot scatter),
and serve the mix's lead-in.  The window then runs for ``seconds``: each
request is submitted at its due time with ``submitted_at`` set to it, and
the loop calls ``engine.run(max_steps=1)`` (one admission and one decode
tick) and stamps a token time for every request whose token count grew.
Once the window has closed, the peak memory is read, the engine's state is
freed, and a sample of the finished requests is compared with the plain
reference (``reference/lm.py``)."""

from __future__ import annotations

import gc
import os
import shutil
import time
from operator import attrgetter
from types import SimpleNamespace

import numpy as np

import arrivals
from compile_clock import CompileClock
from stats import percentile

# the program's fields that vary the block, at the values of the plain
# llama-style block; a field that a reference module's ``CHECKS`` covers is
# held to the configuration's key in its place
_PLAIN = {"norm": "rmsnorm", "mlp_gated": True, "use_rope": True,
          "qkv_bias": False, "window": None, "attn_softcap": None,
          "final_softcap": None, "embed_scale": False,
          "post_block_norm": False, "query_pre_attn_scalar": None,
          "shared_attn_period": 0, "is_encdec": False, "n_img_tokens": 0,
          "moe_shared_dff": 0}


def check_config(cfg, model: dict) -> None:
    """Refuse a program that computes anything else than the reference
    does for the configuration.  The numbers the reference reads are
    declared in ``CHECKS`` by ``reference/lm.py`` and by the module of each
    kind in the pattern: (key of the ``model`` section, the program's field
    or (field, reading), and the key's default, a value or a function of
    the section, where the file may leave it out).  Names every key whose
    value differs from the program's, and every field of the program set
    away from its plain value that no key covers."""
    from reference.lm import CHECKS, kind_module
    decls = CHECKS + [c for k in dict.fromkeys(model["pattern"])
                      for c in getattr(kind_module(k), "CHECKS", [])]
    bad, covered = [], {}
    for key, prog, *default in decls:
        if key in covered:
            continue
        field, read = (prog, attrgetter(prog)) if isinstance(prog, str) \
            else prog
        covered[key] = field
        if key in model:
            want = model[key]
        elif default:
            want = default[0](model) if callable(default[0]) else default[0]
        else:
            bad.append(f"{key}: not in the file, program {read(cfg)!r}")
            continue
        if want != read(cfg):
            bad.append(f"{key}: file {want!r}, program {read(cfg)!r}")
    bad += [f"{k}: program {getattr(cfg, k)!r}, reference {v!r}"
            for k, v in _PLAIN.items()
            if k not in covered.values() and getattr(cfg, k) != v]
    if cfg.moe_experts:
        from repro.models.moe import pad_experts
        ep = pad_experts(cfg.moe_experts)
        if ep != model["moe_experts_padded"]:
            bad.append(f"padded experts: file {model['moe_experts_padded']}"
                       f", program {ep}")
        # dropless: the per-group capacity holds every token of the group
        if cfg.moe_capacity_factor * cfg.moe_top_k < ep:
            bad.append(f"moe_capacity_factor {cfg.moe_capacity_factor} "
                       f"drops tokens; dropless needs >= {ep / cfg.moe_top_k}")
    if bad:
        raise SystemExit("configuration and program disagree: "
                         + "; ".join(bad))


def seed_key(seed: int):
    import jax
    st = np.random.SeedSequence([seed % 2**32, seed // 2**32 % 2**32, 11]
                                ).generate_state(2)
    return jax.random.fold_in(jax.random.PRNGKey(int(st[0]) & 0x7FFFFFFF),
                              int(st[1]) & 0x7FFFFFFF)


def build(conf: dict, seed: int):
    """(engine, params) for a configuration, weights drawn from ``seed``."""
    import jax
    from repro.models import build_model, get_config
    from repro.serving import ServingEngine
    import weights
    cfg = get_config(conf["arch"]).replace(**conf["overrides"])
    check_config(cfg, conf["model"])
    m = build_model(cfg)
    dtype = weights.DTYPES[conf["model"]["dtype"]]
    tmpl = jax.eval_shape(lambda k: m.init(k, dtype), jax.random.PRNGKey(0))
    params = weights.make(tmpl, conf["model"], seed_key(seed))
    eng = ServingEngine(m, params, width=conf["width"],
                        max_len=conf["max_len"])
    return eng, params


def warm(engine, mix: dict, vocab: int) -> None:
    """Compile every program the mix's traffic reaches, and no other."""
    from repro.launch.serve import serve
    from repro.serving import Request
    rng = np.random.default_rng(0)
    cap = engine.max_len - 2
    buckets = arrivals.buckets_reached(mix, engine.prompt_buckets)

    def req(n, new):
        return Request(rid=-1, prompt=rng.integers(0, vocab, n,
                                                   dtype=np.int32),
                       max_new_tokens=new)

    serve(engine, [req(min(b + 1, cap), 2) for b in buckets], warmup=True)
    small = min(buckets[0] + 1, cap)
    for k in range(1, engine.width + 1):
        serve(engine, [req(small, 1) for _ in range(k)])


class Window:
    """The open-loop loop and its records."""

    def __init__(self, engine, sched, t0: float):
        self.engine = engine
        self.sched = sched
        self.t0 = t0
        self.reqs = {}                     # rid -> Request
        self.stamps: dict[int, list[float]] = {}
        self.late: list[float] = []
        self.ticks: list[dict] = []        # ticks inside the traced span
        self.trace_start_s = 0.0
        self.traced = None
        self.i = 0

    def due(self, a) -> float:
        return self.t0 + a.t

    def _submit(self, now: float) -> None:
        from repro.serving import Request
        while self.i < len(self.sched) and self.due(self.sched[self.i]) <= now:
            a = self.sched[self.i]
            r = Request(rid=a.rid, prompt=a.prompt,
                        max_new_tokens=a.max_new_tokens,
                        submitted_at=self.due(a))
            self.engine.submit(r)
            self.reqs[a.rid] = r
            self.stamps[a.rid] = []
            self.late.append(now - self.due(a))
            self.i += 1

    def _tick(self, record: bool) -> None:
        import jax
        eng = self.engine
        waiting = [r for r in eng.queue]
        with jax.profiler.TraceAnnotation("bench.tick"):
            finished = eng.run(max_steps=1)
        t = time.perf_counter()
        rows = []
        for r in list(eng.active.values()) + finished:
            st = self.stamps.get(r.rid)
            if st is None:
                continue
            while len(st) < len(r.tokens):
                st.append(t)
                rows.append(len(r.prompt) - 1 + len(st) - 1)
        if record:
            self.ticks.append({
                "prefill": [len(r.prompt) - 1 for r in waiting
                            if r.admitted_at is not None
                            and len(r.prompt) > 1],
                "decode": rows})

    def run_until(self, t_end: float, trace=None) -> float:
        """Serve until ``t_end``; ``trace`` = (start, stop, dir) opens the
        profiler between ticks for that span.  Returns the loop's end."""
        import jax
        tracing, span = False, None
        while True:
            now = time.perf_counter()
            if trace is not None:
                if not tracing and span is None and now >= trace[0]:
                    jax.profiler.start_trace(trace[2])
                    self.trace_start_s = time.perf_counter() - now
                    span = jax.profiler.TraceAnnotation("bench.traced")
                    span.__enter__()
                    tracing = True
                elif tracing and now >= trace[1]:
                    span.__exit__(None, None, None)
                    jax.profiler.stop_trace()
                    tracing = False
            if now >= t_end:
                break
            with jax.profiler.TraceAnnotation("bench.submit"):
                self._submit(now)
            if self.engine.queue or self.engine.active:
                self._tick(tracing)
                continue
            nxt = (self.due(self.sched[self.i]) if self.i < len(self.sched)
                   else t_end)
            with jax.profiler.TraceAnnotation("bench.sleep"):
                time.sleep(max(0.0, min(nxt, t_end) - time.perf_counter()))
        if tracing:
            span.__exit__(None, None, None)
            jax.profiler.stop_trace()
        return time.perf_counter()


def end_to_end(w: Window, ws: float, we: float) -> dict:
    due = [r for r in w.reqs.values() if ws <= r.submitted_at < we]
    ttft, itl, toks = [], [], 0
    for r in due:
        st = w.stamps[r.rid]
        first = st[0] if st and st[0] <= we else we
        ttft.append(first - r.submitted_at)
    for st in w.stamps.values():
        for a, b in zip(st, st[1:]):
            if ws <= b <= we:
                itl.append(b - a)
        toks += sum(1 for s in st if ws <= s <= we)
    return {"ttft_p50_ms": percentile(ttft, 50) * 1e3,
            "ttft_p90_ms": percentile(ttft, 90) * 1e3,
            "ttft_mean_ms": float(np.mean(ttft)) * 1e3,
            "itl_p95_ms": percentile(itl, 95) * 1e3,
            "out_tok_s": toks / (we - ws),
            "_due": len(due), "_itl_n": len(itl), "_tokens": toks}


def sample(w: Window, we: float, seed: int, tokens: int) -> list:
    """Finished requests, drawn from the seed, the longest among them,
    until they hold ``tokens`` served tokens."""
    done = sorted((r for r in w.reqs.values()
                   if r.done and r.finished_at <= we),
                  key=lambda r: r.rid)
    if not done:
        return []
    longest = max(done, key=lambda r: (len(r.tokens), -r.rid))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng([seed % 2**32, seed // 2**32 % 2**32, 13])
    out, n = [longest], len(longest.tokens)
    for j in rng.permutation(len(rest)):
        if n >= tokens:
            break
        out.append(rest[j])
        n += len(rest[j].tokens)
    return out


def serve_window(engine, mix: dict, seed: int, seconds: float, vocab: int,
                 trace_dir: str | None = None):
    """Serve the mix's lead-in, then a window of ``seconds`` (profiled for
    a few seconds in its middle when ``trace_dir`` is given).  Returns
    (window records, window start, window end); the records' ``traced``
    is the profiled span."""
    sched = arrivals.schedule(mix, seconds, seed, vocab)
    w = Window(engine, sched, time.perf_counter())
    w.run_until(w.t0 + mix["lead_in_s"])
    ws = time.perf_counter()
    if trace_dir is None:
        return w, ws, w.run_until(ws + seconds)
    span = min(8.0, seconds / 2)
    t_tr = ws + (seconds - span) / 2
    w.traced = (t_tr, t_tr + span)
    return w, ws, w.run_until(ws + seconds, (t_tr, t_tr + span, trace_dir))


def gap_stats(gaps) -> dict:
    """Numbers a check can compare, over the served tokens of a sample:
    the widest gap (``token_gap``), the mean gap (``gap_mean``) and the
    share of tokens whose gap is not 0 (``flip_share``)."""
    g = np.concatenate(gaps) if gaps else np.zeros(1)
    return {"token_gap": float(g.max()), "gap_mean": float(g.mean()),
            "flip_share": float(np.mean(g > 0))}


def check(conf: dict, mix: dict, params, w: Window, we: float, seed: int,
          control: bool = False) -> tuple[dict, dict]:
    """Compare a seeded sample of the window's finished requests with the
    reference, by the gap by which each served token's reference logit
    lies below the reference's best (``gap_stats``).  Returns the checks
    (every number that the configuration's ``limits`` names, beside its
    limit) and the gap numbers of the sample (``{"program": ...}``).  With
    ``control`` (tools and tests only) the gap numbers also hold
    ``"control"``, read for the tokens that the float8 reference puts
    first, and ``"control_checks"``, the same checks on those."""
    from reference.lm import token_gaps
    picked = sample(w, we, seed, mix["check_tokens"])
    gaps, lows, served, short = [], [], 0, 0
    for r in picked:
        g, c = token_gaps(conf["model"], params, r.prompt, r.tokens,
                          conf["max_len"], control=control)
        gaps.append(g)
        lows.append(c)
        served += len(r.tokens)
        short += len(r.tokens) != r.max_new_tokens

    def limited(got: dict) -> dict:
        out = {k: {"value": got[k], "limit": lim}
               for k, lim in conf["limits"].items()}
        out["served_checked"] = {"value": served,
                                 "limit": mix["check_tokens"]}
        out["wrong_lengths"] = {"value": short, "limit": 0}
        return out

    got = {"program": gap_stats(gaps)}
    if control:
        got["control"] = gap_stats(lows)
        got["control_checks"] = limited(got["control"])
    return limited(got["program"]), got


def passed(checks: dict) -> bool:
    """Every limited number at or under its limit, enough tokens checked,
    and every checked request served its whole length."""
    ok = all(c["value"] <= c["limit"] for k, c in checks.items()
             if c["limit"] is not None and k not in ("served_checked",))
    return ok and checks["served_checked"]["value"] >= \
        checks["served_checked"]["limit"]


def free(engine) -> None:
    """Drop the engine's caches so that the reference has the memory."""
    engine.pool.cache = None
    engine._scratch = None
    gc.collect()


def run(conf: dict, mix: dict, seed: int, seconds: float, trace: bool,
        t_start: float, out_dir: str, devices, control: bool = False
        ) -> SimpleNamespace:
    import jax
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    # cache every program, however quick its compile, so that only a
    # checkout's first run compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    clock = CompileClock()
    model = conf["model"]
    engine, params = build(conf, seed)
    warm(engine, mix, model["vocab"])
    c0 = clock.snapshot()
    tdir = None
    if trace:
        tdir = os.path.join(out_dir, f"trace-{os.getpid()}")
        shutil.rmtree(tdir, ignore_errors=True)
        # the profiler's first start takes tens of seconds on the chip:
        # pay it in set-up, not inside the window
        jax.profiler.start_trace(tdir + "-warm")
        jax.profiler.stop_trace()
        shutil.rmtree(tdir + "-warm", ignore_errors=True)
    w, ws, we = serve_window(engine, mix, seed, seconds, model["vocab"],
                             tdir)
    setup_s = ws - t_start
    c1 = clock.snapshot()
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    e2e = end_to_end(w, ws, we)
    pend = len(engine.queue) + len(engine.active)
    free(engine)
    del engine

    t_ref = time.perf_counter()
    checks, gaps = check(conf, mix, params, w, we, seed, control)
    info = {
        "requests_due": e2e["_due"], "itl_samples": e2e["_itl_n"],
        "tokens_in_window": e2e["_tokens"],
        "finished_in_window": sum(1 for r in w.reqs.values()
                                  if r.done and ws <= r.finished_at <= we),
        "pending_at_close": pend,
        "window_s": we - ws,
        "compiles_in_window": c1["compiles"] - c0["compiles"],
        "cache_misses_in_window": c1["cache_misses"] - c0["cache_misses"],
        "setup_compile_s": c0["compile_s"],
        "late_p50_ms": percentile(w.late, 50) * 1e3,
        "late_max_ms": max(w.late) * 1e3,
        "reference_s": time.perf_counter() - t_ref,
        "trace_start_s": w.trace_start_s,
    }
    for k in ("ttft_p90_ms", "ttft_mean_ms", "out_tok_s"):
        info[k] = e2e[k]
    metrics = {"ttft_p50_ms": e2e["ttft_p50_ms"],
               "itl_p95_ms": e2e["itl_p95_ms"], "setup_s": setup_s}
    return SimpleNamespace(
        correct=passed(checks), attempted=e2e["_due"], failed=0,
        metrics=metrics, checks=checks, gaps=gaps, info=info,
        memory_peak_bytes=peak,
        trace_dir=tdir,
        ctx=SimpleNamespace(model=model, window=(ws, we), traced=w.traced,
                            reqs=w.reqs, ticks=w.ticks, trace=None,
                            peaks=None))
