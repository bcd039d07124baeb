"""Serving launcher: batched greedy generation through the engine.

    PYTHONPATH=src python -m repro.launch.serve --arch granite-8b --tiny \
        --requests 8 --width 4

Without ``--tiny`` the config runs at its published widths and depth.
:func:`build_engine`, :func:`make_requests` and :func:`serve` are the
launcher's whole path; ``chip_smoke.py`` drives the same functions.
"""

from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.models import build_model, get_config
from repro.serving import Request, ServingEngine
from .cache import enable_compile_cache
from .train import tiny as tiny_config


def build_engine(arch: str, *, tiny: bool = False, width: int = 4,
                 max_len: int = 64) -> ServingEngine:
    """A :class:`ServingEngine` over ``arch`` with random weights from a
    fixed seed (``tiny`` swaps in the CPU-sized preset)."""
    cfg = get_config(arch)
    if tiny:
        cfg = tiny_config(cfg)
    if cfg.is_encdec or cfg.n_img_tokens:
        raise SystemExit("serve CLI supports decoder-only archs; use the "
                         "examples for enc-dec")
    model = build_model(cfg)
    # one program: XLA draws each f32 normal and casts it to bf16 in place,
    # where eager init holds the f32 copy of the largest leaf beside the rest
    params = jax.jit(model.init)(jax.random.PRNGKey(0))
    return ServingEngine(model, params, width=width, max_len=max_len)


def make_requests(vocab: int, n: int, *, prompt_lens: tuple[int, int],
                  max_new: int, seed: int = 0) -> list[Request]:
    """``n`` requests with seeded random prompts whose lengths are drawn
    uniformly from ``[prompt_lens[0], prompt_lens[1])``."""
    rng = np.random.default_rng(seed)
    return [Request(rid=rid,
                    prompt=rng.integers(0, vocab,
                                        int(rng.integers(*prompt_lens))),
                    max_new_tokens=max_new)
            for rid in range(n)]


def serve(engine: ServingEngine, requests: list[Request], *,
          warmup: bool = False) -> list[Request]:
    """Queue ``requests``, optionally pre-compile what they need, and run
    the engine until all of them finish."""
    for req in requests:
        engine.submit(req)
    if warmup:
        engine.warmup()
    return engine.run()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-8b")
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--width", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--max-len", type=int, default=64)
    args = ap.parse_args()

    enable_compile_cache()
    eng = build_engine(args.arch, tiny=args.tiny, width=args.width,
                       max_len=args.max_len)
    reqs = make_requests(eng.cfg.vocab, args.requests, prompt_lens=(4, 16),
                         max_new=args.max_new)
    t0 = time.perf_counter()
    done = serve(eng, reqs)
    wall = time.perf_counter() - t0
    n_tok = sum(len(r.tokens) for r in done)
    print(f"served {len(done)} requests, {n_tok} tokens in "
          f"{wall:.2f}s ({n_tok / wall:.1f} tok/s aggregate)")
    ttfts = [r.first_token_at - r.submitted_at for r in done]
    print(f"TTFT p50={np.percentile(ttfts, 50) * 1e3:.0f}ms "
          f"p95={np.percentile(ttfts, 95) * 1e3:.0f}ms")


if __name__ == "__main__":
    main()
