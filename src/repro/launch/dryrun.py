"""Multi-pod dry-run: lower + compile every (architecture × input shape) on
the production meshes and extract memory/cost/collective analysis.

MUST be executed as its own process (``python -m repro.launch.dryrun``):
:func:`main` appends the 512-host-device flag to ``XLA_FLAGS``, which takes
effect only before jax initialises its backends.

    python -m repro.launch.dryrun --arch gemma2-9b --shape train_4k
    python -m repro.launch.dryrun --all [--multi-pod-only|--single-pod-only]
    python -m repro.launch.dryrun --all --out results/dryrun.json
"""

import argparse
import json
import os
import re
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs.base import ALL_SHAPES, ShapeConfig, shape_by_name
from repro.kernels.substrate import compiled_costs
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import (input_specs, make_decode_step,
                                make_prefill_step, make_train_step,
                                model_flops, rules_for, shardings_for)
from repro.models import build_model, get_config
from repro.optim import AdamWConfig

ARCHS = ["gemma2-9b", "starcoder2-15b", "gemma-7b", "granite-8b",
         "zamba2-2.7b", "xlstm-125m", "whisper-medium", "internvl2-76b",
         "qwen2-moe-a2.7b", "granite-moe-3b-a800m"]

# long_500k needs sub-quadratic attention (DESIGN.md §Arch-applicability)
def cell_skipped(arch: str, shape: ShapeConfig) -> str | None:
    cfg = get_config(arch)
    if shape.name == "long_500k" and not cfg.sub_quadratic:
        return "full-attention arch: 512k decode KV is quadratic-infeasible"
    return None


_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")
_SHAPE_RE = re.compile(r"(f32|bf16|f16|s32|u32|s8|u8|pred|s64|f64)"
                       r"\[([0-9,]*)\]")
_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "s8": 1,
          "u8": 1, "pred": 1, "s64": 8, "f64": 8}


def collective_bytes(hlo_text: str) -> dict[str, float]:
    """Sum output-shape bytes of every collective op in the partitioned HLO
    (per-device traffic; ring-algorithm bytes ≈ output size)."""
    out: dict[str, float] = {c: 0.0 for c in _COLLECTIVES}
    for line in hlo_text.splitlines():
        stripped = line.lstrip()
        # match result-producing collective instructions, e.g.
        #   %all-reduce.5 = bf16[...] all-reduce(...)
        m = re.search(r"=\s*[^=]*?\b(" + "|".join(_COLLECTIVES)
                      + r")(?:-start|-done)?\(", stripped)
        if not m:
            continue
        if "-done(" in stripped:      # avoid double counting start/done pairs
            continue
        op = m.group(1)
        shapes = _SHAPE_RE.findall(stripped.split("=")[1].split("(")[0])
        nbytes = 0.0
        for dt, dims in shapes:
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            nbytes += n * _BYTES[dt]
        out[op] += nbytes
    out["total"] = sum(out[c] for c in _COLLECTIVES)
    return out


def reduced_groups_cfg(cfg, n_groups: int):
    """Same architecture with only ``n_groups`` scan groups — used for the
    two-point cost extrapolation (XLA cost analysis counts a while-loop
    body once, so scanned-layer costs must be recovered by fitting
    cost(G) = base + G·slope from G=1 and G=2)."""
    if cfg.shared_attn_period:
        n_layers = cfg.shared_attn_period * n_groups
    else:
        n_layers = len(cfg.pattern) * n_groups
    kw = {"n_layers": n_layers}
    if cfg.is_encdec:
        kw["encoder_layers"] = n_groups
    return cfg.replace(**kw)


def build_step_and_args(arch: str, shape: ShapeConfig, mesh, multi_pod: bool,
                        cfg=None):
    cfg = cfg if cfg is not None else get_config(arch)
    model = build_model(cfg)
    rules = rules_for(shape, multi_pod=multi_pod)
    specs = input_specs(cfg, shape)
    shards = shardings_for(cfg, shape, mesh, rules, specs)

    if shape.kind == "train":
        step = make_train_step(model, AdamWConfig(), rules, mesh)
        args = (specs["params"], specs["opt_state"], specs["batch"])
        in_sh = (shards["params"], shards["opt_state"], shards["batch"])
        out_sh = (shards["params"], shards["opt_state"], None)
        donate = (0, 1)
    elif shape.kind == "prefill":
        step = make_prefill_step(model, rules, mesh)
        args = (specs["params"], specs["cache"], specs["batch"])
        in_sh = (shards["params"], shards["cache"], shards["batch"])
        out_sh = (None, shards["cache"])
        donate = (1,)
    else:
        step = make_decode_step(model, rules, mesh)
        args = (specs["params"], specs["cache"], specs["token"],
                specs["cache_len"])
        in_sh = (shards["params"], shards["cache"], shards["token"],
                 shards["cache_len"])
        out_sh = (None, None, shards["cache"])
        donate = (1,)
    return step, args, in_sh, out_sh, donate


def run_cell(arch: str, shape: ShapeConfig, *, multi_pod: bool,
             verbose: bool = True) -> dict:
    mesh_name = "2x16x16" if multi_pod else "16x16"
    rec = {"arch": arch, "shape": shape.name, "mesh": mesh_name}
    skip = cell_skipped(arch, shape)
    if skip:
        rec.update(status="SKIP", reason=skip)
        return rec

    t0 = time.time()
    try:
        mesh = make_production_mesh(multi_pod=multi_pod)

        def compile_cfg(cfg):
            step, args, in_sh, out_sh, donate = build_step_and_args(
                arch, shape, mesh, multi_pod, cfg=cfg)
            with mesh:
                jitted = jax.jit(step, in_shardings=in_sh,
                                 out_shardings=out_sh,
                                 donate_argnums=donate)
                return jitted.lower(*args).compile()

        def costs(compiled):
            cost = compiled_costs(compiled)
            coll = collective_bytes(compiled.as_text())
            return (cost.get("flops", 0.0),
                    cost.get("bytes accessed", 0.0), coll)

        full_cfg = get_config(arch)
        compiled = compile_cfg(full_cfg)
        t_compile = time.time() - t0

        # two-point extrapolation over *unrolled* 2- and 3-group variants:
        # XLA cost analysis counts a while body once and ignores trip
        # counts, so every scan (layer stack, q-chunks, SSD chunks, loss
        # chunks) must be unrolled/maximised in the costing variant for the
        # per-group slope to be real.  cost(G) = base + G·slope.
        G = full_cfg.n_groups

        def costing_cfg(g):
            # q-chunking/loss-chunking do the same work dense, so maximise
            # the chunk; SSD's chunked algorithm does *different* (O(S·L))
            # work than its dense form, so unroll its chunk scan instead.
            return reduced_groups_cfg(full_cfg, g).replace(
                scan_layers=False, q_chunk=1_000_000_000,
                loss_seq_chunk=None, unroll_scans=True)

        f1, b1, c1 = costs(compile_cfg(costing_cfg(2)))
        f2, b2, c2 = costs(compile_cfg(costing_cfg(3)))

        def extrap(v1, v2):
            return v1 + (G - 2) * (v2 - v1)

        mem = compiled.memory_analysis()
        coll_raw = costs(compiled)[2]
        coll = {k: extrap(c1[k], c2[k]) for k in c1}
        n_dev = mesh.devices.size
        t_lower = 0.0

        flops_per_dev = extrap(f1, f2)
        bytes_per_dev = extrap(b1, b2)
        rec.update(
            status="OK",
            lower_s=round(t_lower, 1),
            compile_s=round(t_compile, 1),
            n_devices=n_dev,
            hlo_flops_per_device=flops_per_dev,
            hlo_bytes_per_device=bytes_per_dev,
            collective_bytes_per_device=coll["total"],
            collectives=coll,
            collectives_scan_body_once=coll_raw,
            memory={
                "argument_bytes": int(getattr(mem, "argument_size_in_bytes",
                                              0)),
                "output_bytes": int(getattr(mem, "output_size_in_bytes", 0)),
                "temp_bytes": int(getattr(mem, "temp_size_in_bytes", 0)),
                "alias_bytes": int(getattr(mem, "alias_size_in_bytes", 0)),
            },
            model_flops_total=model_flops(get_config(arch), shape),
        )
        if verbose:
            print(f"[{arch} × {shape.name} × {mesh_name}] OK "
                  f"compile={t_compile:.0f}s "
                  f"flops/dev={flops_per_dev:.3e} "
                  f"bytes/dev={bytes_per_dev:.3e} "
                  f"coll/dev={coll['total']:.3e} "
                  f"temp={rec['memory']['temp_bytes'] / 2**30:.2f}GiB")
    except Exception as e:  # noqa: BLE001 — a failing cell is a bug report
        rec.update(status="FAIL", error=f"{type(e).__name__}: {e}",
                   trace=traceback.format_exc()[-2000:])
        if verbose:
            print(f"[{arch} × {shape.name} × {mesh_name}] FAIL: "
                  f"{rec['error']}")
    return rec


def main():
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=512").strip()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--single-pod-only", action="store_true")
    ap.add_argument("--multi-pod-only", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    cells: list[tuple[str, ShapeConfig, bool]] = []
    if args.all:
        meshes = [False, True]
        if args.single_pod_only:
            meshes = [False]
        if args.multi_pod_only:
            meshes = [True]
        for arch in ARCHS:
            for shape in ALL_SHAPES:
                for mp in meshes:
                    cells.append((arch, shape, mp))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells.append((args.arch, shape_by_name(args.shape), args.multi_pod))

    results = [run_cell(a, s, multi_pod=mp) for a, s, mp in cells]

    ok = sum(r["status"] == "OK" for r in results)
    skip = sum(r["status"] == "SKIP" for r in results)
    fail = sum(r["status"] == "FAIL" for r in results)
    print(f"\n== dry-run: {ok} OK, {skip} SKIP, {fail} FAIL "
          f"of {len(results)} cells ==")

    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)
        print(f"wrote {args.out}")
    return 1 if fail else 0


if __name__ == "__main__":
    raise SystemExit(main())
