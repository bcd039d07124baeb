"""Substrate under the Pallas TPU kernels.

All three kernels (``flash_attention``, ``decode_attention``, ``ssd_scan``)
and the measurement layer (``core/bench.py``, ``launch/dryrun.py``) route
through this module.  It provides:

* **Compiler params** — :func:`tpu_compiler_params` builds
  ``pltpu.CompilerParams`` and raises on a field the installed class does
  not take, so a kernel never loses e.g. ``dimension_semantics`` unseen.
* **Cost analysis** — :func:`normalize_cost_analysis` keeps the numeric
  entries of the dict ``compile().cost_analysis()`` returns, so providers
  can always call ``.get``.
* **Pad-and-mask helpers** — :func:`round_up` / :func:`pad_axis_to` let the
  kernels accept sequence lengths that are not multiples of the block size:
  inputs are zero-padded up to the next block boundary, padded key/value
  positions are masked to ``-inf`` inside the kernel, and padded query/time
  rows are sliced off the output.
* **Block-size autotuner** — :class:`KernelAutotuner` sweeps
  ``(block_q, block_k, chunk)`` candidates per (kernel, shape, resource),
  caches the winner, and rewrites tunable graph nodes in place so the
  benchmark providers measure *tuned* kernel timings.  Winners are carried
  into ``BenchmarkDB`` records (``BlockBenchmark.tuned_params``), which is
  what the partition/query engines consume.
"""

from __future__ import annotations

import dataclasses
import json
import math
import sys
import time
from dataclasses import dataclass, field, asdict
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

# ---------------------------------------------------------------------------
# compiler params
# ---------------------------------------------------------------------------


def tpu_compiler_params(**kwargs) -> pltpu.CompilerParams:
    """``pltpu.CompilerParams(**kwargs)``, refusing unknown fields by name."""
    known = {f.name for f in dataclasses.fields(pltpu.CompilerParams)}
    unknown = sorted(set(kwargs) - known)
    if unknown:
        raise TypeError(f"pltpu.CompilerParams takes no field(s) {unknown}")
    return pltpu.CompilerParams(**kwargs)


# ---------------------------------------------------------------------------
# cost analysis
# ---------------------------------------------------------------------------

def normalize_cost_analysis(cost: dict[str, Any]) -> dict[str, float]:
    """The numeric entries of a ``compile().cost_analysis()`` dict."""
    if not isinstance(cost, dict):
        raise TypeError(f"cost_analysis() returned {type(cost).__name__}, "
                        "expected a dict")
    return {k: float(v) for k, v in cost.items()
            if isinstance(v, (int, float))}


def compiled_costs(compiled) -> dict[str, float]:
    """``normalize_cost_analysis`` straight off a compiled executable."""
    return normalize_cost_analysis(compiled.cost_analysis())


# ---------------------------------------------------------------------------
# interpret default + pad/mask helpers
# ---------------------------------------------------------------------------

def default_interpret() -> bool:
    """Pallas kernels interpret on non-TPU backends so the same call sites
    work in CPU tests/examples; on TPU they compile through Mosaic."""
    return jax.default_backend() != "tpu"


def round_up(n: int, multiple: int) -> int:
    if multiple <= 0:
        raise ValueError(f"multiple must be positive, got {multiple}")
    return ((n + multiple - 1) // multiple) * multiple


def pad_axis_to(x, axis: int, target: int):
    """Zero-pad ``x`` along ``axis`` up to length ``target`` (no-op when
    already there)."""
    size = x.shape[axis]
    if size == target:
        return x
    if size > target:
        raise ValueError(f"cannot pad axis {axis} from {size} down to {target}")
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, target - size)
    return jnp.pad(x, pads)


# ---------------------------------------------------------------------------
# block-size autotuner
# ---------------------------------------------------------------------------

# Candidate sweeps per kernel.  Defaults (the kernels' keyword defaults) are
# always included so "tuned == default" is an observable outcome.
DEFAULT_CANDIDATES: dict[str, list[dict[str, int]]] = {
    "flash_attention": [{"block_q": bq, "block_k": bk}
                        for bq in (64, 128, 256)
                        for bk in (64, 128, 256)],
    "decode_attention": [{"block_k": bk} for bk in (128, 256, 512)],
    "ssd_scan": [{"chunk": c} for c in (32, 64, 128, 256)],
}

DEFAULT_PARAMS: dict[str, dict[str, int]] = {
    "flash_attention": {"block_q": 128, "block_k": 128},
    "decode_attention": {"block_k": 256},
    "ssd_scan": {"chunk": 128},
}


@dataclass
class TuneRecord:
    """Outcome of one (kernel, shape, resource) sweep."""

    kernel: str
    shape_key: str
    resource: str
    params: dict[str, int]            # winning block sizes
    time_s: float                     # winner's measured time
    default_params: dict[str, int]
    default_time_s: float               # NaN when the default never compiled
    trials: dict[str, float] = field(default_factory=dict)  # json(params) -> s
    # candidates statically pruned by the VMEM analyzer before timing:
    # json(params) -> computed footprint in bytes (empty when unconstrained)
    pruned: dict[str, float] = field(default_factory=dict)
    vmem_limit: float | None = None   # the budget the sweep ran under
    # candidates statically pruned by the TPU tiling analyzer before
    # timing: json(params) -> misalignment reason (repro.analysis.tiling)
    tile_pruned: dict[str, str] = field(default_factory=dict)
    # candidates the compiler or runtime refused:
    # json(params) -> "ExceptionType: message"
    refused: dict[str, str] = field(default_factory=dict)

    @property
    def changed_default(self) -> bool:
        return self.params != self.default_params

    @property
    def speedup_vs_default(self) -> float:
        # default_time_s is NaN when the default candidate never compiled
        # on this JAX version — no meaningful baseline, report parity.
        if not self.time_s or math.isnan(self.default_time_s):
            return 1.0
        return self.default_time_s / self.time_s


def _shape_key(specs) -> str:
    parts = []
    for s in jax.tree.leaves(specs):
        shape = getattr(s, "shape", None)
        dtype = getattr(s, "dtype", None)
        parts.append(f"{jnp.dtype(dtype).name if dtype is not None else '?'}"
                     f"{list(shape) if shape is not None else '?'}")
    return "x".join(parts)


class KernelAutotuner:
    """Sweeps block-size candidates and caches per-(kernel, shape, resource)
    winners.

    ``tune`` measures wall-clock of a jit'd candidate callable (min over
    ``runs`` after a compile warm-up) — the same measurement discipline as
    ``TimingProvider``.  A candidate that fails to trace, compile or run
    (e.g. a block shape the TPU compiler refuses) is recorded in
    ``TuneRecord.refused`` and reported on stderr, and the sweep goes on
    with the rest.  A custom ``measure`` hook replaces wall-clock timing
    (used by unit tests and by roofline-style offline tuning).
    """

    def __init__(self, candidates: dict[str, list[dict[str, int]]] | None = None,
                 runs: int = 2,
                 measure: Callable[[Callable, tuple], float] | None = None,
                 vmem_limits: dict[str, float] | None = None,
                 tile_check: bool = True):
        self.candidates = dict(DEFAULT_CANDIDATES)
        if candidates:
            self.candidates.update(candidates)
        self.runs = runs
        self.measure = measure
        self.records: dict[tuple[str, str, str], TuneRecord] = {}
        # Measurements are host wall-clock and independent of the emulated
        # resource (speed factors scale uniformly), so trial tables are
        # shared across resources; each resource still gets its own record.
        self._trials: dict[tuple[str, str], dict[str, float]] = {}
        self._refused: dict[tuple[str, str], dict[str, str]] = {}
        # Per-resource VMEM budgets in bytes: candidates whose static
        # footprint (repro.analysis.kernel_vmem) exceeds the tuned
        # resource's budget are pruned before timing.
        self.vmem_limits: dict[str, float] = dict(vmem_limits or {})
        # Static TPU tile-alignment pruning (repro.analysis.tiling):
        # sublane-misaligned candidates are dropped before compile/measure
        # unless that would empty the sweep.
        self.tile_check = tile_check

    def register_resources(self, resources) -> None:
        """Adopt ``Resource.vmem_bytes`` budgets from a testbed (called by
        ``benchmark_model`` so the sweep and the fleet stay in sync)."""
        for r in resources:
            budget = getattr(r, "vmem_bytes", None)
            if budget is not None:
                self.vmem_limits[r.name] = float(budget)

    # -- measurement --------------------------------------------------------
    def _time_candidate(self, fn: Callable, args: tuple) -> float:
        if self.measure is not None:
            return self.measure(fn, args)
        jf = jax.jit(fn)
        out = jf(*args)             # warm-up / compile
        jax.block_until_ready(out)
        best = float("inf")
        for _ in range(max(1, self.runs)):
            t0 = time.perf_counter()
            jax.block_until_ready(jf(*args))
            best = min(best, time.perf_counter() - t0)
        return best

    # -- core sweep ---------------------------------------------------------
    def tune(self, kernel: str, factory: Callable[[dict[str, int]], Callable],
             args: tuple, *, resource: str = "host",
             defaults: dict[str, int] | None = None,
             shape_key: str | None = None,
             config_key: str = "",
             options: dict | None = None) -> TuneRecord:
        """Sweep candidates for ``kernel`` at the shapes of ``args``.

        ``factory(params)`` returns the callable to measure.  ``config_key``
        distinguishes factories whose behaviour differs beyond the argument
        shapes (causal/window/softcap, closed-over cache sizes, ...);
        ``options`` are the node's ``kernel_options``, consumed by the
        static VMEM analyzer for dimensions the args don't expose.  The
        winning record is cached per (kernel, shape+config, resource), and
        the underlying trial table is shared across resources — mirroring
        ``BenchmarkDB``'s benchmark-once/query-many contract.

        When the tuned resource has a VMEM budget (``self.vmem_limits``),
        candidates whose static footprint exceeds it are pruned *before*
        timing (``TuneRecord.pruned`` records them) and the winner is the
        fastest *admissible* candidate — so a shared trial table measured
        under one budget serves stricter budgets without re-timing.  With
        ``tile_check`` (the default), sublane-misaligned candidates are
        likewise pruned statically (``TuneRecord.tile_pruned`` records the
        reason) unless no aligned candidate would remain.
        """
        defaults = dict(defaults or DEFAULT_PARAMS.get(kernel, {}))
        shape_key = shape_key or _shape_key(
            [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in args])
        if config_key:
            shape_key = f"{shape_key}|{config_key}"
        key = (kernel, shape_key, resource)
        if key in self.records:
            return self.records[key]

        candidates = list(self.candidates.get(kernel, []))
        if defaults and defaults not in candidates:
            candidates.insert(0, defaults)
        if not candidates:
            candidates = [defaults]

        budget = self.vmem_limits.get(resource)
        pruned: dict[str, float] = {}
        kept = candidates
        if budget is not None:
            from ..analysis.kernel_vmem import lint_candidates
            kept, pruned_b, _ = lint_candidates(
                kernel, candidates, args, vmem_limit=budget,
                options=options, subject=f"{kernel}@{resource}")
            pruned = {k: float(v) for k, v in pruned_b.items()}
            if not kept:
                sizes = "; ".join(f"{k} -> {v / 2**20:.2f}MiB"
                                  for k, v in sorted(pruned.items()))
                raise RuntimeError(
                    f"autotune: every candidate of {kernel} {shape_key} "
                    f"exceeds the {budget / 2**20:.2f}MiB VMEM budget of "
                    f"resource {resource!r}: {sizes}")

        tile_pruned: dict[str, str] = {}
        if self.tile_check and kept:
            from ..analysis.tiling import misaligned_candidates
            flagged = misaligned_candidates(kernel, kept, args, options)
            aligned = [p for p in kept
                       if json.dumps(p, sort_keys=True) not in flagged]
            # static analysis narrows a sweep but never empties it: with no
            # aligned candidate left, measure the flagged ones anyway
            if aligned and flagged:
                kept = aligned
                tile_pruned = flagged

        trials = self._trials.setdefault((kernel, shape_key), {})
        failures = self._refused.setdefault((kernel, shape_key), {})
        for params in kept:
            pkey = json.dumps(params, sort_keys=True)
            if pkey in trials or pkey in failures:
                continue
            try:
                trials[pkey] = self._time_candidate(factory(params), args)
            except Exception as e:  # noqa: BLE001 — recorded and reported
                failures[pkey] = f"{type(e).__name__}: {e}"
                print(f"autotune: {kernel} {shape_key} candidate {pkey} "
                      f"refused on {jax.default_backend()}: "
                      f"{failures[pkey]}", file=sys.stderr)

        kept_keys = {json.dumps(p, sort_keys=True) for p in kept}
        admissible = {k: t for k, t in trials.items() if k in kept_keys}
        if not admissible:
            detail = "; ".join(f"{k} -> {err}"
                               for k, err in sorted(failures.items())) \
                or "no candidate produced a measurement"
            raise RuntimeError(
                f"autotune: every candidate failed for {kernel} "
                f"{shape_key}: {detail}")

        best_key = min(admissible, key=admissible.get)
        best = json.loads(best_key)
        dkey = json.dumps(defaults, sort_keys=True)
        rec = TuneRecord(kernel=kernel, shape_key=shape_key, resource=resource,
                         params=best, time_s=admissible[best_key],
                         default_params=defaults,
                         default_time_s=admissible.get(dkey, float("nan")),
                         trials=admissible, pruned=pruned, vmem_limit=budget,
                         tile_pruned=tile_pruned,
                         refused={k: v for k, v in failures.items()
                                  if k in kept_keys})
        self.records[key] = rec
        return rec

    # -- graph integration --------------------------------------------------
    def tune_node(self, node, resource: str = "host",
                  in_specs=None) -> TuneRecord | None:
        """Tune one kernel-bearing ``LayerNode`` in place.

        Nodes opt in by carrying ``kernel`` (substrate kernel name),
        ``kernel_factory`` (params -> apply callable) and optionally
        ``kernel_params`` (defaults).  ``in_specs`` are the node's input
        ShapeDtypeStructs (``tune_block`` derives them from the graph).
        The node's ``apply`` is rewritten to the tuned callable, so any
        provider measuring the node afterwards measures tuned timings.
        """
        kernel = getattr(node, "kernel", None)
        factory = getattr(node, "kernel_factory", None)
        if not kernel or factory is None:
            return None
        args = tuple(jnp.zeros(s.shape, s.dtype)
                     for s in (in_specs or []))
        if not args:
            return None
        options = getattr(node, "kernel_options", None)
        rec = self.tune(kernel, factory, args, resource=resource,
                        defaults=getattr(node, "kernel_defaults", None)
                        or DEFAULT_PARAMS.get(kernel),
                        config_key=json.dumps(options, sort_keys=True,
                                              default=str)
                        if options else "",
                        options=options)
        node.kernel_params = dict(rec.params)
        node.apply = factory(rec.params)
        return rec

    def tune_block(self, block, resource: str = "host") -> list[TuneRecord]:
        """Tune every kernel node of a fused block (providers call this right
        before measuring the block)."""
        out = []
        g = block.graph
        for i in block.node_ids:
            node = g.nodes[i]
            if getattr(node, "kernel", None) and \
                    getattr(node, "kernel_factory", None) is not None:
                specs = [g.nodes[p].out_spec for p in g.preds[i]]
                rec = self.tune_node(node, resource=resource, in_specs=specs)
                if rec is not None:
                    out.append(rec)
        return out

    def params_for_block(self, block) -> dict[str, dict[str, int]]:
        """Winning block sizes per kernel node of ``block`` (for embedding
        into ``BlockBenchmark.tuned_params``)."""
        out: dict[str, dict[str, int]] = {}
        for i in block.node_ids:
            node = block.graph.nodes[i]
            if getattr(node, "kernel", None) and \
                    getattr(node, "kernel_params", None):
                out[node.name] = dict(node.kernel_params)
        return out

    # -- persistence --------------------------------------------------------
    def to_json(self) -> str:
        return json.dumps([asdict(r) for r in self.records.values()])

    @classmethod
    def from_json(cls, s: str) -> "KernelAutotuner":
        tuner = cls()
        for d in json.loads(s):
            rec = TuneRecord(**d)
            tuner.records[(rec.kernel, rec.shape_key, rec.resource)] = rec
        return tuner


# ---------------------------------------------------------------------------
# serving-time tuned-params registry
# ---------------------------------------------------------------------------
# A tuned BenchmarkDB documents the block sizes its timings were measured
# with (``BlockBenchmark.tuned_params``).  Adopting it here makes those
# winners the process-wide serving defaults, so model-zoo layers
# (``models/layers.py`` attention, ``models/ssm.py`` SSD) run the same
# kernel configuration the cost model priced — not just the benchmark
# graphs built from ``kernels/ops.py``.

_SERVING_PARAMS: dict[str, dict[str, int]] = {}


def kernel_for_params(params: dict) -> str | None:
    """Map a tuned-params dict to the kernel it configures by exact
    parameter-name match ({block_q, block_k} -> flash_attention, ...).
    ``BlockBenchmark.tuned_params`` is keyed by node name, not kernel, so
    adoption needs this reverse lookup."""
    keys = frozenset(params)
    for kernel, defaults in DEFAULT_PARAMS.items():
        if keys == frozenset(defaults):
            return kernel
    return None


def adopt_tuned_params(db, *, dtype="float32") -> dict[str, dict[str, int]]:
    """Adopt a BenchmarkDB's tuned winners as serving defaults.

    Walks every record's ``tuned_params`` in deterministic order (sorted
    resources, blocks in order, sorted node names; later entries win),
    validates each candidate against the static tile-alignment analyzer
    for ``dtype`` — a misaligned winner is *rejected*, the lint-validated
    discipline — and installs the survivors.  Returns the adopted
    ``{kernel: params}`` mapping."""
    import numpy as np

    from ..analysis.tiling import min_tile

    sublane, _ = min_tile(np.dtype(dtype))
    adopted: dict[str, dict[str, int]] = {}
    records = getattr(db, "records", {})
    for rname in sorted(records):
        for rec in records[rname]:
            tuned = getattr(rec, "tuned_params", None) or {}
            for node in sorted(tuned):
                params = dict(tuned[node])
                kernel = kernel_for_params(params)
                if kernel is None:
                    continue
                values_ok = all(
                    isinstance(v, int) and v > 0 and v % sublane == 0
                    for v in params.values())
                if values_ok:
                    adopted[kernel] = params
    _SERVING_PARAMS.update(adopted)
    return adopted


def serving_param(kernel: str, name: str, fallback: int) -> int:
    """The adopted tuned value of ``kernel``'s ``name`` parameter, or
    ``fallback`` when no tuned DB has been adopted."""
    return int(_SERVING_PARAMS.get(kernel, {}).get(name, fallback))


def clear_tuned_params() -> None:
    """Drop adopted serving defaults (tests / model switches)."""
    _SERVING_PARAMS.clear()
