"""Static VMEM-footprint analysis of the Pallas kernel candidates (SCN2xx).

Each kernel's ``pallas_call`` declares exactly which tiles live in VMEM at
once: the gridded input/output blocks (shape × dtype from the BlockSpecs)
plus the scratch buffers.  That makes the footprint of a block-size
candidate a *static* function of (kernel, candidate params, argument
shapes) — no tracing, no compilation — so over-budget candidates can be
pruned before the autotuner spends compile/measure time on them, and a
deployment plan can be checked against a resource's ``vmem_bytes``
capability offline.

Footprint model (documented assumption, same shape as the guide's
``compute_vmem_bytes`` discipline): the Pallas TPU pipeline double-buffers
every gridded input and output block (compute on one buffer while DMA
fills the other), scratch buffers are single-buffered, and SMEM operands
(e.g. ``decode_attention``'s lengths vector) do not count against VMEM:

    vmem = 2 * (sum of input blocks + sum of output blocks) + scratch

The per-kernel functions below mirror the BlockSpecs in ``kernels/*.py``
one for one — head-major ``(B, H, S, ·)`` operands whose squeezed batch and
head dims count as 1, and the ``min(block, dim)`` clamping the kernels
apply — so the analyzer and the kernels cannot drift apart silently (the unit
tests assert the mirrored shapes against the kernel sources' specs).
"""

from __future__ import annotations

import json
from dataclasses import dataclass, field
from typing import Any, Sequence

import numpy as np

from .diagnostics import Diagnostic, ERROR, INFO

# Pallas TPU pipelining: gridded in/out blocks are double-buffered.
DOUBLE_BUFFER = 2

# A practical per-core budget for TPU targets (the guide's ~16 MB/core);
# exported so testbeds can write ``vmem_bytes=TPU_VMEM_BYTES`` instead of a
# magic number.
TPU_VMEM_BYTES = 16 * 1024 * 1024


def _itemsize(dtype) -> int:
    return int(np.dtype(dtype).itemsize)


def _nbytes(shape: Sequence[int], dtype) -> int:
    n = 1
    for d in shape:
        n *= int(d)
    return n * _itemsize(dtype)


@dataclass(frozen=True)
class KernelFootprint:
    """Static VMEM footprint of one (kernel, candidate, shape) combination.

    ``parts`` break the total down into double-buffered input blocks,
    double-buffered output blocks and single-buffered scratch.
    """

    kernel: str
    params: dict
    in_bytes: int                   # already double-buffered
    out_bytes: int                  # already double-buffered
    scratch_bytes: int
    blocks: dict[str, tuple] = field(default_factory=dict)

    @property
    def vmem_bytes(self) -> int:
        return self.in_bytes + self.out_bytes + self.scratch_bytes


def _flash_attention_footprint(params: dict, args: Sequence,
                               options: dict) -> KernelFootprint:
    q = args[0]
    B, Sq, H, hd = q.shape
    if len(args) >= 3:
        Sk = args[1].shape[1]
    else:                           # self-attention node: q == k == v
        Sk = Sq
    bq = min(int(params.get("block_q", 128)), int(Sq))
    bk = min(int(params.get("block_k", 128)), int(Sk))
    blocks = {
        "q": (1, 1, bq, hd), "k": (1, 1, bk, hd), "v": (1, 1, bk, hd),
        "o": (1, 1, bq, hd),
    }
    in_b = sum(_nbytes(blocks[n], q.dtype) for n in ("q", "k", "v"))
    out_b = _nbytes(blocks["o"], q.dtype)
    scratch = _nbytes((bq, 1), np.float32) * 2 \
        + _nbytes((bq, hd), np.float32)
    return KernelFootprint("flash_attention", dict(params),
                           DOUBLE_BUFFER * in_b, DOUBLE_BUFFER * out_b,
                           scratch, blocks)


def _decode_attention_footprint(params: dict, args: Sequence,
                                options: dict) -> KernelFootprint:
    q = args[0]
    if q.ndim == 4:                 # already grouped (B, Hk, G, hd)
        B, Hk, G, hd = q.shape
        H = Hk * G
    else:                           # public layout (B, H, hd)
        B, H, hd = q.shape
        Hk = int(options.get("kv_heads",
                             args[1].shape[2] if len(args) >= 3 else H))
        G = H // max(1, Hk)
    Smax = int(args[1].shape[1]) if len(args) >= 3 \
        else int(options.get("cache_len", 0))
    if Smax <= 0:
        raise ValueError("decode_attention footprint needs the cache "
                         "length (k/v argument or options['cache_len'])")
    bk = min(int(params.get("block_k", 256)), Smax)
    blocks = {
        "q": (1, 1, G, hd), "k": (1, 1, bk, hd), "v": (1, 1, bk, hd),
        "o": (1, 1, G, hd),
    }
    # the lengths vector lives in SMEM — excluded from the VMEM budget
    in_b = sum(_nbytes(blocks[n], q.dtype) for n in ("q", "k", "v"))
    out_b = _nbytes(blocks["o"], q.dtype)
    scratch = _nbytes((G, 1), np.float32) * 2 \
        + _nbytes((G, hd), np.float32)
    return KernelFootprint("decode_attention", dict(params),
                           DOUBLE_BUFFER * in_b, DOUBLE_BUFFER * out_b,
                           scratch, blocks)


def _ssd_scan_footprint(params: dict, args: Sequence,
                        options: dict) -> KernelFootprint:
    x = args[0]
    B, S, H, P = x.shape
    N = int(args[2].shape[-1]) if len(args) >= 4 \
        else int(options.get("state_dim", 16))
    L = min(int(params.get("chunk", 128)), int(S))
    blocks = {
        "x": (1, 1, L, P), "log_a": (1, 1, L, 1), "b": (1, 1, L, N),
        "c": (1, 1, L, N), "y": (1, 1, L, P), "final": (1, 1, N, P),
    }
    in_b = sum(_nbytes(blocks[n], x.dtype)
               for n in ("x", "log_a", "b", "c"))
    out_b = _nbytes(blocks["y"], x.dtype) \
        + _nbytes(blocks["final"], np.float32)
    scratch = _nbytes((N, P), np.float32)
    return KernelFootprint("ssd_scan", dict(params),
                           DOUBLE_BUFFER * in_b, DOUBLE_BUFFER * out_b,
                           scratch, blocks)


_FOOTPRINTS = {
    "flash_attention": _flash_attention_footprint,
    "decode_attention": _decode_attention_footprint,
    "ssd_scan": _ssd_scan_footprint,
}


def known_kernels() -> tuple[str, ...]:
    return tuple(sorted(_FOOTPRINTS))


def kernel_footprint(kernel: str, params: dict, args: Sequence,
                     options: dict | None = None) -> KernelFootprint | None:
    """Static VMEM footprint of one candidate, or ``None`` for a kernel the
    analyzer does not know.  ``args`` are the kernel's positional arguments
    (arrays or ShapeDtypeStructs — only ``.shape``/``.dtype`` are read);
    ``options`` are the node's ``kernel_options`` (used when a graph node's
    single input does not expose every dimension, e.g. a closed-over KV
    cache)."""
    fn = _FOOTPRINTS.get(kernel)
    if fn is None:
        return None
    return fn(params or {}, args, options or {})


def kernel_vmem_bytes(kernel: str, params: dict, args: Sequence,
                      options: dict | None = None) -> int | None:
    fp = kernel_footprint(kernel, params, args, options)
    return None if fp is None else fp.vmem_bytes


def _mb(n: float) -> str:
    return f"{n / 2**20:.2f}MiB"


def lint_candidates(kernel: str, candidates: Sequence[dict], args: Sequence,
                    *, vmem_limit: float | None,
                    options: dict | None = None,
                    subject: str = "") -> tuple[list[dict], dict[str, int],
                                                list[Diagnostic]]:
    """Split a candidate sweep into (admissible, pruned, diagnostics).

    ``pruned`` maps the candidate's canonical JSON key to its computed
    footprint in bytes.  With no ``vmem_limit`` (or an unknown kernel)
    every candidate is admissible.  SCN201 (info) is emitted per pruned
    candidate, SCN202 (error) when nothing survives, SCN203 (info) when
    the kernel is unknown to the analyzer.
    """
    subject = subject or kernel
    diags: list[Diagnostic] = []
    if vmem_limit is None:
        return list(candidates), {}, diags
    kept: list[dict] = []
    pruned: dict[str, int] = {}
    for params in candidates:
        fp = kernel_footprint(kernel, params, args, options)
        if fp is None:
            diags.append(Diagnostic(
                "SCN203", INFO,
                f"kernel {kernel!r} is unknown to the VMEM analyzer; "
                f"candidate {params} kept unchecked", subject=subject,
                hint="register a footprint function in "
                     "repro.analysis.kernel_vmem._FOOTPRINTS"))
            kept.append(params)
            continue
        if fp.vmem_bytes > vmem_limit:
            key = json.dumps(params, sort_keys=True)
            pruned[key] = fp.vmem_bytes
            diags.append(Diagnostic(
                "SCN201", INFO,
                f"candidate {params} needs {_mb(fp.vmem_bytes)} VMEM "
                f"(> budget {_mb(vmem_limit)}); pruned before timing",
                subject=subject,
                hint="shrink the block sizes or raise the resource's "
                     "vmem_bytes"))
        else:
            kept.append(params)
    if candidates and not kept:
        smallest = min(pruned.values(), default=0)
        diags.append(Diagnostic(
            "SCN202", ERROR,
            f"every candidate of {kernel!r} exceeds the "
            f"{_mb(vmem_limit)} VMEM budget (smallest needs "
            f"{_mb(smallest)})", subject=subject,
            hint="add smaller block-size candidates to the sweep or raise "
                 "the resource's vmem_bytes"))
    return kept, pruned, diags
