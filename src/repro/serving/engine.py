"""Batched serving engine: continuous-batching decode loop over the
prefill/decode step functions, with Scission-placed stages.

This module is the compatibility surface of the ``repro.serving`` package
(the old monolithic engine split into layers, the same way
``core/partition.py`` became ``core/lattice/``): :class:`Request` lives in
:mod:`repro.serving.requests`, :class:`KVCachePool` and the prompt-bucket
machinery in :mod:`repro.serving.queues`, :class:`ServingStats` in
:mod:`repro.serving.metrics`, and :func:`simulate_pipeline_throughput` in
:mod:`repro.serving.sim` — all re-exported here, so
``from repro.serving.engine import ServingEngine, ServingStats,
simulate_pipeline_throughput`` keeps working unchanged.

The engine owns:
* a :class:`KVCachePool` (slot-per-sequence paging at sequence granularity),
* a request queue with admission up to the batch width,
* the jitted prefill/decode steps — same-tick admissions share **one**
  prefill over a padded prompt bucket and a power-of-two row bucket that
  covers them (:func:`~repro.serving.queues.row_bucket`), into a zeros
  cache built inside the prefill program; compiles are bounded by row
  buckets x length buckets, instead of one jit call + fresh batch-1 cache
  per request.

On a cloud-edge deployment the *placement* of the two phases comes from the
Scission query engine (e.g. prefill on the pod, decode on the regional
slice, or the paper's device/edge/cloud split for CNNs); here the engine
runs single-host but the phase boundary and cache handoff are the same.

Each tick's phases are ``jax.profiler.TraceAnnotation`` spans, nested on
the calling thread, with the layer's counters as the spans' arguments
(event stats in a profiler trace; nothing is formatted when no profiler
session is active):

* ``serving.step`` (``queued``, ``active`` at entry): one :meth:`step`;
* ``serving.admit`` (``rows``, ``rids``: space-joined request ids, as a
  comma would split the annotation's arguments): an admission that took
  requests from the queue;
* ``serving.prefill`` (``rows``: the real rows; ``width``: the rows
  computed, the row bucket; ``bucket``; ``real_tokens``: the prompt
  positions the rows hold, the rest of ``width x bucket`` being padding
  that the chip computes): dispatch of one prefill;
* ``serving.scatter`` (``rows``): the prefilled rows' copy into the pool;
* ``serving.decode`` (``rows``: active slots): dispatch of a decode step;
* ``serving.readback``: the host's wait for the decoded tokens;
* ``serving.bookkeep`` (``finished``): the per-slot loop after it.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core.partition import PartitionConfig
from repro.launch.steps import make_decode_step, make_fresh_prefill_step

from .metrics import ServingStats, mean, percentile
from .queues import KVCachePool, PROMPT_BUCKETS, bucket_for, row_bucket
from .requests import Request
from .sim import simulate_pipeline_throughput

__all__ = ["KVCachePool", "Request", "ServingEngine", "ServingStats",
           "simulate_pipeline_throughput"]

# sub-layer kinds whose cache is a recurrent state rather than per-position
# K/V: a padded prefill would fold the padding into the state irreversibly,
# so bucketed admission auto-disables for models containing any of these
# (attention caches are safe: positions beyond a row's length are never
# visible — the per-row cache_len masks them, and each position is
# overwritten by the real token before cache_len reaches it)
RECURRENT_KINDS = frozenset({"mamba2", "mlstm", "slstm"})


class ServingEngine:
    """Continuous-batching engine, optionally driven by a Scission
    operating point: constructing with ``config=`` (a
    :class:`PartitionConfig`, e.g. a frontier point) sets the admission
    width to the operating point's batch size, so the engine admits exactly
    the concurrency the cost model priced.  An explicit ``width`` always
    wins.

    ``prompt_buckets`` controls admission batching: ``"auto"`` (default)
    batches same-tick admissions into one padded-prompt-bucket prefill for
    attention-cache models and falls back to exact per-request prefill for
    recurrent-state models (see :data:`RECURRENT_KINDS`); an explicit
    tuple forces those buckets; ``None`` forces the exact path.
    """

    def __init__(self, model, params, *, width: int | None = None,
                 max_len: int = 256, eos_id: int | None = None,
                 config: PartitionConfig | None = None,
                 prompt_buckets: tuple[int, ...] | str | None = "auto"):
        if width is None:
            width = config.batch_size if config is not None else 4
        if width < 1:
            raise ValueError(f"admission width must be >= 1, got {width}")
        self.model = model
        self.cfg = model.cfg
        self.params = params
        self.config = config
        self.width = width
        self.max_len = max_len
        self.eos_id = eos_id
        self.pool = KVCachePool(model, width, max_len)
        self._prefill = jax.jit(make_fresh_prefill_step(model, max_len))
        self._decode = jax.jit(make_decode_step(model, None, None))
        if prompt_buckets == "auto":
            kinds = set(getattr(self.cfg, "group_kinds", ()) or ())
            prompt_buckets = None if kinds & RECURRENT_KINDS \
                else PROMPT_BUCKETS
        if prompt_buckets is not None:
            # clip to the cache length; always keep one bucket that covers
            # the longest admissible prompt
            prompt_buckets = tuple(sorted(
                {b for b in prompt_buckets if b < max_len} | {max_len}))
        self.prompt_buckets = prompt_buckets
        self.queue: list[Request] = []
        self.active: dict[int, Request] = {}       # slot -> request
        self._next_tok = np.zeros((width, 1), np.int32)
        self.stats = ServingStats()

    # -- client API -----------------------------------------------------------
    def submit(self, req: Request) -> None:
        if len(req.prompt) > self.max_len - 1:
            raise ValueError(
                f"prompt of request {req.rid} is {len(req.prompt)} tokens; "
                f"the engine's cache holds max_len={self.max_len} (prompt "
                "must leave room for at least one generated token)")
        self.queue.append(req)

    def warmup(self) -> "ServingEngine":
        """Pre-compile the decode step and the prefill bucket(s) the queued
        requests will need (the smallest bucket when the queue is empty),
        each at every row bucket up to the width, so the next
        :meth:`run`'s :class:`ServingStats` measure serving, not jit
        compilation.  The prefills are compiled, not run: running every row
        bucket at every length would add their device time to set-up.  The
        decode step runs once.  Idempotent; no engine state changes."""
        dec = self._decode(self.params, self.pool.cache,
                           jnp.asarray(self._next_tok),
                           jnp.asarray(self.pool.lengths, jnp.int32))
        jax.block_until_ready(dec[0])
        if self.prompt_buckets is None:
            # exact-path compiles key on prompt length; warm each distinct
            # length present in the queue
            shapes = [(1, L) for L in sorted({len(r.prompt)
                                              for r in self.queue
                                              if len(r.prompt) > 1})]
        else:
            if self.queue:
                buckets = sorted({bucket_for(max(len(r.prompt) - 1, 1),
                                             self.prompt_buckets)
                                  for r in self.queue if len(r.prompt) > 1})
            else:
                buckets = [min(self.prompt_buckets)]
            rows = sorted({row_bucket(k, self.width)
                           for k in range(1, self.width + 1)})
            shapes = [(r, b) for b in buckets for r in rows]
        for shape in shapes:
            self._prefill.lower(self.params, {"tokens": jax.ShapeDtypeStruct(
                shape, jnp.int32)}).compile()
        return self

    def step(self) -> list[Request]:
        """One engine tick: admit what the queue holds and the pool has
        slots for, then one decode step over the active slots.  Returns
        the requests that finished in it."""
        with TraceAnnotation("serving.step", queued=len(self.queue),
                             active=len(self.active)):
            self._admit()
            return self._decode_step() if self.active else []

    def run(self, max_steps: int = 10_000) -> list[Request]:
        finished: list[Request] = []
        steps = 0
        t0 = time.perf_counter()
        while (self.queue or self.active) and steps < max_steps:
            finished += self.step()
            steps += 1
        waits = [r.queue_wait_s for r in finished
                 if r.queue_wait_s is not None]
        self.stats = ServingStats(
            requests=len(finished),
            tokens=sum(len(r.tokens) for r in finished),
            wall_s=time.perf_counter() - t0,
            queue_wait_mean_s=mean(waits),
            queue_wait_p99_s=percentile(waits, 99))
        return finished

    @property
    def measured_throughput_rps(self) -> float:
        """Request throughput observed on the last :meth:`run`."""
        return self.stats.requests_per_s

    # -- internals --------------------------------------------------------------
    def _admit(self) -> None:
        batch: list[tuple[Request, int]] = []
        while self.queue and self.pool.free:
            req = self.queue.pop(0)
            slot = self.pool.acquire(req.rid)
            batch.append((req, slot))
        if not batch:
            return
        with TraceAnnotation("serving.admit", rows=len(batch),
                             rids=" ".join(str(r.rid) for r, _ in batch)):
            if self.prompt_buckets is None:
                for req, slot in batch:
                    self._admit_exact(req, slot)
            else:
                self._admit_bucketed(batch)

    def _admit_exact(self, req: Request, slot: int) -> None:
        """Legacy per-request prefill (recurrent-state models): one jit
        call per distinct prompt length, fresh batch-1 cache, the first
        token taken from the prefill logits."""
        prompt = jnp.asarray(req.prompt, jnp.int32)[None, :]
        n = len(req.prompt)
        with TraceAnnotation("serving.prefill", rows=1, width=1, bucket=n,
                             real_tokens=n):
            logits, single = self._prefill(self.params, {"tokens": prompt})
        tok = int(jnp.argmax(logits[0, -1]))
        req.tokens.append(tok)
        req.admitted_at = time.perf_counter()
        req.first_token_at = req.admitted_at
        self._write_slot(single, slot)
        self.pool.lengths[slot] = len(req.prompt)
        self._next_tok[slot, 0] = tok
        self.active[slot] = req

    def _admit_bucketed(self, batch: list[tuple[Request, int]]) -> None:
        """One prefill for every same-tick admission: prompts minus their
        last token are right-padded into the smallest covering length
        bucket, over the :func:`row_bucket` of the admitted rows (so
        compiles are bounded by row buckets x length buckets, and the chip
        computes the padding rows up to the next power of two, not up to
        the width: ``serving.prefill``'s ``width`` is the rows computed),
        the resulting cache rows are scattered into the admitted slots,
        and the *last* prompt token becomes each slot's first decode input
        — the next decode step then produces the first generated token
        from logits identical to an exact prefill's last position (causal
        attention never sees the right padding, and the per-row cache_len
        masks the padded cache positions until real tokens overwrite
        them)."""
        now = time.perf_counter()
        pre = max(len(req.prompt) - 1 for req, _ in batch)
        if pre > 0:
            bucket = bucket_for(pre, self.prompt_buckets)
            rows = row_bucket(len(batch), self.width)
            toks = np.zeros((rows, bucket), np.int32)
            for j, (req, _) in enumerate(batch):
                toks[j, :len(req.prompt) - 1] = req.prompt[:-1]
            with TraceAnnotation(
                    "serving.prefill", rows=len(batch), width=rows,
                    bucket=bucket,
                    real_tokens=sum(len(r.prompt) - 1 for r, _ in batch)):
                _, cache = self._prefill(self.params,
                                         {"tokens": jnp.asarray(toks)})
            self._scatter_rows(cache, rows=list(range(len(batch))),
                               slots=[slot for _, slot in batch])
        for req, slot in batch:
            req.admitted_at = now
            self.pool.lengths[slot] = len(req.prompt) - 1
            self._next_tok[slot, 0] = int(req.prompt[-1])
            self.active[slot] = req

    def _scatter_rows(self, src_cache, rows: list[int],
                      slots: list[int]) -> None:
        """Copy batch rows ``rows`` of a prefill's cache into pool slots
        ``slots`` (batch lives at axis 1 of every cache leaf, after the
        layer-stack axis)."""
        rows_ix = jnp.asarray(rows)
        slots_ix = jnp.asarray(slots)

        def write(dst, src):
            return dst.at[:, slots_ix].set(src[:, rows_ix])

        with TraceAnnotation("serving.scatter", rows=len(rows)):
            self.pool.cache = jax.tree.map(write, self.pool.cache,
                                           src_cache)

    def _write_slot(self, single_cache, slot: int) -> None:
        def write(dst, src):
            # batch dim position differs per leaf kind; all our cache leaves
            # carry batch at axis 1 (after the layer-stack axis) except
            # scalar-state tuples where it is axis 1 as well.
            return dst.at[:, slot:slot + 1].set(src)

        with TraceAnnotation("serving.scatter", rows=1):
            self.pool.cache = jax.tree.map(write, self.pool.cache,
                                           single_cache)

    def _decode_step(self) -> list[Request]:
        # ragged continuous batching: per-slot cache lengths drive per-row
        # positions, write offsets and attention masks
        with TraceAnnotation("serving.decode", rows=len(self.active)):
            cache_len = jnp.asarray(self.pool.lengths, jnp.int32)
            tok = jnp.asarray(self._next_tok)
            next_tok, logits, self.pool.cache = self._decode(
                self.params, self.pool.cache, tok, cache_len)
        with TraceAnnotation("serving.readback"):
            nxt = np.asarray(next_tok)
        finished: list[Request] = []
        with TraceAnnotation("serving.bookkeep") as span:
            now = time.perf_counter()
            for slot, req in list(self.active.items()):
                t = int(nxt[slot, 0])
                req.tokens.append(t)
                if req.first_token_at is None:
                    req.first_token_at = now
                self.pool.lengths[slot] += 1
                limit = (len(req.tokens) >= req.max_new_tokens
                         or (self.eos_id is not None and t == self.eos_id)
                         or self.pool.lengths[slot] >= self.max_len - 1)
                if limit:
                    req.done = True
                    req.finished_at = now
                    finished.append(req)
                    del self.active[slot]
                    self.pool.release(slot)
                else:
                    self._next_tok[slot, 0] = t
            span.set_metadata(finished=len(finished))
        return finished
