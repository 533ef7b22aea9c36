"""Continuous-batching inference runtime — the serving data plane.

The engine promotes the serving pattern that used to live in
``examples/serve_batch.py`` into a reusable runtime:

  * **slot-based KV cache** — one batched cache of ``capacity`` slots,
    each slot carrying its own write position (``pos`` is a per-slot
    vector, not the shared scalar of the training-side decode), so
    slots at different depths coexist in one jit'd decode step;
  * **continuous batching** — finished sequences retire immediately and
    queued requests are prefilled into the freed slots mid-flight
    (equal-length queue neighbours prefill together as one batch);
  * **bounded admission queue** — ``submit`` rejects when the queue is
    full (REST maps ``QueueFull`` to HTTP 429) and every request may
    carry a deadline, enforced both while queued and while decoding.

Decode is ``jit(model.decode)`` over the whole slot-batched cache, each
row masked and positioned at its own slot's ``pos``: each slot is
mathematically an independent batch-1 decode, which is what makes a
mid-flight join token-identical to running the request alone
(tests/test_serving.py asserts exactly that). The step reads the cache
once and writes only each slot's new KV rows, in place in the donated
buffer; ``stats()`` reports whether the compiled program did so. Greedy
(argmax) sampling keeps the engine deterministic.
"""
from __future__ import annotations

import collections
import logging
import re
import threading
import time
import uuid
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.configs.base import ArchConfig
from repro.distributed.sharding import Dist
from repro.models import make_model
from repro.observability.trace import maybe_span
from repro.platform.cluster import UserError
from repro.platform.metrics import MetricsService
from repro.runtime.learner import _flat_io

log = logging.getLogger("repro.serving")
job_log = logging.getLogger("repro.job")

# inference keeps no activations for a backward pass; attention chunks
# are the model defaults, clipped to the prompt length
ENGINE_OPTS = {"remat": "none"}

# names of the engine's jitted programs: XLA calls them jit_<name>, and the
# profiler's trace and the benchmark find them by these names
PREFILL_PROGRAM = "serve_prefill"
DECODE_PROGRAM = "serve_decode"

# The serve loop writes one profiler span per phase (TraceAnnotation: on the
# profiler's clock beside the device's ops, and nowhere else; about 1 us when
# no profiler runs). The phases are siblings that tile each iteration:
#   serve.wait      nothing to run (no live slot, or the pause gate holds)
#   serve.schedule  preemption check, queue expiry, picking the next batch
#   serve.admit     one prefill batch: upload, prefill, first tokens, splices
#   serve.dispatch  next-token upload, decode step and argmax dispatched
#   serve.read      the step's tokens read on the host (waits for the device)
#   serve.bookkeep  tokens appended, requests retired and settled, gauges
# so each serve.read is one decode step.

# request states
R_QUEUED, R_RUNNING, R_DONE, R_REJECTED, R_EXPIRED, R_FAILED = (
    "QUEUED", "RUNNING", "DONE", "REJECTED", "EXPIRED", "FAILED")


class QueueFull(Exception):
    """Admission queue at capacity — REST maps this to HTTP 429."""


class EndpointClosed(Exception):
    """Endpoint draining/stopped: no new requests accepted (HTTP 409)."""


class DeadlineExceeded(Exception):
    """Request deadline elapsed before completion (HTTP 504)."""


@dataclass
class InferenceRequest:
    req_id: str
    prompt: np.ndarray                      # (P,) int32
    max_new: int
    deadline: Optional[float]               # absolute wall-clock, or None
    submitted: float = field(default_factory=time.time)
    status: str = R_QUEUED
    tokens: List[int] = field(default_factory=list)
    error: str = ""
    done: threading.Event = field(default_factory=threading.Event)
    started_ts: Optional[float] = None      # taken from the queue into prefill
    first_token_ts: Optional[float] = None  # first token read on the host
    finished_ts: Optional[float] = None

    def wait(self, timeout: Optional[float] = None) -> bool:
        return self.done.wait(timeout)

    @property
    def queue_s(self) -> Optional[float]:
        """Seconds from submission to being taken into prefill."""
        if self.started_ts is None:
            return None
        return self.started_ts - self.submitted

    @property
    def ttft_s(self) -> Optional[float]:
        """Seconds from submission to the first token."""
        if self.first_token_ts is None:
            return None
        return self.first_token_ts - self.submitted


def decode_in_place(compiled, cache) -> tuple:
    """(temporary bytes, every cache leaf updated in place) of a compiled
    decode program ``(params, cache, tokens) -> (logits, cache)``: the
    cache leaves are outputs 1..n, each in place where XLA aliased it to
    its donated input."""
    text = compiled.as_text()
    header = text[:text.find("\n")]
    aliased = {int(i) for i in re.findall(r"\{(\d+)\}: \(\d+, \{\}", header)}
    n = len(jax.tree.leaves(cache))
    return (int(compiled.memory_analysis().temp_size_in_bytes),
            set(range(1, n + 1)) <= aliased)


def _named(fn, name: str):
    """``fn`` under ``name``, which jit gives its program (jit_<name>)
    whatever the function is called."""
    def named(*args):
        return fn(*args)
    named.__name__ = named.__qualname__ = name
    return named


class InferenceEngine:
    """Continuous-batching greedy decoder over a slot-based KV cache.

    Thread model: ``submit``/``stats``/``drain`` are safe from any
    thread; ``start`` + ``run`` belong to the single server task body
    (the endpoint's LCM-deployed task). ``run`` honors the same
    step-boundary contract as training bodies: preemption via the
    watchdog, pause via JobControl — an aborted incarnation re-queues
    its in-flight requests so the re-placed task resumes them.
    """

    def __init__(self, cfg: ArchConfig, *, capacity: int = 2,
                 max_seq: int = 64, max_queue: int = 16,
                 default_max_new: int = 16, eos_id: Optional[int] = None,
                 seed: int = 0, metrics: Optional[MetricsService] = None,
                 endpoint_id: str = "endpoint", tracer=None):
        if cfg.family == "encdec":
            raise UserError(
                "serving supports decoder-family archs only (dense/moe/"
                f"ssm/hybrid/vlm); {cfg.arch_id!r} is encoder-decoder")
        if capacity < 1 or max_queue < 1 or max_seq < 2:
            raise UserError("capacity/max_queue must be >= 1, max_seq >= 2")
        self.cfg = cfg
        self.model = make_model(cfg, Dist(), dict(ENGINE_OPTS))
        self.capacity = int(capacity)
        self.max_seq = int(max_seq)
        self.max_queue = int(max_queue)
        self.default_max_new = int(default_max_new)
        self.eos_id = eos_id
        self.seed = int(seed)
        self.metrics = metrics
        self.endpoint_id = endpoint_id
        self.tracer = tracer
        self._req_spans: Dict[str, object] = {}  # req_id -> open span

        self._lock = threading.RLock()
        self._queue: collections.deque = collections.deque()
        self._wake = threading.Event()
        self._ready = threading.Event()
        self._draining = False
        self._released = False
        self._slots: List[Optional[InferenceRequest]] = \
            [None] * self.capacity
        self._next_tok = np.zeros(self.capacity, np.int32)
        self._cache = None
        # SLO remediation knobs: a shed limit tightens admission below
        # max_queue (429 earlier under a p99 burn); pended slots grow
        # capacity at the NEXT start() — the KV cache and decode jit are
        # shaped by capacity, so a live incarnation can't grow in place
        self._shed_limit: Optional[int] = None
        self._pending_slots = 0
        self.params = None
        self._axes = self._cache_axes()
        self._flat_io = None                # (ravel, unravel, size)
        # accounting (guarded by _lock; mirrored into MetricsService).
        # Latencies are a rolling window: endpoints are long-lived and
        # per-request state must not grow without bound.
        self._counts = collections.Counter()
        self._latencies: collections.deque = collections.deque(
            maxlen=4096)
        self._decode_steps = 0
        self._occupied_slot_steps = 0
        self._decode_s = 0.0        # host time inside decode steps
        # from the decode program's background compile (status.perf):
        # its temporary bytes, and whether every cache leaf is updated in
        # place (the donated input aliased to the output)
        self._decode_temp_bytes: Optional[int] = None
        self._decode_cache_aliased: Optional[bool] = None
        # roofline estimate of the decode step (status.perf), analyzed
        # in the background once the jits are built
        from repro.analysis.perf import JobPerf
        self.perf = JobPerf(endpoint_id or "endpoint", metrics,
                            unit="decode_step")

    # ---- weight I/O -------------------------------------------------------
    def _ensure_flat_io(self):
        if self._flat_io is None:
            shapes = jax.eval_shape(self.model.init, jax.random.PRNGKey(0))
            ravel, unravel = _flat_io(shapes)
            size = int(sum(np.prod(l.shape, dtype=np.int64)
                           for l in jax.tree.leaves(shapes)))
            self._flat_io = (ravel, unravel, size)
        return self._flat_io

    @property
    def flat_size(self) -> int:
        """Length of the flat f32 weight vector (the training wire /
        results-store layout) this engine's arch expects."""
        return self._ensure_flat_io()[2]

    # ---- lifecycle --------------------------------------------------------
    def start(self, flat_params: Optional[np.ndarray] = None):
        """(Re)build jits + the slot cache and load weights; flips the
        engine READY. ``flat_params`` is the flat f32 vector a training
        job uploaded (None: fresh init from ``seed`` — deploy-from-arch).
        Called once per task incarnation: a re-placed endpoint rebuilds
        everything and resumes its re-queued requests."""
        with self._lock:
            if self._pending_slots:
                # apply slots pended by add_slot(): this incarnation's
                # cache/jits are built at the grown capacity below
                self.capacity += self._pending_slots
                self._pending_slots = 0
                self._slots = [None] * self.capacity
                self._next_tok = np.zeros(self.capacity, np.int32)
        _, unravel, size = self._ensure_flat_io()
        if flat_params is not None:
            flat_params = np.asarray(flat_params, np.float32).reshape(-1)
            if flat_params.size != size:
                raise UserError(
                    f"weights size {flat_params.size} does not match "
                    f"arch {self.cfg.arch_id!r} ({size} params)")
            self.params = unravel(jnp.asarray(flat_params))
        else:
            self.params = self.model.init(jax.random.PRNGKey(self.seed))
        self._prefill = jax.jit(_named(self.model.prefill, PREFILL_PROGRAM))
        self._decode = self.decode_program()
        self._splice = jax.jit(self._splice_fn, donate_argnums=(0,))
        with self._lock:
            self._cache = self._empty_cache()
            self._released = False
            self._ready.set()
        # snapshot shapes eagerly (the live cache is donated every
        # decode step; ShapeDtypeStructs stay valid), lower lazily
        sds = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)
        p0 = jax.tree.map(sds, self.params)
        c0 = self.cache_shapes()
        t0 = jax.ShapeDtypeStruct((self.capacity, 1), jnp.int32)
        dec = self._decode

        def analyze():
            compiled = dec.lower(p0, c0, t0).compile()
            temp, aliased = decode_in_place(compiled, c0)
            with self._lock:
                self._decode_temp_bytes = temp
                self._decode_cache_aliased = aliased
            return compiled.as_text()

        self.perf.start_async(analyze)

    def decode_program(self):
        """The jitted decode step ``(params, cache, tokens (capacity, 1)) ->
        (logits, cache)``: one token for every slot, the slot cache
        donated and updated in place."""
        def step(params, cache, tokens):
            return self.model.decode(params, cache, {"tokens": tokens})
        return jax.jit(_named(step, DECODE_PROGRAM), donate_argnums=(1,))

    def cache_shapes(self) -> Dict[str, jax.ShapeDtypeStruct]:
        """Shapes of the slot cache: the model's cache at capacity x
        max_seq, with one write position per slot."""
        out = dict(self.model.cache_specs(self.capacity, self.max_seq))
        # per-slot write position (the training decode shares one
        # scalar; serving slots run at different depths)
        out["pos"] = jax.ShapeDtypeStruct((self.capacity,), jnp.int32)
        return out

    @property
    def ready(self) -> bool:
        return self._ready.is_set()

    @property
    def draining(self) -> bool:
        return self._draining

    @property
    def released(self) -> bool:
        return self._released

    def drain(self):
        """Stop accepting requests; ``run`` exits once in-flight and
        already-queued work finishes."""
        with self._lock:
            self._draining = True
        self._wake.set()

    def release(self):
        """Teardown: free the slot KV cache and jit handles and fail any
        still-queued requests closed. Called after the endpoint's task
        exited (terminal state) — mirrors the PR 3 pattern of
        snapshotting stats at completion so the buffers can go."""
        with self._lock:
            self._draining = True
            self._released = True
            pending = list(self._queue)
            self._queue.clear()
            self._cache = None
            self._decode = self._prefill = self._splice = None
            self.params = None
            self._ready.clear()
        now = time.time()
        for r in pending:
            self._settle(r, R_FAILED, now, error="endpoint stopped")
        self._wake.set()

    # ---- admission --------------------------------------------------------
    def submit(self, prompt, max_new: Optional[int] = None,
               deadline_s: Optional[float] = None) -> InferenceRequest:
        """Admit one request (any thread). Raises ``QueueFull`` when the
        bounded queue is at capacity, ``EndpointClosed`` when draining,
        ``UserError`` on malformed input."""
        prompt = np.asarray(prompt, dtype=np.int32).reshape(-1)
        max_new = int(max_new if max_new is not None
                      else self.default_max_new)
        if prompt.size == 0 or max_new < 1:
            raise UserError("prompt must be non-empty and max_new >= 1")
        if prompt.size + max_new > self.max_seq:
            raise UserError(
                f"prompt ({prompt.size}) + max_new ({max_new}) exceeds "
                f"the endpoint's max_seq ({self.max_seq})")
        if int(prompt.min()) < 0 or int(prompt.max()) >= self.cfg.vocab_size:
            raise UserError(
                f"token ids must be in [0, {self.cfg.vocab_size})")
        req = InferenceRequest(
            req_id=f"req-{uuid.uuid4().hex[:8]}", prompt=prompt,
            max_new=max_new,
            deadline=(time.time() + float(deadline_s)
                      if deadline_s is not None else None))
        with self._lock:
            if self._draining or self._released:
                raise EndpointClosed(
                    f"endpoint {self.endpoint_id} is not accepting "
                    f"requests")
            self._incr("requests_total")
            limit = (self._shed_limit if self._shed_limit is not None
                     else self.max_queue)
            if len(self._queue) >= limit:
                req.status = R_REJECTED
                req.done.set()
                self._incr("rejected_total")
                raise QueueFull(
                    f"admission queue full ({limit} waiting"
                    + (", load shed" if self._shed_limit is not None
                       else "") + ")")
            self._queue.append(req)
            depth = len(self._queue)
            if self.tracer is not None:
                # per-request span in the endpoint's trace: admission
                # to settle, closed in _settle with the final status
                self._req_spans[req.req_id] = self.tracer.start(
                    self.endpoint_id, "request", req_id=req.req_id,
                    plen=int(prompt.size), max_new=max_new)
        self._gauge("queue_depth", depth)
        self._wake.set()
        return req

    # ---- serve loop -------------------------------------------------------
    def run(self, *, wd=None, control=None):
        """Serve until drained. ``wd`` (Watchdog) adds preemption checks
        + heartbeats; ``control`` (JobControl) adds the pause gate. Both
        are observed at batch-step boundaries, exactly like training
        bodies. On abort (preemption/crash) in-flight requests re-queue
        so the next incarnation resumes them."""
        should_abort = wd.maybe_preempt if wd is not None else None
        served = 0
        try:
            while True:
                with TraceAnnotation("serve.schedule"):
                    if wd is not None:
                        wd.maybe_preempt()
                    paused = control is not None and control.paused
                    if not paused:
                        self._expire_queued()
                        batch = self._take_batch()
                        with self._lock:
                            live = sum(1 for r in self._slots
                                       if r is not None)
                            idle_exit = (self._draining and live == 0
                                         and not self._queue)
                if paused:
                    with TraceAnnotation("serve.wait"):
                        control.wait_while_paused(should_abort=should_abort)
                elif batch is not None:
                    # admit every batch that fits before the next step
                    self._admit(*batch)
                elif idle_exit:
                    break
                elif live:
                    served += self._decode_once()
                    if wd is not None and self._decode_steps % 32 == 0:
                        with TraceAnnotation("serve.bookkeep"):
                            wd.heartbeat(self._decode_steps, served=served)
                else:
                    with TraceAnnotation("serve.wait"):
                        if self._wake.wait(timeout=0.02):
                            self._wake.clear()
        except BaseException:
            # preemption or infra failure: put in-flight work back at
            # the head of the queue (newest first through appendleft,
            # so the oldest request ends up frontmost — FIFO survives
            # preemption); the re-placed incarnation resumes them
            with self._lock:
                inflight = [r for r in self._slots if r is not None]
                self._slots = [None] * self.capacity
                for r in sorted(inflight, key=lambda r: r.submitted,
                                reverse=True):
                    r.tokens = []
                    r.status = R_QUEUED
                    r.started_ts = r.first_token_ts = None
                    self._queue.appendleft(r)
                self._ready.clear()
            raise

    # ---- internals --------------------------------------------------------
    def _cache_axes(self) -> Dict[str, int]:
        """Slot (batch) axis per cache leaf, where a prefilled request's
        cache is spliced in. Derived from the family cache layouts in
        models/model.py:cache_specs."""
        axes = {}
        for k, v in self.model.cache_specs(1, 8).items():
            if k == "pos":
                axes[k] = 0
            elif k in ("k", "v", "cross_k", "cross_v"):
                axes[k] = 1
            elif k == "ssm":
                axes[k] = 1 if v.ndim == 5 else 2      # hybrid: (np,per-1,B,…)
            elif k == "conv":
                axes[k] = 1 if v.ndim == 4 else 2
            else:
                raise ValueError(f"unknown cache leaf {k!r}")
        return axes

    def _empty_cache(self):
        return {k: jnp.zeros(s.shape, s.dtype)
                for k, s in self.cache_shapes().items()}

    def _splice_fn(self, cache, one, slot):
        """Write one prefilled request cache (batch dim 1, seq padded to
        max_seq) into slot ``slot`` of the batched cache."""
        out = {}
        for k, v in cache.items():
            if k == "pos":
                out[k] = v.at[slot].set(one["pos"].astype(v.dtype))
            else:
                out[k] = jax.lax.dynamic_update_slice_in_dim(
                    v, one[k].astype(v.dtype), slot, axis=self._axes[k])
        return out

    def _pad_prefill(self, cache):
        """Pad a prefill cache's sequence dim out to max_seq (k/v caches
        only; ssm/conv state has no sequence dim)."""
        out = dict(cache)
        for k in ("k", "v"):
            if k in out:
                pads = [(0, 0)] * out[k].ndim
                pads[2] = (0, self.max_seq - out[k].shape[2])
                out[k] = jnp.pad(out[k], pads)
        return out

    def _take_batch(self):
        """Take the next prefill batch off the queue: the head request
        and its equal-length queue neighbours (continuous batching's
        batched-prefill path), at most one per free slot. Returns
        (batch, free slots), or None when nothing can be admitted."""
        with self._lock:
            if not self._queue or self._cache is None:
                return None
            free = [s for s in range(self.capacity)
                    if self._slots[s] is None]
            if not free:
                return None
            batch = [self._queue.popleft()]
            plen = batch[0].prompt.size
            while (len(batch) < len(free) and self._queue
                   and self._queue[0].prompt.size == plen):
                batch.append(self._queue.popleft())
            depth = len(self._queue)
            now = time.time()
            for req in batch:
                req.started_ts = now
        self._gauge("queue_depth", depth)
        return batch, free

    def _admit(self, batch: List[InferenceRequest], free: List[int]):
        """Prefill one batch and splice each request's cache into its
        free slot."""
        plen = int(batch[0].prompt.size)
        with TraceAnnotation("serve.admit", n=len(batch), plen=plen):
            toks = jnp.asarray(np.stack([r.prompt for r in batch]))
            # the span ends with the first tokens on the host, that is
            # once the device has finished the prefill
            with maybe_span(self.tracer, self.endpoint_id, "prefill",
                            n=len(batch), plen=plen):
                logits, c1 = self._prefill(self.params,
                                           {"tokens": toks})
                c1 = self._pad_prefill(c1)
                first = np.asarray(
                    jnp.argmax(logits[:, -1, :], axis=-1)).astype(np.int32)
                now = time.time()
            for i, req in enumerate(batch):
                slot = free[i]
                one = {k: jax.lax.slice_in_dim(v, i, i + 1,
                                               axis=self._axes[k])
                       for k, v in c1.items() if k != "pos"}
                # prefill emits one shared scalar pos; the slot cache
                # tracks a per-slot position instead
                one["pos"] = jnp.asarray(req.prompt.size, jnp.int32)
                self._cache = self._splice(self._cache, one,
                                           jnp.asarray(slot, jnp.int32))
                with self._lock:
                    req.status = R_RUNNING
                    req.first_token_ts = now
                    req.tokens.append(int(first[i]))
                    self._slots[slot] = req
                    self._next_tok[slot] = first[i]
                    self._maybe_retire(slot, req, now)

    def _decode_once(self) -> int:
        with TraceAnnotation("serve.dispatch"):
            t0 = time.perf_counter()
            toks = jnp.asarray(self._next_tok.reshape(self.capacity, 1))
            logits, self._cache = self._decode(self.params, self._cache,
                                               toks)
            nxt = jnp.argmax(logits[:, -1, :], axis=-1)
        with TraceAnnotation("serve.read"):
            nxt = np.asarray(nxt).astype(np.int32)
            step_s = time.perf_counter() - t0
        with TraceAnnotation("serve.bookkeep"):
            now = time.time()
            live = 0
            with self._lock:
                for s in range(self.capacity):
                    r = self._slots[s]
                    if r is None:
                        continue
                    live += 1
                    r.tokens.append(int(nxt[s]))
                    self._next_tok[s] = nxt[s]
                    self._maybe_retire(s, r, now)
                self._decode_steps += 1
                self._decode_s += step_s
                self._occupied_slot_steps += live
            self._gauge("batch_occupancy", live / self.capacity,
                        step=self._decode_steps)
        return live

    def _maybe_retire(self, slot: int, req: InferenceRequest, now: float):
        """Retire a finished/expired slot (caller holds the lock)."""
        finished = (len(req.tokens) >= req.max_new
                    or (self.eos_id is not None
                        and req.tokens[-1] == self.eos_id))
        if finished:
            self._slots[slot] = None
            self._settle(req, R_DONE, now)
        elif req.deadline is not None and now > req.deadline:
            self._slots[slot] = None
            self._settle(req, R_EXPIRED, now)

    def _expire_queued(self):
        now = time.time()
        expired = []
        with self._lock:
            if any(r.deadline is not None and now > r.deadline
                   for r in self._queue):
                keep = collections.deque()
                while self._queue:
                    r = self._queue.popleft()
                    if r.deadline is not None and now > r.deadline:
                        expired.append(r)
                    else:
                        keep.append(r)
                self._queue = keep
        for r in expired:
            self._settle(r, R_EXPIRED, now)

    def _settle(self, req: InferenceRequest, status: str, now: float,
                error: str = ""):
        """Final bookkeeping for one request (any terminal status)."""
        with self._lock:
            req.status = status
            req.finished_ts = now
            req.error = error
            lat = now - req.submitted
            if status == R_DONE:
                self._latencies.append(lat)
                self._incr("completed_total")
                self._incr("tokens_out_total", len(req.tokens))
                if self.metrics is not None:
                    self.metrics.record_bounded(
                        self.endpoint_id, "latency_s",
                        self._decode_steps, lat)
            elif status == R_EXPIRED:
                self._incr("expired_total")
            elif status == R_FAILED:
                self._incr("failed_total")
            span = self._req_spans.pop(req.req_id, None)
        if span is not None:
            self.tracer.end(span,
                            status=("ok" if status == R_DONE
                                    else "error"),
                            result=status, tokens=len(req.tokens),
                            queue_s=req.queue_s, ttft_s=req.ttft_s)
        job_log.debug("request %s %s tokens=%d latency=%.4fs",
                      req.req_id, status, len(req.tokens), lat,
                      extra={"job_id": self.endpoint_id})
        req.done.set()

    def _incr(self, counter: str, value: float = 1.0):
        self._counts[counter] += value
        if self.metrics is not None:
            try:
                self.metrics.incr(self.endpoint_id, counter, value)
            except Exception as e:           # accounting must not kill serving
                log.warning("metrics incr failed: %s", e)

    def _gauge(self, metric: str, value: float,
               step: Optional[int] = None):
        if self.metrics is not None:
            try:
                # bounded: endpoints are long-lived — one entry per
                # decode step / request must not grow RSS forever
                self.metrics.record_bounded(
                    self.endpoint_id, metric,
                    step if step is not None else self._decode_steps,
                    value)
            except Exception as e:
                log.warning("metrics record failed: %s", e)

    # ---- SLO remediation hooks --------------------------------------------
    def shed(self, frac: float = 0.5):
        """Tighten admission to ``frac`` of max_queue (min 1): requests
        beyond it 429 immediately instead of queueing into a latency
        burn. Reversed by ``unshed``."""
        with self._lock:
            self._shed_limit = max(1, int(self.max_queue * frac))
        log.warning("endpoint %s shedding load: admission limit %d "
                    "(of %d)", self.endpoint_id, self._shed_limit,
                    self.max_queue)

    def unshed(self):
        with self._lock:
            was, self._shed_limit = self._shed_limit, None
        if was is not None:
            log.info("endpoint %s shed lifted (limit %d -> %d)",
                     self.endpoint_id, was, self.max_queue)

    def add_slot(self, n: int = 1):
        """Pend ``n`` extra decode slots; applied at the next ``start()``
        (the KV cache and decode jit are shaped by capacity). The caller
        recycles the server task so its next incarnation picks them up."""
        with self._lock:
            self._pending_slots += max(0, int(n))
        log.warning("endpoint %s pending +%d decode slot(s) (capacity "
                    "%d -> %d at next start)", self.endpoint_id, n,
                    self.capacity, self.capacity + self._pending_slots)

    # ---- observability ----------------------------------------------------
    def decode_rate(self) -> Optional[float]:
        """Decode steps/s over the host time spent inside decode steps,
        from dispatch to the tokens read, so time the engine sits idle
        is not counted (the measured term of the status.perf roofline
        fraction)."""
        with self._lock:
            steps, secs = self._decode_steps, self._decode_s
        return steps / secs if steps and secs > 0 else None

    def perf_status(self) -> Dict:
        """``status.perf``: the decode step's roofline estimate folded
        with the measured rate, and its in-place figures."""
        with self._lock:
            mem = {"decode_temp_bytes": self._decode_temp_bytes,
                   "decode_cache_aliased": self._decode_cache_aliased}
        return dict(self.perf.snapshot(self.decode_rate()), **mem)

    def stats(self) -> Dict:
        """Counters + latency percentiles + occupancy — what endpoint
        status exposes and the serving benchmark samples."""
        with self._lock:
            lat = sorted(self._latencies)
            steps = self._decode_steps
            occ = self._occupied_slot_steps
            out = {
                "requests_total": int(self._counts["requests_total"]),
                "completed_total": int(self._counts["completed_total"]),
                "rejected_total": int(self._counts["rejected_total"]),
                "expired_total": int(self._counts["expired_total"]),
                "failed_total": int(self._counts["failed_total"]),
                "tokens_out_total": int(self._counts["tokens_out_total"]),
                "queue_depth": len(self._queue),
                "active": sum(1 for r in self._slots if r is not None),
                "capacity": self.capacity,
                "max_queue": self.max_queue,
                "shed_limit": self._shed_limit,
                "pending_slots": self._pending_slots,
                "decode_steps": steps,
                "occupied_slot_steps": occ,
                "mean_batch_occupancy": round(
                    occ / (steps * self.capacity), 4) if steps else 0.0,
                "decode_temp_bytes": self._decode_temp_bytes,
                "decode_cache_aliased": self._decode_cache_aliased,
            }
        if self.metrics is not None:
            p50 = self.metrics.percentile(self.endpoint_id, "latency_s", 50)
            p99 = self.metrics.percentile(self.endpoint_id, "latency_s", 99)
        else:
            p50 = p99 = None
        if p50 is None and lat:               # metrics absent or dropped
            # same nearest-rank formula as MetricsService.percentile
            p50 = lat[max(0, int(np.ceil(0.50 * len(lat))) - 1)]
            p99 = lat[max(0, int(np.ceil(0.99 * len(lat))) - 1)]
        out["p50_latency_s"] = round(p50, 4) if p50 is not None else None
        out["p99_latency_s"] = round(p99, 4) if p99 is not None else None
        return out
