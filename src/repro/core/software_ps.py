"""Software parameter server (paper §Parameter Server) — the control-plane
faithful implementation used by the as-a-Service path, where learners are
simulated containers (threads).

Mirrors the paper's structure: (i) a group of PS *shards* that collectively
store and aggregate the model partitions, (ii) a PS *client* that evenly
partitions the flat model by shard ID ("as all the learners of the same
training job follow exactly the same model partitioning scheme, the same
partitions from different learners are gathered by the same server"), and
synchronous ``push``/``pull`` plus ``join``/``leave`` connection calls.
Data moves in raw binary (numpy views) — "DLaaS does not use any parameter
serialization or deserialization".

The data plane is built for throughput (the paper calls the PS "a
throughput-critical system"):

  * **Zero-copy receive** — the partition layout (``ShardLayout``) is
    computed once at construction; each learner owns a row of a
    preallocated ``(n_learners, padded)`` receive buffer and writes its
    push straight into it, outside any lock. No per-push padding,
    stacking or concatenation allocations.
  * **Pipelined push/pull** — receives overlap across learners, and pulls
    (which read the parameter block under per-shard locks) overlap with
    the next round's receives because the two touch disjoint buffers.
  * **Fused aggregation** — a BSP round applies mean-aggregation + the
    solver update as one fused read-modify-write pass
    (``kernels/ps_aggregate.py`` on TPU, the in-place numpy twin
    ``kernels/ref.py:ps_aggregate_np`` elsewhere): whole-model for small
    models, per shard in parallel on a small pool for large ones.
  * **int8 wire compression** — ``PSClient`` optionally block-quantizes
    pushes (``core/compression.py``, error feedback per learner) so ~4x
    fewer bytes cross the simulated wire; the PS dequantizes directly
    into the receive row.

Aggregation triggers: ``bsp`` waits until all partitions are gathered
(model averaging / PSGD), ``on_arrival`` applies each push immediately
(Downpour). The TPU adaptation of the same scheme is core/ps.py
(reduce-scatter/all-gather); solver math is shared via kernels/ref.py's
``ps_aggregate_ref`` update rules.
"""
from __future__ import annotations

import contextlib
import functools
import logging
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass
from typing import Dict, List, Optional, Union

import numpy as np

from repro.core.compression import BLOCK, CompressedPush, make_compressor
from repro.kernels.grid import TILE, pad_to

log = logging.getLogger("repro.ps")

# below this many elements a BSP round is applied serially: the pool
# dispatch would cost more than the fused update itself
PARALLEL_AGG_MIN_ELEMS = 1 << 20

# one process-wide aggregation pool shared by every PS instance — the
# service keeps completed jobs (and their PS) around for status
# reporting, so per-instance pools would leak threads per job
_AGG_POOL: Optional[ThreadPoolExecutor] = None
_AGG_POOL_LOCK = threading.Lock()


def _agg_pool() -> ThreadPoolExecutor:
    global _AGG_POOL
    with _AGG_POOL_LOCK:
        if _AGG_POOL is None:
            import os
            _AGG_POOL = ThreadPoolExecutor(
                max_workers=max(2, min(8, os.cpu_count() or 2)),
                thread_name_prefix="ps-agg")
        return _AGG_POOL

# PS-side solver name -> fused-kernel solver name (kernels/ref.py /
# kernels/ps_aggregate.py). 'easgd' pushes carry beta * (x_i - center)
# already, so the center rule applies the mean with beta = 1.
_FUSED_SOLVER = {"sgd": "sgd", "momentum": "momentum", "adam": "adam",
                 "average": "average", "easgd": "easgd_center"}


@dataclass(frozen=True)
class ShardLayout:
    """Even partition of the flat model by shard ID, fixed at server
    construction (every learner follows the same scheme). ``shard_len``
    is rounded up to whole (8, 128) f32 tiles (``TILE``, a multiple of
    the compression block), so every shard is a legal operand of the
    aggregation kernel and a compressed push splits into per-shard views
    without re-blocking."""
    size: int               # true (unpadded) model size
    n_shards: int
    shard_len: int          # multiple of TILE
    padded: int             # n_shards * shard_len

    @classmethod
    def build(cls, size: int, n_shards: int) -> "ShardLayout":
        per = max(1, -(-size // n_shards))
        shard_len = pad_to(per, TILE)
        return cls(size=size, n_shards=n_shards, shard_len=shard_len,
                   padded=shard_len * n_shards)

    def shard_slice(self, s: int) -> slice:
        return slice(s * self.shard_len, (s + 1) * self.shard_len)

    def valid_len(self, s: int) -> int:
        """Elements of shard ``s`` that map to real (unpadded) model."""
        return max(0, min(self.shard_len, self.size - s * self.shard_len))


class SoftwareParameterServer:
    def __init__(self, init_flat: np.ndarray, *, n_shards: int = 4,
                 n_learners: int = 1, optimizer: str = "sgd",
                 lr: float = 0.1, trigger: str = "bsp",
                 compression: str = "none",
                 momentum: float = 0.9, b1: float = 0.9, b2: float = 0.999,
                 eps: float = 1e-8, metrics=None, job_id: str = None):
        assert trigger in ("bsp", "on_arrival")
        if optimizer not in _FUSED_SOLVER:
            raise ValueError(optimizer)
        assert compression in ("none", "int8"), compression
        self.n_learners = n_learners
        self.trigger = trigger
        self.optimizer = optimizer
        self.lr = lr
        self.compression = compression
        self.momentum, self.b1, self.b2, self.eps = momentum, b1, b2, eps
        self.metrics, self.job_id = metrics, job_id

        init_flat = np.asarray(init_flat, np.float32).ravel()
        self.size = init_flat.size
        self.layout = ShardLayout.build(self.size, n_shards)
        lay = self.layout
        # global state: one contiguous block per quantity; shard s owns
        # the contiguous view [s*shard_len, (s+1)*shard_len)
        self._params = np.zeros(lay.padded, np.float32)
        self._params[: self.size] = init_flat
        self._m = np.zeros(lay.padded, np.float32)
        self._v = np.zeros(lay.padded, np.float32)
        self._step = 0                      # solver step (adam bias corr.)
        self._step_lock = threading.Lock()
        self._shard_locks = [threading.Lock() for _ in range(n_shards)]
        # zero-copy receive: learner i owns row [i]; rows are written
        # outside the round lock so receives overlap across learners
        self._recv = np.zeros((n_learners, lay.padded), np.float32)
        self._agg, self.agg_path = self._make_agg_fn()
        self.quantize_path: Optional[str] = None  # set by int8 clients
        self._pool: Optional[ThreadPoolExecutor] = None
        if n_shards > 1 and lay.padded >= PARALLEL_AGG_MIN_ELEMS:
            self._pool = _agg_pool()

        self._members: set = set()
        self._lock = threading.Lock()
        self._bsp_cond = threading.Condition()
        self._bsp_round = 0
        self._arrived: List[int] = []
        self._round_t0: Optional[float] = None   # first arrival this round
        # fault injection: slot -> [sleep_seconds, rounds_left] (0 =
        # until cleared); a restarted learner rejoins clean via leave()
        self._slow: Dict[int, List] = {}
        self._pull_bufs: Dict[int, np.ndarray] = {}
        # data-plane counters — always mutated under _stats_lock (pushes
        # arrive concurrently; unsynchronized += drops increments)
        self._stats_lock = threading.Lock()
        self.push_count = 0
        self.pull_count = 0
        self.push_timeouts = 0
        self.bytes_pushed_wire = 0
        self.bytes_pushed_dense = 0
        self.bytes_pulled = 0
        self.agg_rounds = 0
        self.agg_time_s = 0.0

    # ---- fused aggregation ------------------------------------------------
    def _make_agg_fn(self):
        """``(agg, path)``: ``agg(grads (NL, L), params/m/v (L,) views,
        step)`` is one fused mean+solver pass, updating the state views
        in place — the Pallas kernel on TPU, the in-place numpy twin
        elsewhere (both validated against kernels/ref.py:ps_aggregate_ref).
        ``path`` names which one (``pallas`` | ``numpy``)."""
        import jax
        kw = dict(solver=_FUSED_SOLVER[self.optimizer], lr=self.lr,
                  b1=self.b1, b2=self.b2, eps=self.eps,
                  momentum=self.momentum, beta=1.0)
        if jax.default_backend() == "tpu":
            from repro.kernels.autotune import tuned_ps_block
            from repro.kernels.ps_aggregate import ps_aggregate
            jfn = jax.jit(functools.partial(ps_aggregate, **kw),
                          static_argnames=("block",))

            def agg(rows, p, m, v, step):
                # tuned block resolved outside the jit (cached per shape)
                blk = tuned_ps_block(rows.shape[0], rows.shape[1],
                                     rows.dtype)
                pn, mn, vn = jfn(rows, p, m, v, np.float32(step),
                                 block=blk)
                np.copyto(p, np.asarray(pn))
                np.copyto(m, np.asarray(mn))
                np.copyto(v, np.asarray(vn))
            return agg, "pallas"
        from repro.kernels.ref import ps_aggregate_np
        return functools.partial(ps_aggregate_np, **kw), "numpy"

    def _apply_shard(self, s: int, rows: np.ndarray, step: int):
        lay = self.layout
        sl = lay.shard_slice(s)
        # the per-shard column slice is strided across learner rows;
        # make it contiguous for the fused kernel (worker-local copy)
        shard_rows = np.ascontiguousarray(rows[:, sl])
        with self._shard_locks[s]:
            self._agg(shard_rows, self._params[sl], self._m[sl],
                      self._v[sl], step)

    def _apply_slots(self, slots: List[int]):
        """One aggregation round over the registered receive rows: one
        fused mean+solver pass — whole-model for small shards (fewest
        dispatches), per shard on the pool for large models."""
        with self._step_lock:
            self._step += 1
            step = self._step
        if len(slots) == self.n_learners:
            rows = self._recv
        elif len(slots) == 1:
            rows = self._recv[slots[0]: slots[0] + 1]   # view, not copy
        else:
            rows = self._recv[slots]    # partial round (member left)
        t0 = time.perf_counter()
        if self._pool is not None:
            futs = [self._pool.submit(self._apply_shard, s, rows, step)
                    for s in range(self.layout.n_shards)]
            for f in futs:
                f.result()
        else:
            # whole-model fused pass; hold every shard lock (ascending,
            # same order as pull/load_flat) to keep pulls shard-consistent
            with contextlib.ExitStack() as stack:
                for lk in self._shard_locks:
                    stack.enter_context(lk)
                self._agg(rows, self._params, self._m, self._v, step)
        dt = time.perf_counter() - t0
        with self._stats_lock:
            self.agg_rounds += 1
            self.agg_time_s += dt
            rounds = self.agg_rounds
        if self.metrics is not None and self.job_id is not None:
            self.metrics.record(self.job_id, "agg_time_ms", rounds,
                                dt * 1e3)

    # ---- connection management (paper: join/leave) ------------------------
    def join(self, learner_id: int):
        with self._lock:
            self._members.add(learner_id)

    def leave(self, learner_id: int):
        with self._lock:
            self._members.discard(learner_id)
            # a crashed learner must not deadlock a BSP barrier
        # restart cures an injected slowdown: the replacement
        # incarnation of this slot starts from a clean data plane
        with self._stats_lock:
            self._slow.pop(learner_id % self.n_learners, None)
        with self._bsp_cond:
            self._bsp_cond.notify_all()

    @property
    def active(self) -> int:
        with self._lock:
            return len(self._members)

    def make_client(self, learner_id: int) -> "PSClient":
        """Learner-side endpoint carrying the per-learner staging and
        error-feedback state for this server's compression setting."""
        return PSClient(self, learner_id, compression=self.compression)

    # ---- receive side -----------------------------------------------------
    def _receive(self, slot: int, payload: Union[np.ndarray,
                                                 CompressedPush]):
        """Land a push in the learner's receive row (no lock: the row is
        owned by the pushing learner). Compressed pushes dequantize
        straight into the row — the dense vector is never materialized
        anywhere else."""
        lay = self.layout
        row = self._recv[slot]
        if isinstance(payload, CompressedPush):
            # the payload covers the model rounded up to whole blocks;
            # the row's tile padding beyond it stays zero
            n = payload.q.size
            np.multiply(payload.q.reshape(-1, BLOCK),
                        payload.scales[:, None],
                        out=row[:n].reshape(-1, BLOCK))
            return payload.wire_nbytes, payload.dense_nbytes
        flat = np.asarray(payload, np.float32).ravel()
        assert flat.size in (self.size, lay.padded), flat.size
        np.copyto(row[: flat.size], flat)
        # pad tail stays zero from construction
        return flat.nbytes, flat.nbytes

    # ---- push / pull ------------------------------------------------------
    def push(self, learner_id: int, payload,
             timeout: float = 30.0) -> bool:
        """Send locally accumulated gradients (or weights, per solver) —
        a dense f32 vector or a ``CompressedPush`` (int8 + scales).
        Returns False iff a BSP push timed out and its contribution was
        withdrawn (never aggregated) — callers with error-feedback state
        must put the signal back."""
        slot = learner_id % self.n_learners
        # injected slowdown (fault drills): sleep outside every lock so
        # a slow slot delays only itself — exactly like a degraded host
        sleep_s = 0.0
        with self._stats_lock:
            ent = self._slow.get(slot)
            if ent is not None:
                sleep_s = ent[0]
                if ent[1] > 0:
                    ent[1] -= 1
                    if ent[1] == 0:
                        del self._slow[slot]
        if sleep_s > 0:
            time.sleep(sleep_s)
        wire, dense = self._receive(slot, payload)
        with self._stats_lock:
            self.push_count += 1
            self.bytes_pushed_wire += wire
            self.bytes_pushed_dense += dense
        if self.metrics is not None and self.job_id is not None:
            self.metrics.incr(self.job_id, "ps_bytes_wire", wire)
            self.metrics.incr(self.job_id, "ps_bytes_dense", dense)
        if self.trigger == "on_arrival":          # Downpour
            self._apply_slots([slot])
            return True
        # BSP: wait until all ACTIVE learners contributed, then aggregate
        with self._bsp_cond:
            my_round = self._bsp_round
            if slot not in self._arrived:     # re-push after a timeout
                # PS-side straggler signal: arrival time relative to the
                # round's FIRST arrival. The BSP barrier inverts
                # learner-side timing (fast learners block waiting for
                # the straggler), so this is the honest per-slot lag.
                now_pc = time.perf_counter()
                if not self._arrived:
                    self._round_t0 = now_pc
                lag = now_pc - (self._round_t0 or now_pc)
                self._arrived.append(slot)    # replaces the row in place
                if self.metrics is not None and self.job_id is not None:
                    self.metrics.record_bounded(
                        self.job_id, f"ps_lag_s.{slot}",
                        self._bsp_round, lag, keep=256)
            if len(self._arrived) >= max(1, self.active):
                self._finish_round_locked()
            else:
                self._bsp_cond.wait_for(
                    lambda: self._bsp_round != my_round
                    or len(self._arrived) >= max(1, self.active),
                    timeout=timeout)
                # if members left, a later pusher completes the round
                if self._bsp_round == my_round and \
                        len(self._arrived) >= max(1, self.active):
                    self._finish_round_locked()
                elif self._bsp_round == my_round \
                        and slot in self._arrived:
                    # timed out with the round still open: withdraw our
                    # row so a later re-push cannot double-register it
                    # or tear it under a concurrent round completion.
                    # The contribution is LOST — count and report it,
                    # never drop it silently.
                    self._arrived.remove(slot)
                    with self._stats_lock:
                        self.push_timeouts += 1
                    log.warning(
                        "BSP push from learner %s timed out after %ss; "
                        "contribution withdrawn", learner_id, timeout,
                        extra={"job_id": self.job_id or "-"})
                    if self.metrics is not None and \
                            self.job_id is not None:
                        self.metrics.incr(self.job_id,
                                          "ps_push_timeouts")
                    return False
        return True

    def _finish_round_locked(self):
        """Aggregate the arrived rows and release the barrier. Caller
        holds ``_bsp_cond``; waiters are parked, and pulls/receives use
        disjoint locks, so holding it here serializes nothing new."""
        slots, self._arrived = self._arrived, []
        self._apply_slots(sorted(slots))
        self._bsp_round += 1
        self._bsp_cond.notify_all()

    def pull(self, learner_id: int) -> np.ndarray:
        """Fetch global weights into this learner's pull buffer (one
        copy, shard-consistent). The buffer is reused by the learner's
        next pull — consume (or copy) before pulling again."""
        buf = self._pull_bufs.get(learner_id)
        if buf is None:
            buf = self._pull_bufs.setdefault(
                learner_id, np.empty(self.size, np.float32))
        lay = self.layout
        for s in range(lay.n_shards):
            k = lay.valid_len(s)
            if k == 0:
                break
            with self._shard_locks[s]:
                np.copyto(buf[s * lay.shard_len: s * lay.shard_len + k],
                          self._params[lay.shard_slice(s)][:k])
        with self._stats_lock:
            self.pull_count += 1
            self.bytes_pulled += buf.nbytes
        return buf

    # ---- fault injection ---------------------------------------------------
    def slow_learner(self, slot: int, seconds: float, rounds: int = 0):
        """Inject a per-push delay into one learner slot (the SLOW fault
        kind): every push from ``slot`` sleeps ``seconds`` first, for
        ``rounds`` pushes (0 = until the learner leaves — a restart via
        the drain/requeue path clears it in ``leave``)."""
        with self._stats_lock:
            self._slow[slot % self.n_learners] = [float(seconds),
                                                  int(rounds)]
        log.warning("injected slowdown: slot %d sleeps %.3fs per push "
                    "(%s rounds)", slot % self.n_learners, seconds,
                    rounds or "unbounded",
                    extra={"job_id": self.job_id or "-"})

    # ---- state management -------------------------------------------------
    def load_flat(self, flat: np.ndarray):
        """Overwrite the global weights (checkpoint-restore republish)."""
        flat = np.asarray(flat, np.float32).ravel()
        assert flat.size == self.size, (flat.size, self.size)
        lay = self.layout
        for s in range(lay.n_shards):
            k = lay.valid_len(s)
            with self._shard_locks[s]:
                view = self._params[lay.shard_slice(s)]
                np.copyto(view[:k], flat[s * lay.shard_len:
                                         s * lay.shard_len + k])
                view[k:] = 0.0

    # ---- data-plane stats -------------------------------------------------
    @property
    def bytes_moved(self) -> int:
        with self._stats_lock:
            return self.bytes_pushed_wire + self.bytes_pulled

    def stats(self) -> Dict:
        """JSON-ready data-plane counters for status surfaces."""
        with self._stats_lock:
            wire, dense = self.bytes_pushed_wire, self.bytes_pushed_dense
            rounds, agg_s = self.agg_rounds, self.agg_time_s
            out = {
                "compression": self.compression,
                "ps_shards": self.layout.n_shards,
                "push_count": self.push_count,
                "pull_count": self.pull_count,
                "push_timeouts": self.push_timeouts,
                "bytes_pushed_wire": wire,
                "bytes_pushed_dense": dense,
                "bytes_pulled": self.bytes_pulled,
                "agg_rounds": rounds,
                # which implementation ran: aggregation pallas|numpy,
                # int8 quantization pallas|jnp (None: no compression)
                "agg_path": self.agg_path,
                "quantize_path": self.quantize_path,
                "slow_slots": sorted(self._slow),
            }
        out["compression_ratio"] = round(dense / wire, 3) if wire else None
        out["agg_ms_per_round"] = (round(agg_s / rounds * 1e3, 3)
                                   if rounds else None)
        return out


class PSClient:
    """Per-learner push/pull endpoint: owns the padded staging buffer and
    (under int8 compression) the error-feedback buffer, so quantization
    is unbiased over time (Seide et al. style). Compression runs through
    the Pallas kernel on TPU and the jit'd jnp reference elsewhere; the
    server's ``quantize_path`` records which."""

    def __init__(self, ps: SoftwareParameterServer, learner_id: int,
                 compression: str = "none"):
        assert compression in ("none", "int8"), compression
        self.ps = ps
        self.learner_id = learner_id
        self.compression = compression
        if compression == "int8":
            import jax.numpy as jnp
            self._stage = np.zeros(ps.layout.padded, np.float32)
            self._err = jnp.zeros(ps.layout.padded, jnp.float32)
            self._compress, ps.quantize_path = make_compressor()

    def push(self, flat: np.ndarray, timeout: float = 30.0) -> bool:
        if self.compression == "none":
            return self.ps.push(self.learner_id, flat, timeout=timeout)
        flat = np.asarray(flat, np.float32).ravel()
        self._stage[: flat.size] = flat
        q, scales, self._err = self._compress(self._stage, self._err)
        # only whole blocks that hold model values go on the wire; the
        # rest of the staging buffer is tile padding and quantizes to 0
        n = -(-flat.size // BLOCK) * BLOCK
        ok = self.ps.push(
            self.learner_id,
            CompressedPush(q=np.asarray(q)[:n],
                           scales=np.asarray(scales)[: n // BLOCK],
                           dense_nbytes=flat.nbytes),
            timeout=timeout)
        if not ok:
            # BSP timeout: the wire payload was withdrawn unaggregated —
            # put it back into the feedback buffer so the accumulated
            # transmitted signal stays unbiased (rare path, eager ok)
            from repro.core.compression import dequantize_int8
            self._err = self._err + dequantize_int8(q, scales)
        return ok

    def pull(self) -> np.ndarray:
        return self.ps.pull(self.learner_id)
