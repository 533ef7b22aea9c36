"""Roofline-seeded autotuner for the Pallas kernel block sizes.

``fit_block`` is a static heuristic: largest divisor under a fixed cap.
This module replaces that guess with a short predict -> rank -> measure
sweep per (kernel, shape, dtype, backend):

  1. enumerate the legal candidates (divisors of F no larger than the
     VMEM budget allows; multiples of the quantization block where
     scales are per-block),
  2. rank them with a tiny roofline-style cost model — HBM traffic is
     identical across candidates, so the ranking terms are per-grid-step
     dispatch overhead against the VMEM working-set ceiling,
  3. measure the top-K survivors with the real kernel and keep the
     fastest.

Choices persist in an on-disk JSON cache keyed by
``(kernel, shape, dtype, backend)`` so jobs after the first pay zero
tuning cost; the in-memory mirror makes repeat lookups free within a
process.

Measurement only runs on a real accelerator backend (or when forced via
``DLAAS_AUTOTUNE_MEASURE=1``): interpret-mode timings on CPU are
Python-loop artifacts that would mislead the choice, so CPU keeps the
best *predicted* candidate. A candidate that fails while it is measured
is an error, never a silent fallback. ``DLAAS_AUTOTUNE=0`` disables the
tuner entirely (callers fall back to ``fit_block``);
``DLAAS_AUTOTUNE_CACHE`` overrides the cache path.
"""
from __future__ import annotations

import json
import logging
import os
import tempfile
import threading
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from repro.analysis.roofline import PEAKS, TARGET_KIND
from repro.kernels.grid import TILE, fit_block, pad_to

log = logging.getLogger("repro.autotune")

# Machine-model terms of the target chip (analysis/roofline.py PEAKS).
# Absolute values only set the overhead/bandwidth balance; the ranking is
# what matters and it is stable across a wide range of either constant.
HBM_BW = PEAKS[TARGET_KIND]["hbm_bw"]
GRID_STEP_US = 1.0         # per-grid-step dispatch overhead
VMEM_BUDGET = 12 << 20     # of the 16 MiB scoped VMEM, minus headroom

MEASURE_REPS = 3           # timed repetitions per measured candidate
TOP_K = 3                  # measured survivors of the predicted ranking

_DEFAULT_CACHE = os.path.join(tempfile.gettempdir(),
                              "dlaas-autotune-cache.json")


def enabled() -> bool:
    return os.environ.get("DLAAS_AUTOTUNE", "1") != "0"


def measurement_allowed() -> bool:
    """Measured timings are meaningful on a real accelerator backend;
    interpret-mode timings are not. Force with DLAAS_AUTOTUNE_MEASURE=1
    (tests), suppress with =0."""
    forced = os.environ.get("DLAAS_AUTOTUNE_MEASURE")
    if forced is not None:
        return forced == "1"
    import jax
    return jax.default_backend() == "tpu"


def _backend() -> str:
    import jax
    return jax.default_backend()


def cache_path() -> str:
    return os.environ.get("DLAAS_AUTOTUNE_CACHE", _DEFAULT_CACHE)


class AutotuneCache:
    """Persistent kernel-choice cache: a flat JSON object of
    key -> record, written atomically (tmp + rename) so concurrent
    processes never observe a torn file. Records keep the predicted and
    measured timings alongside the choice for observability."""

    def __init__(self, path: str):
        self.path = path
        self._lock = threading.Lock()
        self._data: Optional[Dict[str, Dict]] = None
        self.hits = 0       # lookups served from the cache
        self.misses = 0     # lookups that forced a tuning sweep

    def _load(self) -> Dict[str, Dict]:
        if self._data is None:
            try:
                with open(self.path) as f:
                    self._data = json.load(f)
            except (OSError, json.JSONDecodeError):
                self._data = {}
        return self._data

    def get(self, key: str) -> Optional[Dict]:
        with self._lock:
            rec = self._load().get(key)
            if rec is None:
                self.misses += 1
            else:
                self.hits += 1
            return rec

    def size(self) -> int:
        with self._lock:
            return len(self._load())

    def put(self, key: str, record: Dict) -> None:
        with self._lock:
            # merge-on-write: pick up keys other processes stored since
            # our load, so concurrent tuners don't clobber each other
            on_disk: Dict[str, Dict] = {}
            try:
                with open(self.path) as f:
                    on_disk = json.load(f)
            except (OSError, json.JSONDecodeError):
                pass
            data = self._load()
            for k, v in on_disk.items():
                data.setdefault(k, v)
            data[key] = record
            try:
                fd, tmp = tempfile.mkstemp(
                    dir=os.path.dirname(self.path) or ".",
                    prefix=".autotune.")
                with os.fdopen(fd, "w") as f:
                    json.dump(data, f, indent=1, sort_keys=True)
                os.replace(tmp, self.path)
            except OSError as e:       # read-only FS: in-memory only
                log.warning("autotune cache not persisted to %s: %s",
                            self.path, e)

    def clear(self) -> None:
        with self._lock:
            self._data = {}
            try:
                os.unlink(self.path)
            except OSError:
                pass


_caches: Dict[str, AutotuneCache] = {}
_caches_lock = threading.Lock()


def get_cache() -> AutotuneCache:
    path = cache_path()
    with _caches_lock:
        c = _caches.get(path)
        if c is None:
            c = _caches[path] = AutotuneCache(path)
        return c


def make_key(kernel: str, shape: Sequence[int], dtype, extra: str = "") \
        -> str:
    dt = getattr(dtype, "name", None) or str(dtype)
    key = f"{kernel}|{'x'.join(str(int(d)) for d in shape)}|{dt}|{_backend()}"
    return key + (f"|{extra}" if extra else "")


def divisor_blocks(f: int, multiple: int = 1, cap: int = 1 << 16) \
        -> List[int]:
    """All blocks that tile F exactly: divisors of F that are multiples
    of ``multiple``, capped (huge blocks exceed VMEM anyway)."""
    out = []
    d = multiple
    while d <= min(f, cap):
        if f % d == 0:
            out.append(d)
        d += multiple
        if multiple == 1 and d > 4096 and f % 4096:
            break               # dense scan is pointless past this
    return out or [fit_block(f, cap, multiple)]


def _measure(fn: Callable[[], None], reps: int = MEASURE_REPS) -> float:
    """Best-of-reps wall time in seconds (one untimed warmup for
    compilation)."""
    fn()
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def tune(kernel: str, shape: Sequence[int], dtype, *,
         candidates: Sequence,
         predict_us: Callable[..., float],
         measure_s: Optional[Callable[..., float]] = None,
         default, top_k: int = TOP_K, extra_key: str = ""):
    """Generic predict -> rank -> measure-top-K flow.

    ``candidates`` are opaque configs (ints or tuples), all legal for
    the kernel. ``predict_us`` maps a candidate to a modelled time
    (``inf`` = infeasible). ``measure_s``, when given, maps a candidate
    to measured seconds; when None the best *predicted* candidate wins.
    Returns the chosen config; ``default`` is returned when no candidate
    is feasible and when tuning is disabled. A cached choice that is no
    longer a candidate is tuned again."""
    if not enabled() or not candidates:
        return default
    cache = get_cache()
    key = make_key(kernel, shape, dtype, extra_key)
    rec = cache.get(key)
    if rec is not None:
        choice = rec.get("choice", default)
        choice = tuple(choice) if isinstance(choice, list) else choice
        if choice in candidates:
            return choice

    ranked = sorted(candidates, key=predict_us)
    predicted = {str(c): round(predict_us(c), 3) for c in ranked}
    feasible = [c for c in ranked if predict_us(c) != float("inf")]
    if not feasible:
        feasible, choice = [default], default
    else:
        choice = feasible[0]
    measured: Dict[str, float] = {}
    source = "predicted"
    if measure_s is not None and len(feasible) > 1:
        for c in feasible[:top_k]:
            measured[str(c)] = round(measure_s(c) * 1e6, 3)
        choice = min(feasible[:top_k], key=lambda c: measured[str(c)])
        source = "measured"
    cache.put(key, {"choice": choice, "source": source,
                    "predicted_us": predicted, "measured_us": measured})
    log.info("autotune %s -> %s (%s)", key, choice, source)
    return choice


# ---------------------------------------------------------------------------
# Per-kernel entry points
# ---------------------------------------------------------------------------


def _dtype_bytes(dtype) -> int:
    import numpy as np
    try:
        return int(np.dtype(dtype).itemsize)
    except TypeError:
        return 4


def _under_trace() -> bool:
    """True while tracing (jit, vmap, eval_shape) — measurement there
    would run eager probes mid-trace; prediction stays safe either way."""
    import jax
    return not jax.core.trace_ctx.is_top_level()


def tuned_ps_block(nl: int, f: int, dtype="float32", *,
                   default_block: int = TILE) -> int:
    """Block size (elements, whole (8, 128) tiles) for the fused PS
    aggregation over (nl, f) grads; f is padded to a whole tile."""
    fp = pad_to(f, TILE)
    default = fit_block(fp, default_block, multiple=TILE)
    ib = _dtype_bytes(dtype)

    def predict_us(block: int) -> float:
        # per grid step: (nl+3) block reads + 3 block writes, each
        # double-buffered in VMEM, plus the f32 mean and new m/v values
        vmem = (2 * (nl + 6) + 3) * block * 4
        if vmem > VMEM_BUDGET:
            return float("inf")
        steps = fp // block
        bytes_moved = fp * (nl + 6) * ib
        return bytes_moved / HBM_BW * 1e6 + steps * GRID_STEP_US

    measure_s = None
    if measurement_allowed() and not _under_trace():
        def measure_s(block: int) -> float:
            import jax
            import jax.numpy as jnp
            from repro.kernels.ps_aggregate import ps_aggregate
            interp = _backend() != "tpu"
            g = jnp.zeros((nl, f), dtype)
            p = jnp.zeros((f,), dtype)
            fn = jax.jit(lambda g, p: ps_aggregate(
                g, p, p, p, 1, solver="adam", lr=1e-3, block=block,
                interpret=interp))
            return _measure(
                lambda: jax.block_until_ready(fn(g, p)))

    return tune("ps_aggregate", (nl, f), dtype,
                candidates=divisor_blocks(fp, multiple=TILE, cap=1 << 17),
                predict_us=predict_us, measure_s=measure_s,
                default=default)


def tuned_quantize_block(f: int, qblock: int = 256, dtype="float32") \
        -> int:
    """Block size (elements, 128 quantization blocks per unit) for the
    int8 quantize/dequantize pass over (f,); f is padded to a unit."""
    unit = 128 * qblock
    fp = pad_to(f, unit)
    default = unit
    ib = _dtype_bytes(dtype)

    def predict_us(block: int) -> float:
        # x, err, q, new_err double-buffered, plus the f32 temporaries
        vmem = 2 * (3 * ib + 1) * block + 3 * 4 * block
        if vmem > VMEM_BUDGET:
            return float("inf")
        steps = fp // block
        bytes_moved = fp * (3 * ib + 1) + 4 * (fp // qblock)
        return bytes_moved / HBM_BW * 1e6 + steps * GRID_STEP_US

    measure_s = None
    if measurement_allowed() and not _under_trace():
        def measure_s(block: int) -> float:
            import jax
            import jax.numpy as jnp
            from repro.kernels.quantize import quantize_ef
            interp = _backend() != "tpu"
            x = jnp.zeros((f,), dtype)
            fn = jax.jit(lambda x, e: quantize_ef(
                x, e, qblock=qblock, block=block, interpret=interp))
            return _measure(
                lambda: jax.block_until_ready(fn(x, x)))

    return tune("quantize_ef", (f,), dtype,
                candidates=divisor_blocks(fp, multiple=unit, cap=1 << 20),
                predict_us=predict_us, measure_s=measure_s,
                default=default, extra_key=f"q{qblock}")


def tuned_flash_blocks(bh: int, sq: int, sk: int, hd: int,
                       dtype="float32", *,
                       default: Tuple[int, int] = (128, 128)) \
        -> Tuple[int, int]:
    """(block_q, block_k) for flash attention over (bh, sq|sk, hd).
    Blocks are whole lane tiles (128, 256 or 512) dividing the sequence,
    or the whole sequence when it is shorter than a tile."""
    def legal(s: int) -> List[int]:
        return [b for b in (128, 256, 512) if s % b == 0] or [s]

    dflt = (fit_block(sq, default[0], 128) if sq % 128 == 0 else sq,
            fit_block(sk, default[1], 128) if sk % 128 == 0 else sk)
    ib = _dtype_bytes(dtype)
    cands = [(bq, bk) for bq in legal(sq) for bk in legal(sk)]

    def predict_us(c: Tuple[int, int]) -> float:
        bq, bk = c
        # VMEM: q tile + k/v tiles + f32 acc/m/l scratch + out tile
        vmem = (2 * bq * hd + 2 * bk * hd) * ib \
            + (bq * hd + 2 * bq) * 4
        if vmem > VMEM_BUDGET:
            return float("inf")
        steps = bh * (sq // bq) * (sk // bk)
        # k/v stream once per q-row of the grid; q/out stream once
        bytes_moved = (bh * (sq // bq) * sk * hd * 2 * ib
                       + 2 * bh * sq * hd * ib)
        return bytes_moved / HBM_BW * 1e6 + steps * GRID_STEP_US

    measure_s = None
    if measurement_allowed() and not _under_trace():
        def measure_s(c: Tuple[int, int]) -> float:
            import jax
            import jax.numpy as jnp
            from repro.kernels.flash_attention import flash_attention_fwd
            interp = _backend() != "tpu"
            q = jnp.zeros((bh, sq, hd), dtype)
            k = jnp.zeros((bh, sk, hd), dtype)
            fn = jax.jit(lambda q, k: flash_attention_fwd(
                q, k, k, causal=True, block_q=c[0], block_k=c[1],
                interpret=interp))
            return _measure(
                lambda: jax.block_until_ready(fn(q, k)))

    out = tune("flash_attention", (bh, sq, sk, hd), dtype,
               candidates=cands, predict_us=predict_us,
               measure_s=measure_s, default=dflt)
    return tuple(out)
