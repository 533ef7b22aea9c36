"""What every cell shares: the spec and data files found by name, the
device check, compile events, the program's configuration, and the
result line."""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import sys
import threading
import time
from pathlib import Path
from typing import Dict, List, Optional

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"


class NoChip(RuntimeError):
    """No TPU, or fewer chips than the cell asks for."""


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict            # bench/configs/<config>.json
    traffic: dict           # bench/traffic/<traffic>.json
    params: dict            # bench/cells/<cell>.json, or {}
    end_to_end: List[dict]
    per_layer: List[dict]


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def find_cell(name: str, root: Path = ROOT) -> Cell:
    spec = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    cfg = next(c for c in spec["configs"] if c["name"] == w["config"])
    cell_file = root / "bench" / "cells" / f"{name}.json"
    return Cell(
        name=name, chips=int(w["chips"]),
        config=load_json(root / cfg["file"]),
        traffic=load_json(root / "bench" / "traffic" / f"{w['traffic']}.json"),
        params=load_json(cell_file) if cell_file.exists() else {},
        end_to_end=[m for m in spec["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in spec["per_layer"] if _applies(m, name)])


def driver(kind: str, root: Path = ROOT):
    return load_module(root / "bench" / "drivers" / f"{kind}.py",
                       f"bench_driver_{kind}")


def reference(family: str, root: Path = ROOT):
    return load_module(root / "bench" / "reference" / f"{family}.py",
                       f"bench_reference_{family}")


def metric_reader(name: str, root: Path = ROOT):
    return load_module(root / "bench" / "metrics" / f"{name}.py",
                       f"bench_metric_{name.replace('.', '_')}")


def device_info(chips: int) -> dict:
    """Platform, kind and count of the devices JAX sees; ``NoChip`` unless
    they are TPUs of a kind in ``peaks.json``, at least ``chips`` of them."""
    import jax
    try:
        devs = jax.devices()
    except RuntimeError as e:
        raise NoChip(f"JAX found no usable device: {e}") from None
    d0 = devs[0]
    if d0.platform != "tpu":
        raise NoChip(f"found {d0.platform}, not a TPU: this runs on the chip only")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    peaks = load_json(BENCH / "peaks.json")["devices"]
    if d0.device_kind not in peaks:
        raise NoChip(f"device kind {d0.device_kind!r} is not in peaks.json")
    return {"platform": d0.platform, "kind": d0.device_kind, "count": len(devs)}


def memory_peak_bytes(chips: int) -> Optional[int]:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()[:chips]]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


class CompileLog:
    """Programs compiled, or loaded from the persistent cache, with the
    time each happened (JAX monitoring events)."""

    def __init__(self):
        import jax
        self._lock = threading.Lock()
        self.events: List[tuple] = []          # (time, kind, seconds)

        def on_duration(event, secs, **kw):
            if event == "/jax/core/compile/backend_compile_duration":
                with self._lock:
                    self.events.append((time.monotonic(), "compile", secs))

        def on_event(event, **kw):
            if event == "/jax/compilation_cache/cache_hits":
                with self._lock:
                    self.events.append((time.monotonic(), "cache_hit", 0.0))

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def between(self, t0: float, t1: float) -> List[tuple]:
        with self._lock:
            return [e for e in self.events if t0 <= e[0] <= t1]


# ---- the program's configuration -------------------------------------------

def _get(obj, dotted: str):
    for part in dotted.split("."):
        obj = getattr(obj, part)
    return obj


def register_config(config: dict) -> str:
    """The registry id the program runs ``config`` under. A cut
    configuration (``arch_overrides``) is registered under its own name,
    so the normal path (``resolve_arch``) serves or trains it."""
    from repro.configs.registry import REGISTRY, resolve_arch
    overrides = config.get("arch_overrides") or {}
    if not overrides:
        return config["arch"]
    base = resolve_arch(config["arch"])
    REGISTRY[config["name"]] = dataclasses.replace(
        base, arch_id=config["name"], **overrides)
    return config["name"]


def check_program_config(arch_id: str, config: dict, ref) -> None:
    """The program's configuration must state the sizes the file does."""
    from repro.configs.registry import resolve_arch
    arch = resolve_arch(arch_id)
    wrong = {k: (config[k], _get(arch, f)) for k, f in ref.PROGRAM_FIELDS.items()
             if config[k] != _get(arch, f)}
    if wrong or arch.dtype != config["dtype"]:
        raise RuntimeError(f"program config {arch_id} differs from "
                           f"{config['name']}: {wrong}, dtype {arch.dtype}")


# ---- output ------------------------------------------------------------------

def result_line(*, correct: bool, attempted: int, failed: int,
                metrics: Dict[str, dict], device: dict, checks: Dict[str, dict],
                breakdown: Optional[dict] = None) -> str:
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return json.dumps(out)


def say(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)
