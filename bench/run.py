"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json``: its configuration, traffic
mix and per-layer metrics are files under ``bench/`` found by name, and
the traffic file names the driver (``bench/drivers/<kind>.py``). One
process holds the chip: it builds the platform, sets the cell up, warms
every shape, measures for ``--seconds``, checks what it served against
the plain reference, and prints one JSON line. With ``--trace 0`` the line
carries the cell's end-to-end metrics, with ``--trace 1`` its per-layer
metrics read from a profiler trace of the window. Exits 2, printing no
result, where JAX finds no TPU or fewer chips than the cell needs.
"""
from __future__ import annotations

import time

T_START = time.time()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, *, check_chip: bool = True, t_start: float = T_START) -> int:
    args = parse(argv)
    if args.seed < 0:
        raise SystemExit("--seed must be >= 0")
    for p in (ROOT / "src", ROOT):
        if str(p) not in sys.path:
            sys.path.insert(0, str(p))
    from bench import harness, trace as tracing
    cell = harness.find_cell(args.workload)
    try:
        device = (harness.device_info(cell.chips) if check_chip else
                  {"platform": "cpu", "kind": "cpu", "count": 1})
    except harness.NoChip as e:
        harness.say(f"FAIL: {e}")
        return 2
    clog = harness.CompileLog()
    drv = harness.driver(cell.traffic["kind"])
    out, checks, correct, mem, setup_s = drv.run(
        cell, seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
        t_start=t_start, clog=clog, check_chip=check_chip)
    for line in out.notes:
        print(line, flush=True)
    device["memory_peak_bytes"] = mem
    breakdown = None
    if args.trace:
        tr = out.trace
        busy = tracing.mean_busy_s(tr)
        device["busy_s"], device["window_s"] = busy, tr.window_s
        metrics = {}
        for m in cell.per_layer:
            v = harness.metric_reader(m["name"]).read(out, tr)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        print(f"programs in the traced window: {tracing.top_modules(tr)}",
              flush=True)
        breakdown = {"device_ops": tracing.top_ops(tr),
                     "idle_gaps": tracing.longest_gaps(tr)}
    else:
        metrics = {m["name"]: {"value": out.e2e[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
    print(harness.result_line(
        correct=correct, attempted=out.attempted, failed=out.failed,
        metrics=metrics, device=device, checks=checks, breakdown=breakdown),
        flush=True)
    for name, c in checks.items():
        harness.say(f"check {name}: {c['value']} (limit {c['limit']})")
    return 0


if __name__ == "__main__":
    # the persistent compile cache lives in the checkout at a fixed path
    # (the path is part of the cache's key), so that a run shares it with
    # nothing outside its checkout, and holds every program the cell
    # compiles: no size cap (set before JAX is imported)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".bench_cache" / "jax")
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
    sys.exit(main())
