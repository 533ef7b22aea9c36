"""Readings that set a serve cell's limit, and its knee, on the chip.

    python3 bench/tools/calibrate.py --workload <cell> --seeds 1,2,3 \
        --control-seeds 1,2,3 --seconds 20 [--rates 4,6,8]

One process, one endpoint, set up and warmed once. With ``--rates`` it
sweeps the offered rate (one window per rate, rising, first seed), prints
per rate what failed, the latency tails and the backlog in the first and
last quarters of the window, and stops past the knee (``sweep``); with
``--set-rate`` it writes 0.8 x the knee into ``bench/cells/<cell>.json``.
Otherwise, for each seed it loads that seed's weights, runs a window of
the cell's own traffic at its own rate and prints the widest logit gap of
what the program served; for each control seed also the gap of the fp8
reference's tokens at the same positions. Not run by the benchmark; the
readings and what was set from them are in PERF.md.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


# a serve cell offers this share of its knee
RATE_SHARE = 0.8


def sweep(sess, cell, seed, seconds, rates):
    """One window per rate, rising; returns the knee: the highest rate at
    which nothing failed and the backlog (queued and active requests)
    grew by less than the capacity from the window's first quarter to its
    last. Stops at the first rate past it."""
    cap = cell.config["serving"]["capacity"]
    knee = None
    for rate in rates:
        depth, stop = [], threading.Event()

        def sample():
            while not stop.wait(0.25):
                s = sess.engine.stats()
                depth.append((time.monotonic(), s["queue_depth"] + s["active"]))

        t0 = time.monotonic()
        th = threading.Thread(target=sample, daemon=True)
        th.start()
        out = sess.window(seed, seconds, rate)
        stop.set()
        th.join()
        inside = [d for t, d in depth if t - t0 <= seconds]
        q = len(inside) // 4 or 1
        first, last = sum(inside[:q]) / q, sum(inside[-q:]) / q
        held = not out.failed and last - first < cap
        print("SWEEP " + json.dumps({
            "rate_rps": rate, "attempted": out.attempted, "failed": out.failed,
            **out.e2e,
            "occupancy_%": 100 * out.counters["occupied_slot_steps"]
            / max(1, out.counters["decode_steps"] * out.counters["capacity"]),
            "backlog_first_quarter": first, "backlog_last_quarter": last,
            "lateness_p95_s": out.lateness_p95_s,
            "window_compiles": out.window_compiles, "held": held}), flush=True)
        if not held:
            break
        knee = rate
    return knee


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--rates", default="")
    ap.add_argument("--set-rate", action="store_true",
                    help="write %s x the knee into bench/cells/<cell>.json" % RATE_SHARE)
    args = ap.parse_args()
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench import harness
    from bench.drivers import open_loop_serve as drv
    cell = harness.find_cell(args.workload)
    print(json.dumps({"device": harness.device_info(cell.chips)}), flush=True)
    seeds = [int(s) for s in args.seeds.split(",")]
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    sess = drv.Session(cell, harness.CompileLog(), cache_all=True)
    try:
        sess.load_weights(seeds[0])
        t = time.time()
        sess.warm()
        print(f"warm-up {time.time() - t:.1f} s", flush=True)
        if args.rates:
            knee = sweep(sess, cell, seeds[0], args.seconds,
                         [float(r) for r in args.rates.split(",")])
            print(f"KNEE {knee}", flush=True)
            if args.set_rate and knee:
                path = ROOT / "bench" / "cells" / f"{cell.name}.json"
                params = dict(cell.params, rate_rps=round(RATE_SHARE * knee, 3))
                path.write_text(json.dumps(params, indent=2) + "\n")
                print(f"RATE {params['rate_rps']} written to {path}", flush=True)
            return 0
        rate = float(cell.params["rate_rps"])
        for seed in seeds:
            sess.load_weights(seed)
            out = sess.window(seed, args.seconds, rate)
            gap, n = sess.compare(out.done, seed)
            row = {"seed": seed, "widest_logit_gap": gap, "tokens": n,
                   "failed": out.failed, "window_compiles": out.window_compiles,
                   **out.e2e}
            if seed in controls:
                row["control_gap"], _ = sess.compare(out.done, seed, control=True)
            print("CAL " + json.dumps(row), flush=True)
    finally:
        sess.close()
    return 0


if __name__ == "__main__":
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".bench_cache" / "jax")
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
    sys.exit(main())
