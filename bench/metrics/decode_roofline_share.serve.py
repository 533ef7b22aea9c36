"""Share of its roofline that the decode step reaches: the least time a
decode step can take (``bench/work/hybrid.py``'s least bytes, with the
window's mean live slots, over the device's HBM bandwidth in
``peaks.json``) over the traced device time of one decode step
(``jit_serve_decode``).

``bench/run.py`` hands a reader no cell name, so this one reads the
configuration of the one cell its entry in ``BENCHMARK.json`` lists, and
raises if the entry lists any other number of cells: a second cell would
otherwise be measured against the first one's bytes."""
import jax

from bench import harness, trace as tracing
from bench.work import hybrid as work

NAME = "decode_roofline_share.serve"


def _config():
    spec = harness.load_json(harness.ROOT / "BENCHMARK.json")
    metric = next(m for m in spec["per_layer"] if m["name"] == NAME)
    cells = metric.get("workloads", [])
    if len(cells) != 1:
        raise RuntimeError(
            f"{NAME} reads the configuration of the one cell it lists, and "
            f"its entry lists {len(cells)}: pass readers the cell instead")
    cell = next(w for w in spec["workloads"] if w["name"] == cells[0])
    conf = next(c for c in spec["configs"] if c["name"] == cell["config"])
    return harness.load_json(harness.ROOT / conf["file"])


def read(out, trace):
    secs, n = tracing.module_time(trace, "decode")
    c = out.counters
    peaks = harness.load_json(harness.BENCH / "peaks.json")["devices"].get(
        jax.devices()[0].device_kind)
    if not n or not c["decode_steps"] or peaks is None:
        return None
    live = c["occupied_slot_steps"] / c["decode_steps"]
    least_s = work.decode_least_bytes(_config(), live) / peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / (secs / n)
