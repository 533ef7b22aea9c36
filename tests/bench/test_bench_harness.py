"""The harness: cells found by name from data files, cut configurations
through the normal resolver, and no result without a TPU."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness  # noqa: E402
from bench.reference import dense  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())


def test_every_cell_resolves_and_has_its_files():
    for w in SPEC["workloads"]:
        cell = harness.find_cell(w["name"])
        assert cell.traffic["kind"] == "open_loop_serve"
        assert harness.driver(cell.traffic["kind"]).run
        assert cell.params["rate_rps"] > 0 and cell.params["widest_logit_gap_limit"] > 0
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
        assert len(cell.end_to_end) >= 2 and cell.per_layer
        for m in cell.per_layer:
            assert harness.metric_reader(m["name"]).read
        ref = harness.reference(cell.config["family"])
        harness.check_program_config(harness.register_config(cell.config),
                                     cell.config, ref)


def test_cut_configuration_resolves_through_the_registry():
    from repro.configs.registry import REGISTRY, resolve_arch
    cfg = json.loads((ROOT / "bench/configs/stablelm-1.6b.json").read_text())
    cut = dict(cfg, name="stablelm-1.6b-cut12", num_hidden_layers=12,
               reduced=["num_hidden_layers"], arch_overrides={"n_layers": 12})
    try:
        arch = harness.register_config(cut)
        assert arch == "stablelm-1.6b-cut12"
        got = resolve_arch(arch)
        assert got.n_layers == 12 and got.d_model == 2048 and got.arch_id == arch
        harness.check_program_config(arch, cut, dense)
        with pytest.raises(RuntimeError, match="differs"):
            harness.check_program_config(arch, dict(cut, num_hidden_layers=24), dense)
    finally:
        REGISTRY.pop("stablelm-1.6b-cut12", None)


def _run_bench(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=120)


def test_run_exits_nonzero_without_a_tpu():
    cell = SPEC["workloads"][0]["name"]
    r = _run_bench(ROOT, "--workload", cell, "--seed", str(2**33), "--seconds", "1",
                   "--trace", "0")
    assert r.returncode == 2, r.stderr[-2000:]
    assert r.stdout.strip() == ""
    assert "not a TPU" in r.stderr


def test_run_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in SPEC["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    r = _run_bench(tmp_path, "--workload", SPEC["workloads"][0]["name"],
                   "--seed", "1", "--seconds", "1", "--trace", "0")
    assert r.returncode != 0 and r.stdout.strip() == ""


def test_new_files_and_entries_are_picked_up_without_edits(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in (tmp_path / "bench").rglob("*") if p.is_file()}
    b = tmp_path / "bench"
    cfg = json.loads((b / "configs/stablelm-1.6b.json").read_text())
    (b / "configs/stablelm-1.6b-x.json").write_text(json.dumps(dict(cfg, name="stablelm-1.6b-x")))
    chat = json.loads((b / "traffic/serve-chat.json").read_text())
    (b / "traffic/serve-long.json").write_text(json.dumps(
        dict(chat, prompt=dict(chat["prompt"], min=1024, max=2048))))
    (b / "cells/stablelm-1.6b-x.serve-long.json").write_text(json.dumps(
        {"rate_rps": 1.0, "widest_logit_gap_limit": 0.5}))
    (b / "metrics/queue_depth.serve.py").write_text(
        "def read(out, trace):\n    return 42.0\n")
    spec = json.loads((tmp_path / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "stablelm-1.6b-x", "source": cfg["source"],
                            "file": "bench/configs/stablelm-1.6b-x.json",
                            "reduced": [], "why": "a new one"})
    spec["workloads"].append({"name": "stablelm-1.6b-x.serve-long", "config": "stablelm-1.6b-x",
                              "traffic": "serve-long", "chips": 1, "why": "long prompts"})
    spec["per_layer"].append({"name": "queue_depth.serve", "unit": "count", "better": "lower",
                              "source": "program_counter", "layer": "serving engine",
                              "moves": "serve_latency_p95_s",
                              "workloads": ["stablelm-1.6b-x.serve-long"]})
    spec["end_to_end"][1]["workloads"].append("stablelm-1.6b-x.serve-long")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    cell = harness.find_cell("stablelm-1.6b-x.serve-long", root=tmp_path)
    assert cell.traffic["prompt"]["max"] == 2048 and cell.params["rate_rps"] == 1.0
    assert [m["name"] for m in cell.per_layer] == ["queue_depth.serve"]
    assert [m["name"] for m in cell.end_to_end] == ["setup_s", "serve_latency_p95_s"]
    assert harness.metric_reader("queue_depth.serve", root=tmp_path).read(None, None) == 42.0
    for p, data in before.items():
        assert p.read_bytes() == data, p
