"""chip_smoke.py's phases, run in-process on CPU at the smoke arch ids,
and its refusal to report success without a TPU."""
import importlib.util
from pathlib import Path

import pytest

from repro.service.core import DLaaSCore

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_phases_pass_at_smoke_ids(chip_smoke, tmp_path, capsys):
    arch = "stablelm-1.6b-smoke"
    core = DLaaSCore(str(tmp_path))
    clog = chip_smoke.CompileLog()
    try:
        toks = chip_smoke.phase_serve(core, arch, clog, capacity=2,
                                      max_seq=64, max_new=4,
                                      prompt_lens=(5, 12))
        chip_smoke.phase_pjit(core, arch, clog,
                              candidates=(("sgd", 2, 16),), steps=2)
        chip_smoke.phase_ps(core, arch, clog, agg_path="numpy",
                            quantize_path="jnp", steps=2)
    finally:
        core.close()
    assert sorted(len(t) for t in toks.values()) == [4, 4]
    out = capsys.readouterr().out
    for phase in ("serve", "pjit", "ps"):
        assert f"[{phase}] wall " in out
    assert "aggregation numpy, quantization jnp" in out


def test_phase_fails_on_wrong_kernel_path(chip_smoke, tmp_path):
    core = DLaaSCore(str(tmp_path))
    try:
        with pytest.raises(chip_smoke.SmokeFailure, match="pallas"):
            chip_smoke.phase_ps(core, "stablelm-1.6b-smoke",
                                chip_smoke.CompileLog(),
                                agg_path="pallas", quantize_path="pallas",
                                steps=1)
    finally:
        core.close()


def test_entry_point_refuses_cpu(chip_smoke, capsys):
    assert chip_smoke.main() != 0
    assert '"ok": true' not in capsys.readouterr().out
