"""Manifest edge cases: the framework.distribution field (execution
backend selection), the software-PS data-plane knobs
(framework.compression / framework.ps_shards), JSON manifests, and
validation errors."""
import json

import pytest

from repro.platform.cluster import UserError
from repro.service.manifest import (DEFAULT_DISTRIBUTION, DISTRIBUTIONS,
                                    parse_manifest, resolve_distribution,
                                    resolve_framework, resolve_ps_options,
                                    validate_manifest)

BASE = {"name": "m", "framework": {"name": "repro-mlp"}}


def test_default_backend_selection():
    assert DEFAULT_DISTRIBUTION == "software-ps"
    assert resolve_distribution(dict(BASE)) == "software-ps"
    assert validate_manifest(dict(BASE)) == []


def test_explicit_distribution_and_precedence():
    m = {"name": "m", "framework": {"name": "repro-lm",
                                    "distribution": "pjit"}}
    assert resolve_distribution(m) == "pjit"
    # a top-level key (REST/CLI override path) wins over the framework's
    m2 = dict(m, distribution="software-ps")
    assert resolve_distribution(m2) == "software-ps"
    for d in DISTRIBUTIONS:
        assert validate_manifest(
            {"name": "m", "framework": {"name": "x",
                                        "distribution": d}}) == []


def test_unknown_distribution_rejected_with_usererror():
    m = {"name": "m", "framework": {"name": "repro-lm",
                                    "distribution": "horovod"}}
    with pytest.raises(UserError) as ei:
        resolve_distribution(m)
    # the error must name the bad value and the supported ones
    assert "horovod" in str(ei.value)
    assert "software-ps" in str(ei.value) and "pjit" in str(ei.value)
    errs = validate_manifest(m)
    assert any("distribution" in e and "horovod" in e for e in errs)


def test_json_manifest_roundtrip():
    m = {"name": "json-model", "learners": 2,
         "framework": {"name": "repro-lm", "arch": "stablelm-1.6b-smoke",
                       "distribution": "pjit"},
         "data": {"n_docs": 64, "seq_len": 16}}
    parsed = parse_manifest(json.dumps(m))
    assert parsed == m
    assert validate_manifest(parsed) == []
    assert resolve_distribution(parsed) == "pjit"


def test_json_manifest_bad_distribution():
    parsed = parse_manifest(json.dumps(
        {"name": "x", "framework": {"name": "y",
                                    "distribution": "mpi"}}))
    assert validate_manifest(parsed) != []
    with pytest.raises(UserError):
        resolve_distribution(parsed)


def test_yaml_distribution_key_parses():
    m = parse_manifest("name: x\n"
                       "framework:\n"
                       "  name: repro-lm\n"
                       "  distribution: pjit\n")
    assert m["framework"]["distribution"] == "pjit"
    assert resolve_distribution(m) == "pjit"


def test_ps_options_defaults_and_precedence():
    assert resolve_ps_options(dict(BASE)) == ("none", 4)
    m = {"name": "m", "framework": {"name": "repro-lm",
                                    "compression": "int8",
                                    "ps_shards": 8}}
    assert resolve_ps_options(m) == ("int8", 8)
    assert validate_manifest(m) == []
    # top-level override (REST/CLI path) wins over the framework's
    m2 = dict(m, compression="none", ps_shards=2)
    assert resolve_ps_options(m2) == ("none", 2)


def test_ps_options_rejected_with_usererror():
    m = {"name": "m", "framework": {"name": "x", "compression": "zstd"}}
    with pytest.raises(UserError) as ei:
        resolve_ps_options(m)
    assert "zstd" in str(ei.value) and "int8" in str(ei.value)
    assert any("zstd" in e for e in validate_manifest(m))
    for bad in (0, -1, "four", True):
        errs = validate_manifest(
            {"name": "m", "framework": {"name": "x", "ps_shards": bad}})
        assert any("ps_shards" in e for e in errs), bad


def test_ps_options_not_leaked_into_plugin_cfg():
    """compression/ps_shards configure the platform, not the framework
    plugin — they must not reach the plugin's config dict."""
    m = {"name": "m", "framework": {"name": "repro-lm", "arch": "a",
                                    "compression": "int8",
                                    "ps_shards": 2,
                                    "distribution": "software-ps"}}
    name, cfg = resolve_framework(m)
    assert name == "repro-lm"
    assert cfg == {"arch": "a"}
