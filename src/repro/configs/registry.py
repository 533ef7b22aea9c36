"""Architecture registry — ``--arch <id>`` resolution.

An arch id names a model: a registered id (``stablelm-1.6b``) is its
published config, and ``<id>-smoke`` is ``reduce_for_smoke(<id>)``, the
tiny same-family config CPU tests and examples run. ``resolve_arch`` is
the one place either is turned into a config."""
from __future__ import annotations

from typing import Dict

from repro.configs.base import ArchConfig, reduce_for_smoke
from repro.configs import (
    kimi_k2_1t_a32b, grok_1_314b, stablelm_1_6b, minitron_8b, qwen1_5_110b,
    granite_20b, mamba2_1_3b, whisper_large_v3, jamba_1_5_large_398b,
    qwen2_vl_2b,
)

_MODULES = (
    kimi_k2_1t_a32b, grok_1_314b, stablelm_1_6b, minitron_8b, qwen1_5_110b,
    granite_20b, mamba2_1_3b, whisper_large_v3, jamba_1_5_large_398b,
    qwen2_vl_2b,
)

REGISTRY: Dict[str, ArchConfig] = {m.CONFIG.arch_id: m.CONFIG for m in _MODULES}

ARCH_IDS = tuple(sorted(REGISTRY))

SMOKE_SUFFIX = "-smoke"
# what a manifest or endpoint that names no arch gets
DEFAULT_ARCH = "stablelm-1.6b" + SMOKE_SUFFIX


def get_arch(arch_id: str) -> ArchConfig:
    try:
        return REGISTRY[arch_id]
    except KeyError:
        raise KeyError(
            f"unknown arch {arch_id!r}; available: {', '.join(ARCH_IDS)}"
        ) from None


def resolve_arch(arch_id: str) -> ArchConfig:
    """Config for an arch id: the published config of a registered id,
    or the smoke reduction of one for ``<id>-smoke``."""
    if arch_id.endswith(SMOKE_SUFFIX):
        return reduce_for_smoke(get_arch(arch_id[: -len(SMOKE_SUFFIX)]))
    return get_arch(arch_id)
