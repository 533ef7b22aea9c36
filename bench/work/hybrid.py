"""Least HBM bytes of one decode step of a hybrid (``granitemoehybrid``)
configuration, from its file's keys.

What every correct step must move, whatever implements it: each weight
outside the routed experts once (the mixers, the norms, the routers, the
shared experts, and the embedding table once as the tied output head),
and the SSM and convolution state of every live slot read and written
once. Left out, so that it stays a lower bound: the routed experts (how
many a step touches is not known to a reader), the KV rows (under 1 % of
the step), activations and the embedding rows the tokens look up.
"""
from __future__ import annotations

from typing import Dict

DTYPE_BYTES = {"bfloat16": 2, "float32": 4}
STATE_BYTES = 4             # the SSM state is kept in float32


def _pattern(c: dict):
    kinds = c["layer_types"][:c["num_hidden_layers"]]
    return kinds.count("attention"), kinds.count("mamba")


def weight_params(c: dict) -> Dict[str, int]:
    """Parameters read by every decode step, by part."""
    d, V = c["hidden_size"], c["vocab_size"]
    H, KV = c["num_attention_heads"], c["num_key_value_heads"]
    hd = d // H
    din = c["mamba_expand"] * d
    nh = din // c["mamba_d_head"]
    gn = c["mamba_n_groups"] * c["mamba_d_state"]
    k = c["mamba_d_conv"]
    n_attn, n_mamba = _pattern(c)
    L = n_attn + n_mamba
    attn = d * H * hd + 2 * d * KV * hd + H * hd * d
    mamba = (d * (2 * din + 2 * gn + nh) + din * d + k * (din + 2 * gn)
             + 3 * nh + din)
    return {
        "attention": n_attn * attn,
        "mamba": n_mamba * mamba,
        "norms": 2 * L * d + d,
        "router": L * d * c["published_num_local_experts"],
        "shared": L * 3 * d * c["shared_intermediate_size"],
        "table": V * d,
    }


def state_bytes_per_slot(c: dict) -> int:
    """SSM and conv state of one slot, over every mamba layer, read once
    and written once."""
    d = c["hidden_size"]
    din = c["mamba_expand"] * d
    gn = c["mamba_n_groups"] * c["mamba_d_state"]
    _, n_mamba = _pattern(c)
    ssm = din * c["mamba_d_state"] * STATE_BYTES
    conv = (c["mamba_d_conv"] - 1) * (din + 2 * gn) * DTYPE_BYTES[c["dtype"]]
    return 2 * n_mamba * (ssm + conv)


def decode_least_bytes(c: dict, live_slots: float) -> float:
    """Least bytes of one decode step with ``live_slots`` slots live."""
    weights = sum(weight_params(c).values()) * DTYPE_BYTES[c["dtype"]]
    return weights + live_slots * state_bytes_per_slot(c)
