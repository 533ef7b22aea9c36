"""Serving sizes of a configuration from ``compiled.memory_analysis()``.

    JAX_PLATFORMS=cpu python3 bench/tools/size.py --config mamba2-1.3b \
        --capacities 16,32 [--bytes-limit 15.75e9]

Compiles, for a described TPU v5e (no chip needed), the engine's decode
step over ``capacity`` slots and the largest prefill the traffic can
cause (``capacity`` prompts of the longest length at once), and prints
what each holds on the device. The engine holds the weights and the slot
cache all along; a prefill adds its outputs, its temporaries and, for a
KV cache, the copy of its cache padded to ``max_seq``. A capacity fits where the larger of
the two programs' needs stays under 85 % of the chip's ``bytes_limit``.
Not run by the benchmark; the figures are in PERF.md.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
HEADROOM = 0.85


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--capacities", required=True)
    ap.add_argument("--traffic", default="serve-chat")
    ap.add_argument("--bytes-limit", type=float, default=15.75e9,
                    help="memory_stats()['bytes_limit'] of the chip")
    args = ap.parse_args()
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from bench import harness, traffic as gen
    from repro.configs.registry import resolve_arch
    from repro.serving.engine import InferenceEngine

    jax.config.update("jax_enable_compilation_cache", False)
    config = harness.load_json(ROOT / "bench" / "configs" / f"{args.config}.json")
    traffic = harness.load_json(ROOT / "bench" / "traffic" / f"{args.traffic}.json")
    cfg = resolve_arch(harness.register_config(config))
    max_seq = config["serving"]["max_seq"]
    longest = max(gen.prompt_lengths(traffic))
    chip = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=chip), tree)

    for cap in (int(c) for c in args.capacities.split(",")):
        eng = InferenceEngine(cfg, capacity=cap, max_seq=max_seq)
        model, axes = eng.model, eng._axes
        params = on_chip(model.abstract_params())
        cache = on_chip({k: (jax.ShapeDtypeStruct((cap,), jnp.int32) if k == "pos"
                             else s)
                         for k, s in model.cache_specs(cap, max_seq).items()})

        def decode_one(p, c, tok):      # as the engine builds it
            c = {k: v if k == "pos" else jnp.expand_dims(v, axes[k])
                 for k, v in c.items()}
            logits, new = model.decode(p, c, {"tokens": tok})
            return logits, {k: v if k == "pos" else jnp.squeeze(v, axes[k])
                            for k, v in new.items()}

        decode = jax.jit(jax.vmap(decode_one, in_axes=(None, axes, 0),
                                  out_axes=(0, axes)), donate_argnums=(1,))
        tok = jax.ShapeDtypeStruct((cap, 1, 1), jnp.int32, sharding=chip)
        d = decode.lower(params, cache, tok).compile().memory_analysis()
        ptok = jax.ShapeDtypeStruct((cap, longest), jnp.int32, sharding=chip)
        p = jax.jit(model.prefill).lower(params, {"tokens": ptok}
                                         ).compile().memory_analysis()
        nbytes = lambda t: sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(t))
        w, c = nbytes(params), nbytes(cache)
        decode_need = w + c + d.output_size_in_bytes - d.alias_size_in_bytes \
            + d.temp_size_in_bytes
        # a KV cache out of prefill is padded to max_seq: one more slot
        # cache (a state cache has no sequence axis and is not padded)
        padded = c if "k" in cache else 0
        prefill_need = w + c + p.output_size_in_bytes + p.temp_size_in_bytes + padded
        need = max(decode_need, prefill_need)
        print(json.dumps({
            "config": args.config, "capacity": cap, "max_seq": max_seq,
            "prefill_prompt": longest, "weights_bytes": w, "slot_cache_bytes": c,
            "decode": {"temp": d.temp_size_in_bytes, "output": d.output_size_in_bytes,
                       "alias": d.alias_size_in_bytes, "need": decode_need},
            "prefill": {"temp": p.temp_size_in_bytes, "output": p.output_size_in_bytes,
                        "need": prefill_need},
            "fits_85%": need <= HEADROOM * args.bytes_limit,
            "share_of_limit": need / args.bytes_limit}), flush=True)
    return 0


if __name__ == "__main__":
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.exit(main())
