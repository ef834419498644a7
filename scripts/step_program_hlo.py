#!/usr/bin/env python3
"""The paged step programs' optimised HLO, compiled for a described v5e.

Says whether a change to shared code left a configuration's compiled
step programs what they were: compile them here, WITHOUT a chip, from
two trees and compare the texts.

    python scripts/step_program_hlo.py --tree /root/scratch/parent --out /root/scratch/hlo_parent
    python scripts/step_program_hlo.py --out /root/scratch/hlo_change --against /root/scratch/hlo_parent

For each benchmark configuration without recurrent layers, at its
cell's sizes: ``fused_step_paged`` (3 lanes with decode groups, 1 lane
without), ``decode_step_paged`` and ``prefill_chunk_paged`` (3 lanes),
from abstract shapes only (no weights are made). Source locations and
the Mosaic kernels' payloads — which embed source paths — are blanked,
so a kernel file that was edited must be compared by itself.
``--against`` exits 1 if any text differs and prints the first lines
that do.
"""

from __future__ import annotations

import argparse
import os
import re
import sys
from functools import partial

# configuration -> (layers, slots, pages, decode groups), as its cell runs it
CELLS = {
    "mistral-7b": (32, 8, 512, 4),
    "qwen2-7b": (28, 16, 1024, 8),
    "deepseek-v2-lite": (19, 16, 1024, 8),
}


def normalised(text: str) -> str:
    text = re.sub(r", metadata=\{[^}]*\}", "", text)
    text = re.sub(r'backend_config="[^"]*"', 'backend_config=""', text)
    text = re.sub(r"backend_config=\{[^\n]*", "backend_config={}", text)
    return "\n".join(
        line for line in text.split("\n")
        if not re.match(
            r"^(\d+ |FileNames|FunctionNames|FileLocations|StackFrames)", line)
    )


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tree", default=os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    ap.add_argument("--out", required=True)
    ap.add_argument("--against", default="")
    args = ap.parse_args()
    sys.path.insert(0, args.tree)

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    jax.default_backend = lambda: "tpu"  # kernels compile, not interpret

    from llm_consensus_tpu.models import transformer as T
    from llm_consensus_tpu.models.configs import PRESETS
    from llm_consensus_tpu.models.paged_cache import (
        DecodeGroupArrays,
        PagedKVCache,
    )

    def described(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one), tree)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one)

    os.makedirs(args.out, exist_ok=True)
    differ = []
    for model, (layers, slots, pages, gm) in CELLS.items():
        cfg = PRESETS[model]
        if layers != cfg.n_layers:
            cfg = cfg.with_layers(layers)
        cfg = cfg.with_(use_pallas=True)
        params = described(jax.eval_shape(
            lambda: T.init_params_quantized(cfg, jax.random.PRNGKey(0))))
        cache = described(jax.eval_shape(
            lambda: PagedKVCache.create(cfg, pages, 64, slots, 48)))
        groups = DecodeGroupArrays(i32(slots), i32(gm), i32(gm), i32(slots))
        fused = jax.jit(partial(T.fused_step_paged, cfg), donate_argnums=(2,))
        with jax.default_matmul_precision("default"):
            lowered = {
                "fused3g": fused.lower(params, i32(slots, 1), cache,
                                       i32(3, 64), i32(3, 48), i32(3), groups),
                "fused1": fused.lower(params, i32(slots, 1), cache,
                                      i32(1, 64), i32(1, 48), i32(1), None),
                "decode": jax.jit(
                    partial(T.decode_step_paged, cfg), donate_argnums=(2,)
                ).lower(params, i32(slots, 1), cache, groups),
                "chunk3": jax.jit(
                    partial(T.prefill_chunk_paged, cfg), donate_argnums=(4,)
                ).lower(params, i32(3, 64), i32(3, 48), i32(3), cache),
            }
            for name, low in lowered.items():
                text = normalised(low.compile().as_text())
                with open(os.path.join(args.out, f"{model}.{name}.txt"), "w") as f:
                    f.write(text)
                if not args.against:
                    continue
                with open(os.path.join(args.against, f"{model}.{name}.txt")) as f:
                    other = f.read()
                if text != other:
                    differ.append(f"{model}.{name}")
                    pairs = zip(other.split("\n"), text.split("\n"))
                    a, b = next((a, b) for a, b in pairs if a != b)
                    print(f"{model}.{name}:\n  - {a[:240]}\n  + {b[:240]}")
    n = len(CELLS) * 4
    print(f"programs: {n}" + (f" different: {len(differ)} {differ}"
                              if args.against else f" written to {args.out}"))
    return 1 if differ else 0


if __name__ == "__main__":
    sys.exit(main())
