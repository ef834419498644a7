"""Roofline shares of a block-diffusion model's two kernels: the ragged
attention (``kernels.attn.seconds`` of the trace) and the grouped expert
matmul (``moe_grouped_matmul``'s row of ``breakdown.device_ops``), each
its least seconds over its device seconds, traced runs only.

``what`` picks the kernel:

- ``attention_roofline_pct``: least bytes are the pool pages the
  programs' attention read (``gateway_attention_pages_read_total``) at
  2 x kv heads x head_dim bf16 bytes a token a layer, least operations
  the (query, key) pairs under the block rule
  (``gateway_attention_pairs_total``) at 4 x heads x head_dim a pair a
  layer (``kernel_costs_block_diffusion.py``);
- ``moe_roofline_pct``: ``kernel_costs_mla_moe.moe_grouped_matmul`` of
  a layer-program's experts reached and assignments
  (``gateway_moe_*_total``), times the layer-programs a program runs.

The trace gives how many programs of each module ran in the traced
stretch (``jit_denoise_step`` carries the block rows, with or without
chunk lanes: counter kinds ``denoise`` and ``fused``;
``jit_prefill_chunk`` the standalone chunks: kind ``prefill``); the
counters give each module's means a program over the window. A run on a
program without these counters, or without a trace, gives None.
"""

from __future__ import annotations

import kernel_costs
import kernel_costs_block_diffusion as costs
import kernel_costs_mla_moe
from layer_metrics.mla_moe import _device_seconds, _grew, _peak

MODULES = {"jit_denoise_step": ("denoise", "fused"),
           "jit_prefill_chunk": ("prefill",)}


def _per_program(run, name: str, kinds) -> float | None:
    """Growth of counter ``name`` a program of ``kinds``."""
    programs = sum(_grew(run, "gateway_device_programs_total", k)
                   for k in kinds)
    if not programs:
        return None
    return sum(_grew(run, name, k) for k in kinds) / programs


def _least(run, what: str, peak: dict) -> float:
    cfg = run.config
    layers = cfg["num_hidden_layers"]
    least = 0.0
    for module, kinds in MODULES.items():
        count = run.trace.get("programs", {}).get(module, {}).get("count")
        if not count:
            continue
        if what == "attention_roofline_pct":
            pages = _per_program(run, "gateway_attention_pages_read_total",
                                 kinds)
            pairs = _per_program(run, "gateway_attention_pairs_total", kinds)
            if pages is None:
                continue
            cost = costs.block_attention(
                pages, pairs, layers, run.config["batcher"]["page_size"],
                4 * cfg["num_key_value_heads"] * cfg["head_dim"],
                cfg["num_attention_heads"], cfg["head_dim"])
            least += count * kernel_costs.least_seconds(cost, peak)[0]
            continue
        per = _per_program(run, "gateway_moe_layer_programs_total", kinds)
        if not per:
            continue
        layer_programs = sum(
            _grew(run, "gateway_moe_layer_programs_total", k) for k in kinds)
        assignments = sum(_grew(run, "gateway_moe_assignments_total", k)
                          for k in kinds) / layer_programs
        experts = sum(_grew(run, "gateway_moe_experts_touched_total", k)
                      for k in kinds) / layer_programs
        cost = kernel_costs_mla_moe.moe_grouped_matmul(
            assignments / cfg["num_experts_per_tok"], assignments, experts,
            cfg["hidden_size"], cfg["moe_intermediate_size"])
        least += count * per * kernel_costs.least_seconds(cost, peak)[0]
    return least


def read(run, what: str):
    if run.trace is None or not run.trace.get("busy_s"):
        return None
    if not _grew(run, "gateway_diffusion_blocks_total"):
        return None
    peak = _peak(run)
    if peak is None:
        return None
    seconds = (
        run.trace.get("kernels", {}).get("attn", {}).get("seconds")
        if what == "attention_roofline_pct"
        else _device_seconds(run, "moe_grouped_matmul")
    )
    least = _least(run, what, peak)
    if not seconds or not least:
        return None
    return 100.0 * least / seconds
