"""Per-layer numbers of a latent-attention, routed-expert model: what
the expert layers' counters say about routing, and what share of the
device's busy time, and of their rooflines, its two kernels take.

``what`` picks the number:

- ``experts_touched_pct``: growth of ``gateway_moe_experts_touched_total``
  over growth of ``gateway_moe_layer_programs_total`` x the layer's
  experts: the share of a layer's expert matrices a step reads.
- ``tokens_per_expert``: growth of ``gateway_moe_assignments_total``
  over growth of experts touched: rows one expert's read serves.
- ``moe_dev_pct`` / ``attn_dev_pct``: device seconds of
  ``moe_grouped_matmul`` (its row of ``breakdown.device_ops``) / of the
  attention kernel (``kernels.attn.seconds``) over ``busy_s``, traced
  runs only.
- ``moe_roofline_pct`` / ``attn_roofline_pct``: the kernel's least
  seconds (``kernel_costs_mla_moe.py``, ``peaks.json``) over its device
  seconds. The trace gives how many programs of each kind ran in the
  traced stretch (``programs.jit_<kind>.count``) and no routing; the
  counters give, per kind, the window's means a program (experts
  reached and assignments a layer-program; cached tokens read a
  program). Least bytes are those counts times those means. The
  attention's least time counts bytes alone (the pairs a call computes
  are not counted anywhere yet), so it reads low where the chunk lane's
  products dominate.

A run on a program without these counters or kernels, or without a
trace, gives None: the metric is left out.
"""

from __future__ import annotations

import json
import os

import kernel_costs
import kernel_costs_mla_moe as costs
from server import metric

KINDS = {"decode": "jit_decode_step", "fused": "jit_fused_step",
         "prefill": "jit_prefill_chunk"}


def _grew(run, name: str, kind: str = "") -> float:
    labels = f'kind="{kind}"' if kind else ""
    return (metric(run.metrics_after, name, labels)
            - metric(run.metrics_before, name, labels))


def _peak(run) -> dict | None:
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "peaks.json")) as f:
        peaks = json.load(f)
    # One device kind has peaks here; the run itself checked that the
    # server's device is in the table.
    kinds = [v for k, v in peaks.items() if isinstance(v, dict)]
    return kinds[0] if len(kinds) == 1 else None


def _device_seconds(run, op: str) -> float | None:
    for name, seconds in run.trace["breakdown"]["device_ops"]:
        if name == op:
            return seconds
    return None


def _traced_programs(run) -> dict:
    """kind -> programs of it in the traced stretch."""
    programs = run.trace.get("programs", {})
    return {kind: programs[module]["count"]
            for kind, module in KINDS.items() if module in programs}


def read(run, what: str):
    cfg = run.config
    experts = cfg.get("n_routed_experts")
    layer_programs = _grew(run, "gateway_moe_layer_programs_total")
    touched = _grew(run, "gateway_moe_experts_touched_total")
    if not experts or not layer_programs or not touched:
        return None
    if what == "experts_touched_pct":
        return 100.0 * touched / (layer_programs * experts)
    if what == "tokens_per_expert":
        return _grew(run, "gateway_moe_assignments_total") / touched
    if run.trace is None or not run.trace.get("busy_s"):
        return None
    busy = run.trace["busy_s"]
    moe_s = _device_seconds(run, "moe_grouped_matmul")
    attn_s = run.trace.get("kernels", {}).get("attn", {}).get("seconds")
    if what == "moe_dev_pct":
        return None if moe_s is None else 100.0 * moe_s / busy
    if what == "attn_dev_pct":
        return None if not attn_s else 100.0 * attn_s / busy
    peak = _peak(run)
    traced = _traced_programs(run)
    if peak is None or not traced:
        return None
    layers = cfg["num_hidden_layers"]
    moe_layers = layers - cfg["first_k_dense_replace"]
    k = cfg["num_experts_per_tok"]
    least = 0.0
    for kind, count in traced.items():
        if what == "moe_roofline_pct":
            per = _grew(run, "gateway_moe_layer_programs_total", kind)
            if not per:
                continue
            assignments = _grew(
                run, "gateway_moe_assignments_total", kind) / per
            cost = costs.moe_grouped_matmul(
                assignments / k, assignments,
                _grew(run, "gateway_moe_experts_touched_total", kind) / per,
                cfg["hidden_size"], cfg["moe_intermediate_size"])
            least += count * moe_layers * kernel_costs.least_seconds(
                cost, peak)[0]
        else:
            per = _grew(run, "gateway_device_programs_total", kind)
            if not per:
                continue
            lanes = -(-(cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])
                      // 128) * 128
            cost = costs.latent_attention(
                _grew(run, "gateway_attention_tokens_read_total", kind) / per,
                lanes)
            least += count * layers * kernel_costs.least_seconds(
                cost, peak)[0]
    seconds = moe_s if what == "moe_roofline_pct" else attn_s
    if not seconds or not least:
        return None
    return 100.0 * least / seconds
