"""Per-layer numbers of a hybrid state-space / expert model: what share
of the device's busy time, and of its roofline, the state-space scan
takes; the ungated expert matmul's share of ITS roofline; and how often
an admission that matched pages found a state snapshot to start from.

``what`` picks the number:

- ``ssm_dev_pct``: device seconds of ``ssm_scan`` (its row of
  ``breakdown.device_ops``) over ``busy_s``, traced runs only.
- ``ssm_roofline_pct`` / ``moe_roofline_pct``: the op's least seconds
  (``kernel_costs_ssm_moe.py``, ``peaks.json``) over its device seconds.
  The trace gives how many programs of each kind ran in the traced
  stretch (``programs.jit_<kind>.count``); the counters give, per kind,
  the window's means a program: tokens through the state layers
  (``gateway_ssm_tokens_total``), chunk lanes
  (``gateway_chunk_lanes_total``; a lane is ``prefill-chunk`` tokens of
  one row, a decode row one), and for the experts the assignments and
  experts reached a layer-program. Least bytes and operations are those
  counts times those means.
- ``snapshot_hit_pct``: growth of ``gateway_state_snapshots_total``
  ``restored`` over ``restored`` + ``missed``.

A run on a program without these counters or ops, or without a trace,
gives None: the metric is left out.
"""

from __future__ import annotations

import re

import kernel_costs
import kernel_costs_ssm_moe as costs
from layer_metrics.mla_moe import (
    _device_seconds, _grew, _peak, _traced_programs,
)
from server import metric


def _lanes(run, kind: str) -> float:
    """Chunk lanes the window's programs of ``kind`` carried."""
    total = 0.0
    for n in set(re.findall(
            r'gateway_chunk_lanes_total\{[^}]*lanes="(\d+)"',
            run.metrics_after)):
        labels = f'kind="{kind}",lanes="{n}"'
        total += int(n) * (
            metric(run.metrics_after, "gateway_chunk_lanes_total", labels)
            - metric(run.metrics_before, "gateway_chunk_lanes_total", labels))
    return total


def _plan(cfg: dict) -> str:
    return cfg.get("hybrid_override_pattern", "")[
        :cfg.get("num_hidden_layers", 0)]


def _ssm_least(run, peak: dict, traced: dict) -> float:
    cfg = run.config
    chunk = cfg["serve"]["prefill-chunk"]
    layers = _plan(cfg).count("M")
    least = 0.0
    for kind, count in traced.items():
        programs = _grew(run, "gateway_device_programs_total", kind)
        tokens = _grew(run, "gateway_ssm_tokens_total", kind)
        if not programs or not tokens:
            continue
        lanes = _lanes(run, kind) if kind in ("fused", "prefill") else 0.0
        rows = tokens - lanes * (chunk - 1)
        cost = costs.ssm_scan(
            rows / programs, tokens / programs, cfg["mamba_num_heads"],
            cfg["mamba_head_dim"], cfg["ssm_state_size"], cfg["n_groups"],
            cfg["conv_kernel"])
        least += count * layers * kernel_costs.least_seconds(cost, peak)[0]
    return least


def _moe_least(run, peak: dict, traced: dict) -> float:
    cfg = run.config
    k = cfg["num_experts_per_tok"]
    stored = -(-cfg["moe_intermediate_size"] // 128) * 128
    layers = _plan(cfg).count("E")
    least = 0.0
    for kind, count in traced.items():
        per = _grew(run, "gateway_moe_layer_programs_total", kind)
        if not per:
            continue
        assignments = _grew(run, "gateway_moe_assignments_total", kind) / per
        cost = costs.moe_relu2_matmul(
            assignments / k, assignments,
            _grew(run, "gateway_moe_experts_touched_total", kind) / per,
            cfg["hidden_size"], stored)
        least += count * layers * kernel_costs.least_seconds(cost, peak)[0]
    return least


def read(run, what: str):
    if what == "snapshot_hit_pct":
        name = "gateway_state_snapshots_total"
        restored = _grew_event(run, name, "restored")
        asked = restored + _grew_event(run, name, "missed")
        return 100.0 * restored / asked if asked else None
    if "M" not in _plan(run.config):
        return None
    if run.trace is None or not run.trace.get("busy_s"):
        return None
    op = "moe_grouped_matmul" if what == "moe_roofline_pct" else "ssm_scan"
    seconds = _device_seconds(run, op)
    if not seconds:
        return None
    if what == "ssm_dev_pct":
        return 100.0 * seconds / run.trace["busy_s"]
    peak, traced = _peak(run), _traced_programs(run)
    if peak is None or not traced:
        return None
    least = (_moe_least if what == "moe_roofline_pct" else _ssm_least)(
        run, peak, traced)
    return 100.0 * least / seconds if least else None


def _grew_event(run, name: str, event: str) -> float:
    labels = f'event="{event}"'
    return (metric(run.metrics_after, name, labels)
            - metric(run.metrics_before, name, labels))

