"""Roofline model: compute / memory / collective terms from compiled dry-runs.

Measurement strategy (DESIGN.md §6): XLA's ``cost_analysis`` counts a
``lax.scan`` body ONCE, so the full-model compile proves lowering and gives
``memory_analysis`` while the cost terms are extracted from two *unrolled*
probe compiles (1 stack-unit and 2 stack-units) and scaled::

    per_unit = cost(2u) - cost(1u)
    total    = cost(1u) - per_unit      # base: embed/lm-head/loss/optimizer
               + n_units * per_unit

Collective bytes come from parsing post-SPMD HLO of the probes (ring-algorithm
link-byte estimates per collective kind).  Analytic matmul FLOPs from
``ModelConfig.flops_per_token_fwd`` provide the primary compute term and the
MODEL_FLOPS/HLO_FLOPs "useful compute" ratio.
"""
from __future__ import annotations

import dataclasses
import math
import re
from typing import Dict

from repro.configs.base import InputShape, ModelConfig
from repro.launch.mesh import peaks

# the dry run plans for a v5e pod (``mesh.make_production_mesh``)
_V5E = peaks("TPU v5 lite")

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "token": 0, "opaque": 0,
}

_COLL_OPS = ("all-to-all", "all-gather", "all-reduce", "reduce-scatter",
             "collective-permute")
_SHAPE_RE = re.compile(r"(\w+)\[([0-9,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=(\{\{[^}]*\}[^)]*?\}|\[\d+,\d+\])")


def _shape_bytes(lhs: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(lhs):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _group_size(line: str, default: int) -> int:
    m = _GROUPS_RE.search(line)
    if not m:
        return default
    g = m.group(1)
    if g.startswith("[{") or g.startswith("{{"):
        first = g[g.index("{{") + 2:]
        first = first[:first.index("}")]
        return len([x for x in first.split(",") if x.strip() != ""])
    if g.startswith("["):
        # iota form [num_groups,group_size]
        dims = g.strip("[]").split(",")
        return int(dims[1])
    return default


def _link_bytes(op: str, size: int, n: int) -> float:
    """Ring-algorithm per-device link bytes for a collective with result
    bytes ``size`` over ``n`` participants."""
    if n <= 1:
        return 0.0
    if op == "all-gather":
        return size * (n - 1) / n
    if op == "reduce-scatter":
        return size * (n - 1)          # result is the scattered shard
    if op == "all-reduce":
        return 2 * size * (n - 1) / n
    if op == "all-to-all":
        return size * (n - 1) / n
    if op == "collective-permute":
        return float(size)
    return 0.0


def parse_collectives(hlo_text: str, n_devices: int) -> Dict:
    """Sum estimated link bytes per collective kind from post-SPMD HLO.

    Matches ``<result-shapes> <op>(`` — result shapes may be a tuple with
    ``/*index=N*/`` comments; every dtype[shape] token left of the op name on
    the line is summed.  ``-done`` halves of async pairs are skipped.
    """
    per_op: Dict[str, float] = {}
    count: Dict[str, int] = {}
    for line in hlo_text.splitlines():
        for op in _COLL_OPS:
            marker = f" {op}("
            start_marker = f" {op}-start("
            if start_marker in line:
                marker = start_marker
            elif marker not in line:
                continue
            if f"{op}-done(" in line:
                break
            lhs = line.split(marker)[0]
            if "= " in lhs:
                lhs = lhs.split("= ", 1)[1]
            size = _shape_bytes(lhs)
            n = _group_size(line, n_devices)
            per_op[op] = per_op.get(op, 0.0) + _link_bytes(op, size, n)
            count[op] = count.get(op, 0) + 1
            break
    return {"link_bytes": per_op, "counts": count,
            "total_link_bytes": sum(per_op.values())}


# ---------------------------------------------------------------------------
# Probe scaling
# ---------------------------------------------------------------------------
def probe_units(cfg: ModelConfig):
    """(unit_layer_counts_for_probes, n_units_full, probe_cfg_fn)."""
    if cfg.family == "hybrid":
        k = cfg.attn_every
        return (k, 2 * k), cfg.n_layers / k
    if cfg.family == "ssm" and cfg.slstm_every:
        k = cfg.slstm_every
        return (k, 2 * k), cfg.n_layers / k
    if cfg.family == "moe":
        fd = cfg.first_dense
        return (fd + 1, fd + 2), cfg.n_layers - fd
    return (1, 2), cfg.n_layers


def probe_config(cfg: ModelConfig, n_layers: int) -> ModelConfig:
    return dataclasses.replace(cfg, n_layers=n_layers)


def scale_probe_costs(cost1: Dict, cost2: Dict, n_units: float) -> Dict:
    out = {}
    for k in set(cost1) | set(cost2):
        c1, c2 = cost1.get(k, 0.0), cost2.get(k, 0.0)
        # XLA may make different fusion/collective choices at 1u vs 2u; a
        # negative delta is measurement noise, not real cost -> clamp
        per_unit = max(0.0, c2 - c1)
        out[k] = max(0.0, c1 - per_unit) + n_units * per_unit
    return out


# ---------------------------------------------------------------------------
# Analytic FLOPs / bytes
# ---------------------------------------------------------------------------
def analytic_flops(cfg: ModelConfig, shape: InputShape, window: int) -> float:
    b, s = shape.global_batch, shape.seq_len
    if shape.kind == "train":
        fwd = cfg.flops_per_token_fwd(s) * b * s
        return 3.0 * fwd                       # fwd + backward (2x)
    if shape.kind == "prefill":
        return cfg.flops_per_token_fwd(s) * b * s
    return cfg.flops_per_token_fwd(1, kv_len=s, window=window) * b


def model_flops(cfg: ModelConfig, shape: InputShape) -> float:
    """The 6·N·D (train) / 2·N·D (inference) convention, active params for
    MoE; attention score FLOPs excluded by convention."""
    n = cfg.active_param_count()
    if shape.kind == "train":
        return 6.0 * n * shape.tokens
    if shape.kind == "prefill":
        return 2.0 * n * shape.tokens
    return 2.0 * n * shape.global_batch


def analytic_hbm_bytes(cfg: ModelConfig, shape: InputShape, window: int,
                       n_chips: int) -> float:
    """Per-step HBM traffic floor, summed over chips: every resident param
    byte read once (+3x for train: grad write, two optimizer-moment
    read-writes approximated), plus decode KV-cache read."""
    p_bytes = cfg.param_count() * 2        # bf16 residency
    if shape.kind == "train":
        traffic = p_bytes * (1 + 2) + cfg.param_count() * 4 * 4  # p+g, m/v rw
    elif shape.kind == "decode":
        # params read once per step (weights stream regardless of batch);
        # MoE: a large decode batch touches ~all experts, small batch only
        # the routed ones — use active counts as the floor
        traffic = cfg.active_param_count() * 2
        traffic += _decode_cache_bytes(cfg, shape, window)
    else:
        traffic = cfg.active_param_count() * 2
    return float(traffic)


def _decode_cache_bytes(cfg: ModelConfig, shape: InputShape,
                        window: int) -> float:
    b = shape.global_batch
    t = min(shape.seq_len, window) if window else shape.seq_len
    if cfg.family == "ssm":
        d = cfg.d_model
        per_layer = b * (cfg.n_heads * (2 * d // max(cfg.n_heads, 1)) ** 2) * 4
        return cfg.n_layers * per_layer
    if cfg.family == "hybrid":
        sites = math.ceil(cfg.n_layers / cfg.attn_every)
        attn = sites * b * t * 2 * cfg.n_kv_heads * cfg.head_dim * 2
        ssm = cfg.n_layers * b * cfg.ssm_heads * cfg.ssm_state * \
            cfg.ssm_head_dim * 4
        return attn + ssm
    if cfg.attn_type == "mla":
        return cfg.n_layers * b * t * (cfg.kv_lora_rank + cfg.qk_rope_dim) * 2
    return cfg.n_layers * b * t * 2 * cfg.n_kv_heads * cfg.head_dim * 2


# ---------------------------------------------------------------------------
# The three terms
# ---------------------------------------------------------------------------
def roofline_terms(cfg: ModelConfig, shape: InputShape, *, n_chips: int,
                   window: int, hlo_flops: float, hlo_bytes: float,
                   link_bytes: float) -> Dict:
    a_flops = analytic_flops(cfg, shape, window)
    m_flops = model_flops(cfg, shape)
    a_bytes = analytic_hbm_bytes(cfg, shape, window, n_chips)
    compute_s = a_flops / (n_chips * _V5E["flops_bf16"])
    compute_hlo_s = hlo_flops / (n_chips * _V5E["flops_bf16"])
    # hlo_bytes is per-device (post-SPMD program) -> per-chip time directly
    memory_s = hlo_bytes / _V5E["hbm_bw"]
    memory_analytic_s = a_bytes / (n_chips * _V5E["hbm_bw"])
    collective_s = link_bytes / _V5E["ici_bw"]     # per-device link bytes
    terms = {
        "compute_s": compute_s,
        "compute_hlo_s": compute_hlo_s,
        "memory_s": memory_s,
        "memory_analytic_s": memory_analytic_s,
        "collective_s": collective_s,
        "analytic_flops": a_flops,
        "hlo_flops": hlo_flops,
        "model_flops_6nd": m_flops,
        "useful_ratio": (m_flops / hlo_flops) if hlo_flops else None,
        "hlo_bytes_per_chip": hlo_bytes,
        "link_bytes_per_chip": link_bytes,
    }
    dom = max(("compute_s", "memory_s", "collective_s"),
              key=lambda k: terms[k])
    terms["dominant"] = dom
    total = terms["compute_s"] + terms["memory_s"] + terms["collective_s"]
    terms["bound_fraction"] = terms[dom] / total if total else None
    return terms
