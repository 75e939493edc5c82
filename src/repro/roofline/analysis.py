"""Roofline analysis from a compiled (dry-run) executable.

Three terms per (arch × shape × mesh), in seconds (per training/serve step):

  compute    = HLO_FLOPs_global   / (chips × peak_FLOP/s)
  memory     = HLO_bytes_global   / (chips × HBM_bw)
  collective = collective_bytes   / (chips × link_bw)

`cost_analysis()` on the compiled SPMD module reports *per-device* flops and
bytes; we multiply by chip count for the global view and divide back for the
per-chip time terms (so the ×chips cancels — the terms below use per-device
numbers directly). Collective bytes are not in cost_analysis: we parse the
post-SPMD HLO and sum the result-shape bytes of every collective op.

Hardware model (TPU v5e per chip): 197 TFLOP/s bf16, 819 GB/s HBM,
~50 GB/s/link ICI (bidirectional per link; we charge each collective byte
once per hop-step against one link).
"""
from __future__ import annotations

import math
import re
from collections import defaultdict
from typing import Dict, Optional

PEAK_FLOPS = 197e12          # bf16 / chip
HBM_BW = 819e9               # bytes/s / chip
ICI_BW = 50e9                # bytes/s / link

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1,
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")

# one shape literal, e.g. f32[8,128]{1,0} or bf16[4]
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_OP_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(\([^)]*\)|[\w\[\]{},.]+)\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(",
)


def xla_cost_analysis(compiled) -> Dict[str, float]:
    """``compiled.cost_analysis()``, or an empty dict where the backend
    reports none."""
    return compiled.cost_analysis() or {}


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(shape_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def collective_bytes_from_hlo(hlo_text: str) -> Dict[str, int]:
    """Sum result-shape bytes per collective kind from post-SPMD HLO text.
    `-start`/`-done` pairs are counted once (on `-start`; `-done` results are
    skipped by checking the op suffix in the matched source line)."""
    per_kind = defaultdict(int)
    for line in hlo_text.splitlines():
        m = _OP_RE.match(line)
        if not m:
            continue
        if "-done(" in line:
            continue
        shape_str, kind = m.group(1), m.group(2)
        per_kind[kind] += _shape_bytes(shape_str)
    return dict(per_kind)


def model_flops(cfg, shape) -> float:
    """MODEL_FLOPS = 6·N_active·D for training, 2·N_active·D for inference
    (forward only), D = processed tokens per step."""
    n_active = active_param_count(cfg)
    if shape.is_decode:
        tokens = shape.global_batch            # one token per sequence
        mult = 2.0
    elif shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        mult = 2.0
    else:
        tokens = shape.global_batch * shape.seq_len
        mult = 6.0
    return mult * n_active * tokens


def active_param_count(cfg) -> float:
    """Parameters touched per token (MoE counts shared + top_k routed
    experts only; embeddings excluded by convention)."""
    from repro.models import model_zoo
    from repro.models.common import is_spec_leaf, param_count

    import jax

    defs = model_zoo.param_defs(cfg)
    total = param_count(defs)
    # subtract embedding / lm head (not matmul-FLOPs-per-token in 6ND conv.)
    emb = cfg.vocab_size * cfg.d_model
    total -= emb
    if not cfg.tie_embeddings:
        total -= emb
    if cfg.moe is not None:
        m = cfg.moe
        per_expert = 3 * cfg.d_model * m.d_ff_expert
        total -= cfg.num_layers * m.num_experts * per_expert
        total += cfg.num_layers * m.top_k * per_expert
    return float(max(total, 0))


def analyze_compiled(compiled, cfg, shape, mesh, n_params_defs=None) -> Dict:
    """Extract the three roofline terms + supporting stats.

    Uses the loop-aware HLO cost model (roofline/hlo_cost.py): the XLA
    backend's cost_analysis() counts while bodies once, which undercounts
    scan-over-layers flops/bytes/collectives by ~num_layers. The backend's
    raw numbers are kept in ``xla_*_uncorrected`` fields for reference.
    """
    from repro.roofline.hlo_cost import analyze_hlo_text

    chips = int(math.prod(mesh.devices.shape))
    ca = xla_cost_analysis(compiled)

    hlo = compiled.as_text()
    cost = analyze_hlo_text(hlo)
    flops_dev = float(cost.flops)
    bytes_dev = float(cost.bytes)
    coll = {k: float(v) for k, v in cost.collective.items()}
    coll_bytes_dev = float(sum(coll.values()))

    t_compute = flops_dev / PEAK_FLOPS
    t_memory = bytes_dev / HBM_BW
    t_collective = coll_bytes_dev / ICI_BW
    terms = {"compute": t_compute, "memory": t_memory,
             "collective": t_collective}
    dominant = max(terms, key=terms.get)

    mf = model_flops(cfg, shape)
    hlo_flops_global = flops_dev * chips
    mem = compiled.memory_analysis()
    record = {
        "chips": chips,
        "flops_per_device": flops_dev,
        "bytes_per_device": bytes_dev,
        "xla_flops_uncorrected": float(ca.get("flops", 0.0)),
        "xla_bytes_uncorrected": float(ca.get("bytes accessed", 0.0)),
        "collective_bytes_per_device": coll_bytes_dev,
        "collectives": coll,
        "t_compute_s": t_compute,
        "t_memory_s": t_memory,
        "t_collective_s": t_collective,
        "dominant": dominant,
        "model_flops_global": mf,
        "hlo_flops_global": hlo_flops_global,
        "useful_flops_ratio": mf / hlo_flops_global if hlo_flops_global else 0,
        "temp_bytes_per_device": getattr(mem, "temp_size_in_bytes", None),
        "arg_bytes_per_device": getattr(mem, "argument_size_in_bytes", None),
        "output_bytes_per_device": getattr(mem, "output_size_in_bytes", None),
        "peak_bytes_per_device": (
            (getattr(mem, "temp_size_in_bytes", 0) or 0)
            + (getattr(mem, "argument_size_in_bytes", 0) or 0)),
    }
    return record


def step_time_bound(record: Dict) -> float:
    """Lower-bound step time = max of the three terms (no overlap model)."""
    return max(record["t_compute_s"], record["t_memory_s"],
               record["t_collective_s"])
