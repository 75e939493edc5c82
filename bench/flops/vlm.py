"""Decoder LM with an image-patch prefix (InternVL2 on Qwen2).

Per position and layer: the q, k, v and output projections and the SwiGLU
MLP. Causal attention counts the lower triangle: position i attends to
i + 1 positions, so QKᵀ and PV together cost 2·(i+1)·d_head·heads
multiply-adds; over a sequence of S positions that is S(S+1)·d_head·heads.
The head counts only the text positions, whose logits the loss uses (the
patch positions have no label)."""
from __future__ import annotations

from typing import Dict


def flops_per_row(model: Dict, layout: Dict) -> float:
    d, f = model["d_model"], model["d_ff"]
    dh = model.get("head_dim") or d // model["num_heads"]
    hq, hkv = model["num_heads"] * dh, model["num_kv_heads"] * dh
    s = int(layout["seq_len"])
    text = s - int(model.get("vision", {}).get("num_patches", 0))
    proj = d * (hq + 2 * hkv) + hq * d + 3 * d * f
    attn = s * (s + 1) * dh * model["num_heads"]
    layer_macs = s * proj + attn
    head_macs = text * d * model["vocab_size"]
    return 6.0 * (model["num_layers"] * layer_macs + head_macs)
