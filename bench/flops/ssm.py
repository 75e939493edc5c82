"""Mamba-2 LM. Per position and layer: the input projections (z, x, B, C,
dt), the depthwise convolution and the output projection. The state space
duality (SSD) counts the paper's chunked algorithm with chunk Q, state N,
head size P, H heads and G groups: within a chunk CBᵀ over the lower
triangle (Q(Q+1)/2·N per chunk and group) and its product with x
(Q(Q+1)/2·P per chunk and head); the state each chunk leaves (Q·N·P per
chunk and head); the output from the state entering it (Q·N·P per chunk
and head); the passing of states between chunks is N·P per chunk and head
and is counted too. The head counts every position."""
from __future__ import annotations

from typing import Dict


def flops_per_row(model: Dict, layout: Dict) -> float:
    d, v, m = model["d_model"], model["vocab_size"], model["ssm"]
    s = int(layout["seq_len"])
    d_in = m["expand"] * d
    p, n, q, g = m["head_dim"], m["d_state"], m["chunk_size"], m["ngroups"]
    h = d_in // p
    gn = g * n
    proj = d * (2 * d_in + 2 * gn + h) + d_in * d
    conv = m["d_conv"] * (d_in + 2 * gn)
    chunks = s // q
    tri = q * (q + 1) // 2
    ssd = chunks * (g * tri * n + h * (tri * p + 2 * q * n * p + n * p))
    layer_macs = s * (proj + conv) + ssd
    return 6.0 * (model["num_layers"] * layer_macs + s * d * v)
