"""Each cell of BENCHMARK.json at a size a CPU test can run: the same
files, the widths, depth and sequence cut down."""
from __future__ import annotations

import copy
import dataclasses
import json
import os

from bench.spec import ROOT, Cell, load_cell

#: every cell of BENCHMARK.json, by name
CELLS = [w["name"] for w in json.load(
    open(os.path.join(ROOT, "BENCHMARK.json")))["workloads"]]

#: each config group's sizes at a CPU test's size, set where a
#: configuration has the group
GROUPS = {
    "ssm": {"d_state": 16, "head_dim": 16, "chunk_size": 32},
    "vision": {"num_patches": 8},
    "moe": {"num_experts": 4, "num_experts_unpadded": 4, "top_k": 2,
            "d_ff_expert": 32, "num_shared_experts": 1, "d_ff_shared": 32},
    "mla": {"kv_lora_rank": 16, "qk_nope_head_dim": 8,
            "qk_rope_head_dim": 8, "v_head_dim": 16},
}


def shrink(config: dict) -> dict:
    config = copy.deepcopy(config)
    m, lay = config["model"], config["layout"]
    m.update(num_layers=2, d_model=64, vocab_size=256)
    for group, sizes in GROUPS.items():
        if group in m:
            m[group].update(sizes)
    if "ssm" in m:
        lay["seq_len"] = 128
    else:
        m.update(num_heads=4, num_kv_heads=2, head_dim=16, d_ff=128)
        lay["seq_len"] = 40
    return config


def tiny_cell(name: str) -> Cell:
    cell = load_cell(name)
    return dataclasses.replace(cell, config=shrink(cell.config))
