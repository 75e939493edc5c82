"""Each cell of BENCHMARK.json at a size a CPU test can run: the same
files, the widths, depth and sequence cut down."""
from __future__ import annotations

import copy
import dataclasses

from bench.spec import Cell, load_cell


def shrink(config: dict) -> dict:
    config = copy.deepcopy(config)
    m, lay = config["model"], config["layout"]
    m.update(num_layers=2, d_model=64, vocab_size=256)
    if "ssm" in m:
        m["ssm"].update(d_state=16, head_dim=16, chunk_size=32)
        lay["seq_len"] = 128
    else:
        m.update(num_heads=4, num_kv_heads=2, head_dim=16, d_ff=128)
        m["vision"] = {"num_patches": 8}
        lay["seq_len"] = 40
    return config


def tiny_cell(name: str) -> Cell:
    cell = load_cell(name)
    return dataclasses.replace(cell, config=shrink(cell.config))
