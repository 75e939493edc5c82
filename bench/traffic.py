"""The one general generator behind every traffic mix and every input.

A traffic mix (``traffic/<mix>.json``) gives the spot price process, the
workers' bids and the engine's runtime model. Prices are drawn per tick
and stratified in blocks: each block of ``block`` ticks holds exactly
``count`` draws from each stratum ``[lo, hi)``, uniform within it, in an
order the seed shuffles. With stratum counts proportional to their widths
this is Uniform[price.lo, price.hi] with no sampling noise in how many
ticks of each kind a block holds.

The token stream is a copy of the program's synthetic LM data (Zipf tokens
with a bigram structure, Gaussian stub patch embeddings), so that the
inputs come from the benchmark and the reference reads them from here.
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np

#: prices keep this far from every stratum edge, so no bid lies within
#: rounding of a price and the active mask is the same in float32 and here
EDGE = 1e-4


def check_mix(mix: Dict) -> None:
    """Refuse a mix whose strata do not tile [lo, hi) in proportion to
    their counts (which would not be the stated uniform distribution)."""
    p = mix["price"]
    strata = sorted(p["strata"], key=lambda s: s["lo"])
    if sum(s["count"] for s in strata) != p["block"]:
        raise ValueError(f"stratum counts do not add up to block "
                         f"{p['block']}")
    edges = [p["lo"]] + [s["hi"] for s in strata]
    if [s["lo"] for s in strata] != edges[:-1] or edges[-1] != p["hi"]:
        raise ValueError("strata do not tile [price.lo, price.hi)")
    width = p["hi"] - p["lo"]
    for s in strata:
        share = (s["hi"] - s["lo"]) / width
        if abs(share - s["count"] / p["block"]) > 1e-9:
            raise ValueError(f"stratum {s} holds {s['count']} of "
                             f"{p['block']} ticks but {share:.4f} of the "
                             "price range")


def price_trace(mix: Dict, seed: int, n_ticks: int) -> np.ndarray:
    """(n_ticks,) float32 spot prices, one per engine tick."""
    check_mix(mix)
    p = mix["price"]
    block = int(p["block"])
    if n_ticks % block:
        raise ValueError(f"{n_ticks} ticks is not a whole number of "
                         f"{block}-tick blocks")
    rng = np.random.default_rng((int(seed), 7))
    n_blocks = n_ticks // block
    cols = []
    for s in p["strata"]:
        u = rng.uniform(s["lo"] + EDGE, s["hi"] - EDGE,
                        (n_blocks, int(s["count"])))
        cols.append(u)
    prices = np.concatenate(cols, axis=1)            # (n_blocks, block)
    order = np.argsort(rng.random((n_blocks, block)), axis=1)
    return np.take_along_axis(prices, order, axis=1).reshape(-1).astype(
        np.float32)


def active_masks(mix: Dict, prices: np.ndarray) -> np.ndarray:
    """(n_ticks, n_workers) bool: a worker is up on a tick iff its bid
    covers the price (the paper's spot semantics, Section IV)."""
    bids = np.asarray(mix["bids"], np.float64)
    return bids[None, :] >= prices[:, None].astype(np.float64)


def iteration_masks(masks: np.ndarray, j_target: Sequence[int],
                    chunk: int) -> List[np.ndarray]:
    """The masks of the iterations that chunks ending at iteration targets
    ``j_target`` run: chunk c covers ticks [c·chunk, (c+1)·chunk) and runs
    iterations while fewer than ``j_target[c]`` have run."""
    out: List[np.ndarray] = []
    for c, target in enumerate(j_target):
        for t in range(c * chunk, (c + 1) * chunk):
            if len(out) < target and masks[t].any():
                out.append(masks[t])
        if len(out) < target:
            raise ValueError(f"chunk {c} ran {len(out)} iterations, short "
                             f"of {target}")
    return out


# ------------------------------------------------------------------- data


def _tokens(vocab: int, seed: int, index: int, rows: int,
            length: int) -> np.ndarray:
    """Zipf tokens with every odd position one above its left neighbour
    (the program's `data.synthetic.TokenStream.batch`, copied)."""
    rng = np.random.default_rng((int(seed), index))
    base = rng.zipf(1.2, size=(rows, length + 1))
    toks = np.minimum(base - 1, vocab - 1).astype(np.int32)
    toks[:, 1::2] = np.minimum(toks[:, 0:-1:2] + 1, vocab - 1)
    return toks


def batch(model: Dict, layout: Dict, seed: int, index: int
          ) -> Dict[str, np.ndarray]:
    """Training batch ``index`` of the stream ``seed``: tokens and labels
    (B, text positions), and for a model with an image prefix the stub
    patch embeddings (B, patches, d_model)."""
    rows, seq = int(layout["global_batch"]), int(layout["seq_len"])
    patches = int(model.get("vision", {}).get("num_patches", 0))
    toks = _tokens(int(model["vocab_size"]), seed, index, rows,
                   seq - patches)
    out = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
    if patches:
        rng = np.random.default_rng((int(seed), index, 1))
        out["patches"] = (0.5 * rng.standard_normal(
            (rows, patches, int(model["d_model"])), np.float32))
    return out
