"""What the plain references share: the weights made from the seed, the
numerics helpers, the masked elastic loss, and three steps of SGD with
momentum. Plain `jax.numpy` in float32; it imports nothing of the program.

The weights are the benchmark's: `make_params` draws every leaf from the
seed, and the harness hands the same values to the program as its initial
carry, so the program and the reference start from one point without the
reference reading anything the program made.
"""
from __future__ import annotations

import math
import zlib
from typing import Callable, Dict, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


class Init(NamedTuple):
    """One parameter leaf: its shape and how it is drawn."""

    shape: tuple
    kind: str = "normal"      # normal | zeros | ones | a_log | dt_bias
    scale: float = 1.0        # stddev of "normal"


def dense(d_in: int, d_out: int) -> Init:
    return Init((d_in, d_out), "normal", d_in ** -0.5)


def stacked(tree, n: int):
    """The same leaves with a leading layer axis (layers scanned)."""
    return jax.tree.map(lambda s: Init((n,) + s.shape, s.kind, s.scale),
                        tree, is_leaf=lambda x: isinstance(x, Init))


def is_init(x) -> bool:
    return isinstance(x, Init)


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed below 2**64."""
    seed = int(seed)
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"seed {seed} outside [0, 2**64)")
    return jnp.asarray([(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF],
                       jnp.uint32)


def _leaf_key(key, path) -> jax.Array:
    return jax.random.fold_in(
        key, zlib.crc32(jax.tree_util.keystr(path).encode()) & 0x7FFFFFFF)


def make_params(spec, key) -> Dict:
    """Float32 weights for the `Init` tree ``spec``. Mamba-2's A and dt
    follow its paper: A uniform in [1, 16] (stored as log A), dt
    log-uniform in [0.001, 0.1] (stored as softplus⁻¹ dt)."""

    def make(path, s: Init):
        k = _leaf_key(key, path)
        if s.kind == "zeros":
            return jnp.zeros(s.shape, F32)
        if s.kind == "ones":
            return jnp.ones(s.shape, F32)
        if s.kind == "a_log":
            return jnp.log(jax.random.uniform(k, s.shape, F32, 1.0, 16.0))
        if s.kind == "dt_bias":
            lo, hi = math.log(1e-3), math.log(1e-1)
            dt = jnp.exp(jax.random.uniform(k, s.shape, F32, lo, hi))
            return dt + jnp.log(-jnp.expm1(-dt))      # softplus⁻¹(dt)
        return jax.random.normal(k, s.shape, F32) * s.scale

    return jax.tree_util.tree_map_with_path(make, spec, is_leaf=is_init)


def shapes(spec) -> Dict:
    return jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, F32), spec,
                        is_leaf=is_init)


# -------------------------------------------------------------- numerics


def identity(x):
    return x


def fp8(x):
    """Round to float8 e4m3 (saturating at its largest finite value) and
    back: a matmul operand as an fp8 path would hold it."""
    big = float(jnp.finfo(jnp.float8_e4m3fn).max)
    return jnp.clip(x, -big, big).astype(jnp.float8_e4m3fn).astype(F32)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x, theta: float):
    """Rotary position embedding, rotate-half form. x: (S, H, D), positions
    0..S-1."""
    s, _, d = x.shape
    half = d // 2
    freqs = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(s, dtype=F32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def nll_sum(logits, labels):
    """Σ over positions of -log softmax(logits)[label]. logits (S, V)."""
    lse = jax.nn.logsumexp(logits, -1)
    gold = jnp.take_along_axis(logits, labels[:, None], -1)[:, 0]
    return jnp.sum(lse - gold)


# ------------------------------------------------------------ training


class Readings(NamedTuple):
    """What the comparison reads of three training steps."""

    losses: np.ndarray        # (3,) loss of each step
    grad_norms: Dict          # leaf path -> |g| of the first step
    change_norms: Dict        # leaf path -> |w3 - w0|


def leaf_norms(tree) -> Dict[str, float]:
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {jax.tree_util.keystr(p): float(v) for p, v in flat}


@jax.jit
def _norms(tree):
    return jax.tree.map(lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(F32)))),
                        tree)


def norms(tree) -> Dict[str, float]:
    return leaf_norms(_norms(tree))


class Stepper:
    """Three steps of elastic SGD with momentum from the seed's weights,
    compiled once for a model (its `Init` tree and per-row loss) and used
    for any seed.

    Step k's loss is the mean next-token loss over the rows of the workers
    that ``masks[k]`` holds up (Eq. (5) of the paper: the preempted shards
    count for nothing, the mean is over the active ones). Its gradient is
    the sum of the per-row gradients over those rows, one row at a time so
    that a published-width model fits, divided by the number of positions
    they hold. Each row's gradient goes straight into the momentum, which
    the step first scales by the momentum factor: the weights, the momentum
    and one row's gradient are all the reference holds."""

    def __init__(self, spec, row_loss: Callable, lr: float,
                 momentum: float):
        self.init = jax.jit(lambda key: make_params(spec, key))
        self.row_grad = jax.jit(jax.value_and_grad(row_loss))
        self.zero = jax.jit(lambda t: jax.tree.map(jnp.zeros_like, t))
        self.decay = jax.jit(
            lambda m: jax.tree.map(lambda m_: momentum * m_, m),
            donate_argnums=0)
        self.add = jax.jit(
            lambda m, g, scale: jax.tree.map(lambda m_, g_: m_ + g_ * scale,
                                             m, g), donate_argnums=0)
        self.sgd = jax.jit(
            lambda w, m: jax.tree.map(lambda w_, m_: w_ - lr * m_, w, m),
            donate_argnums=0)
        self.change = jax.jit(lambda w, key: _norms(jax.tree.map(
            jnp.subtract, w, make_params(spec, key))))

    def run(self, key, batches: Sequence[Dict],
            masks: Sequence[np.ndarray], rows_per_worker: int,
            positions: int, keep_rows: Optional[Callable] = None
            ) -> Readings:
        """``positions``: label positions per row. ``keep_rows`` picks a
        subset of the active rows (the half-batch fault)."""
        with jax.default_matmul_precision("highest"):
            return self._run(key, batches, masks, rows_per_worker,
                             positions, keep_rows)

    def _run(self, key, batches, masks, rows_per_worker, positions,
             keep_rows):
        w = self.init(key)
        m = self.zero(w)
        losses, grad_norms = [], None
        for k in range(3):
            rows = [r for r in range(len(masks[k]) * rows_per_worker)
                    if masks[k][r // rows_per_worker]]
            if keep_rows is not None:
                rows = keep_rows(rows)
            denom = float(len(rows) * positions)
            scale, total = jnp.float32(1.0 / denom), 0.0
            m = self.decay(m)
            for r in rows:
                row = {n: jnp.asarray(v[r]) for n, v in batches[k].items()}
                nll, g = self.row_grad(w, row)
                m = self.add(m, g, scale)
                del g
                total += float(nll)
            losses.append(total / denom)
            if k == 0:
                # the momentum after one step from zero: the first gradient
                grad_norms = norms(m)
            w = self.sgd(w, m)
        del m
        return Readings(np.asarray(losses), grad_norms,
                        leaf_norms(self.change(w, key)))
