"""Plain reference of what the packed CIM serving path computes, written
from the configuration file alone. It imports nothing of the program.

Semantics (NeuRRAM voltage-mode MVM, one core = one tile):

  * weights -> differential conductances per matrix: w_max = max|w|,
    g+ = max(g_max w / w_max, g_min), g- = max(-g_max w / w_max, g_min);
  * a matrix is cut into tiles of (core_rows / 2) weight rows (two cells per
    weight) by core_cols columns; each tile is one core;
  * inputs are PACT-quantized to signed in_bits integers with clip in_alpha;
  * a tile's charge q = v_read (x_int . (g+ - g-)) / norm, norm being the
    column sum of g+ + g- over the tile's own rows; its ADC count is
    sign(q) min(floor(|q| / v_decr + 0.5), 2^(out_bits-1) - 1);
  * v_decr is calibrated per tile: the `adc_coverage` quantile of |q| over a
    calibration batch (the configuration's `calibration_rule`), over the
    largest count;
  * counts are de-normalized (count norm v_decr), summed over a column's row
    tiles, and scaled by w_max (alpha / n) / (v_read g_max).

Each model family's layers are written in its own file,
bench/families/<family>.py (`layers`), from the parts here: the CIM
tiles (`Chip`), `contract`, `rms`, `rope` and causal softmax `attention`.
This file adds the embedding, the final norm and the LM head.

`precision` is "highest" (float32, the configuration's statement) or "high":
every contraction as three bfloat16 products (hi*hi + hi*lo + lo*hi), the
control that stands for the next precision down.
"""
from __future__ import annotations

import functools
import math
from typing import Callable, Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .families import family

_HI = jax.lax.Precision.HIGHEST
ROW_BLOCK = 128          # rows per CIM application block (bounds memory)


def _split_bf16(a):
    hi = a.astype(jnp.bfloat16).astype(jnp.float32)
    lo = (a - hi).astype(jnp.bfloat16).astype(jnp.float32)
    return hi, lo


def contract(spec: str, a, b, precision: str):
    """einsum at the configuration's precision, or the control's."""
    if precision == "highest":
        return jnp.einsum(spec, a, b, precision=_HI)
    if precision == "high":
        ah, al = _split_bf16(a)
        bh, bl = _split_bf16(b)
        e = functools.partial(jnp.einsum, spec, precision=_HI)
        return e(ah, bh) + (e(ah, bl) + e(al, bh))
    raise ValueError(f"precision must be 'highest' or 'high', got "
                     f"{precision!r}")


# ------------------------------------------------------------------ CIM

def _quantize(x, alpha: float, bits: int):
    n = max((1 << (bits - 1)) - 1, 1)
    scale = jnp.asarray(alpha, jnp.float32) / n
    return jnp.clip(jnp.round(x / scale), -n, n), scale


def _geometry(cim: dict, k: int, n: int):
    rb = cim["core_rows"] // 2 if cim["differential_rows"] \
        else cim["core_rows"]
    cb = cim["core_cols"]
    return rb, cb, -(-k // rb), -(-n // cb)


@functools.partial(jax.jit, static_argnames=("cim_items", "alpha",
                                             "precision"))
def _table(w, x_cal, *, cim_items, alpha, precision):
    """Programmed conductances and per-tile ADC steps of one matrix."""
    cim = dict(cim_items)
    k, n = w.shape
    rb, cb, nk, nn = _geometry(cim, k, n)
    w_max = jnp.maximum(jnp.max(jnp.abs(w)), 1e-12)
    s = cim["g_max_uS"] * w / w_max
    gp = jnp.maximum(s, cim["g_min_uS"])
    gn = jnp.maximum(-s, cim["g_min_uS"])
    pad = ((0, nk * rb - k), (0, nn * cb - n))
    gd = jnp.pad(gp - gn, pad).reshape(nk, rb, nn, cb)
    norm = jnp.pad(gp + gn, pad).reshape(nk, rb, nn, cb).sum(1)
    norm = jnp.where(norm > 0, norm, 1.0)          # padded columns
    xi, _ = _quantize(x_cal, alpha, cim["in_bits"])
    xi = jnp.pad(xi, ((0, 0), (0, nk * rb - k))).reshape(-1, nk, rb)
    q = jnp.abs(contract("bkr,krnc->bknc", xi, gd, precision)
                * cim["v_read"] / norm)
    full = n // cb
    cov = cim["adc_coverage"]
    parts = []
    if full:
        parts.append(jnp.quantile(q[:, :, :full, :], cov, axis=(0, 3)))
    if n % cb:                      # the last column tile is narrower
        parts.append(jnp.quantile(q[:, :, full, :n % cb], cov,
                                  axis=(0, 2))[:, None])
    vd = jnp.concatenate(parts, axis=1)                        # (nk, nn)
    levels = (1 << (cim["out_bits"] - 1)) - 1
    vd = jnp.maximum(vd, 1e-9) / levels
    return {"gd": gd, "norm": norm, "vd": vd, "w_max": w_max}


@functools.partial(jax.jit, static_argnames=("cim_items", "alpha", "n",
                                             "precision"))
def _apply(x, t, *, cim_items, alpha, n, precision):
    """x (S, K) float -> (S, N) through the tiles of table t; S is a whole
    number of ROW_BLOCKs."""
    cim = dict(cim_items)
    nk, rb, nn, cb = t["gd"].shape
    levels = (1 << (cim["out_bits"] - 1)) - 1
    xi, scale = _quantize(x, alpha, cim["in_bits"])
    xi = jnp.pad(xi, ((0, 0), (0, nk * rb - x.shape[1])))

    inv_norm = 1.0 / t["norm"]
    denorm = t["norm"] * t["vd"][:, :, None]

    def block(xb):
        xb = xb.reshape(-1, nk, rb)
        q = contract("skr,krnc->sknc", xb, t["gd"], precision) \
            * cim["v_read"] * inv_norm
        vd = t["vd"][None, :, :, None]
        counts = jnp.sign(q) * jnp.minimum(jnp.floor(jnp.abs(q) / vd + 0.5),
                                           levels)
        # a column's row tiles are summed in row order, as the chip's
        # digital accumulator does
        acc = counts[:, 0] * denorm[0]
        for i in range(1, nk):
            acc = acc + counts[:, i] * denorm[i]
        return acc

    acc = jax.lax.map(block, xi.reshape(-1, ROW_BLOCK, xi.shape[1]))
    acc = acc.reshape(x.shape[0], nn * cb)[:, :n]
    return acc * t["w_max"] * scale / (cim["v_read"] * cim["g_max_uS"])


def scalars(c: dict) -> tuple:
    """A dict's top-level numbers, as a static jit argument."""
    return tuple(sorted((k, v) for k, v in c.items()
                        if isinstance(v, (int, float, bool))))


def chip_key(deploy_key, layer: int):
    """The key of one layer's chip under the configurations'
    `calibration_rule`: fold_in(fold_in(deploy_key, 1), layer)."""
    return jax.random.fold_in(jax.random.fold_in(deploy_key, 1), layer)


def calibration_batch(key, index: int, k: int, alpha: float, batch: int):
    """The calibration inputs of a chip's `index`-th projection (in sorted
    name order), from the chip's key."""
    _, k_syn = jax.random.split(jax.random.fold_in(key, index))
    return alpha * jax.random.truncated_normal(k_syn, -2.0, 2.0, (batch, k))


class Chip:
    """Tables of one chip's projections, built on first use.

    weights: name -> (K, N) matrix; key: the chip's key (`chip_key` for a
    layer's chip); names: the projections the chip holds, which fix each
    one's calibration index (default: the configuration's projections)."""

    def __init__(self, config: dict, weights: Dict, key, precision: str,
                 names: Sequence[str] = ()):
        self.cim = config["cim"]
        self.items = scalars(self.cim)
        self.w = weights
        self.key = key
        self.precision = precision
        self.names = sorted(names or self.cim["projections"])
        self._tables: Dict[str, dict] = {}

    def alpha(self, name: str) -> float:
        return float(self.cim["in_alpha_overrides"].get(
            name, self.cim["in_alpha"]))

    def __call__(self, name: str, x):
        w = self.w[name]
        if name not in self._tables:
            xc = calibration_batch(self.key, self.names.index(name),
                                   w.shape[0], self.alpha(name),
                                   self.cim["calibration_batch"])
            self._tables[name] = _table(w, xc, cim_items=self.items,
                                        alpha=self.alpha(name),
                                        precision=self.precision)
        return _apply(x, self._tables[name], cim_items=self.items,
                      alpha=self.alpha(name), n=int(w.shape[1]),
                      precision=self.precision)


# ------------------------------------------------------------- layers

def rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale


def rope(x, theta):
    """x (S, H, D), rotate-half layout, positions 0..S-1."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("c_items", "precision"))
def attention(q, k, v, *, c_items, precision):
    """Causal softmax attention of one sequence, q/k/v (S, H, D)."""
    c = dict(c_items)
    q, k = rope(q, c["rope_theta"]), rope(k, c["rope_theta"])
    rep = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    s = contract("qhd,khd->hqk", q, k, precision) / math.sqrt(q.shape[-1])
    causal = jnp.tril(jnp.ones((q.shape[0], q.shape[0]), bool))
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    return contract("hqk,khd->qhd", p, v, precision)


def each_layer(config: dict, params: Dict, x, deploy_key, precision: str,
               layer: Callable):
    """x through one uniform stack params["layers"], each layer on its own
    chip: x = layer(config, p, chip, x, precision)."""
    for li in range(config["num_hidden_layers"]):
        p = jax.tree_util.tree_map(lambda a: a[li], params["layers"])
        x = layer(config, p, Chip(config, p, chip_key(deploy_key, li),
                                  precision), x, precision)
    return x


def logits(config: dict, params: Dict, deploy_key,
           seqs: Sequence[np.ndarray], rows: Sequence[np.ndarray],
           precision: str = "highest") -> List[np.ndarray]:
    """Logits of each sequence at the given positions, as numpy (rows, V).

    seqs: token ids, one sequence per request (prompt then served tokens);
    rows: for each, the positions whose next-token logits are wanted.
    Sequences are padded to one length, so each program compiles once;
    padding sits after every wanted position and cannot reach it (causal
    attention, forward recurrence).
    """
    s_max = max(len(t) for t in seqs)
    s_pad = -(-s_max // ROW_BLOCK) * ROW_BLOCK
    toks = np.zeros((len(seqs), s_pad), np.int32)
    for i, t in enumerate(seqs):
        toks[i, :len(t)] = t
    x = params["embed"][jnp.asarray(toks)]
    x = family(config).layers(config, params, x, deploy_key, precision)
    out = []
    for i, r in enumerate(rows):
        xf = rms(x[i, np.asarray(r)], params["ln_f"], config["rms_norm_eps"])
        out.append(np.asarray(contract("sd,dv->sv", xf, params["unembed"],
                                       precision)))
    return out
