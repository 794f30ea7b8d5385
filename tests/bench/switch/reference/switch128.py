"""Plain float32 reference of the program's Switch Transformer decoder
(``switch128``), with the weights of its layout (``layouts/
switch_transformers.py``, handed in as ``W``) and nothing of the program.

Per layer ``l`` on the residual stream ``h`` (positions 0..T-1), every
matrix product at ``Precision.HIGHEST``:

    a   = rmsnorm(h, norm1)                  rmsnorm(x, s) =
    q, k, v = rope(a Wq), rope(a Wk), a Wv     x / sqrt(mean(x^2) + eps)
    h  += softmax(q k^T / sqrt(hd), causal) v Wo     * (1 + s)
    m   = rmsnorm(h, norm2)
    h  += FFN(m)                   where l is dense
    h  += sum_k g_k FFN_{e_k}(m)   where l is MoE: top-k of m Wr, g a
                                   softmax over the k selected logits
    FFN(x) = gelu(x Win) Wout      (GELU's tanh form)

then ``logits = rmsnorm(h, final_norm) E^T`` with the embedding ``E``.
These are the program's equations; where they depart from the published
Switch Transformer (RoPE for its relative bias, GELU for its ReLU, RMSNorm
with ``1 + s``) the configuration file lists it.

The control is the same computation with every weight and activation
entering a matrix product rounded first: to float8 (e4m3, one scale per
tensor) with ``precision="fp8"``, to bfloat16 with ``"bf16"``.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
BUCKET = 64             # sequences padded to a multiple of this
ROWS = 16               # rows whose logits are read, padded likewise
F8_MAX = 448.0          # largest float8_e4m3fn


def _q(x, precision):
    if precision == "f32":
        return x
    if precision == "bf16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    if precision == "fp8":
        s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
        return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    raise ValueError(f"unknown precision {precision!r}")


def _mm(a, b, precision, spec="td,df->tf"):
    return jnp.einsum(spec, _q(a, precision), _q(b, precision), precision=HI)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + scale)


def _rope(x, theta):
    T, _, hd = x.shape
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None, None] * freqs
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    c, s = jnp.cos(ang), jnp.sin(ang)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _ffn(x, w_in, w_out, precision):
    return _mm(jax.nn.gelu(_mm(x, w_in, precision)), w_out, precision)


@partial(jax.jit, static_argnames=("W", "dims", "l", "precision"))
def _layer(key, h, *, W, dims, l: int, precision: str):
    p = W.layer(key, l, dims)
    T, hd = h.shape[0], dims.head_dim
    a = _rms(h, p["norm1"], dims.norm_eps)
    q = _rope(_mm(a, p["wq"], precision, "td,dhk->thk"), dims.rope_theta)
    k = _rope(_mm(a, p["wk"], precision, "td,dhk->thk"), dims.rope_theta)
    v = _mm(a, p["wv"], precision, "td,dhk->thk")
    s = jnp.einsum("qhd,khd->hqk", q, k, precision=HI) * hd ** -0.5
    causal = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(
        jnp.where(causal[None], s, -jnp.inf), axis=-1), v, precision=HI)
    h = h + _mm(o, p["wo"], precision, "thk,hkd->td")

    m = _rms(h, p["norm2"], dims.norm_eps)
    if not dims.is_moe(l):
        return h + _ffn(m, p["mlp_w_in"], p["mlp_w_out"], precision)
    logits = jnp.einsum("td,de->te", m, p["router"], precision=HI)
    top, idx = jax.lax.top_k(logits, dims.top_k)
    comb = jnp.sum(jax.nn.one_hot(idx, dims.experts)
                   * jax.nn.softmax(top, axis=-1)[..., None], 1)
    y = jnp.zeros_like(h)
    for e in range(dims.experts):
        w = W.expert(key, l, e, dims)
        y = y + comb[:, e:e + 1] * _ffn(m, w["w_in"], w["w_out"], precision)
    return h + y


@partial(jax.jit, static_argnames=("W", "dims", "precision"))
def _head(key, h, *, W, dims, precision: str):
    x = _rms(h, W.final_norm(key, dims), dims.norm_eps)
    return _mm(x, W.table(key, dims).astype(jnp.float32), precision,
               "td,vd->tv")


def logits(W, key, dims, tokens: np.ndarray, positions: np.ndarray,
           precision: str = "f32") -> np.ndarray:
    """Logits [len(positions), vocab] at ``positions`` of ``tokens``."""
    T = len(tokens)
    toks = np.zeros((-(-T // BUCKET) * BUCKET,), np.int32)
    toks[:T] = tokens
    h = W.table(key, dims).astype(jnp.float32)[jnp.asarray(toks)]
    for l in range(dims.layers):
        h = _layer(key, h, W=W, dims=dims, l=l, precision=precision)
    P = len(positions)
    pos = np.zeros((-(-P // ROWS) * ROWS,), np.int32)
    pos[:P] = positions
    out = _head(key, h[jnp.asarray(pos)], W=W, dims=dims,
                precision=precision)
    return np.asarray(out[:P])
