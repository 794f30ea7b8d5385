"""Plain float32 reference of the served Qwen1.5-MoE equations.

One sequence at a time, one layer at a time, in ``jax.numpy`` with every
matrix product at ``Precision.HIGHEST``; no cache, no kernels, no
batching, no capacity.  The weights are the benchmark's, regenerated here
from the seed by the configuration's layout (``layouts/qwen2_moe.py``,
handed in as ``W``); nothing is taken from the program.

Per layer, on the residual stream ``h`` (positions 0..T-1):

    a   = rmsnorm(h, norm1)                  rmsnorm(x, s) =
    q, k, v = a Wq, a Wk, a Wv                 x / sqrt(mean(x^2) + eps)
    q, k = rope(q), rope(k)                    * (1 + s)
    h  += softmax(q k^T / sqrt(hd), causal) v Wo
    m   = rmsnorm(h, norm2)
    top = top-k of m Wr; g = softmax over the k selected logits
    h  += sum_k g_k FFN_{e_k}(m) + FFN_shared(m)
    FFN(x) = (silu(x Wgate) * (x Win)) Wout

then ``logits = rmsnorm(h, final_norm) Whead^T`` over the whole
vocabulary.  RoPE rotates the two halves of each head (base
``rope_theta``).  These are the program's equations; where they depart
from the published model (the shared expert has no sigmoid gate, the
gate is a softmax over the k selected experts, no q/k/v bias) the
configuration file lists it under ``departures``.

The control is the same computation one precision below the served
one: with ``precision="fp8"`` (for a bfloat16 model) every weight and
every activation entering a matrix product is rounded to float8 (e4m3,
one scale per tensor) first; ``"bf16"`` (for a float32 model) rounds
them to bfloat16.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
# sequences are padded to a multiple of BUCKET (three lengths up to the
# chat mix's 4,608, each compiled once per checkout), the rows whose
# logits are read to a multiple of ROWS
BUCKET = 1536
ROWS = 512
Q_BLOCK = 512           # query rows per attention block
F8_MAX = 448.0          # largest float8_e4m3fn


def _q(x, precision):
    """``x`` rounded to ``precision`` (and back to float32)."""
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


def _attention(q, k, v, hd):
    T = q.shape[0]
    rep = q.shape[1] // k.shape[1]
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    kpos = jnp.arange(T)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK, 0)
        s = jnp.einsum("qhd,khd->hqk", qb, k, precision=HI) * hd ** -0.5
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.where(kpos[None, None, :] <= qpos[None, :, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HI)

    out = jax.lax.map(block, jnp.arange(T // Q_BLOCK))
    return out.reshape(T, *q.shape[1:])


@partial(jax.jit, static_argnames=("W", "dims", "precision"))
def _layer(key, l, h, *, W, dims, precision: str):
    p = W.layer(key, l, dims)
    hd = dims.head_dim
    a = _rms(h, p["norm1"], dims.norm_eps)
    q = _mm(a, p["wq"], precision, "td,dhk->thk")
    k = _mm(a, p["wk"], precision, "td,dhk->thk")
    v = _mm(a, p["wv"], precision, "td,dhk->thk")
    q, k = _rope(q, dims.rope_theta), _rope(k, dims.rope_theta)
    o = _attention(q, k, v, hd)
    h = h + _mm(o, p["wo"], precision, "thk,hkd->td")

    m = _rms(h, p["norm2"], dims.norm_eps)
    logits = jnp.einsum("td,de->te", m, p["router"], precision=HI)
    top, idx = jax.lax.top_k(logits, dims.top_k)
    gate = jax.nn.softmax(top, axis=-1)
    comb = jnp.sum(jax.nn.one_hot(idx, dims.experts) * gate[..., None], 1)

    def ffn(x, w_in, w_gate, w_out):
        g = _mm(x, w_gate, precision)
        u = _mm(x, w_in, precision)
        return _mm(jax.nn.silu(g) * u, w_out, precision)

    def expert(e, y):
        w = W.expert(key, l, e, dims)
        c = jax.lax.dynamic_slice_in_dim(comb, e, 1, axis=1)
        return y + c * ffn(m, w["w_in"], w["w_gate"], w["w_out"])

    y = jax.lax.fori_loop(0, dims.experts, expert, jnp.zeros_like(h))
    y = y + ffn(m, p["shared_w_in"], p["shared_w_gate"], p["shared_w_out"])
    return h + y


@partial(jax.jit, static_argnames=("W", "dims"))
def _embed(key, tokens, *, W, dims):
    return W.table(key, "embed", dims)[tokens].astype(jnp.float32)


@partial(jax.jit, static_argnames=("W", "dims", "precision"))
def _head(key, h, *, W, dims, precision: str):
    x = _rms(h, W.final_norm(key, dims), dims.norm_eps)
    head = W.table(key, "lm_head", dims).astype(jnp.float32)
    return _mm(x, head, precision, "td,vd->tv")


def logits(W, key, dims, tokens: np.ndarray, positions: np.ndarray,
           precision: str = "f32") -> np.ndarray:
    """Logits [len(positions), vocab] at ``positions`` of ``tokens``, with
    the weights of layout ``W`` (whose ``Dims`` ``dims`` is)."""
    T = len(tokens)
    Tp = -(-T // BUCKET) * BUCKET
    toks = np.zeros((Tp,), np.int32)
    toks[:T] = tokens
    h = _embed(key, jnp.asarray(toks), W=W, dims=dims)
    for l in range(dims.layers):
        h = _layer(key, jnp.int32(l), h, W=W, dims=dims, precision=precision)
    P = len(positions)
    pos = np.zeros((-(-P // ROWS) * ROWS,), np.int32)
    pos[:P] = positions
    out = _head(key, h[jnp.asarray(pos)], W=W, dims=dims,
                precision=precision)
    return np.asarray(out[:P])
