"""Plain float32 forward of a decoder-only transformer (Llama / Qwen2).

Written from the published architecture, in plain ``jax.numpy``, with no
cache, no kernels and no batching: token embedding; per layer RMSNorm,
Q/K/V projections (with bias where the configuration has it), rotary
embeddings (``rotate_half`` form, ``inv_freq = theta^(-2i/d)``), causal
grouped-query attention, output projection, residual, RMSNorm, SwiGLU MLP,
residual; final RMSNorm and the (tied) unembedding.  Every matrix product
runs at ``Precision.HIGHEST``.  It imports nothing of the program.

``precision="fp8"`` is the control: every matrix product's operands are
rounded to float8 e4m3 (scaled per row of the left operand and per column
of the right, as an fp8 serving path would be) before a float32 product.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
E4M3_MAX = 448.0


def _fp8(x, axis):
    """x rounded to e4m3 with one scale per slice along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    s = jnp.where(amax > 0, amax / E4M3_MAX, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(spec, a, b, fp8: bool):
    """``einsum(spec, a, b)`` in float32; in fp8 the operands are rounded
    along their contracted axis first."""
    if fp8:
        ins = spec.split("->")[0].split(",")
        shared = set(ins[0]) & set(ins[1])
        a = _fp8(a, tuple(i for i, c in enumerate(ins[0]) if c in shared))
        b = _fp8(b, tuple(i for i, c in enumerate(ins[1]) if c in shared))
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """Rotary embedding of x (S, heads, hd), position = row index."""
    S, _, hd = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None]
    emb = jnp.concatenate([ang, ang], -1)[:, None, :]
    half = hd // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * jnp.cos(emb) + rot * jnp.sin(emb)


def _layer(c, fp8, x, w):
    w = jax.tree.map(lambda a: a.astype(jnp.float32), w)
    S = x.shape[0]
    H, KV = c["num_attention_heads"], c["num_key_value_heads"]
    hd = c.get("head_dim") or c["hidden_size"] // H
    eps, theta = c["rms_norm_eps"], c["rope_theta"]
    h = _rmsnorm(x, w["ln1"], eps)
    q = _mm("sd,de->se", h, w["wq"], fp8)
    k = _mm("sd,de->se", h, w["wk"], fp8)
    v = _mm("sd,de->se", h, w["wv"], fp8)
    if "bq" in w:
        q, k, v = q + w["bq"], k + w["bk"], v + w["bv"]
    q = _rope(q.reshape(S, H, hd), theta)
    k = _rope(k.reshape(S, KV, hd), theta)
    v = v.reshape(S, KV, hd)
    q = q.reshape(S, KV, H // KV, hd)
    s = _mm("qkgd,tkd->kgqt", q, k, fp8) / jnp.sqrt(jnp.float32(hd))
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    s = jnp.where(causal, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = _mm("kgqt,tkd->qkgd", p, v, fp8).reshape(S, H * hd)
    x = x + _mm("se,ed->sd", o, w["wo"], fp8)
    h = _rmsnorm(x, w["ln2"], eps)
    g = _mm("sd,df->sf", h, w["w_gate"], fp8)
    u = _mm("sd,df->sf", h, w["w_up"], fp8)
    x = x + _mm("sf,fd->sd", jax.nn.silu(g) * u, w["w_down"], fp8)
    return x, None


@functools.partial(jax.jit, static_argnames=("config", "precision"))
def _logits(weights, tokens, config, precision):
    c = dict(config)
    fp8 = precision == "fp8"
    emb = weights["embed"].astype(jnp.float32)
    x = emb[tokens]
    x, _ = jax.lax.scan(functools.partial(_layer, c, fp8), x,
                        weights["layers"])
    x = _rmsnorm(x, weights["final_norm"].astype(jnp.float32),
                 c["rms_norm_eps"])
    head = weights.get("head", weights["embed"]).astype(jnp.float32)
    return _mm("sd,vd->sv", x, head, fp8)


def logits(weights, tokens, config: dict, precision: str = "float32"):
    """Next-token logits (S, V) float32 after each position of ``tokens``.

    ``config`` holds the sizes in Hugging Face ``config.json`` naming;
    ``weights`` the arrays under the names
    ``embed, final_norm, [head], layers/{ln1, ln2, wq, wk, wv, wo,
    [bq, bk, bv], w_gate, w_up, w_down}`` with a leading layer axis.
    """
    if precision not in ("float32", "fp8"):
        raise ValueError(f"precision {precision!r}")
    keys = ("num_attention_heads", "num_key_value_heads", "head_dim",
            "hidden_size", "rms_norm_eps", "rope_theta")
    frozen = tuple((k, config.get(k)) for k in keys)
    return _logits(weights, jnp.asarray(tokens, jnp.int32), frozen,
                   precision)
