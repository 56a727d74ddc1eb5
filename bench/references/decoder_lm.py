"""Plain reference of a decoder-only transformer LM, in float32.

Follows the published description (Hugging Face ``Qwen2ForCausalLM`` and
``Starcoder2ForCausalLM``): token embedding; per layer a pre-norm (RMSNorm
or LayerNorm), grouped-query causal attention with rotary embeddings
(``rotate_half`` form, ``inv_freq = theta ** (-2i / Dh)``) and optional q/k/v
and o biases, a residual add, a pre-norm MLP (SwiGLU, or GeLU with the tanh
approximation and biases) and a residual add; a final norm; the readout
through the tied embedding or an untied head.  Which of those a
configuration has is its file's ``layout``.

It imports nothing of the system under test.  Weights come from the
benchmark's own generator.  Every matrix product runs at
``Precision.HIGHEST`` on float32 copies of the served weights; attention is
computed in blocks of query rows so a 4k context fits beside the weights.

``precision="fp8"`` is the control: the same arithmetic with every matrix
product's operands rounded to float8 e4m3 (one scale per tensor, f32
accumulation) — the step below the configuration's bfloat16 that would
tempt a later change.
"""
from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
Q_BLOCK = 512
_F8_MAX = 448.0


def _fp8(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / _F8_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(spec, a, b, fp8):
    if fp8:
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _norm(lay, p, x):
    eps = lay["norm_eps"]
    if lay["norm"] == "rms":
        y = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
        return y * p["scale"]
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def _rope(x, pos, theta):
    dh = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))
    ang = pos.astype(jnp.float32)[:, None] * inv          # (S, Dh/2)
    cos = jnp.cos(jnp.concatenate([ang, ang], -1))[:, None, :]
    sin = jnp.sin(jnp.concatenate([ang, ang], -1))[:, None, :]
    x1, x2 = x[..., : dh // 2], x[..., dh // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def _attention(q, k, v, fp8):
    """Causal GQA.  q (S, H, Dh), k/v (S, K, Dh) -> (S, H, Dh)."""
    S, H, Dh = q.shape
    K = k.shape[1]
    G = H // K
    qb = q.reshape(S // Q_BLOCK, Q_BLOCK, K, G, Dh)
    cols = jnp.arange(S)

    def block(args):
        i, qi = args                                       # (QB, K, G, Dh)
        s = _mm("qkgd,tkd->kgqt", qi, k, fp8) / jnp.sqrt(jnp.float32(Dh))
        rows = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.where(cols[None, :] <= rows[:, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return _mm("kgqt,tkd->qkgd", p, v, fp8)

    out = jax.lax.map(block, (jnp.arange(S // Q_BLOCK), qb))
    return out.reshape(S, H, Dh)


@functools.lru_cache(maxsize=None)
def _program(cfg_json: str, s_pad: int, r_pad: int, fp8: bool):
    import json

    cfg = json.loads(cfg_json)
    lay = cfg["layout"]
    D, H = cfg["hidden_size"], cfg["num_attention_heads"]
    K = cfg["num_key_value_heads"]
    Dh = cfg.get("head_dim") or D // H
    theta = float(cfg["rope_theta"])

    def layer(h, p):
        p = jax.tree.map(lambda x: x.astype(jnp.float32), p)
        pos = jnp.arange(s_pad)
        x = _norm(lay, p["norm1"], h)
        a = p["attn"]
        q = _mm("sd,dh->sh", x, a["wq"], fp8)
        k = _mm("sd,dh->sh", x, a["wk"], fp8)
        v = _mm("sd,dh->sh", x, a["wv"], fp8)
        if lay["qkv_bias"]:
            q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
        q = _rope(q.reshape(s_pad, H, Dh), pos, theta)
        k = _rope(k.reshape(s_pad, K, Dh), pos, theta)
        ctx = _attention(q, k, v.reshape(s_pad, K, Dh), fp8)
        o = _mm("sh,hd->sd", ctx.reshape(s_pad, H * Dh), a["wo"], fp8)
        if lay["o_bias"]:
            o = o + a["bo"]
        h = h + o
        x = _norm(lay, p["norm2"], h)
        m = p["mlp"]
        if lay["mlp"] == "swiglu":
            g = _mm("sd,df->sf", x, m["w_gate"], fp8)
            u = _mm("sd,df->sf", x, m["w_up"], fp8)
            y = _mm("sf,fd->sd", jax.nn.silu(g) * u, m["w_down"], fp8)
        else:
            u = _mm("sd,df->sf", x, m["w_up"], fp8)
            if lay["mlp_bias"]:
                u = u + m["b_up"]
            y = _mm("sf,fd->sd", jax.nn.gelu(u, approximate=True), m["w_down"], fp8)
            if lay["mlp_bias"]:
                y = y + m["b_down"]
        return h + y, None

    def forward(w, tokens, rows):
        h = w["embed"]["table"][tokens].astype(jnp.float32)
        h, _ = jax.lax.scan(layer, h, w["layers"])
        fn = jax.tree.map(lambda x: x.astype(jnp.float32), w["final_norm"])
        hr = _norm(lay, fn, h[rows])
        if lay["tied"]:
            return _mm("rd,vd->rv", hr, w["embed"]["table"].astype(jnp.float32), fp8)
        return _mm("rd,dv->rv", hr, w["lm_head"]["w"].astype(jnp.float32), fp8)

    return jax.jit(forward)


def logits_rows(cfg: Dict[str, Any], weights: Any, tokens, rows, *,
                s_pad: int, r_pad: int, precision: str = "f32") -> np.ndarray:
    """float32 logits at positions ``rows`` of the sequence ``tokens``
    (each row predicts the token after it).  ``tokens`` is zero-padded to
    ``s_pad`` (a multiple of 512; causal attention keeps the padding out of
    every real row) and ``rows`` to ``r_pad``."""
    import json

    tokens = np.asarray(tokens, np.int32)
    rows = np.asarray(rows, np.int32)
    if len(tokens) > s_pad or len(rows) > r_pad or s_pad % Q_BLOCK:
        raise ValueError(f"bad padding: {len(tokens)}/{s_pad}, {len(rows)}/{r_pad}")
    t = np.zeros(s_pad, np.int32)
    t[: len(tokens)] = tokens
    r = np.zeros(r_pad, np.int32)
    r[: len(rows)] = rows
    fn = _program(json.dumps(cfg, sort_keys=True), s_pad, r_pad,
                  precision == "fp8")
    out = fn(weights, jnp.asarray(t), jnp.asarray(r))
    return np.asarray(out)[: len(rows)]
