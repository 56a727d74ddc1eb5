"""Random weights from a seed, made on the device in one jitted call.

The benchmark makes the weights, not the system under test: the same
function feeds the system (in the dtype it serves) and, after the system's
state is freed, the plain reference, which regenerates them from the seed.
The tree is laid out as the system's parameters are (layers stacked on a
leading axis); ``check_layout`` compares it with the system's abstract
parameters before a run.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Spec = Dict[str, Any]


def seed_key(seed: int, stream: int) -> jax.Array:
    """A PRNG key for ``(seed, stream)``; any non-negative seed, also one
    wider than 32 bits."""
    words = np.random.SeedSequence([int(seed), int(stream)]).generate_state(2)
    return jax.random.wrap_key_data(
        jnp.asarray(words, jnp.uint32), impl="threefry2x32"
    )


def tree_spec(cfg: Dict[str, Any]) -> Spec:
    """``{path: (shape, kind)}`` leaves, ``kind`` one of "matrix" (N(0,
    1/fan_in)), "embed" (N(0, initializer_range)), "bias" (N(0, 0.02)),
    "scale" (1 + N(0, 0.02))."""
    lay = cfg["layout"]
    L, D = cfg["num_hidden_layers"], cfg["hidden_size"]
    H, K = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    Dh = cfg.get("head_dim") or D // H
    F, V = cfg["intermediate_size"], cfg["vocab_size"]

    def norm(stacked):
        pre = (L,) if stacked else ()
        d = {"scale": (pre + (D,), "scale")}
        if lay["norm"] == "ln":
            d["bias"] = (pre + (D,), "bias")
        return d

    attn = {
        "wq": ((L, D, H * Dh), "matrix"), "wk": ((L, D, K * Dh), "matrix"),
        "wv": ((L, D, K * Dh), "matrix"), "wo": ((L, H * Dh, D), "matrix"),
    }
    if lay["qkv_bias"]:
        attn.update(bq=((L, H * Dh), "bias"), bk=((L, K * Dh), "bias"),
                    bv=((L, K * Dh), "bias"))
    if lay["o_bias"]:
        attn["bo"] = ((L, D), "bias")
    if lay["mlp"] == "swiglu":
        mlp = {"w_gate": ((L, D, F), "matrix"), "w_up": ((L, D, F), "matrix"),
               "w_down": ((L, F, D), "matrix")}
    else:
        mlp = {"w_up": ((L, D, F), "matrix"), "w_down": ((L, F, D), "matrix")}
        if lay["mlp_bias"]:
            mlp.update(b_up=((L, F), "bias"), b_down=((L, D), "bias"))
    tree = {
        "embed": {"table": ((V, D), "embed")},
        "layers": {"norm1": norm(True), "attn": attn, "norm2": norm(True),
                   "mlp": mlp},
        "final_norm": norm(False),
    }
    if not lay["tied"]:
        tree["lm_head"] = {"w": ((D, V), "embed")}
    return tree


def _leaves(spec: Spec, prefix: Tuple[str, ...] = ()):
    for k in sorted(spec):
        v = spec[k]
        if isinstance(v, dict):
            yield from _leaves(v, prefix + (k,))
        else:
            yield prefix + (k,), v


def make(cfg: Dict[str, Any], seed: int, dtype) -> Dict[str, Any]:
    """The weights of ``cfg`` for ``seed``, in ``dtype``, on the default
    device, from one compiled program."""
    spec = tree_spec(cfg)
    leaves = list(_leaves(spec))
    std_embed = float(cfg.get("initializer_range", 0.02))

    def build(key):
        out: Dict[str, Any] = {}
        for i, (path, (shape, kind)) in enumerate(leaves):
            k = jax.random.fold_in(key, i)
            z = jax.random.normal(k, shape, jnp.float32)
            if kind == "matrix":
                x = z / math.sqrt(shape[-2])
            elif kind == "embed":
                x = z * std_embed
            elif kind == "bias":
                x = z * 0.02
            else:
                x = 1.0 + z * 0.02
            node = out
            for p in path[:-1]:
                node = node.setdefault(p, {})
            node[path[-1]] = x.astype(dtype)
        return out

    return jax.jit(build)(seed_key(seed, 0))


def check_layout(params: Any, abstract: Any) -> None:
    """Refuse weights whose tree, shapes or dtypes differ from the system's
    abstract parameters."""
    got = jax.tree_util.tree_flatten_with_path(params)[0]
    want = jax.tree_util.tree_flatten_with_path(abstract)[0]
    gs = {jax.tree_util.keystr(p): (tuple(x.shape), str(x.dtype)) for p, x in got}
    ws = {jax.tree_util.keystr(p): (tuple(x.shape), str(x.dtype)) for p, x in want}
    if gs != ws:
        diff = sorted(set(gs.items()) ^ set(ws.items()))
        raise ValueError(f"weights do not match the system's layout: {diff[:8]}")
