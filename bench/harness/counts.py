"""Operations and bytes that a decoder-only transformer step needs, from shapes.

"Needed" means what the request requires, whatever implements it: a decode
call reads the live context's K/V, never the null slots a block table pads
with, so a kernel that stops walking them earns its share and no reading
can pass 100%.  Matrix products count 2 operations per multiply-add.
Attention per query row and layer over ``ctx`` keys: ``4 * H * Dh * ctx``
(scores and the weighted sum).  Every function takes the configuration's
sizes as a ``Shapes``; bf16 storage is 2 bytes.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Iterable, Tuple


@dataclasses.dataclass(frozen=True)
class Shapes:
    n_layers: int
    d_model: int
    n_heads: int
    n_kv: int
    head_dim: int
    d_ff: int
    vocab: int
    gated_mlp: bool             # SwiGLU: three d x d_ff matrices, else two
    kv_bytes: int = 2
    act_bytes: int = 2

    @staticmethod
    def from_config(cfg: Dict[str, Any]) -> "Shapes":
        """From a configuration file's published (Hugging Face) keys."""
        d, h = cfg["hidden_size"], cfg["num_attention_heads"]
        return Shapes(
            n_layers=cfg["num_hidden_layers"],
            d_model=d,
            n_heads=h,
            n_kv=cfg["num_key_value_heads"],
            head_dim=cfg.get("head_dim") or d // h,
            d_ff=cfg["intermediate_size"],
            vocab=cfg["vocab_size"],
            gated_mlp=cfg["hidden_act"] == "silu",
        )


def layer_matmul_params(s: Shapes) -> int:
    """Weights one token multiplies through in one layer."""
    qo = 2 * s.d_model * s.n_heads * s.head_dim
    kv = 2 * s.d_model * s.n_kv * s.head_dim
    mlp = (3 if s.gated_mlp else 2) * s.d_model * s.d_ff
    return qo + kv + mlp


def attention_flops(s: Shapes, ctx: int) -> int:
    """One query row over ``ctx`` keys, one layer."""
    return 4 * s.n_heads * s.head_dim * ctx


def token_flops(s: Shapes, ctx: int, readout: bool) -> int:
    """Model operations of one token at context length ``ctx`` through every
    layer, plus the vocabulary readout when the token's logits are used."""
    f = s.n_layers * (2 * layer_matmul_params(s) + attention_flops(s, ctx))
    return f + (2 * s.d_model * s.vocab if readout else 0)


def prefill_chunk_flops(s: Shapes, q_start: int, q_len: int, last: bool) -> int:
    """Model operations of one prompt chunk (causal); ``last`` adds the one
    readout row that yields the first token."""
    attn = 4 * s.n_heads * s.head_dim * (q_len * q_start + q_len * (q_len + 1) // 2)
    f = s.n_layers * (2 * layer_matmul_params(s) * q_len + attn)
    return f + (2 * s.d_model * s.vocab if last else 0)


def decode_kernel(s: Shapes, contexts: Iterable[int]) -> Tuple[int, int]:
    """(operations, bytes) one paged decode attention call (one layer) needs
    for the live requests' context lengths: their K/V, q and the output."""
    ctx = list(contexts)
    flops = sum(attention_flops(s, c) for c in ctx)
    kv = sum(c * s.n_kv * s.head_dim * 2 * s.kv_bytes for c in ctx)
    qo = len(ctx) * s.n_heads * s.head_dim * 2 * s.act_bytes
    return flops, kv + qo


def prefill_kernel(s: Shapes, q_start: int, q_len: int) -> Tuple[int, int]:
    """(operations, bytes) one chunked paged prefill attention call (one
    layer) needs: causal scores over the context so far, the K/V of
    ``q_start + q_len`` positions, q and the output."""
    flops = 4 * s.n_heads * s.head_dim * (q_len * q_start + q_len * (q_len + 1) // 2)
    kv = (q_start + q_len) * s.n_kv * s.head_dim * 2 * s.kv_bytes
    qo = q_len * s.n_heads * s.head_dim * 2 * s.act_bytes
    return flops, kv + qo
