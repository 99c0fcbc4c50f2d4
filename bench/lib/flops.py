"""Model FLOPs of a dense decoder-only transformer, counted from its shapes.

The count is what the forward and backward passes require, with no
recomputation: 6 FLOPs per matmul weight per token (2 forward, 4 backward),
the tied head counted as a matmul (the embedding lookup is free), and
causal attention's score and value products, 6 * layers * heads * head_dim
* seq_len per token (12 per position pair, half of the pairs are masked).
Norm weights do no matmul and are left out.
"""
from __future__ import annotations

import dataclasses

__all__ = ["LMShapes", "lm_shapes", "matmul_params", "train_flops_per_token"]


@dataclasses.dataclass(frozen=True)
class LMShapes:
    """The shape fields of a dense LM, named as the program's ArchConfig
    names them, so that either can be passed to the functions below."""
    n_layers: int
    d_model: int
    n_heads: int
    n_kv: int
    d_head: int
    d_ff: int
    vocab: int
    tie_embeddings: bool = True


def lm_shapes(model: dict) -> LMShapes:
    """Shapes from a configuration file's ``model`` group (Hugging Face
    ``config.json`` key names)."""
    return LMShapes(
        n_layers=int(model["num_hidden_layers"]),
        d_model=int(model["hidden_size"]),
        n_heads=int(model["num_attention_heads"]),
        n_kv=int(model["num_key_value_heads"]),
        d_head=int(model["head_dim"]),
        d_ff=int(model["intermediate_size"]),
        vocab=int(model["vocab_size"]),
        tie_embeddings=bool(model["tie_word_embeddings"]))


def matmul_params(s) -> dict:
    """Matmul weights by part: ``head`` (the output projection, which is
    the embedding when tied), ``layers`` (attention and MLP), ``total``."""
    D, H, KV, dh, F = s.d_model, s.n_heads, s.n_kv, s.d_head, s.d_ff
    attn = D * H * dh + 2 * D * KV * dh + H * dh * D
    mlp = 3 * D * F
    layers = s.n_layers * (attn + mlp)
    head = s.vocab * D
    return {"head": head, "layers": layers, "total": head + layers}


def train_flops_per_token(s, seq_len: int) -> float:
    """Forward + backward FLOPs per trained token at context ``seq_len``."""
    attn = 6.0 * s.n_layers * s.n_heads * s.d_head * seq_len
    return 6.0 * matmul_params(s)["total"] + attn
