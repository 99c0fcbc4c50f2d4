"""A plain reference for GenQSGD training of a Qwen3 dense decoder.

Written from the published description, with no kernels, caches or
batching tricks, in float32 at ``jax.lax.Precision.HIGHEST``:

* Qwen3 (Hugging Face ``Qwen3ForCausalLM``): pre-norm RMSNorm blocks,
  grouped-query attention with a per-head RMSNorm on queries and keys,
  rotary embeddings (rotate-half form, base ``rope_theta``), causal
  softmax attention scaled by 1/sqrt(head_dim), a SwiGLU MLP, a final
  RMSNorm and an output head tied to the embedding;
* one GenQSGD round (the paper's Algorithm 1): every worker runs K local
  SGD steps from the global model, quantizes its normalized delta
  (x_K - x)/gamma per tensor with QSGD (s levels, L2 norm), the server
  averages the workers' quantized deltas, quantizes that mean with its own
  s0 and applies x + gamma * Q(mean).

QSGD's stochastic rounding draws its uniforms from the counter hash the
runtime's specification fixes: per tensor ``i`` of worker ``n`` a seed
mixed from the round key's words and salt ``i`` (``1000 + i`` on the
server), then murmur3's finalizer over each element's index.

Parameters are a pytree laid out as the program lays out its own
(``embed``, ``final_norm``, ``segments[0]`` with layer-stacked leaves), so
the benchmark can hand both the same weights; :func:`init_params` makes
them from a key.  ``matmul_dtype`` rounds every matmul operand to a lower
precision first: the precision control of the benchmark's check.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["Shapes", "init_params", "row_loss", "round_ref",
           "leaf_norms", "uniform_like", "seed_from"]

HIGHEST = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class Shapes:
    n_layers: int
    d_model: int
    n_heads: int
    n_kv: int
    d_head: int
    d_ff: int
    vocab: int
    rope_theta: float
    norm_eps: float

    @classmethod
    def from_hf(cls, m: dict) -> "Shapes":
        if m.get("model_type") != "qwen3" or not m["tie_word_embeddings"]:
            raise ValueError("the reference is a Qwen3 dense model with a "
                             "tied head")
        return cls(n_layers=int(m["num_hidden_layers"]),
                   d_model=int(m["hidden_size"]),
                   n_heads=int(m["num_attention_heads"]),
                   n_kv=int(m["num_key_value_heads"]),
                   d_head=int(m["head_dim"]),
                   d_ff=int(m["intermediate_size"]),
                   vocab=int(m["vocab_size"]),
                   rope_theta=float(m["rope_theta"]),
                   norm_eps=float(m["rms_norm_eps"]))


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------
def init_params(key, s: Shapes):
    """Seeded f32 weights: N(0, 1/fan_in) matrices, the embedding
    N(0, 1/d_model), norm weights 1."""
    L, D, H, KV, dh, F = (s.n_layers, s.d_model, s.n_heads, s.n_kv,
                          s.d_head, s.d_ff)
    ks = jax.random.split(key, 8)

    def dense(k, fan_in, shape):
        return jax.random.normal(k, shape, jnp.float32) / np.sqrt(fan_in)

    return {
        "embed": dense(ks[0], D, (s.vocab, D)),
        "final_norm": jnp.ones((D,), jnp.float32),
        "segments": [{
            "ln1": jnp.ones((L, D), jnp.float32),
            "ln2": jnp.ones((L, D), jnp.float32),
            "attn": {
                "wq": dense(ks[1], D, (L, D, H * dh)),
                "wk": dense(ks[2], D, (L, D, KV * dh)),
                "wv": dense(ks[3], D, (L, D, KV * dh)),
                "wo": dense(ks[4], H * dh, (L, H * dh, D)),
                "q_norm": jnp.ones((L, dh), jnp.float32),
                "k_norm": jnp.ones((L, dh), jnp.float32),
            },
            "mlp": {
                "w_gate": dense(ks[5], D, (L, D, F)),
                "w_up": dense(ks[6], D, (L, D, F)),
                "w_down": dense(ks[7], F, (L, F, D)),
            },
        }],
    }


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    """x: (S, heads, dh); rotate-half RoPE."""
    half = x.shape[-1] // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * inv          # (S, half)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _rounder(matmul_dtype):
    if matmul_dtype is None:
        return lambda a: a
    return lambda a: a.astype(matmul_dtype).astype(jnp.float32)


def row_loss(params, tokens, labels, s: Shapes, matmul_dtype=None):
    """Mean next-token cross entropy of one sequence: tokens, labels (S,)."""
    r = _rounder(matmul_dtype)

    def mm(a, b):
        return jnp.matmul(r(a), r(b), precision=HIGHEST)

    S = tokens.shape[0]
    G = s.n_heads // s.n_kv
    pos = jnp.arange(S)
    causal = pos[None, :] <= pos[:, None]
    h = params["embed"][tokens]
    seg = params["segments"][0]
    for l in range(s.n_layers):
        p = jax.tree.map(lambda a: a[l], seg)
        x = _rms(h, p["ln1"], s.norm_eps)
        q = mm(x, p["attn"]["wq"]).reshape(S, s.n_heads, s.d_head)
        k = mm(x, p["attn"]["wk"]).reshape(S, s.n_kv, s.d_head)
        v = mm(x, p["attn"]["wv"]).reshape(S, s.n_kv, s.d_head)
        q = _rope(_rms(q, p["attn"]["q_norm"], s.norm_eps), pos, s.rope_theta)
        k = _rope(_rms(k, p["attn"]["k_norm"], s.norm_eps), pos, s.rope_theta)
        k = jnp.repeat(k, G, axis=1)            # head h reads kv head h // G
        v = jnp.repeat(v, G, axis=1)
        sc = jnp.einsum("qhd,khd->hqk", r(q), r(k),
                        precision=HIGHEST) / np.sqrt(s.d_head)
        sc = jnp.where(causal[None], sc, -jnp.inf)
        w = jax.nn.softmax(sc, axis=-1)
        o = jnp.einsum("hqk,khd->qhd", r(w), r(v), precision=HIGHEST)
        h = h + mm(o.reshape(S, s.n_heads * s.d_head), p["attn"]["wo"])
        x = _rms(h, p["ln2"], s.norm_eps)
        y = jax.nn.silu(mm(x, p["mlp"]["w_gate"])) * mm(x, p["mlp"]["w_up"])
        h = h + mm(y, p["mlp"]["w_down"])
    h = _rms(h, params["final_norm"], s.norm_eps)
    logits = mm(h, params["embed"].T)
    gold = jnp.take_along_axis(logits, labels[:, None], -1)[:, 0]
    return jnp.mean(jax.nn.logsumexp(logits, -1) - gold)


@functools.partial(jax.jit, static_argnums=(4, 5, 6))
def _sgd_step(params, tokens, labels, gamma, s: Shapes, matmul_dtype,
              rows):
    """One local SGD step on the mean loss of the first ``rows``
    sequences: -> (mean loss, params - gamma * mean gradient).  The
    gradient is taken one sequence at a time and folded straight into the
    new parameters, so a full-vocabulary row fits beside them."""
    vg = jax.value_and_grad(row_loss)
    scale = gamma / rows

    def body(acc, tl):
        l, g = vg(params, tl[0], tl[1], s, matmul_dtype)
        new = jax.tree.map(lambda p, gg: p - scale * gg, acc[1], g)
        return (acc[0] + l, new), None

    (l, new), _ = jax.lax.scan(body, (jnp.zeros((), jnp.float32), params),
                               (tokens[:rows], labels[:rows]))
    return l / rows, new


# ---------------------------------------------------------------------------
# QSGD with the runtime's counter-hash uniforms
# ---------------------------------------------------------------------------
def _mix32(z):
    z = (z ^ (z >> 16)) * jnp.uint32(0x85EBCA6B)
    z = (z ^ (z >> 13)) * jnp.uint32(0xC2B2AE35)
    return z ^ (z >> 16)


def seed_from(key, salt: int):
    words = jnp.asarray(key).reshape(-1).astype(jnp.uint32)
    seed = jnp.uint32(salt * 0x9E3779B9 & 0xFFFFFFFF)
    for i in range(words.shape[0]):
        seed = _mix32(seed ^ words[i])
    return seed


def uniform_like(x, seed):
    n = int(np.prod(x.shape)) if x.shape else 1
    idx = jnp.arange(n, dtype=jnp.uint32).reshape(x.shape)
    z = idx * jnp.uint32(0x9E3779B9) + seed.astype(jnp.uint32)
    z = _mix32(_mix32(z) + jnp.uint32(0x27D4EB2F))
    return (z >> jnp.uint32(8)).astype(jnp.float32) * jnp.float32(2.0**-24)


@functools.partial(jax.jit, static_argnums=(1, 3))
def _qsgd(y, s_levels: int, key, salt: int):
    """Q(y; s) = norm * sign(y) * xi / s, xi the stochastic level drawn
    with the uniforms of (key, salt)."""
    u = uniform_like(y, seed_from(key, salt))
    norm = jnp.sqrt(jnp.sum(y * y))
    scaled = s_levels * jnp.abs(y) / jnp.where(norm > 0, norm, 1.0)
    base = jnp.floor(scaled)
    xi = base + (u < scaled - base).astype(jnp.float32)
    return jnp.sign(y) * xi * (norm / s_levels)


@jax.jit
def _delta(p, x, gamma):
    return (p - x) / gamma


@jax.jit
def _apply(x, q, gamma):
    return x + gamma * q


# ---------------------------------------------------------------------------
# one round
# ---------------------------------------------------------------------------
def round_ref(x_hat, tokens, labels, round_key, gamma: float, s0: int,
              sn: int, s: Shapes, matmul_dtype=None,
              rows: Optional[int] = None):
    """One GenQSGD round.  tokens/labels: (workers, K, B, S); every worker
    runs all K steps.  ``rows`` < B leaves the other rows of each batch out
    (a fault the benchmark's tests plant).  -> (x_new, mean local loss,
    worker 0's parameters after its first local step)."""
    n_workers, K, B = tokens.shape[:3]
    rows = B if rows is None else rows
    gamma = jnp.float32(gamma)
    keys = jax.random.split(round_key, n_workers + 1)
    leaves_hat, treedef = jax.tree.flatten(x_hat)
    mean, losses, first_step = None, [], None
    for n in range(n_workers):
        p = x_hat
        for k in range(K):
            l, p = _sgd_step(p, tokens[n, k], labels[n, k], gamma, s,
                             matmul_dtype, rows)
            if first_step is None:
                first_step = p
            losses.append(l)
        q = [_qsgd(_delta(a, b, gamma), sn, keys[n], i) for i, (a, b) in
             enumerate(zip(jax.tree.leaves(p), leaves_hat))]
        del p
        mean = q if mean is None else [m + v for m, v in zip(mean, q)]
    mean = [m / n_workers for m in mean]
    new = [_apply(xh, _qsgd(m, s0, keys[-1], 1000 + i), gamma)
           for i, (xh, m) in enumerate(zip(leaves_hat, mean))]
    return (jax.tree.unflatten(treedef, new), jnp.mean(jnp.stack(losses)),
            first_step)


def leaf_norms(a, b=None):
    """Per-leaf L2 norms of ``a`` (or of ``a - b``), in flatten order."""
    if b is None:
        return [jnp.sqrt(jnp.sum(jnp.square(x))) for x in jax.tree.leaves(a)]
    return [jnp.sqrt(jnp.sum(jnp.square(x - y)))
            for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))]
