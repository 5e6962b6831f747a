"""Plain float32 GPT-2: the full forward pass over whole sequences.

Radford et al. 2019 as `openai-community/gpt2` configures it: pre-LN
decoder, learned positions, fused qkv, causal attention, tanh GELU
(`gelu_new`), final LayerNorm, the head tied to the token table.  No cache,
no paging, no chunks: every position attends to all earlier ones in one
pass.  Imports nothing of the program; parameters come as a dict of float32
arrays under the names `benchmark/families/gpt2.py::param_spec` lists.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .lowprec import linear

HI = jax.lax.Precision.HIGHEST


def layer_norm(x, g, b, eps):
    m = x.mean(-1, keepdims=True)
    v = ((x - m) ** 2).mean(-1, keepdims=True)
    return (x - m) * jax.lax.rsqrt(v + eps) * g + b


def hidden_states(p, cfg, ids, precision="float32"):
    """ids (b, l) -> final-normed hidden states (b, l, h)."""
    b, l = ids.shape
    h, nh = cfg["n_embd"], cfg["n_head"]
    hd = h // nh
    eps = cfg["layer_norm_epsilon"]
    x = p["transformer.word_embed.weight"][ids] \
        + p["transformer.position_embed.weight"][jnp.arange(l)][None]
    causal = jnp.tril(jnp.ones((l, l), bool))
    for i in range(cfg["n_layer"]):
        pre = f"transformer.layers.{i}."
        a = layer_norm(x, p[pre + "attn_norm.gamma"],
                       p[pre + "attn_norm.beta"], eps)
        qkv = linear(a, p[pre + "attention.attn_qkv.weight"],
                     p[pre + "attention.attn_qkv.bias"], precision)
        q, k, v = (qkv[..., j * h:(j + 1) * h].reshape(b, l, nh, hd)
                   for j in range(3))
        s = jnp.einsum("bqnd,bknd->bnqk", q, k, precision=HI) / hd ** 0.5
        s = jnp.where(causal[None, None], s, -1e30)
        ctx = jnp.einsum("bnqk,bknd->bqnd", jax.nn.softmax(s, axis=-1), v,
                         precision=HI)
        x = x + linear(ctx.reshape(b, l, h),
                       p[pre + "attention.attn_proj.weight"],
                       p[pre + "attention.attn_proj.bias"], precision)
        f = layer_norm(x, p[pre + "ffn_norm.gamma"],
                       p[pre + "ffn_norm.beta"], eps)
        f = jax.nn.gelu(linear(f, p[pre + "ffn.ffn_intermediate.weight"],
                               p[pre + "ffn.ffn_intermediate.bias"],
                               precision), approximate=True)
        x = x + linear(f, p[pre + "ffn.ffn_output.weight"],
                       p[pre + "ffn.ffn_output.bias"], precision)
    return layer_norm(x, p["transformer.final_norm.gamma"],
                      p["transformer.final_norm.beta"], eps)


def logits_at(p, cfg, ids, positions, precision="float32"):
    """Logits (b, k, V) at `positions` (b, k) of each row."""
    hs = hidden_states(p, cfg, ids, precision)
    rows = jnp.take_along_axis(hs, positions[..., None], axis=1)
    return linear(rows, p["transformer.word_embed.weight"], None, precision)
