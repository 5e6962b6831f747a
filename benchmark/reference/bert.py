"""Plain float32 BERT pretraining: forward, MLM + NSP loss, gradients, Adam.

Devlin et al. 2018 as `google-bert/bert-base-uncased` configures it:
post-LN encoder, learned positions, token types, erf GELU, MLM head
(dense, GELU, LayerNorm, decoder with its own weight and bias) on the
masked positions only, NSP head on the tanh-pooled first position.  No
dropout (the cell states 0), no kernels, no batching tricks.  Imports
nothing of the program; parameters come as a dict of float32 arrays under
the names `benchmark/families/bert.py::param_spec` lists.

Rows are processed in blocks whose gradients are summed, so the full batch
fits beside the program's peak.
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


def _dense(p, name, x, precision):
    return linear(x, p[name + ".weight"], p[name + ".bias"], precision)


def forward(p, cfg, ids, token_types, valid_length, masked_positions,
            precision="float32"):
    """-> (mlm logits (b, m, V), nsp logits (b, 2))."""
    b, l = ids.shape
    h, nh = cfg["hidden_size"], cfg["num_attention_heads"]
    hd = h // nh
    eps = cfg["layer_norm_eps"]
    x = p["bert.word_embed.weight"][ids] \
        + p["bert.position_embed.weight"][jnp.arange(l)][None] \
        + p["bert.token_type_embed.weight"][token_types]
    x = layer_norm(x, p["bert.embed_norm.gamma"], p["bert.embed_norm.beta"],
                   eps)
    keep = (jnp.arange(l)[None, :] < valid_length[:, None])    # (b, l) keys
    for i in range(cfg["num_hidden_layers"]):
        pre = f"bert.layers.{i}."
        qkv = _dense(p, pre + "attention.attn_qkv", x, precision)
        q, k, v = (qkv[..., j * h:(j + 1) * h].reshape(b, l, nh, hd)
                   for j in range(3))
        s = jnp.einsum("bqnd,bknd->bnqk", q, k, precision=HI) / hd ** 0.5
        s = jnp.where(keep[:, None, None, :], s, -1e30)
        a = jax.nn.softmax(s, axis=-1)
        ctx = jnp.einsum("bnqk,bknd->bqnd", a, v, precision=HI)
        att = _dense(p, pre + "attention.attn_proj", ctx.reshape(b, l, h),
                     precision)
        x = layer_norm(x + att, p[pre + "attn_norm.gamma"],
                       p[pre + "attn_norm.beta"], eps)
        y = jax.nn.gelu(_dense(p, pre + "ffn_intermediate", x, precision),
                        approximate=False)
        y = _dense(p, pre + "ffn_output", y, precision)
        x = layer_norm(x + y, p[pre + "ffn_norm.gamma"],
                       p[pre + "ffn_norm.beta"], eps)
    pooled = jnp.tanh(_dense(p, "bert.pooler", x[:, 0], precision))
    seq = jnp.take_along_axis(x, masked_positions[..., None], axis=1)
    t = jax.nn.gelu(_dense(p, "mlm_dense", seq, precision), approximate=False)
    t = layer_norm(t, p["mlm_norm.gamma"], p["mlm_norm.beta"], eps)
    return (_dense(p, "mlm_decoder", t, precision),
            _dense(p, "nsp_classifier", pooled, precision))


def _xent_sum(logits, labels):
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.sum(lse - picked)


def loss_sum(p, cfg, batch, precision="float32"):
    """Sum over the block's rows of (mean MLM loss of the row) / 1 ... kept
    as two sums, so that blocks add up to the batch's two means."""
    ids, types, vlen, mpos, mlm_labels, nsp_labels = batch
    mlm, nsp = forward(p, cfg, ids, types, vlen, mpos, precision)
    return _xent_sum(mlm, mlm_labels), _xent_sum(nsp, nsp_labels)


def loss_and_grads(p, cfg, batch, block_rows: int, precision="float32"):
    """Loss = mean MLM cross-entropy over all masked positions + mean NSP
    cross-entropy over all rows, with its gradient, block by block."""
    rows, n_mask = batch[3].shape

    def block_loss(pp, blk):
        a, b = loss_sum(pp, cfg, blk, precision)
        return a / (rows * n_mask) + b / rows

    step = jax.jit(jax.value_and_grad(block_loss))
    total, grads = 0.0, None
    for r in range(0, rows, block_rows):
        blk = tuple(x[r:r + block_rows] for x in batch)
        val, g = step(p, blk)
        total = total + val
        grads = g if grads is None else jax.tree_util.tree_map(
            jnp.add, grads, g)
    return total, grads


def adam_update(p, grads, m, v, t, hp):
    """Adam (Kingma & Ba) with bias correction folded into the rate, as the
    configuration states it: no weight decay, no clipping."""
    b1, b2, eps, lr = hp["beta1"], hp["beta2"], hp["epsilon"], \
        hp["learning_rate"]
    lr_t = lr * (1 - b2 ** t) ** 0.5 / (1 - b1 ** t)

    @jax.jit
    def one(pp, gg, mm, vv):
        mm = jax.tree_util.tree_map(lambda a, g: b1 * a + (1 - b1) * g,
                                    mm, gg)
        vv = jax.tree_util.tree_map(lambda a, g: b2 * a + (1 - b2) * g * g,
                                    vv, gg)
        pp = jax.tree_util.tree_map(
            lambda w, a, c: w - lr_t * a / (jnp.sqrt(c) + eps), pp, mm, vv)
        return pp, mm, vv
    return one(p, grads, m, v)


def _stored(p, storage):
    """Round each leaf to the type it is stored in, in float32.
    `reduce_precision` and not a cast there and back: XLA drops such a pair
    of casts as excess precision, on the TPU and on the CPU alike."""
    def one(x, dtype):
        info = jnp.finfo(dtype)
        return jax.lax.reduce_precision(x, exponent_bits=info.nexp,
                                        mantissa_bits=info.nmant)
    return jax.jit(lambda t: {k: one(x, storage[k]) for k, x in t.items()})(p)


def train_steps(p0, cfg, batches, hp, block_rows: int,
                precision="float32", storage=None):
    """Follow `len(batches)` steps from `p0`.  Returns the losses, the first
    gradient and the parameters after the last step.

    `storage` maps a leaf to the type the configuration keeps it in between
    steps (bfloat16 weights with no float32 master copy): every product, sum
    and moment is float32, and the updated leaf is rounded to that type
    once, as a parameter stored in it must be.  Without it the leaves stay
    float32."""
    zeros = jax.tree_util.tree_map(jnp.zeros_like, p0)
    p, m, v = p0, zeros, zeros
    losses, first = [], None
    for t, batch in enumerate(batches, start=1):
        loss, grads = loss_and_grads(p, cfg, batch, block_rows, precision)
        if first is None:
            first = grads
        p, m, v = adam_update(p, grads, m, v, t, hp)
        if storage:
            p = _stored(p, storage)
        losses.append(float(loss))
    return {"losses": losses, "grad": first, "params": p}
