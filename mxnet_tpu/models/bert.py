"""BERT (flagship benchmark model — BASELINE.json north-star config #3:
"BERT-base pretraining (GluonNLP, KVStore data-parallel → ICI all-reduce)").

Gluon-style HybridBlocks; attention lowers to the fused multi-head attention
op (Pallas flash kernel on TPU, `mxnet_tpu/ops/attention.py`). Layer naming
matches `parallel.sharding.default_tp_rules` so tensor parallelism works by
annotation alone; sequence parallelism slots in by swapping the attention op
for `parallel.ring_attention` (see `parallel/ring_attention.py`).
"""
from __future__ import annotations

import math
from typing import Optional

import jax

from ..gluon import nn
from ..gluon.block import HybridBlock
from .layers import FusedSelfAttention, check_max_position
from .. import numpy as np
from .. import numpy_extension as npx

__all__ = ["BertConfig", "BertModel", "BertForPretraining", "bert_base",
           "bert_large"]


class BertConfig:
    def __init__(self, vocab_size=30522, hidden_size=768, num_layers=12,
                 num_heads=12, intermediate_size=3072, max_position=512,
                 type_vocab_size=2, dropout=0.1, layer_norm_eps=1e-12,
                 dtype="float32", remat=False, window=None):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_layers = num_layers
        self.num_heads = num_heads
        self.intermediate_size = intermediate_size
        self.max_position = max_position
        self.type_vocab_size = type_vocab_size
        self.dropout = dropout
        self.layer_norm_eps = layer_norm_eps
        self.dtype = dtype
        # recompute each layer's activations in backward (jax.checkpoint)
        # — the long-sequence memory knob (docs/performance.md)
        self.remat = remat
        # Longformer-style symmetric sliding-window attention ([q-w, q+w]):
        # O(L·window) in the fused flash kernel — the long-document knob
        if window is not None and window < 1:
            raise ValueError(f"window must be >= 1, got {window}")
        self.window = window


def bert_base(**kwargs):
    return BertConfig(**kwargs)


def bert_large(**kwargs):
    cfg = dict(hidden_size=1024, num_layers=24, num_heads=16,
               intermediate_size=4096)
    cfg.update(kwargs)
    return BertConfig(**cfg)


class BertSelfAttention(FusedSelfAttention):
    """Back-compat shim over the shared fused-QKV block (models/layers.py):
    accepts both the original `(cfg)` constructor + `attn_mask` keyword and
    the shared `(hidden_size, num_heads, ...)` + `mask` surface."""

    def __init__(self, cfg_or_hidden, *args, **kwargs):
        if isinstance(cfg_or_hidden, BertConfig):
            cfg = cfg_or_hidden
            super().__init__(cfg.hidden_size, cfg.num_heads,
                             dropout=cfg.dropout, dtype=cfg.dtype,
                             window=getattr(cfg, "window", None))
        else:
            super().__init__(cfg_or_hidden, *args, **kwargs)

    def forward(self, x, attn_mask=None, mask=None):
        return super().forward(x, mask=mask if mask is not None
                               else attn_mask)


class BertLayer(HybridBlock):
    def __init__(self, cfg: BertConfig):
        super().__init__()
        self.attention = FusedSelfAttention(cfg.hidden_size,
                                            cfg.num_heads,
                                            dropout=cfg.dropout,
                                            dtype=cfg.dtype,
                                            window=getattr(cfg, "window",
                                                           None))
        self.attn_norm = nn.LayerNorm(epsilon=cfg.layer_norm_eps,
                                      in_channels=cfg.hidden_size)
        self.ffn_intermediate = nn.Dense(cfg.intermediate_size,
                                         in_units=cfg.hidden_size,
                                         flatten=False, dtype=cfg.dtype)
        self.ffn_output = nn.Dense(cfg.hidden_size,
                                   in_units=cfg.intermediate_size,
                                   flatten=False, dtype=cfg.dtype)
        self.ffn_norm = nn.LayerNorm(epsilon=cfg.layer_norm_eps,
                                     in_channels=cfg.hidden_size)
        self.dropout = nn.Dropout(cfg.dropout)

    def forward(self, x, attn_mask=None):
        # `jax.named_scope`s (trace-time only): stable `mx.*` block
        # names in the compiled step's op metadata
        with jax.named_scope("mx.attn"):
            a = self.attention(x, attn_mask)
        with jax.named_scope("mx.norm"):
            x = self.attn_norm(x + a)
        with jax.named_scope("mx.ffn"):
            y = npx.gelu(self.ffn_intermediate(x))
            y = self.dropout(self.ffn_output(y))
        with jax.named_scope("mx.norm"):
            return self.ffn_norm(x + y)


class BertModel(HybridBlock):
    def __init__(self, cfg: BertConfig):
        super().__init__()
        self.cfg = cfg
        self.word_embed = nn.Embedding(cfg.vocab_size, cfg.hidden_size,
                                       dtype=cfg.dtype)
        self.token_type_embed = nn.Embedding(cfg.type_vocab_size,
                                             cfg.hidden_size, dtype=cfg.dtype)
        self.position_embed = nn.Embedding(cfg.max_position, cfg.hidden_size,
                                           dtype=cfg.dtype)
        self.embed_norm = nn.LayerNorm(epsilon=cfg.layer_norm_eps,
                                       in_channels=cfg.hidden_size)
        self.embed_dropout = nn.Dropout(cfg.dropout)
        self.layers = nn.HybridSequential()
        for _ in range(cfg.num_layers):
            self.layers.add(BertLayer(cfg))
        self.pooler = nn.Dense(cfg.hidden_size, in_units=cfg.hidden_size,
                               activation="tanh", flatten=False,
                               dtype=cfg.dtype)

    def forward(self, input_ids, token_types=None, valid_length=None):
        b, l = input_ids.shape
        check_max_position(l, self.cfg.max_position)
        with jax.named_scope("mx.embed"):
            pos = npx.arange_like(input_ids, axis=1).astype("int32")
            x = self.word_embed(input_ids)
            x = x + self.position_embed(pos.reshape(1, l))
            if token_types is not None:
                x = x + self.token_type_embed(token_types)
            x = self.embed_dropout(self.embed_norm(x))

        mask = None
        if valid_length is not None:
            steps = npx.arange_like(input_ids, axis=1)
            mask = (steps.reshape(1, 1, l) <
                    valid_length.reshape(b, 1, 1)).astype("float32")
            mask = mask.reshape(b, 1, 1, l)

        # remat knob: False/True or a named jax.checkpoint policy
        # string ("dots_saveable", ...); MXTPU_REMAT_POLICY overrides —
        # the export-time remat search writes its winner through here
        remat_on, remat_pol = npx.resolve_remat_policy(
            getattr(self.cfg, "remat", False))
        for layer in self.layers:
            if remat_on:
                x = npx.remat_call(
                    lambda t, _l=layer, _m=mask: _l(t, _m), x,
                    policy=remat_pol)
            else:
                x = layer(x, mask)
        with jax.named_scope("mx.pooler"):
            pooled = self.pooler(x[:, 0])
        return x, pooled


class BertForPretraining(HybridBlock):
    """MLM + NSP heads (GluonNLP BERTForPretrain parity).

    Like the reference pretraining decode path, the MLM head can run on
    `masked_positions` only — the (batch, num_masked) indices of the [MASK]
    slots. Pretraining masks ~15% of tokens, so gathering before the
    hidden→vocab projection cuts the head's matmul and softmax work ~6x;
    on TPU the full-sequence head is HBM-bandwidth-bound (the fp32
    (tokens, vocab) softmax), so this is the difference between the MXU
    idling and not. Omit `masked_positions` to score every position."""

    def __init__(self, cfg: BertConfig):
        super().__init__()
        self.bert = BertModel(cfg)
        self.mlm_dense = nn.Dense(cfg.hidden_size, in_units=cfg.hidden_size,
                                  flatten=False, dtype=cfg.dtype)
        self.mlm_norm = nn.LayerNorm(epsilon=cfg.layer_norm_eps,
                                     in_channels=cfg.hidden_size)
        self.mlm_decoder = nn.Dense(cfg.vocab_size, in_units=cfg.hidden_size,
                                    flatten=False, dtype=cfg.dtype)
        self.nsp_classifier = nn.Dense(2, in_units=cfg.hidden_size,
                                       dtype=cfg.dtype)

    def forward(self, input_ids, token_types=None, valid_length=None,
                masked_positions=None):
        seq, pooled = self.bert(input_ids, token_types, valid_length)
        with jax.named_scope("mx.mlm_head"):
            if masked_positions is not None:
                # (b, l, h) -> (b, m, h) gather of the masked slots
                seq = np.take_along_axis(
                    seq,
                    np.expand_dims(masked_positions.astype("int32"), -1),
                    axis=1)
            mlm = self.mlm_decoder(
                self.mlm_norm(npx.gelu(self.mlm_dense(seq))))
        with jax.named_scope("mx.nsp_head"):
            nsp = self.nsp_classifier(pooled)
        return mlm, nsp

    @staticmethod
    def flops_per_token(cfg: BertConfig, seq_len: int,
                        mask_frac: float = 1.0) -> float:
        """Training FLOPs/token (fwd+bwd ≈ 6·params + attention terms).
        `mask_frac` scales the MLM-head term when the head runs on masked
        positions only (`masked_positions`): 20/128 for phase-1 pretrain."""
        h, l, i = cfg.hidden_size, cfg.num_layers, cfg.intermediate_size
        per_layer = 4 * h * h + 2 * h * i  # qkv+proj + ffn (matmul mults)
        embed = 0  # lookups are bandwidth, not FLOPs
        mlm = (cfg.vocab_size * h + h * h) * mask_frac
        params_matmul = l * per_layer + mlm
        # windowed attention touches min(L, 2w+1) keys per query, not L
        w = getattr(cfg, "window", None)
        kv_span = seq_len if w is None else min(seq_len, 2 * w + 1)
        attn = l * 2 * kv_span * h  # QK^T + PV per token
        return 6.0 * (params_matmul + attn)
