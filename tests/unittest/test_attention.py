"""Attention ops (parity: the reference's transformer kernels
`src/operator/contrib/transformer.cc:675-1095` re-imagined as fused
attention; numerics checked against a NumPy softmax reference)."""
import numpy as onp
import pytest

import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu.ops.attention import (reference_attention,
                                     multi_head_attention)
from mxnet_tpu.test_utils import assert_almost_equal


def _np_attention(q, k, v, causal=False, mask=None):
    d = q.shape[-1]
    s = onp.einsum("bhqd,bhkd->bhqk", q, k) / onp.sqrt(d)
    if causal:
        lq, lk = s.shape[-2], s.shape[-1]
        cm = onp.tril(onp.ones((lq, lk), bool), k=lk - lq)
        s = onp.where(cm, s, -onp.inf)
    if mask is not None:
        s = onp.where(mask, s, -onp.inf)
    p = onp.exp(s - s.max(-1, keepdims=True))
    p = p / p.sum(-1, keepdims=True)
    return onp.einsum("bhqk,bhkd->bhqd", p, v)


def test_reference_attention_numerics():
    onp.random.seed(0)
    q = onp.random.normal(size=(2, 3, 8, 4)).astype(onp.float32)
    k = onp.random.normal(size=(2, 3, 10, 4)).astype(onp.float32)
    v = onp.random.normal(size=(2, 3, 10, 4)).astype(onp.float32)
    got = reference_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
    assert_almost_equal(onp.asarray(got), _np_attention(q, k, v),
                        rtol=1e-5, atol=1e-5)


def test_reference_attention_causal():
    onp.random.seed(1)
    q = onp.random.normal(size=(1, 2, 6, 4)).astype(onp.float32)
    k = onp.random.normal(size=(1, 2, 6, 4)).astype(onp.float32)
    v = onp.random.normal(size=(1, 2, 6, 4)).astype(onp.float32)
    got = reference_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                              causal=True)
    assert_almost_equal(onp.asarray(got), _np_attention(q, k, v, causal=True),
                        rtol=1e-5, atol=1e-5)


def test_multi_head_attention_op():
    onp.random.seed(2)
    b, l, e, h = 2, 6, 12, 3
    q = onp.random.normal(size=(b, l, e)).astype(onp.float32)
    out = multi_head_attention(mx.np.array(q), mx.np.array(q), mx.np.array(q),
                               num_heads=h)
    assert out.shape == (b, l, e)
    hd = e // h
    qh = q.reshape(b, l, h, hd).transpose(0, 2, 1, 3)
    want = _np_attention(qh, qh, qh).transpose(0, 2, 1, 3).reshape(b, l, e)
    assert_almost_equal(onp.asarray(out), want, rtol=1e-4, atol=1e-5)


def test_mha_gradient():
    q = mx.np.array(onp.random.normal(size=(1, 4, 8)).astype(onp.float32))
    q.attach_grad()
    with mx.autograd.record():
        y = multi_head_attention(q, q, q, num_heads=2).sum()
    y.backward()
    assert float(abs(q.grad).sum()) > 0


def test_sliding_window_attention_ops():
    """`_contrib_sldwin_atten_*` parity surface ((B*H, L, D) layout)."""
    b, l, h, d, w = 1, 8, 2, 4, 2
    q = mx.np.array(onp.random.normal(size=(b * h, l, d)).astype(onp.float32))
    k = mx.np.array(onp.random.normal(size=(b * h, l, d)).astype(onp.float32))
    v = mx.np.array(onp.random.normal(size=(b * h, l, d)).astype(onp.float32))
    score = mx.npx.sldwin_atten_score(q, k, dilation=1, w=w, symmetric=True)
    assert score.shape == (b * h, l, 2 * w + 1)
    valid = mx.np.array(onp.full((b,), l, onp.int32))
    mask = mx.npx.sldwin_atten_mask_like(score, 1, valid, num_heads=h,
                                         w=w, symmetric=True)
    assert mask.shape == score.shape
    ctx = mx.npx.sldwin_atten_context(score * mask, v, dilation=1, w=w,
                                      symmetric=True)
    assert ctx.shape == (b * h, l, d)


def test_masked_softmax():
    x = onp.random.normal(size=(2, 4)).astype(onp.float32)
    m = onp.array([[1, 1, 0, 0], [1, 1, 1, 1]], bool)
    got = mx.npx.masked_softmax(mx.np.array(x), mx.np.array(m))
    gv = onp.asarray(got)
    assert abs(gv[0, :2].sum() - 1) < 1e-5
    assert gv[0, 2:].sum() == 0
    assert abs(gv[1].sum() - 1) < 1e-5


def test_flash_kernel_error_propagates(monkeypatch):
    """Where the Pallas route is active, an error inside the flash kernel
    is an error — never a warning plus the O(L²) reference path."""
    from mxnet_tpu.ops import attention as _att
    import mxnet_tpu.ops.pallas.flash_attention as _fa
    import jax.numpy as jnp

    def boom(*a, **k):
        raise ValueError("kernel rejected the input")

    monkeypatch.setattr(_att, "_use_pallas", lambda: True)
    monkeypatch.setattr(_fa, "flash_attention", boom)
    q = jnp.ones((1, 2, 8, 4), jnp.float32)
    with pytest.raises(ValueError, match="kernel rejected"):
        _att.dot_product_attention(q, q, q)
