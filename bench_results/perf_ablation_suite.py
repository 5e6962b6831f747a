# TPU ablation suite (never run; staged for ROADMAP A3 — one process on the
# chip, through the chip tool):
#   python bench_results/perf_ablation_suite.py
# Sections: A full-seq head,
# B no dropout, C dummy loss, D SGD, E small vocab, F matmul ceiling,
# G GPT-2k flash+remat, H masked-flash vs reference-attention (round 3:
# masks now stay on the Pallas path — H measures the kernel's win on
# production-shaped batches).
"""TPU step-time ablations for the BERT bench. One process, incremental
prints, clean exit. Identifies where the 117ms (vs ~28ms ideal) goes."""
import sys, time, functools
sys.path.insert(0, __import__("os").path.dirname(__import__("os").path.dirname(__import__("os").path.abspath(__file__))))
print = functools.partial(print, flush=True)

import numpy as onp
import jax, jax.numpy as jnp

print("devices:", jax.devices())

import mxnet_tpu as mx
from mxnet_tpu import optimizer as opt
from mxnet_tpu.models.bert import BertConfig, BertForPretraining
from mxnet_tpu.parallel import make_mesh, make_sharded_train_step

batch, seq = 64, 128

def timed(fn, n=20):
    r = fn(); jax.device_get(r)
    t0 = time.perf_counter()
    for _ in range(5): r = fn()
    jax.device_get(r); t5 = time.perf_counter()
    for _ in range(n): r = fn()
    jax.device_get(r)
    t = time.perf_counter()
    return (t - t5) / n * 1e3  # slope-free enough; fixed cost amortized

def build_step(cfg, loss_kind="mlm", optimizer=None, dropout=True):
    if not dropout:
        cfg.dropout = 0.0
    model = BertForPretraining(cfg)
    model.initialize()
    rng = onp.random.RandomState(0)
    ids = mx.np.array(rng.randint(0, cfg.vocab_size, (batch, seq)), dtype="int32")
    labels = mx.np.array(rng.randint(0, cfg.vocab_size, (batch, seq)), dtype="int32")
    model(ids)

    def loss_mlm(out, input_ids, lbl):
        mlm, nsp = out
        logp = jax.nn.log_softmax(mlm.astype(jnp.float32), axis=-1)
        ll = jnp.take_along_axis(logp, lbl[..., None].astype(jnp.int32), axis=-1)
        return -jnp.mean(ll)

    def loss_dummy(out, input_ids, lbl):
        mlm, nsp = out
        return jnp.mean(mlm.astype(jnp.float32) ** 2)

    loss_fn = loss_mlm if loss_kind == "mlm" else loss_dummy
    mesh = make_mesh({"dp": 1}, jax.devices()[:1])
    step = make_sharded_train_step(model, optimizer or opt.Adam(learning_rate=1e-4),
                                   loss_fn, mesh, num_model_args=1)
    return lambda: step(ids, labels)

results = {}

# A. full-sequence head (= old bench config)
f = build_step(BertConfig(dtype="bfloat16"))
results["A_full"] = timed(f)
print("A full step:", results["A_full"], "ms")

# A-prof: per-op aggregate table for the full-head step (the VERDICT's
# "name the next limiter" ask) — eager per-op timing via the profiler
# hook; coarse but ranks the offenders
try:
    import mxnet_tpu.profiler as prof
    prof.set_config(aggregate_stats=True)
    prof.start()
    f()
    prof.stop()
    print("A-prof per-op table:")
    print(prof.dumps(reset=True))
except Exception as e:
    print("A-prof failed:", type(e).__name__, e)

# B. no dropout
f = build_step(BertConfig(dtype="bfloat16"), dropout=False)
results["B_no_dropout"] = timed(f)
print("B no dropout:", results["B_no_dropout"], "ms")

# C. dummy loss (no vocab log_softmax / gather; mlm matmul still runs)
f = build_step(BertConfig(dtype="bfloat16"), loss_kind="dummy")
results["C_dummy_loss"] = timed(f)
print("C dummy loss:", results["C_dummy_loss"], "ms")

# D. SGD instead of Adam (optimizer bandwidth)
f = build_step(BertConfig(dtype="bfloat16"), optimizer=opt.SGD(learning_rate=1e-3))
results["D_sgd"] = timed(f)
print("D sgd:", results["D_sgd"], "ms")

# E. tiny vocab (embedding/vocab scatter+gather cost)
f = build_step(BertConfig(dtype="bfloat16", vocab_size=1024))
results["E_vocab1k"] = timed(f)
print("E vocab 1k:", results["E_vocab1k"], "ms")

# F. matmul ceiling: BERT-base-shaped FFN chain
x = jnp.ones((batch * seq, 768), jnp.bfloat16)
w1 = jnp.ones((768, 3072), jnp.bfloat16)
w2 = jnp.ones((3072, 768), jnp.bfloat16)
@jax.jit
def mm(x):
    for _ in range(24):
        x = (x @ w1) @ w2
    return x
t = timed(lambda: mm(x))
results["F_matmul_ms"] = t
fl = 24 * 2 * 2 * batch * seq * 768 * 3072 / (t / 1e3)
print(f"F matmul chain: {t:.2f} ms -> {fl/1e12:.1f} TF/s")

print("RESULTS", results)

# H. masked attention: flash kernel vs XLA reference path (padding masks)
import os as _os2

def build_masked_step(cfg):
    model = BertForPretraining(cfg)
    model.initialize()
    rng = onp.random.RandomState(0)
    ids = mx.np.array(rng.randint(0, cfg.vocab_size, (batch, seq)),
                      dtype="int32")
    vlen = mx.np.array(rng.randint(int(0.85 * seq), seq + 1, (batch,)),
                       dtype="int32")
    labels = mx.np.array(rng.randint(0, cfg.vocab_size, (batch, seq)),
                         dtype="int32")
    model(ids, valid_length=vlen)

    def loss_mlm(out, input_ids, vl, lbl):
        mlm, nsp = out
        logp = jax.nn.log_softmax(mlm.astype(jnp.float32), axis=-1)
        ll = jnp.take_along_axis(logp, lbl[..., None].astype(jnp.int32),
                                 axis=-1)
        return -jnp.mean(ll)

    from mxnet_tpu.gluon.block import HybridBlock

    class W(HybridBlock):
        def __init__(self, m):
            super().__init__()
            self.m = m

        def forward(self, i, vl):
            return self.m(i, valid_length=vl)

    w = W(model)
    mesh = make_mesh({"dp": 1}, jax.devices()[:1])
    step = make_sharded_train_step(w, opt.Adam(learning_rate=1e-4),
                                   loss_mlm, mesh, num_model_args=2)
    return lambda: step(ids, vlen, labels)

f = build_masked_step(BertConfig(dtype="bfloat16"))
results["H_masked_flash"] = timed(f)
print("H masked (flash kernel):", results["H_masked_flash"], "ms")

_os2.environ["MXTPU_DISABLE_FLASH"] = "1"
f = build_masked_step(BertConfig(dtype="bfloat16"))
results["H_masked_reference"] = timed(f)
print("H masked (XLA reference):", results["H_masked_reference"], "ms")
del _os2.environ["MXTPU_DISABLE_FLASH"]

# NOTE: no block sweep here — the bench's seq 128 clamps both block
# sizes to 128, so (block_q, block_k) only matters at long context;
# see H2 next to the GPT-2k legs.

# G. long-context GPT: seq 2048, flash attention + per-layer remat
try:
    from mxnet_tpu.models.gpt import GPTConfig, GPTForCausalLM
    cfg = GPTConfig(vocab_size=50257, hidden_size=768, num_layers=12,
                    num_heads=12, intermediate_size=3072,
                    max_position=2048, dtype="bfloat16", remat=True)
    m = GPTForCausalLM(cfg)
    m.initialize()
    rng = onp.random.RandomState(0)
    B, L = 4, 2048
    ids = mx.np.array(rng.randint(0, cfg.vocab_size, (B, L)), dtype="int32")
    m(ids)

    def lm_loss(out, i):
        from mxnet_tpu.ops.pallas.softmax_xent import softmax_cross_entropy
        return softmax_cross_entropy(out[:, :-1],
                                     i[:, 1:].astype(jnp.int32)).mean()

    mesh = make_mesh({"dp": 1}, jax.devices()[:1])
    gstep = make_sharded_train_step(m, opt.Adam(learning_rate=1e-4),
                                    lm_loss, mesh, num_model_args=1)
    t = timed(lambda: gstep(ids), n=10)
    h, l, i, V = 768, 12, 3072, 50257
    fl = 3 * B * L * (2 * l * (4*h*h + 2*h*i) + 4 * l * L * h + 2 * h * V)
    print(f"G gpt2k flash+remat: {t:.1f} ms -> "
          f"{fl/(t/1e3)/1e12:.1f} TF/s, MFU {fl/(t/1e3)/197e12:.3f}")
    results["G_gpt2k_ms"] = t
except Exception as e:
    print("G gpt2k failed:", type(e).__name__, e)

# G2. long-context GPT with SLIDING-WINDOW attention (window=256):
# same model as G but O(L·w) attention — the banded-kernel win at 2k ctx
try:
    from mxnet_tpu.models.gpt import GPTConfig, GPTForCausalLM
    cfg = GPTConfig(vocab_size=50257, hidden_size=768, num_layers=12,
                    num_heads=12, intermediate_size=3072,
                    max_position=2048, dtype="bfloat16", remat=True,
                    window=256)
    m = GPTForCausalLM(cfg)
    m.initialize()
    rng = onp.random.RandomState(0)
    B, L = 4, 2048
    ids = mx.np.array(rng.randint(0, cfg.vocab_size, (B, L)), dtype="int32")
    m(ids)

    def lm_loss_w(out, i):
        from mxnet_tpu.ops.pallas.softmax_xent import softmax_cross_entropy
        return softmax_cross_entropy(out[:, :-1],
                                     i[:, 1:].astype(jnp.int32)).mean()

    mesh = make_mesh({"dp": 1}, jax.devices()[:1])
    wstep = make_sharded_train_step(m, opt.Adam(learning_rate=1e-4),
                                    lm_loss_w, mesh, num_model_args=1)
    t = timed(lambda: wstep(ids), n=10)
    results["G2_gpt2k_window256_ms"] = t
    print(f"G2 gpt2k window=256 flash+remat: {t:.1f} ms "
          f"(vs G full attention above — the banded-kernel delta)")
except Exception as e:
    print("G2 gpt2k window failed:", type(e).__name__, e)

# H2. flash block-size sweep at LONG context (seq 2048, where blocks
# genuinely vary): if the kernel is the limiter, the winning
# (block_q, block_k) names the fix — exported env knobs, no code change
try:
    import os as _os3
    from mxnet_tpu.models.gpt import GPTConfig as _C2, \
        GPTForCausalLM as _M2

    def _block_step_ms():
        cfg = _C2(vocab_size=50257, hidden_size=768, num_layers=12,
                  num_heads=12, intermediate_size=3072,
                  max_position=2048, dtype="bfloat16", remat=True)
        m = _M2(cfg)
        m.initialize()
        rng = onp.random.RandomState(0)
        ids = mx.np.array(rng.randint(0, cfg.vocab_size, (4, 2048)),
                          dtype="int32")
        m(ids)

        def lm_loss(out, i):
            from mxnet_tpu.ops.pallas.softmax_xent import \
                softmax_cross_entropy
            return softmax_cross_entropy(out[:, :-1],
                                         i[:, 1:].astype(jnp.int32)).mean()

        mesh = make_mesh({"dp": 1}, jax.devices()[:1])
        st = make_sharded_train_step(m, opt.Adam(learning_rate=1e-4),
                                     lm_loss, mesh, num_model_args=1)
        return timed(lambda: st(ids), n=10)

    for bq, bk in ((128, 128), (256, 256), (512, 256), (256, 512),
                   (512, 512)):
        _os3.environ["MXTPU_FLASH_BLOCK_Q"] = str(bq)
        _os3.environ["MXTPU_FLASH_BLOCK_K"] = str(bk)
        try:
            t = _block_step_ms()
            results[f"H2_gpt2k_bq{bq}_bk{bk}"] = t
            print(f"H2 gpt2k block_q={bq} block_k={bk}: {t:.1f} ms")
        except Exception as e:   # a size can exceed VMEM — keep sweeping
            print(f"H2 gpt2k bq={bq} bk={bk} failed:",
                  type(e).__name__, e)
    _os3.environ.pop("MXTPU_FLASH_BLOCK_Q", None)
    _os3.environ.pop("MXTPU_FLASH_BLOCK_K", None)
except Exception as e:
    print("H2 block sweep failed:", type(e).__name__, e)

# J. GQA kernel ablation (round 4). Three legs at gpt2k shapes:
#   J1 num_kv_heads=3, grouped-KV folded kernel (the round-4 path)
#   J2 num_kv_heads=3, SAME model but K/V repeat-expanded to 12 heads
#      before the kernel (the pre-round-4 behavior) — J1 vs J2 isolates
#      the kernel's HBM-bandwidth win at identical params/projections
#   J3 num_kv_heads=12 MHA — the end-to-end model-level GQA-vs-MHA delta
#      (includes the smaller kv projections)
try:
    import mxnet_tpu.ops.pallas.flash_attention as _fa
    from mxnet_tpu.models.gpt import GPTConfig, GPTForCausalLM

    def gqa_step_ms(kv_heads, force_expand=False):
        cfg = GPTConfig(vocab_size=50257, hidden_size=768, num_layers=12,
                        num_heads=12, intermediate_size=3072,
                        max_position=2048, dtype="bfloat16", remat=True,
                        num_kv_heads=None if kv_heads == 12 else kv_heads)
        m = GPTForCausalLM(cfg)
        m.initialize()
        rng = onp.random.RandomState(0)
        B, L = 4, 2048
        ids = mx.np.array(rng.randint(0, cfg.vocab_size, (B, L)),
                          dtype="int32")
        m(ids)

        def lm_loss(out, i):
            from mxnet_tpu.ops.pallas.softmax_xent import \
                softmax_cross_entropy
            return softmax_cross_entropy(out[:, :-1],
                                         i[:, 1:].astype(jnp.int32)).mean()

        orig = _fa.flash_attention
        if force_expand:
            def expanded(q, k, v, **kw):
                if k.shape[1] != q.shape[1]:
                    k, v = _fa._expand_kv(k, v, q.shape[1])
                return orig(q, k, v, **kw)
            _fa.flash_attention = expanded   # dispatcher re-imports per call
        try:
            mesh = make_mesh({"dp": 1}, jax.devices()[:1])
            st = make_sharded_train_step(m, opt.Adam(learning_rate=1e-4),
                                         lm_loss, mesh, num_model_args=1)
            return timed(lambda: st(ids), n=10)
        finally:
            _fa.flash_attention = orig

    t_grouped = gqa_step_ms(3)                      # J1
    t_expanded = gqa_step_ms(3, force_expand=True)  # J2
    t_mha = gqa_step_ms(12)                         # J3
    results["J1_gpt2k_gqa3_grouped_ms"] = t_grouped
    results["J2_gpt2k_gqa3_expanded_ms"] = t_expanded
    results["J3_gpt2k_mha_ms"] = t_mha
    print(f"J gpt2k kv=3 grouped {t_grouped:.1f} ms vs kv=3 expanded "
          f"{t_expanded:.1f} ms (kernel HBM win) vs MHA {t_mha:.1f} ms "
          f"(model-level delta)")
except Exception as e:
    print("J gqa failed:", type(e).__name__, e)

# I. ResNet-50 throughput vs the reference's headline tables
# (BASELINE.md: V100 fp32 inference 1076.81 img/s @ bs32, 1233.15 @ bs128,
# fp16 2085.51 @ bs32; training fp32 251.22 img/s @ bs16). TPU bf16 is
# the comparable mixed-precision config.
try:
    from mxnet_tpu.gluon.model_zoo import vision as _zoo
    from mxnet_tpu.gluon.block import functional_call

    def resnet_infer(bs, dtype="bfloat16"):
        net = _zoo.get_model("resnet50_v1")
        net.initialize()
        x = mx.np.array(onp.random.RandomState(0)
                        .rand(bs, 3, 224, 224).astype("float32"))
        net(x)
        params = {n: p._data._data.astype(dtype)
                  if p._data._data.dtype == jnp.float32 else p._data._data
                  for n, p in net.collect_params().items()}
        xd = x._data.astype(dtype)

        @jax.jit
        def fwd(pv, xv):
            out, _ = functional_call(net, pv, xv, training=False)
            return out

        jax.device_get(fwd(params, xd))
        t = timed(lambda: fwd(params, xd), n=20)
        return bs / (t / 1e3)

    for bs, ref in ((32, 1076.81), (128, 1233.15)):
        ips = resnet_infer(bs)
        results[f"I_resnet50_infer_bs{bs}"] = ips
        print(f"I resnet50 bf16 inference bs={bs}: {ips:.1f} img/s "
              f"(V100 fp32 ref {ref}; fp16 ref 2085.51 @ bs32)")

    def resnet_train(bs):
        net = _zoo.get_model("resnet50_v1")
        net.initialize()
        x = mx.np.array(onp.random.RandomState(0)
                        .rand(bs, 3, 224, 224).astype("float32"))
        net(x)
        y = mx.np.array(onp.random.RandomState(1)
                        .randint(0, 1000, (bs,)), dtype="int32")

        def lf(out, xv, yv):
            from mxnet_tpu.ops.pallas.softmax_xent import \
                softmax_cross_entropy
            return softmax_cross_entropy(out, yv.astype(jnp.int32)).mean()

        mesh = make_mesh({"dp": 1}, jax.devices()[:1])
        tstep = make_sharded_train_step(
            net, opt.SGD(learning_rate=0.1, momentum=0.9), lf, mesh,
            num_model_args=1)
        t = timed(lambda: tstep(x, y), n=10)
        return bs / (t / 1e3)

    ips = resnet_train(32)
    results["I_resnet50_train_bs32"] = ips
    print(f"I resnet50 fp32 train bs=32: {ips:.1f} img/s "
          f"(V100 fp32 ref 251.22 @ bs16, K80 49.48 @ bs32)")
except Exception as e:
    print("I resnet50 failed:", type(e).__name__, e)

print("ALL DONE", results)
