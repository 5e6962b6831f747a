"""BERT pretraining through the program's normal training entry:
`models.bert.BertForPretraining` under `parallel.make_sharded_train_step`.

The names in `param_spec` are the program's parameter names; `build` checks
them against the model it constructs, so a program change that renames or
reshapes a parameter fails here, by name, and not as a wrong comparison.
"""
from __future__ import annotations

import numpy as onp

FAMILY = "bert"


def param_spec(cfg: dict) -> list:
    h, i, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    dt = cfg["dtype"]
    spec = [("bert.word_embed.weight", (v, h), dt, "weight"),
            ("bert.token_type_embed.weight", (cfg["type_vocab_size"], h), dt,
             "weight"),
            ("bert.position_embed.weight",
             (cfg["max_position_embeddings"], h), dt, "weight")]

    def norm(name):
        return [(name + ".gamma", (h,), "float32", "gamma"),
                (name + ".beta", (h,), "float32", "beta")]

    def dense(name, out, inp):
        return [(name + ".weight", (out, inp), dt, "weight"),
                (name + ".bias", (out,), dt, "bias")]

    spec += norm("bert.embed_norm")
    for li in range(cfg["num_hidden_layers"]):
        pre = f"bert.layers.{li}."
        spec += dense(pre + "attention.attn_qkv", 3 * h, h)
        spec += dense(pre + "attention.attn_proj", h, h)
        spec += norm(pre + "attn_norm")
        spec += dense(pre + "ffn_intermediate", i, h)
        spec += dense(pre + "ffn_output", h, i)
        spec += norm(pre + "ffn_norm")
    spec += dense("bert.pooler", h, h)
    spec += dense("mlm_dense", h, h)
    spec += norm("mlm_norm")
    spec += dense("mlm_decoder", v, h)
    spec += dense("nsp_classifier", 2, h)
    return spec


def make_batches(cfg: dict, job: dict, seed: int) -> list:
    """`pool_batches` host batches from the seed; every row differs.  Each is
    (ids, token_types, valid_length, masked_positions, mlm_labels,
    nsp_labels), int32."""
    rng = onp.random.default_rng([int(seed), 0xBE47])
    b, l, m, v = job["global_batch"], job["seq_len"], job["n_masked"], \
        cfg["vocab_size"]
    lo = int(job["valid_length_range"][0] * l)
    hi = int(job["valid_length_range"][1] * l)
    out = []
    for _ in range(job["pool_batches"]):
        vlen = rng.integers(lo, hi + 1, b)
        pos = onp.arange(l)[None, :]
        ids = rng.integers(1, v, (b, l))
        ids = onp.where(pos < vlen[:, None], ids, 0)
        split = (vlen * rng.uniform(0.3, 0.7, b)).astype(onp.int64)
        types = ((pos >= split[:, None]) & (pos < vlen[:, None]))
        # n_masked distinct positions below the row's valid length
        scores = rng.random((b, l))
        scores[pos >= vlen[:, None]] = 2.0
        mpos = onp.sort(onp.argsort(scores, axis=1)[:, :m], axis=1)
        out.append(tuple(onp.ascontiguousarray(x, dtype=onp.int32) for x in (
            ids, types, vlen, mpos, rng.integers(0, v, (b, m)),
            rng.integers(0, 2, b))))
    return out


def adam_hp(job: dict) -> dict:
    o = job["optimizer"]
    if o["name"] != "adam":
        raise ValueError("the bert family trains with Adam")
    return {k: float(o[k]) for k in
            ("learning_rate", "beta1", "beta2", "epsilon")}


class TrainProgram:
    """The compiled step with its state, its feed and what the comparison
    needs of the start."""


def build_train(cfg: dict, job: dict, seed: int, devices) -> TrainProgram:
    import jax
    import jax.numpy as jnp

    import mxnet_tpu as mx
    from mxnet_tpu import optimizer as opt
    from mxnet_tpu.gluon.block import HybridBlock
    from mxnet_tpu.models.bert import BertConfig, BertForPretraining
    from mxnet_tpu.ops.pallas.softmax_xent import softmax_cross_entropy
    from mxnet_tpu.parallel import make_mesh, make_sharded_train_step
    from mxnet_tpu.parallel.sharding import default_tp_rules

    from benchmark.harness import weights as W

    if any(cfg["dropout"].values()):
        raise ValueError(
            "dropout > 0: the program draws attention dropout inside its "
            "kernel, which no reference can follow; the cell states 0")
    mcfg = BertConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        intermediate_size=cfg["intermediate_size"],
        max_position=cfg["max_position_embeddings"],
        type_vocab_size=cfg["type_vocab_size"],
        dropout=cfg["dropout"]["hidden_dropout_prob"],
        layer_norm_eps=cfg["layer_norm_eps"], dtype=cfg["dtype"])

    class Pretrain(HybridBlock):
        def __init__(self, c):
            super().__init__()
            self.model = BertForPretraining(c)

        def forward(self, ids, types, vlen, mpos):
            return self.model(ids, token_types=types, valid_length=vlen,
                              masked_positions=mpos)

    model = Pretrain(mcfg)
    spec = param_spec(cfg)
    w0 = W.make(spec, seed)
    params = model.collect_params()
    names = {n.removeprefix("model."): n for n in params}
    if set(names) != set(w0):
        raise RuntimeError(
            "the program's BERT parameters are not those of param_spec: "
            f"{sorted(set(names) ^ set(w0))[:8]}")
    for short, full in names.items():
        p = params[full]
        p.set_data(w0[short])
        got = p.data()._data
        if got.shape != w0[short].shape or got.dtype != w0[short].dtype:
            raise RuntimeError(f"{short}: program {got.shape} {got.dtype}, "
                               f"spec {w0[short].shape} {w0[short].dtype}")

    def loss_fn(out, ids, types, vlen, mpos, mlm_labels, nsp_labels):
        mlm, nsp = out
        mlm_loss = jnp.mean(softmax_cross_entropy(
            mlm, mlm_labels.astype(jnp.int32)))
        nsp = nsp.astype(jnp.float32)
        picked = jnp.take_along_axis(nsp, nsp_labels[:, None], axis=-1)[:, 0]
        return mlm_loss + jnp.mean(jax.nn.logsumexp(nsp, axis=-1) - picked)

    mesh_axes = {k: int(v) for k, v in job["mesh"].items()}
    n_dev = 1
    for v in mesh_axes.values():
        n_dev *= v
    if n_dev != len(devices):
        raise ValueError(f"mesh {mesh_axes} needs {n_dev} devices, the cell "
                         f"has {len(devices)}")
    mesh = make_mesh(mesh_axes, list(devices))
    hp = adam_hp(job)
    step = make_sharded_train_step(
        model, opt.Adam(learning_rate=hp["learning_rate"], beta1=hp["beta1"],
                        beta2=hp["beta2"], epsilon=hp["epsilon"]),
        loss_fn, mesh, rules=default_tp_rules() if n_dev > 1 else None,
        num_model_args=4)

    host = make_batches(cfg, job, seed)
    first = tuple(mx.np.array(x, dtype="int32") for x in host[0])
    step.warmup(*first)
    prog = TrainProgram()
    prog.step = step
    prog.host_batches = host
    prog.batches = [step.place_batch(*(mx.np.array(x, dtype="int32")
                                       for x in hb)) for hb in host]
    prog.strip = lambda n: n.removeprefix("model.")
    prog.hp = hp
    prog.tokens_per_step = job["global_batch"] * job["seq_len"]
    return prog
