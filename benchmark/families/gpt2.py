"""GPT-2 through the program's normal serving entry:
`models.gpt.GPTForCausalLM` under `serve.InferenceEngine`.

The names in `param_spec` are the program's parameter names; `build_engine`
checks them against the model it constructs.
"""
from __future__ import annotations

FAMILY = "gpt2"


def inner(cfg: dict) -> int:
    return cfg.get("n_inner") or 4 * cfg["n_embd"]


def param_spec(cfg: dict) -> list:
    h, i, dt = cfg["n_embd"], inner(cfg), cfg["dtype"]
    spec = [("transformer.word_embed.weight", (cfg["vocab_size"], h), dt,
             "weight"),
            ("transformer.position_embed.weight", (cfg["n_positions"], h),
             dt, "weight")]

    def norm(name):
        return [(name + ".gamma", (h,), "float32", "gamma"),
                (name + ".beta", (h,), "float32", "beta")]

    def dense(name, out, inp):
        return [(name + ".weight", (out, inp), dt, "weight"),
                (name + ".bias", (out,), dt, "bias")]

    for li in range(cfg["n_layer"]):
        pre = f"transformer.layers.{li}."
        spec += norm(pre + "attn_norm")
        spec += dense(pre + "attention.attn_qkv", 3 * h, h)
        spec += dense(pre + "attention.attn_proj", h, h)
        spec += norm(pre + "ffn_norm")
        spec += dense(pre + "ffn.ffn_intermediate", i, h)
        spec += dense(pre + "ffn.ffn_output", h, i)
    spec += norm("transformer.final_norm")
    return spec


def build_engine(cfg: dict, seed: int, devices):
    """-> (engine, compile seconds).  Weights from the seed, bf16 weights and
    KV, the engine's own defaults for page size, prefill chunk and pool."""
    from mxnet_tpu.models.gpt import GPTConfig, GPTForCausalLM
    from mxnet_tpu.serve import InferenceEngine, ServeConfig

    from benchmark.harness import weights as W

    mcfg = GPTConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["n_embd"],
        num_layers=cfg["n_layer"], num_heads=cfg["n_head"],
        intermediate_size=inner(cfg), max_position=cfg["n_positions"],
        dropout=0.0, layer_norm_eps=cfg["layer_norm_epsilon"],
        tie_embeddings=True, dtype=cfg["dtype"])
    model = GPTForCausalLM(mcfg)
    w = W.make(param_spec(cfg), seed)
    params = model.collect_params()
    if set(params) != set(w):
        raise RuntimeError(
            "the program's GPT parameters are not those of param_spec: "
            f"{sorted(set(params) ^ set(w))[:8]}")
    for name, p in params.items():
        p.set_data(w[name])
        got = p.data()._data
        if got.shape != w[name].shape or got.dtype != w[name].dtype:
            raise RuntimeError(f"{name}: program {got.shape} {got.dtype}, "
                               f"spec {w[name].shape} {w[name].dtype}")
    eng_cfg = cfg["engine"]
    extra = {k: eng_cfg[k] for k in ("page_size", "prefill_chunk",
                                     "num_pages") if k in eng_cfg}
    eng = InferenceEngine(
        model, ServeConfig(max_len=eng_cfg["max_len"],
                           max_slots=eng_cfg["max_slots"], **extra),
        seed=int(seed) & 0x7FFFFFFF)
    return eng, eng.warmup()
