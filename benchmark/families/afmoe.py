"""`afmoe` (arcee-ai Trinity family) through the program's normal serving
entry: `models.afmoe.AfmoeForCausalLM` under `serve.InferenceEngine`, one
chip's share of the model (the experts and the vocabulary rows the
configuration's file says are held here).

The names in `param_spec` are the program's parameter names (the held
experts stacked, one array a matrix); `build_engine` checks them against
the model it constructs.
"""
from __future__ import annotations

FAMILY = "afmoe"


def param_spec(cfg: dict) -> list:
    e, d, dt = cfg["hidden_size"], cfg["head_dim"], cfg["dtype"]
    hq, hkv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    i, f = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    v, held = cfg["vocab_size"], cfg["experts_held"]["count"]
    depth = cfg["depth"]

    def gamma(name, size=e):
        return [(name + ".gamma", (size,), "float32", "gamma")]

    spec = [("embed.weight", (v, e), dt, "weight")]
    for li in range(depth["num_hidden_layers"]):
        pre = f"layers.{li}."
        spec += gamma(pre + "attn_norm")
        spec += [(pre + "attn_qkvg.weight", (2 * hq + 2 * hkv, e), dt,
                  "weight")]
        spec += gamma(pre + "q_norm", d) + gamma(pre + "k_norm", d)
        spec += [(pre + "attn_proj.weight", (e, hq), dt, "weight")]
        spec += gamma(pre + "attn_post_norm") + gamma(pre + "ffn_norm")
        if li < depth["num_dense_layers"]:
            spec += [(pre + "ffn.w13.weight", (2 * i, e), dt, "weight"),
                     (pre + "ffn.w2.weight", (e, i), dt, "weight")]
        else:
            fs = f * cfg["num_shared_experts"]
            spec += [
                (pre + "moe.router.weight", (cfg["num_experts"], e), dt,
                 "weight"),
                (pre + "moe.router_bias", (cfg["num_experts"],), "float32",
                 "beta"),
                (pre + "moe.experts_w13", (held, e, 2 * f), dt, "weight"),
                (pre + "moe.experts_w2", (held, f, e), dt, "weight"),
                (pre + "moe.shared_w13.weight", (2 * fs, e), dt, "weight"),
                (pre + "moe.shared_w2.weight", (e, fs), dt, "weight")]
        spec += gamma(pre + "ffn_post_norm")
    spec += gamma("final_norm")
    spec += [("head.weight", (v, e), dt, "weight")]
    return spec


def model_config(cfg: dict):
    from mxnet_tpu.models.afmoe import AfmoeConfig
    depth, held, voc = cfg["depth"], cfg["experts_held"], cfg["vocabulary"]
    return AfmoeConfig(
        vocab_size=voc["published"], hidden_size=cfg["hidden_size"],
        num_layers=depth["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        intermediate_size=cfg["intermediate_size"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        num_dense_layers=depth["num_dense_layers"],
        num_experts=cfg["num_experts"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        num_shared_experts=cfg["num_shared_experts"],
        route_scale=cfg["route_scale"], layer_types=depth["layer_types"],
        sliding_window=cfg["sliding_window"], rope_theta=cfg["rope_theta"],
        rms_norm_eps=cfg["rms_norm_eps"],
        max_position=cfg["max_position_embeddings"],
        mup_enabled=cfg["mup_enabled"], dtype=cfg["dtype"],
        experts_held=(held["first"], held["count"]),
        vocab_rows=(voc["first_row"], cfg["vocab_size"]))


def build_engine(cfg: dict, seed: int, devices):
    """-> (engine, compile seconds).  Weights from the seed, bf16 weights and
    KV, the engine's own defaults for page size, prefill chunk and pools."""
    from mxnet_tpu.models.afmoe import AfmoeForCausalLM
    from mxnet_tpu.serve import InferenceEngine, ServeConfig

    from benchmark.harness import weights as W

    model = AfmoeForCausalLM(model_config(cfg))
    model.setattr("grad_req", "null")     # served, not trained: no second
    w = W.make(param_spec(cfg), seed)     # copy of 5 GB for gradients
    params = model.collect_params()
    if set(params) != set(w):
        raise RuntimeError(
            "the program's afmoe parameters are not those of param_spec: "
            f"{sorted(set(params) ^ set(w))[:8]}")
    for name, p in params.items():
        p.set_data(w[name])
        got = p.data()._data
        if got.shape != w[name].shape or got.dtype != w[name].dtype:
            raise RuntimeError(f"{name}: program {got.shape} {got.dtype}, "
                               f"spec {w[name].shape} {w[name].dtype}")
    del w
    eng_cfg = cfg["engine"]
    extra = {k: eng_cfg[k] for k in ("page_size", "prefill_chunk",
                                     "num_pages") if k in eng_cfg}
    eng = InferenceEngine(
        model, ServeConfig(max_len=eng_cfg["max_len"],
                           max_slots=eng_cfg["max_slots"], **extra),
        seed=int(seed) & 0x7FFFFFFF)
    return eng, eng.warmup()
