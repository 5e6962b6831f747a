"""A tiny benchmark in a temporary root, for rehearsals and tests on the CPU:
its own BENCHMARK.json, configurations and traffic files (all data), with the
real families, kinds and metric readers found by name."""
from __future__ import annotations

import json
import os

BERT = {"family": "bert", "hidden_size": 32, "num_hidden_layers": 2,
        "num_attention_heads": 2, "intermediate_size": 64, "vocab_size": 96,
        "max_position_embeddings": 16, "type_vocab_size": 2,
        "layer_norm_eps": 1e-12, "dtype": "float32",
        "dropout": {"hidden_dropout_prob": 0.0,
                    "attention_probs_dropout_prob": 0.0}}
GPT2 = {"family": "gpt2", "n_embd": 32, "n_layer": 2, "n_head": 2,
        "n_positions": 128, "n_inner": None, "vocab_size": 8192,
        "layer_norm_epsilon": 1e-5, "dtype": "float32",
        "engine": {"max_len": 128, "max_slots": 4, "page_size": 16,
                   "prefill_chunk": 4}}
ADAM = {"name": "adam", "learning_rate": 1e-3, "beta1": 0.9, "beta2": 0.999,
        "epsilon": 1e-8}
TRAIN = {"kind": "train_job", "global_batch": 8, "seq_len": 16, "n_masked": 3,
         "valid_length_range": [0.8, 1.0], "pool_batches": 4,
         "mesh": {"dp": 1}, "optimizer": ADAM, "check_steps": 3,
         "reference_block_rows": 4,
         "limits": {"loss1_rel": 1e-4, "loss2_rel": 1e-4, "loss3_rel": 1e-4,
                    "grad_norm_gap": 1e-2, "grad_diff_rel": 1e-2,
                    "dparam_norm_gap": 2e-2}}
WAVES = {"kind": "closed_loop_waves", "clients": 4,
         "prompt_lens": [5, 7, 9, 11], "output_lens": [48, 48, 48, 48],
         "sample_requests": 4, "reference_pad_to": 64,
         "limits": {"token_gap": 1e-5, "min_compared_tokens": 8}}
LOOP = {"kind": "closed_loop", "clients": 4, "prompt_lens": [6, 9, 14, 21],
        "output_lens": [29, 35, 32, 38], "ramp_steps": 6,
        "ramp_fractions": [0.5, 1.0, 0.25, 0.75], "sample_requests": 6,
        "reference_pad_to": 64,
        "limits": {"token_gap": 1e-5, "min_compared_tokens": 8}}


def make_root(tmp: str, extra_traffic: dict = None,
              extra_metrics: dict = None) -> str:
    """Write the tiny benchmark under `tmp` and return the root."""
    bdir = os.path.join(tmp, "benchmark")
    for sub in ("configs", "traffic", "metrics"):
        os.makedirs(os.path.join(bdir, sub), exist_ok=True)
    traffic = {"tiny-train": TRAIN, "tiny-waves": WAVES, "tiny-loop": LOOP}
    traffic.update(extra_traffic or {})
    for name, t in traffic.items():
        with open(os.path.join(bdir, "traffic", name + ".json"), "w") as f:
            json.dump(t, f)
    for name, c in (("tiny-bert", BERT), ("tiny-gpt2", GPT2)):
        with open(os.path.join(bdir, "configs", name + ".json"), "w") as f:
            json.dump(c, f)
    for name, src in (extra_metrics or {}).items():
        with open(os.path.join(bdir, "metrics", name + ".py"), "w") as f:
            f.write(src)
    workloads = []
    for t in traffic:
        cfg = "tiny-bert" if traffic[t]["kind"] == "train_job" else "tiny-gpt2"
        workloads.append({"name": f"{cfg}.{t}", "config": cfg, "traffic": t,
                          "chips": 1, "why": "tiny"})
    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "..", "BENCHMARK.json")) as f:
        real = json.load(f)
    per_layer = [dict(m, workloads=[w["name"] for w in workloads])
                 for m in real["per_layer"]]
    for name in (extra_metrics or {}):
        per_layer.append({"name": name, "unit": "count", "better": "lower",
                          "source": "program_counter", "layer": "dummy",
                          "moves": "setup_s",
                          "workloads": [w["name"] for w in workloads]})
    bench = {"command": real["command"], "paths": ["benchmark"],
             "run_seconds": 1,
             "configs": [{"name": n, "source": "tiny", "why": "tiny",
                          "file": f"benchmark/configs/{n}.json",
                          "reduced": []} for n in ("tiny-bert", "tiny-gpt2")],
             "workloads": workloads,
             "end_to_end": [dict(m, workloads=[
                 w["name"] for w in workloads
                 if (m["name"].startswith("train")) ==
                 w["config"].endswith("bert")] if m["name"] != "setup_s"
                 else [w["name"] for w in workloads])
                 for m in real["end_to_end"] if m["name"] != "ttft_p95_ms"],
             "per_layer": per_layer}
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return tmp
