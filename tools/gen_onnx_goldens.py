"""Generate the committed golden .onnx fixtures (VERDICT r4 item 4).

Four tiny models exported with fixed seeds; the exporter is
deterministic, so tests/unittest/test_onnx_goldens.py asserts fresh
exports reproduce these bytes (offline regression), and CI's
onnx-validate job runs the same fixtures through onnx.checker +
onnxruntime against the in-repo interpreter (the external oracle).
"""
from __future__ import annotations

import os

# goldens are generated on the CPU whatever the ambient platform is
os.environ["JAX_PLATFORMS"] = "cpu"
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as onp  # noqa: E402

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu import onnx as monnx  # noqa: E402
from mxnet_tpu.gluon import nn  # noqa: E402

ROOT = os.path.join(os.path.dirname(__file__), "..", "tests", "fixtures",
                    "onnx")


def build_cases():
    """name -> (net, example input). Seeded for reproducible params."""
    onp.random.seed(7)
    mx.random.seed(7)

    mlp = nn.HybridSequential()
    mlp.add(nn.Dense(8, in_units=6, activation="relu"),
            nn.Dense(3, in_units=8))
    mlp.initialize()

    conv = nn.HybridSequential()
    conv.add(nn.Conv2D(4, kernel_size=3, padding=1, in_channels=2),
             nn.Activation("relu"),
             nn.MaxPool2D(pool_size=2),
             nn.Flatten(),
             nn.Dense(5, in_units=4 * 4 * 4))
    conv.initialize()

    norm = nn.HybridSequential()
    norm.add(nn.Dense(6, in_units=4), nn.BatchNorm(in_channels=6),
             nn.Activation("sigmoid"))
    norm.initialize()

    emb = nn.HybridSequential()
    emb.add(nn.Embedding(11, 5), nn.Dense(2, in_units=5, flatten=False))
    emb.initialize()

    return {
        "mlp": (mlp, mx.np.array(onp.random.rand(2, 6), dtype="float32")),
        "conv": (conv, mx.np.array(onp.random.rand(1, 2, 8, 8),
                                   dtype="float32")),
        "batchnorm": (norm, mx.np.array(onp.random.rand(3, 4),
                                        dtype="float32")),
        "embedding": (emb, mx.np.array(onp.array([[1, 4, 9]]),
                                       dtype="int32")),
    }


def main():
    os.makedirs(ROOT, exist_ok=True)
    for name, (net, x) in build_cases().items():
        path = os.path.join(ROOT, f"{name}.onnx")
        monnx.export_model(net, path, example_inputs=x)
        ref = net(x).asnumpy()
        onp.savez(os.path.join(ROOT, f"{name}.io.npz"),
                  x=x.asnumpy(), y=ref)
        print(f"{name}: {os.path.getsize(path)} bytes, out {ref.shape}")


if __name__ == "__main__":
    main()
