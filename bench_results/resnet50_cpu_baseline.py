# ResNet-50 CPU-backend throughput baseline (VERDICT r3 next-step #2).
# The TPU ablation suite (perf_ablation_suite.py section I) is where the
# real number would come from; THIS script pins a clearly-labeled CPU
# regression baseline and says nothing about the chip.  Reference tables for
# context: V100 fp32 inference 1076.81 img/s @ bs32, training 251.22
# img/s @ bs16 (BASELINE.md; reference docs perf.md CPU tables measure
# the same model/batch shapes).
#
# Run:  python bench_results/resnet50_cpu_baseline.py
# Output: one JSON line per (mode, batch) + a combined file
#         bench_results/resnet50_cpu_baseline.json
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"   # before jax/mxnet_tpu import
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import json
import time

import jax
jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp
import numpy as onp

import mxnet_tpu as mx
from mxnet_tpu import optimizer as opt
from mxnet_tpu.gluon.block import functional_call
from mxnet_tpu.gluon.model_zoo import vision as zoo
from mxnet_tpu.parallel import make_mesh, make_sharded_train_step


def timed(fn, n):
    jax.device_get(fn())          # compile + settle
    t0 = time.perf_counter()
    for _ in range(n):
        r = fn()
    jax.device_get(r)
    return (time.perf_counter() - t0) / n


def infer_ips(bs, n=3):
    net = zoo.get_model("resnet50_v1")
    net.initialize()
    x = mx.np.array(onp.random.RandomState(0)
                    .rand(bs, 3, 224, 224).astype("float32"))
    net(x)
    params = {k: p._data._data for k, p in net.collect_params().items()}
    xd = x._data

    @jax.jit
    def fwd(pv, xv):
        out, _ = functional_call(net, pv, xv, training=False)
        return out

    return bs / timed(lambda: fwd(params, xd), n)


def train_ips(bs, n=3):
    net = zoo.get_model("resnet50_v1")
    net.initialize()
    x = mx.np.array(onp.random.RandomState(0)
                    .rand(bs, 3, 224, 224).astype("float32"))
    net(x)
    y = mx.np.array(onp.random.RandomState(1).randint(0, 1000, (bs,)),
                    dtype="int32")

    def lf(out, xv, yv):
        logp = jax.nn.log_softmax(out.astype(jnp.float32), axis=-1)
        return -jnp.take_along_axis(
            logp, yv[:, None].astype(jnp.int32), axis=-1).mean()

    mesh = make_mesh({"dp": 1}, jax.devices("cpu")[:1])
    step = make_sharded_train_step(
        net, opt.SGD(learning_rate=0.1, momentum=0.9), lf, mesh,
        num_model_args=1)
    return bs / timed(lambda: step(x, y), n)


def main():
    host = {"nproc": os.cpu_count(), "platform": "cpu",
            "note": "single-core builder VM; regression baseline only — "
                    "NOT comparable to the V100/TPU tables"}
    lines = []
    for bs in (1, 32):
        ips = infer_ips(bs)
        lines.append({"metric": f"resnet50_v1_infer_img_per_sec_bs{bs}",
                      "value": round(ips, 2), "unit": "img_per_sec",
                      "vs_baseline": 0.0, "extras": dict(host, batch=bs,
                                                         mode="inference",
                                                         dtype="float32")})
        print(json.dumps(lines[-1]), flush=True)
    for bs in (16,):
        ips = train_ips(bs)
        lines.append({"metric": f"resnet50_v1_train_img_per_sec_bs{bs}",
                      "value": round(ips, 2), "unit": "img_per_sec",
                      "vs_baseline": 0.0, "extras": dict(host, batch=bs,
                                                         mode="train",
                                                         dtype="float32")})
        print(json.dumps(lines[-1]), flush=True)
    out = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "resnet50_cpu_baseline.json")
    stamped = {"measured_at": time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                            time.gmtime()),
               "lines": lines}
    with open(out, "w") as f:
        json.dump(stamped, f, indent=1)
    print("wrote", out)


if __name__ == "__main__":
    main()
