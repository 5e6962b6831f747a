"""ONNX golden-fixture regression (VERDICT r4 item 4).

Offline: the committed .onnx fixtures must load through the in-repo
interpreter and reproduce the committed reference outputs, and a fresh
export of the same models must reproduce the committed graph structure
exactly and its tensors to round-off.  When `onnx`/`onnxruntime` are
importable (CI's onnx-validate job installs them), the same fixtures
additionally go through onnx.checker and onnxruntime — the EXTERNAL
oracle the interpreter can't provide.
"""
import importlib.util
import os

import numpy as onp
import pytest

from mxnet_tpu.onnx import _proto, _runtime

FIX = os.path.join(os.path.dirname(__file__), "..", "fixtures", "onnx")
CASES = ["mlp", "conv", "batchnorm", "embedding"]

HAVE_ONNX = importlib.util.find_spec("onnx") is not None
HAVE_ORT = importlib.util.find_spec("onnxruntime") is not None


@pytest.mark.parametrize("name", CASES)
def test_golden_runs_in_interpreter(name):
    io = onp.load(os.path.join(FIX, f"{name}.io.npz"))
    outs = _runtime.run_model(os.path.join(FIX, f"{name}.onnx"),
                              {"data": io["x"]})
    out = next(iter(outs.values()))
    onp.testing.assert_allclose(onp.asarray(out), io["y"], rtol=1e-5,
                                atol=1e-5)


def _structure_and_tensors(model_bytes):
    """(graph structure with tensor payloads blanked, {name: array})."""
    m = _proto.parse_model(model_bytes)
    tensors = {t["name"]: _runtime._tensor_to_np(t)
               for t in m["graph"]["initializers"]}
    for t in m["graph"]["initializers"]:
        t["raw"] = b""
    return m, tensors


def test_fresh_export_reproduces_golden(tmp_path):
    """A fresh export of the same models, holding the golden's parameter
    values, reproduces the committed file: graph STRUCTURE exactly
    (nodes, attributes, names, shapes, dtypes, opset) and every tensor to
    f32 round-off — and evaluates to the committed reference output.
    The parameters are taken from the golden, not re-drawn from the
    seed: a seeded initializer's stream belongs to the installed jax
    (0.9.0 draws different numbers than the toolchain that wrote the
    fixtures), and the exporter is what this test pins."""
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..",
                                    "tools"))
    try:
        import gen_onnx_goldens as g
    finally:
        sys.path.pop(0)
    import mxnet_tpu as mx
    from mxnet_tpu import onnx as monnx
    for name, (net, x) in g.build_cases().items():
        with open(os.path.join(FIX, f"{name}.onnx"), "rb") as f:
            want, want_t = _structure_and_tensors(f.read())
        for pname, p in net.collect_params().items():
            p.set_data(mx.np.array(want_t[pname]))
        fresh = str(tmp_path / f"{name}.onnx")
        monnx.export_model(net, fresh, example_inputs=x)
        with open(fresh, "rb") as f:
            fresh_bytes = f.read()
        got, got_t = _structure_and_tensors(fresh_bytes)
        assert got == want, (
            f"{name}: exporter graph structure drifted from the committed "
            "golden — if intentional, regenerate via "
            "tools/gen_onnx_goldens.py and re-validate in CI")
        assert got_t.keys() == want_t.keys()
        for tname, t in want_t.items():
            assert got_t[tname].dtype == t.dtype, (name, tname)
            onp.testing.assert_allclose(got_t[tname], t, rtol=1e-6,
                                        atol=1e-7, err_msg=f"{name}:{tname}")
        io = onp.load(os.path.join(FIX, f"{name}.io.npz"))
        out = next(iter(_runtime.run_model(
            fresh_bytes, {"data": io["x"]}).values()))
        onp.testing.assert_allclose(onp.asarray(out), io["y"], rtol=1e-5,
                                    atol=1e-5)


@pytest.mark.skipif(not HAVE_ONNX, reason="onnx not installed (CI job "
                    "onnx-validate installs it)")
@pytest.mark.parametrize("name", CASES)
def test_golden_passes_onnx_checker(name):
    import onnx
    model = onnx.load(os.path.join(FIX, f"{name}.onnx"))
    onnx.checker.check_model(model)


@pytest.mark.skipif(not HAVE_ORT, reason="onnxruntime not installed "
                    "(CI job onnx-validate installs it)")
@pytest.mark.parametrize("name", CASES)
def test_golden_matches_onnxruntime(name):
    import onnxruntime as ort
    io = onp.load(os.path.join(FIX, f"{name}.io.npz"))
    sess = ort.InferenceSession(os.path.join(FIX, f"{name}.onnx"),
                                providers=["CPUExecutionProvider"])
    inp = sess.get_inputs()[0].name
    got = sess.run(None, {inp: io["x"]})[0]
    onp.testing.assert_allclose(got, io["y"], rtol=1e-4, atol=1e-4)
    # the in-repo interpreter and ort must agree on the same file
    outs = _runtime.run_model(os.path.join(FIX, f"{name}.onnx"),
                              {inp: io["x"]})
    ours = next(iter(outs.values()))
    onp.testing.assert_allclose(onp.asarray(ours), got, rtol=1e-4,
                                atol=1e-4)
