"""ONNX interchange tests (ref: tests/python-pytest/onnx/ in the reference).

The environment has no onnx package; both directions run on the
self-contained protobuf codec (mxnet_tpu/contrib/onnx_proto.py), so these
tests cover the codec itself plus full export->import round-trips.
"""
import os

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon
from mxnet_tpu.contrib import onnx as mxonnx
from mxnet_tpu.contrib import onnx_proto as oproto
from mxnet_tpu.gluon import nn
from mxnet_tpu.symbol.executor import eval_symbol


# ---------------------------------------------------------------------------
# codec
# ---------------------------------------------------------------------------

def test_varint_negative_roundtrip():
    t = oproto.TensorProto(dims=[3, -1, 5], data_type=7)
    t2 = oproto.TensorProto.decode(t.encode())
    assert t2.dims == [3, -1, 5]
    assert t2.data_type == 7


@pytest.mark.parametrize("dtype", [np.float32, np.float16, np.float64,
                                   np.int32, np.int64, np.uint8, np.bool_])
def test_tensor_roundtrip(dtype):
    rng = np.random.RandomState(0)
    arr = (rng.randn(2, 3, 4) * 10).astype(dtype)
    t = oproto.from_array(arr, name="w")
    out = oproto.to_array(oproto.TensorProto.decode(t.encode()))
    assert out.dtype == arr.dtype
    np.testing.assert_array_equal(out, arr)


def test_typed_data_fallbacks():
    # stock onnx sometimes stores payloads in float_data/int64_data
    t = oproto.TensorProto(dims=[2, 2], data_type=1,
                           float_data=[1.0, 2.0, 3.0, 4.0])
    np.testing.assert_array_equal(
        oproto.to_array(t), np.array([[1, 2], [3, 4]], np.float32))
    t = oproto.TensorProto(dims=[3], data_type=7, int64_data=[-1, 0, 7])
    np.testing.assert_array_equal(
        oproto.to_array(t), np.array([-1, 0, 7], np.int64))


def test_attribute_kinds():
    cases = [("f", 2.5), ("i", 7), ("s", "max"), ("ints", [1, 2, 3]),
             ("floats", [0.5, 1.5])]
    for name, val in cases:
        a = oproto.make_attribute(name, val)
        out = oproto.attribute_value(oproto.AttributeProto.decode(a.encode()))
        if isinstance(val, list):
            assert list(out) == pytest.approx(val)
        else:
            assert out == pytest.approx(val)


def test_model_roundtrip(tmp_path):
    g = oproto.GraphProto(name="g")
    g.node.append(oproto.NodeProto(op_type="Relu", input=["x"],
                                   output=["y"], name="relu0"))
    g.input.append(oproto.make_tensor_value_info("x", 1, (1, "batch", 3)))
    g.output.append(oproto.make_tensor_value_info("y", 1, (1, 3)))
    g.initializer.append(oproto.from_array(np.eye(3, dtype=np.float32), "w"))
    m = oproto.ModelProto(ir_version=7, producer_name="t", graph=g,
                          opset_import=[oproto.OperatorSetIdProto(version=13)])
    path = str(tmp_path / "m.onnx")
    oproto.save(m, path)
    m2 = oproto.load(path)
    assert m2.ir_version == 7
    assert m2.graph.node[0].op_type == "Relu"
    assert m2.graph.input[0].type.tensor_type.shape.dim[1].dim_param == "batch"
    np.testing.assert_array_equal(oproto.to_array(m2.graph.initializer[0]),
                                  np.eye(3, dtype=np.float32))
    assert m2.opset_import[0].version == 13


# ---------------------------------------------------------------------------
# export -> import round trips
# ---------------------------------------------------------------------------

def _roundtrip(net, shape, tmp_path, name, tol=1e-4):
    net.initialize(mx.init.Xavier())
    x = mx.nd.array(np.random.RandomState(0).randn(*shape).astype(np.float32))
    with autograd.pause():
        y0 = net(x)
    path = str(tmp_path / name)
    net.export(path)
    onnx_path = path + ".onnx"
    mxonnx.export_model(path + "-symbol.json", path + "-0000.params",
                        [shape], onnx_file_path=onnx_path)
    sym, arg_params, aux_params = mxonnx.import_model(onnx_path)
    y1 = eval_symbol(sym, ["data"], [x], {**arg_params, **aux_params})
    y1 = y1[0] if isinstance(y1, list) else y1
    np.testing.assert_allclose(y0.asnumpy(), y1.asnumpy(),
                               rtol=tol, atol=tol)
    return onnx_path


def test_mlp_roundtrip(tmp_path):
    net = nn.HybridSequential()
    net.add(nn.Dense(16, activation="relu"), nn.Dense(10))
    _roundtrip(net, (2, 8), tmp_path, "mlp")


def test_cnn_roundtrip(tmp_path):
    net = nn.HybridSequential()
    net.add(nn.Conv2D(8, kernel_size=3, padding=1, activation="relu"),
            nn.BatchNorm(),
            nn.MaxPool2D(pool_size=2, strides=2),
            nn.Flatten(),
            nn.Dense(16, activation="relu"),
            nn.Dropout(0.5),
            nn.Dense(10))
    _roundtrip(net, (2, 3, 8, 8), tmp_path, "cnn")


@pytest.mark.heavy
def test_resnet18_roundtrip(tmp_path):
    from mxnet_tpu.gluon.model_zoo.vision import resnet18_v1
    _roundtrip(resnet18_v1(), (1, 3, 32, 32), tmp_path, "resnet18",
               tol=1e-3)


# ---------------------------------------------------------------------------
# gluon export / SymbolBlock.imports (the checkpoint layout the C predict
# API and Module consume; ref: SURVEY.md §5.4)
# ---------------------------------------------------------------------------

def test_symbolblock_imports(tmp_path):
    net = nn.HybridSequential()
    net.add(nn.Conv2D(4, kernel_size=3, padding=1), nn.BatchNorm(),
            nn.Flatten(), nn.Dense(5))
    net.initialize(mx.init.Xavier())
    x = mx.nd.array(np.random.RandomState(1).randn(2, 3, 6, 6)
                    .astype(np.float32))
    with autograd.pause():
        y0 = net(x)
    path = str(tmp_path / "m")
    net.export(path)
    assert os.path.exists(path + "-symbol.json")
    assert os.path.exists(path + "-0000.params")
    sb = gluon.SymbolBlock.imports(path + "-symbol.json", ["data"],
                                   path + "-0000.params")
    with autograd.pause():
        y1 = sb(x)
    np.testing.assert_allclose(y0.asnumpy(), y1.asnumpy(),
                               rtol=1e-5, atol=1e-5)


def test_double_data_encode_roundtrip():
    t = oproto.TensorProto(dims=[2], data_type=11, double_data=[1.5, -2.5])
    out = oproto.to_array(oproto.TensorProto.decode(t.encode()))
    np.testing.assert_array_equal(out, np.array([1.5, -2.5], np.float64))


def test_clip_tensor_inputs_roundtrip(tmp_path):
    """opset-11 Clip: min/max travel as initializer inputs."""
    from mxnet_tpu.symbol.symbol import create
    from mxnet_tpu import symbol as S
    sym = create("clip", [S.var("data")], {"a_min": -0.5, "a_max": 0.5})
    path = str(tmp_path / "clip.onnx")
    mxonnx.export_model(sym, {}, [(2, 4)], onnx_file_path=path)
    model = oproto.load(path)
    clip_nodes = [n for n in model.graph.node if n.op_type == "Clip"]
    assert len(clip_nodes) == 1 and len(clip_nodes[0].input) == 3
    assert not clip_nodes[0].attribute
    sym2, arg_params, aux2 = mxonnx.import_model(path)
    x = mx.nd.array(np.linspace(-2, 2, 8).reshape(2, 4).astype(np.float32))
    y = eval_symbol(sym2, ["data"], [x], dict(arg_params))
    y = y[0] if isinstance(y, list) else y
    np.testing.assert_allclose(y.asnumpy(),
                               np.clip(x.asnumpy(), -0.5, 0.5))


def test_dense_no_flatten_roundtrip(tmp_path):
    """flatten=False Dense on 3-D input exports as MatMul+Add, not Gemm."""
    net = nn.HybridSequential()
    net.add(nn.Dense(6, flatten=False))
    net.initialize(mx.init.Xavier())
    x = mx.nd.array(np.random.RandomState(0).randn(2, 3, 4)
                    .astype(np.float32))
    with autograd.pause():
        y0 = net(x)
    path = str(tmp_path / "fc3d")
    net.export(path)
    onnx_path = path + ".onnx"
    mxonnx.export_model(path + "-symbol.json", path + "-0000.params",
                        [(2, 3, 4)], onnx_file_path=onnx_path)
    ops = [n.op_type for n in oproto.load(onnx_path).graph.node]
    assert "Gemm" not in ops and "MatMul" in ops
    sym, arg_params, aux_params = mxonnx.import_model(onnx_path)
    y1 = eval_symbol(sym, ["data"], [x], {**arg_params, **aux_params})
    y1 = y1[0] if isinstance(y1, list) else y1
    np.testing.assert_allclose(y0.asnumpy(), y1.asnumpy(),
                               rtol=1e-5, atol=1e-5)


def test_frozen_params_export_as_args(tmp_path):
    """grad_req='null' freezing must not reclassify weights as aux."""
    from mxnet_tpu.ndarray import utils as nd_utils
    net = nn.HybridSequential()
    net.add(nn.Dense(4))
    net.initialize()
    with autograd.pause():
        net(mx.nd.zeros((1, 3)))
    for p in net.collect_params().values():
        p.grad_req = "null"
    path = str(tmp_path / "frozen")
    net.export(path)
    loaded = nd_utils.load(path + "-0000.params")
    assert all(k.startswith("arg:") for k in loaded), sorted(loaded)


def test_export_params_layout(tmp_path):
    """Exported params use the reference's arg:/aux: key convention."""
    from mxnet_tpu.ndarray import utils as nd_utils
    net = nn.HybridSequential()
    net.add(nn.Conv2D(4, kernel_size=1), nn.BatchNorm())
    net.initialize()
    with autograd.pause():
        net(mx.nd.zeros((1, 2, 4, 4)))
    path = str(tmp_path / "m")
    net.export(path)
    loaded = nd_utils.load(path + "-0000.params")
    kinds = {k.split(":", 1)[0] for k in loaded}
    assert kinds == {"arg", "aux"}
    aux = [k for k in loaded if k.startswith("aux:")]
    assert any("running_mean" in k for k in aux)
    assert any("running_var" in k for k in aux)


def test_import_splits_aux_params(tmp_path):
    """BN moving stats come back in aux_params, matching the symbol's
    own arg/aux classification (the reference import contract)."""
    net = nn.HybridSequential()
    net.add(nn.Conv2D(4, kernel_size=1), nn.BatchNorm())
    net.initialize()
    with autograd.pause():
        net(mx.nd.zeros((1, 2, 4, 4)))
    path = str(tmp_path / "m")
    net.export(path)
    mxonnx.export_model(path + "-symbol.json", path + "-0000.params",
                        [(1, 2, 4, 4)], onnx_file_path=path + ".onnx")
    sym, arg_params, aux_params = mxonnx.import_model(path + ".onnx")
    assert set(aux_params) == set(sym.list_auxiliary_states())
    assert len(aux_params) == 2  # moving mean + var
    assert not set(arg_params) & set(aux_params)


def test_softmaxoutput_label_not_exported(tmp_path):
    """The dropped label input must not become a dangling graph input."""
    from mxnet_tpu.symbol.symbol import create
    from mxnet_tpu import symbol as S
    fc = create("FullyConnected", [S.var("data"), S.var("w"), S.var("b")],
                {"num_hidden": 3})
    out = create("SoftmaxOutput", [fc, S.var("softmax_label")], {})
    rs = np.random.RandomState(0)
    params = {"w": mx.nd.array(rs.randn(3, 4).astype(np.float32)),
              "b": mx.nd.array(np.zeros(3, np.float32))}
    path = str(tmp_path / "so.onnx")
    # only ONE input shape: the label consumes no slot
    mxonnx.export_model(out, params, [(2, 4)], onnx_file_path=path)
    model = oproto.load(path)
    assert [i.name for i in model.graph.input] == ["data"]


def test_export_internal_multi_output_consumption_raises(tmp_path):
    from mxnet_tpu.symbol.symbol import create
    from mxnet_tpu import symbol as S
    bn = create("BatchNorm", [S.var("data"), S.var("g"), S.var("b"),
                              S.var("mm"), S.var("mv")],
                {"fix_gamma": False})
    uses_mean = create("relu", [bn[1]], {})
    from mxnet_tpu.base import MXNetError
    with pytest.raises(MXNetError, match="output 1"):
        mxonnx.export_model(uses_mean, {}, [(1, 2, 4, 4)],
                            onnx_file_path=str(tmp_path / "x.onnx"))


def test_import_dropout_mask_unused_ok_consumed_raises(tmp_path):
    base = oproto.GraphProto(name="g")
    base.node.append(oproto.NodeProto(op_type="Dropout", input=["x"],
                                      output=["y", "mask"], name="d0"))
    base.input.append(oproto.make_tensor_value_info("x", 1, (2, 3)))
    base.output.append(oproto.make_tensor_value_info("y", 1, (2, 3)))
    m = oproto.ModelProto(ir_version=7, graph=base,
                          opset_import=[oproto.OperatorSetIdProto(version=11)])
    p = str(tmp_path / "ok.onnx")
    oproto.save(m, p)
    sym, _, _ = mxonnx.import_model(p)  # unused mask: fine

    base.node.append(oproto.NodeProto(op_type="Relu", input=["mask"],
                                      output=["z"], name="r0"))
    base.output.append(oproto.make_tensor_value_info("z", 1, (2, 3)))
    p2 = str(tmp_path / "bad.onnx")
    oproto.save(m, p2)
    from mxnet_tpu.base import MXNetError
    with pytest.raises(MXNetError, match="mask"):
        mxonnx.import_model(p2)


def test_symbolblock_nested_export(tmp_path):
    """A SymbolBlock inside a parent block must trace symbolically
    (regression: eval_symbol crashed on Symbol inputs)."""
    inner = nn.HybridSequential()
    inner.add(nn.Dense(6, activation="relu"))
    inner.initialize(mx.init.Xavier())
    x = mx.nd.array(np.random.RandomState(0).randn(2, 4).astype(np.float32))
    with autograd.pause():
        inner(x)
    ipath = str(tmp_path / "inner")
    inner.export(ipath)
    sb = gluon.SymbolBlock.imports(ipath + "-symbol.json", ["data"],
                                   ipath + "-0000.params")
    outer = nn.HybridSequential()
    outer.add(sb, nn.Dense(3))
    outer.initialize(mx.init.Xavier())
    with autograd.pause():
        y0 = outer(x)
    opath = str(tmp_path / "outer")
    outer.export(opath)
    reloaded = gluon.SymbolBlock.imports(opath + "-symbol.json", ["data"],
                                         opath + "-0000.params")
    with autograd.pause():
        y1 = reloaded(x)
    np.testing.assert_allclose(y0.asnumpy(), y1.asnumpy(),
                               rtol=1e-5, atol=1e-5)
