"""CustomOp bridge (reference tests: test_operator.py ``test_custom_op``;
``python/mxnet/operator.py`` + ``src/operator/custom/custom.cc``).

The reference-style scenario: define softmax as a CustomOp, use it
imperatively, in a Symbol graph, and train a small MLP through Module —
the custom backward must drive learning."""
import numpy as np
import pytest

import mxnet_tpu as mx
import mxnet_tpu.operator as mxop


class Softmax(mxop.CustomOp):
    def forward(self, is_train, req, in_data, out_data, aux):
        x = in_data[0].asnumpy()
        y = np.exp(x - x.max(axis=1, keepdims=True))
        y /= y.sum(axis=1, keepdims=True)
        self.assign(out_data[0], req[0], y)

    def backward(self, req, out_grad, in_data, out_data, in_grad, aux):
        # fused softmax+CE gradient: label arrives as the second input
        lbl = in_data[1].asnumpy().astype("int32")
        y = out_data[0].asnumpy().copy()
        y[np.arange(lbl.shape[0]), lbl] -= 1.0
        self.assign(in_grad[0], req[0], y / lbl.shape[0])
        self.assign(in_grad[1], req[1], np.zeros_like(
            in_data[1].asnumpy()))


@mxop.register("test_softmax")
class SoftmaxProp(mxop.CustomOpProp):
    def __init__(self):
        super().__init__(need_top_grad=False)

    def list_arguments(self):
        return ["data", "label"]

    def list_outputs(self):
        return ["output"]

    def infer_shape(self, in_shape):
        return [in_shape[0], (in_shape[0][0],)], [in_shape[0]], []

    def create_operator(self, ctx, shapes, dtypes):
        return Softmax()


class Scale2(mxop.CustomOp):
    def forward(self, is_train, req, in_data, out_data, aux):
        self.assign(out_data[0], req[0], in_data[0].asnumpy() * 2.0)

    def backward(self, req, out_grad, in_data, out_data, in_grad, aux):
        self.assign(in_grad[0], req[0], out_grad[0].asnumpy() * 2.0)


@mxop.register("test_scale2")
class Scale2Prop(mxop.CustomOpProp):
    def create_operator(self, ctx, shapes, dtypes):
        return Scale2()


def test_custom_imperative_forward():
    x = mx.nd.array(np.arange(6, dtype="float32").reshape(2, 3))
    out = mx.nd.Custom(x, op_type="test_scale2")
    np.testing.assert_allclose(out.asnumpy(), np.arange(6).reshape(2, 3)
                               * 2.0)


def test_custom_autograd_backward():
    from mxnet_tpu import autograd

    x = mx.nd.array(np.ones((2, 3), "float32"))
    autograd.mark_variables([x], [mx.nd.zeros((2, 3))])
    with autograd.record():
        y = mx.nd.Custom(x, op_type="test_scale2")
        loss = y.sum()
    loss.backward()
    np.testing.assert_allclose(x.grad.asnumpy(), 2.0 * np.ones((2, 3)))


def test_custom_symbolic_forward_backward():
    data = mx.sym.Variable("data")
    label = mx.sym.Variable("label")
    net = mx.sym.Custom(data, label, op_type="test_softmax", name="sm")
    rs = np.random.RandomState(0)
    x = rs.randn(4, 5).astype("float32")
    lbl = np.array([0, 2, 1, 4], "float32")
    ex = net.bind(mx.cpu(), {"data": mx.nd.array(x),
                             "label": mx.nd.array(lbl)},
                  args_grad={"data": mx.nd.zeros((4, 5)),
                             "label": mx.nd.zeros((4,))})
    ex.forward(is_train=True)
    expect = np.exp(x - x.max(1, keepdims=True))
    expect /= expect.sum(1, keepdims=True)
    np.testing.assert_allclose(ex.outputs[0].asnumpy(), expect, rtol=1e-5)
    ex.backward()
    ref = expect.copy()
    ref[np.arange(4), lbl.astype(int)] -= 1.0
    np.testing.assert_allclose(ex.grad_dict["data"].asnumpy(), ref / 4,
                               rtol=1e-5, atol=1e-6)


def test_custom_softmax_trains_mlp():
    """Reference 'done' criterion: an MLP whose loss layer is a CustomOp
    learns through Module.fit (split path — Custom is not fusable)."""
    rs = np.random.RandomState(3)
    X = rs.randn(120, 10).astype("float32")
    w = rs.randn(10, 3).astype("float32")
    y = (X @ w).argmax(axis=1).astype("float32")
    it = mx.io.NDArrayIter(X, y, batch_size=20, shuffle=True,
                           label_name="label")
    data = mx.sym.Variable("data")
    fc = mx.sym.FullyConnected(data, num_hidden=3, name="fc")
    net = mx.sym.Custom(fc, mx.sym.Variable("label"),
                        op_type="test_softmax", name="loss")
    mod = mx.mod.Module(net, context=mx.cpu(), label_names=("label",))
    mod.fit(it, num_epoch=30, optimizer="sgd",
            initializer=mx.init.Xavier(),
            optimizer_params={"learning_rate": 1.0})
    assert mod._fused is None or not getattr(mod, "_fused_ran", False)
    score = dict(mod.score(it, mx.metric.Accuracy(label_names=("label",))))
    assert score["accuracy"] > 0.9, score


def test_custom_unknown_op_type_raises():
    with pytest.raises(mx.base.MXNetError):
        mx.nd.Custom(mx.nd.zeros((2, 2)), op_type="nope")


_TPU_WORKER = r'''
import os
import sys
import threading
sys.path.insert(0, ".")
import numpy as np
import jax
import mxnet_tpu as mx
import mxnet_tpu.operator as mxop

# bounded discovery: a chip that another process holds can keep
# jax.devices() waiting (see accel_worker_util / cross_backend_worker)
_found = []
_t = threading.Thread(target=lambda: _found.append(jax.devices()),
                      daemon=True)
_t.start()
_t.join(90)
if not _found:
    print("SKIP no accelerator")
    sys.stdout.flush()
    os._exit(0)
kind = getattr(_found[0][0], "device_kind", "cpu")
if "TPU" not in kind.upper() and _found[0][0].platform == "cpu":
    print("SKIP no accelerator")
    sys.exit(0)


class DeviceGelu(mxop.CustomOp):
    """Written with mx.nd ops only -> traces into the XLA program and
    runs ON THE CHIP (no host callback)."""

    def forward(self, is_train, req, in_data, out_data, aux):
        x = in_data[0]
        y = 0.5 * x * (1.0 + mx.nd.tanh(
            0.7978845608 * (x + 0.044715 * x * x * x)))
        self.assign(out_data[0], req[0], y)

    def backward(self, req, out_grad, in_data, out_data, in_grad, aux):
        x = in_data[0]
        t = mx.nd.tanh(0.7978845608 * (x + 0.044715 * x * x * x))
        dt = (1.0 - t * t) * 0.7978845608 * (1.0 + 3 * 0.044715 * x * x)
        self.assign(in_grad[0], req[0],
                    out_grad[0] * (0.5 * (1.0 + t) + 0.5 * x * dt))


@mxop.register("device_gelu")
class DeviceGeluProp(mxop.CustomOpProp):
    def __init__(self):
        super().__init__(need_top_grad=True)

    def infer_shape(self, in_shape):
        return [in_shape[0]], [in_shape[0]], []

    def create_operator(self, ctx, shapes, dtypes):
        return DeviceGelu()


rs = np.random.RandomState(0)
xv = rs.randn(4, 8).astype("float32")

# imperative forward + autograd backward on the TPU
from mxnet_tpu import autograd
x = mx.nd.array(xv, ctx=mx.current_context())
x.attach_grad()
with autograd.record():
    y = mx.nd.Custom(x, op_type="device_gelu")
    loss = (y * y).sum()
loss.backward()
ref = 0.5 * xv * (1.0 + np.tanh(0.7978845608 * (xv + 0.044715 * xv**3)))
np.testing.assert_allclose(y.asnumpy(), ref, rtol=1e-2, atol=1e-3)
assert abs(x.grad.asnumpy()).sum() > 0
print("imperative custom op on", kind, "OK")

# symbolic: the custom op inside a bound graph, fwd + bwd on the TPU
data = mx.sym.Variable("data")
net = mx.sym.Custom(data, op_type="device_gelu", name="gelu")
net = mx.sym.FullyConnected(net, num_hidden=3, name="fc")
net = mx.sym.SoftmaxOutput(net, name="softmax")
exe = net.simple_bind(mx.current_context(), data=(4, 8))
exe.arg_dict["fc_weight"][:] = rs.randn(3, 8).astype("float32") * 0.1
exe.forward(is_train=True, data=xv,
            softmax_label=np.zeros(4, "float32"))
exe.backward()
assert abs(exe.grad_dict["fc_weight"].asnumpy()).sum() > 0
print("symbolic custom op on", kind, "OK")
print("CUSTOM_OP_TPU_OK")
'''


def test_custom_op_on_accelerator(tmp_path):
    """VERDICT r3 task 5: a CustomOp written with mx.nd ops traces into
    the XLA program and runs on the REAL accelerator — no host
    callback, no JAX_PLATFORMS=cpu pin (the callback tier remains for
    host-bound ops and is what the other tests in this file cover)."""
    from accel_worker_util import run_accel_worker

    script = tmp_path / "worker.py"
    script.write_text(_TPU_WORKER)
    res = run_accel_worker([str(script)])
    assert res.returncode == 0, res.stdout + res.stderr
    assert "CUSTOM_OP_TPU_OK" in res.stdout, res.stdout


class FwdOnly(mxop.CustomOp):
    def forward(self, is_train, req, in_data, out_data, aux):
        self.assign(out_data[0], req[0], in_data[0] * 2.0)
    # backward intentionally not implemented (inference-only op)


@mxop.register("test_fwd_only")
class FwdOnlyProp(mxop.CustomOpProp):
    def __init__(self):
        super().__init__(need_top_grad=False)

    def infer_shape(self, in_shape):
        return [in_shape[0]], [in_shape[0]], []

    def create_operator(self, ctx, shapes, dtypes):
        return FwdOnly()


def test_custom_op_forward_only():
    """An inference-only CustomOp (backward left NotImplemented) must
    run on the device tier; the error surfaces only if gradients are
    requested (reference contract)."""
    x = mx.nd.array(np.ones((2, 3), "float32"))
    y = mx.nd.Custom(x, op_type="test_fwd_only")
    np.testing.assert_allclose(y.asnumpy(), 2 * np.ones((2, 3)))
