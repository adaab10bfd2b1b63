"""The built-in metrics reduce ``NDArray`` predictions on the device.

Each of the eight metrics has one jitted reduction (``metric.py``); only
its scalars cross to the host, and only when the metric is read.  The
numpy body of every metric stays as the reference the device path is held
to, on the same values.
"""
import gc
import math
import weakref

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import compile_cache, metric as metric_mod
from mxnet_tpu.ndarray import NDArray

N, C = 24, 40
BF16 = np.dtype(ml_dtypes.bfloat16)
F32 = np.dtype(np.float32)


def _scores(rng, rows, dtype):
    """(rows, C) positive scores, tie-free in every row and exact in
    bfloat16: a permutation of 1/64 .. C/64."""
    perm = np.stack([rng.permutation(C) for _ in range(rows)])
    return ((perm + 1) / 64.0).astype(dtype)


def _regression(rng, shape, dtype):
    return rng.normal(size=shape).astype(dtype)


# id -> (factory, make(rng, dtype) -> (label or None, pred))
CASES = {
    "ce-1d": (lambda: mx.metric.CrossEntropy(),
              lambda r, dt: (r.integers(0, C, N), _scores(r, N, dt))),
    "ce-2d": (lambda: mx.metric.CrossEntropy(),
              lambda r, dt: (r.integers(0, C, (4, N // 4)),
                             _scores(r, N, dt))),
    "acc-1d": (lambda: mx.metric.Accuracy(),
               lambda r, dt: (r.integers(0, C, N), _scores(r, N, dt))),
    "acc-2d": (lambda: mx.metric.Accuracy(axis=2),
               lambda r, dt: (r.integers(0, C, (4, N // 4)),
                              _scores(r, N, dt).reshape(4, N // 4, C))),
    "acc-ids": (lambda: mx.metric.Accuracy(),
                lambda r, dt: (r.integers(0, 3, N),
                               r.integers(0, 3, N).astype(dt))),
    "top2-1d": (lambda: mx.metric.TopKAccuracy(top_k=2),
                lambda r, dt: (r.integers(0, C, N), _scores(r, N, dt))),
    "top5-1d": (lambda: mx.metric.TopKAccuracy(top_k=5),
                lambda r, dt: (r.integers(0, C, N), _scores(r, N, dt))),
    "top5-2d": (lambda: mx.metric.TopKAccuracy(top_k=5),
                lambda r, dt: (r.integers(0, C, (N, 1)),
                               _scores(r, N, dt))),
    "top50-1d": (lambda: mx.metric.TopKAccuracy(top_k=50),  # more than C
                 lambda r, dt: (r.integers(0, C, N), _scores(r, N, dt))),
    "ppl-1d": (lambda: mx.metric.Perplexity(),
               lambda r, dt: (r.integers(0, C, N), _scores(r, N, dt))),
    "ppl-2d": (lambda: mx.metric.Perplexity(),
               lambda r, dt: (r.integers(0, C, (4, N // 4)),
                              _scores(r, N, dt).reshape(4, N // 4, C))),
    "ppl-ignore": (lambda: mx.metric.Perplexity(ignore_label=0),
                   lambda r, dt: (r.integers(0, 3, (4, N // 4)),
                                  _scores(r, N, dt).reshape(4, N // 4, C))),
    "ppl-ignore-last": (lambda: mx.metric.Perplexity(ignore_label=-1),
                        lambda r, dt: (r.integers(-1, 3, N),
                                       _scores(r, N, dt))),
    "loss-1d": (lambda: mx.metric.Loss(),
                lambda r, dt: (None, _regression(r, (N,), dt))),
    "loss-2d": (lambda: mx.metric.Loss(),
                lambda r, dt: (None, _regression(r, (N, 3), dt))),
}
for _name, _make in (("mae", mx.metric.MAE), ("mse", mx.metric.MSE),
                     ("rmse", mx.metric.RMSE)):
    CASES[_name + "-1d"] = (
        _make, lambda r, dt: (_regression(r, (N,), F32),
                              _regression(r, (N,), dt)))
    CASES[_name + "-2d"] = (
        _make, lambda r, dt: (_regression(r, (N, 1), F32),
                              _regression(r, (N, 1), dt)))
    CASES[_name + "-mixed"] = (
        _make, lambda r, dt: (_regression(r, (N,), F32),
                              _regression(r, (N, 1), dt)))

# one case per metric, for the tests that are about the plumbing
ONE_EACH = ["ce-1d", "acc-1d", "top5-1d", "ppl-ignore", "loss-2d",
            "mae-1d", "mse-1d", "rmse-1d"]


def _batch(case, seed, dtype=F32):
    label, pred = CASES[case][1](np.random.default_rng(seed), dtype)
    if label is not None and label.dtype.kind == "i":
        label = label.astype(np.float32)    # what NDArrayIter hands over
    return label, pred


def _nd(x):
    return None if x is None else NDArray(jnp.asarray(x))


def _update(m, label, pred):
    m.update(None if label is None else [label], [pred])


def _value(m):
    (_, value), = m.get_name_value()
    return float(value)


# -- the device path against the numpy body ------------------------------

@pytest.mark.parametrize("dtype", [F32, BF16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_device_path_equals_numpy_path(case, dtype):
    on_device, on_host = CASES[case][0](), CASES[case][0]()
    for seed in range(3):
        label, pred = _batch(case, seed, dtype)
        _update(on_device, _nd(label), _nd(pred))
        _update(on_host, label, pred)
    assert (on_device.device_updates, on_device.host_updates) == (3, 0)
    assert (on_host.device_updates, on_host.host_updates) == (0, 3)
    assert on_device.num_inst == on_host.num_inst
    assert on_device.num_nonfinite == on_host.num_nonfinite == 0
    if case.startswith("loss") and dtype == BF16:
        # numpy sums bfloat16 IN bfloat16 (8 bits); the device sums in
        # float32, so it is held to the exact sum, and numpy only loosely
        exact = sum(float(np.sum(_batch(case, s, dtype)[1]
                                 .astype(np.float64))) for s in range(3))
        assert float(on_device.sum_metric) == pytest.approx(exact, rel=1e-5)
        assert _value(on_device) == pytest.approx(_value(on_host), abs=0.05)
        return
    assert _value(on_device) == pytest.approx(_value(on_host), rel=2e-6,
                                              abs=1e-7)


def test_argmax_takes_the_first_maximum_as_numpy_does():
    pred = np.zeros((6, 5), np.float32)
    pred[:, 1] = pred[:, 3] = 1.0               # every row ties 1 with 3
    label = np.array([1, 3, 1, 3, 0, 1], np.float32)
    on_device, on_host = mx.metric.Accuracy(), mx.metric.Accuracy()
    _update(on_device, _nd(label), _nd(pred))
    _update(on_host, label, pred)
    assert on_device.get() == on_host.get() == ("accuracy", 0.5)


def test_top_k_counts_a_tie_for_the_label():
    """What CHANGES.md says of ties: a row is a hit when fewer than k
    classes score strictly higher than its label (numpy's unstable
    argsort leaves tied classes in no defined order)."""
    pred = np.array([[0.5, 0.5, 0.5, 0.1],      # label ties for first
                     [0.9, 0.8, 0.5, 0.5],      # label ties for third
                     [0.9, 0.8, 0.7, 0.1]], np.float32)
    label = np.array([2, 3, 3], np.float32)
    m = mx.metric.TopKAccuracy(top_k=2)
    _update(m, _nd(label), _nd(pred))
    assert (m.sum_metric, m.num_inst) == (1, 3)


def test_top_k_label_outside_the_classes_is_a_miss():
    pred = _scores(np.random.default_rng(0), 4, F32)
    for bad in (-1.0, float(C)):
        label = np.array([bad] * 4, np.float32)
        on_device = mx.metric.TopKAccuracy(top_k=C)
        on_host = mx.metric.TopKAccuracy(top_k=C)
        _update(on_device, _nd(label), _nd(pred))
        _update(on_host, label, pred)
        assert on_device.get() == on_host.get() == ("top_k_accuracy_%d" % C,
                                                    0.0)


@pytest.mark.parametrize("make", [mx.metric.CrossEntropy,
                                  mx.metric.Perplexity],
                         ids=["ce", "perplexity"])
def test_label_outside_the_classes_drops_the_batch(make):
    """numpy raises IndexError; the device cannot, and must not clamp in
    silence: the pick reads NaN and the guard drops and counts it."""
    label, pred = _batch("ce-1d", 0)
    bad = label.copy()
    bad[3] = C + 2
    with pytest.raises(IndexError):
        _update(make(), bad, pred)
    m = make()
    _update(m, _nd(bad), _nd(pred))
    assert (m.num_inst, m.num_nonfinite) == (0, 1)
    _update(m, _nd(label), _nd(pred))
    assert m.num_inst > 0 and math.isfinite(_value(m))


@pytest.mark.parametrize("case", ["ce-1d", "acc-1d", "top5-1d"])
def test_shape_mismatch_raises_from_update_as_on_the_host(case):
    """Shapes are checked while the reduction is traced, so the error
    leaves ``update`` itself, every time, and nothing is left pending."""
    label, pred = _batch(case, 0)
    on_host, m = CASES[case][0](), CASES[case][0]()
    with pytest.raises(Exception) as on_the_host:
        _update(on_host, label[:-1], pred)
    for _ in range(2):
        with pytest.raises(on_the_host.type):
            _update(m, _nd(label[:-1]), _nd(pred))
    assert m._increments == []
    with pytest.raises(ValueError, match="does not match"):
        m.update([_nd(label)], [_nd(pred), _nd(pred)])


# -- the non-finite guard --------------------------------------------------

@pytest.mark.parametrize("case", ONE_EACH)
def test_nonfinite_batch_is_dropped_and_the_next_lands(case, caplog):
    label, pred = _batch(case, 0)
    poisoned = pred.copy()
    if label is None or case[:3] in ("mae", "mse", "rms"):
        poisoned.ravel()[1] = np.inf
    else:                                   # the picked score itself
        flat_label = label.ravel().astype(int)
        poisoned.reshape(-1, C)[1, flat_label[1]] = np.nan
    clean_label, clean_pred = _batch(case, 1)
    on_device, on_host = CASES[case][0](), CASES[case][0]()
    with caplog.at_level("WARNING", logger="mxnet_tpu.metric"):
        _update(on_device, _nd(label), _nd(poisoned))
        first = (on_device.num_inst, on_device.num_nonfinite)
    _update(on_host, label, poisoned)
    assert first == (on_host.num_inst, on_host.num_nonfinite)
    guarded = case[:3] not in ("acc", "top")    # counts cannot be NaN
    assert first == ((0, 1) if guarded else (on_host.num_inst, 0))
    assert bool(caplog.records) == guarded
    _update(on_device, _nd(clean_label), _nd(clean_pred))
    _update(on_host, clean_label, clean_pred)
    assert on_device.num_nonfinite == on_host.num_nonfinite
    assert on_device.num_inst == on_host.num_inst > 0
    assert _value(on_device) == pytest.approx(_value(on_host), rel=2e-6)
    on_device.reset()
    assert on_device.num_nonfinite == 0


# -- nothing but scalars crosses, and only at a read -----------------------

@pytest.fixture
def host_traffic(monkeypatch):
    """``asnumpy`` raises and ``jax.device_get`` calls are counted (the
    CPU backend's transfer guard says nothing, so the calls are what can
    be watched here)."""
    def refuse(self):
        raise AssertionError("asnumpy() on a %s array" % (self.shape,))

    gets = []
    real = jax.device_get

    def counting(tree):
        gets.append(jax.tree.leaves(tree))
        return real(tree)

    monkeypatch.setattr(NDArray, "asnumpy", refuse)
    monkeypatch.setattr(jax, "device_get", counting)
    return gets


@pytest.mark.parametrize("case", ONE_EACH)
def test_update_moves_no_prediction_to_the_host(case, host_traffic):
    m = CASES[case][0]()
    expect = CASES[case][0]()
    for seed in range(4):
        label, pred = _batch(case, seed)
        _update(expect, label, pred)
        _update(m, _nd(label), _nd(pred))
    assert host_traffic == []               # update fetched nothing
    assert (m.device_updates, m.host_updates) == (4, 0)
    assert m.num_inst == expect.num_inst    # the first read: one transfer
    assert len(host_traffic) == 1
    assert all(np.ndim(leaf) == 0 for leaf in host_traffic[0])
    m.sum_metric, m.num_nonfinite, m.get(), m.get_name_value(), str(m)
    assert len(host_traffic) == 1           # nothing pending: no transfer
    label, pred = _batch(case, 9)
    _update(m, _nd(label), _nd(pred))
    _update(expect, label, pred)
    assert _value(m) == pytest.approx(_value(expect), rel=2e-6)
    assert len(host_traffic) == 2
    _update(m, _nd(label), _nd(pred))
    m.reset()                               # discards, fetches nothing
    assert len(host_traffic) == 2
    assert (m.num_inst, m.device_updates, m.host_updates) == (0, 0, 0)
    assert math.isnan(_value(m))
    assert len(host_traffic) == 2


def test_numpy_inputs_take_the_host_path_and_mixed_pairs_split():
    label, pred = _batch("ce-1d", 0)
    m = mx.metric.CrossEntropy()
    m.update([label, _nd(label)], [pred, _nd(pred)])
    assert (m.device_updates, m.host_updates) == (1, 1)
    assert m.num_inst == 2 * N
    # a list is no NDArray either
    acc = mx.metric.Accuracy()
    acc.update([[1, 0]], [[[0.3, 0.7], [0.6, 0.4]]])
    assert (acc.device_updates, acc.host_updates) == (0, 1)
    assert acc.get() == ("accuracy", 1.0)
    # an NDArray prediction with a numpy label still reduces on the device
    acc.update([np.array([1.0, 1.0])], [_nd(np.eye(2, dtype=np.float32))])
    assert (acc.device_updates, acc.host_updates) == (1, 1)
    assert acc.get() == ("accuracy", 0.75)


def test_pending_increments_hold_no_prediction():
    label, pred = _batch("ce-1d", 0)
    m = mx.metric.CrossEntropy()
    array = jnp.asarray(pred)
    alive = weakref.ref(array)
    m.update([_nd(label)], [NDArray(array)])
    del array
    gc.collect()
    assert alive() is None                  # before anybody read the metric
    assert m.num_inst == N


def test_pending_list_is_bounded_and_keeps_its_order(monkeypatch):
    monkeypatch.setattr(metric_mod, "_MAX_PENDING", 8)
    m = mx.metric.Loss()
    total = 0.0
    for i in range(30):
        m.update(None, [_nd(np.full((2,), float(i), np.float32))])
        total += 2.0 * i
        assert len(m._increments) <= 8
    assert (m.sum_metric, m.num_inst) == (total, 60)
    assert m._increments == []


def test_reads_at_any_pending_length_compile_nothing():
    """The benchmark counts every request JAX makes of its compilation
    cache: after the first update and the first read, a read at a new
    pending length must add none (an eager ``jnp.stack`` of the pending
    scalars would add one per length)."""
    compile_cache.ensure_initialized()
    requests = lambda: compile_cache.cache_stats()["requests"]
    label, pred = _batch("ce-1d", 0)
    label = np.concatenate([label, label[:3]])      # a shape of its own
    pred = np.concatenate([pred, pred[:3]])
    m = mx.metric.create(["ce", "acc", "top_k_accuracy", "perplexity",
                          "mae", "loss"], top_k=3)
    before = requests()

    def update():
        for child in m.metrics:
            if child.name == "mae":
                child.update([_nd(label)], [_nd(label + 1)])
            else:
                child.update([_nd(label)], [_nd(pred)])

    update()
    m.get_name_value()
    m.reset()
    assert requests() > before          # the counter does see a compile
    warm = requests()
    for pending in (1, 2, 3, 5, 9):
        for _ in range(pending):
            update()
        values = dict(m.get_name_value())
        assert [c.sum_metric for c in m.metrics] and values
    assert sum(c.device_updates for c in m.metrics) == 20 * 6
    assert requests() == warm


def test_label_on_another_device_is_moved_to_the_prediction():
    devices = jax.devices()
    label, pred = _batch("ce-1d", 0)
    m = mx.metric.CrossEntropy()
    m.update([NDArray(jax.device_put(label, devices[1]))],
             [NDArray(jax.device_put(pred, devices[0]))])
    expect = mx.metric.CrossEntropy()
    expect.update([label], [pred])
    assert _value(m) == pytest.approx(_value(expect), rel=2e-6)


# -- the surface users and the benchmark lean on ---------------------------

def test_user_subclass_assigning_the_sums_still_works():
    class Errors(mx.metric.EvalMetric):
        def __init__(self):
            super().__init__("errors")

        def reset(self):
            self.sum_metric = 0.0
            self.num_inst = 0

        def update(self, labels, preds):
            for label, pred in zip(labels, preds):
                wrong = pred.asnumpy().argmax(1) != label.asnumpy()
                self.sum_metric += wrong.sum()
                self.num_inst += len(wrong)

    m = Errors()
    assert (m.device_updates, m.host_updates, m.num_nonfinite) == (0, 0, 0)
    label = np.array([0.0, 1.0, 1.0, 0.0], np.float32)
    pred = np.eye(2, dtype=np.float32)[[0, 1, 0, 0]]
    m.update([_nd(label)], [_nd(pred)])
    m.update([_nd(label)], [_nd(pred)])
    assert m.get() == ("errors", 0.25)
    assert (m.sum_metric, m.num_inst) == (2.0, 8)
    m.reset()
    assert (m.sum_metric, m.num_inst) == (0.0, 0)


def test_subclass_of_a_builtin_overriding_reset_sees_updates_in_order():
    """An assignment is a read: it folds what was pending first, so the
    assigned value stands and later updates add to it."""
    class FromTen(mx.metric.Loss):
        def reset(self):
            self.sum_metric = 10.0
            self.num_inst = 1

    m = FromTen()
    m.update(None, [_nd(np.array([1.0, 2.0], np.float32))])
    m.reset()
    m.update(None, [_nd(np.array([4.0], np.float32))])
    assert (m.sum_metric, m.num_inst) == (14.0, 2)


def test_step_loss_from_running_sums_is_exact():
    """``benchmark/jobs/fit.py`` takes each step's loss as a difference of
    the running sums, read after every update: float64 sums on the host
    keep that difference at float32's own rounding however long the run."""
    m = mx.metric.CrossEntropy()
    seen = (0.0, 0)
    for step in range(40):
        label, pred = _batch("ce-1d", step)
        _update(m, _nd(label), _nd(pred))
        total, count = float(m.sum_metric), int(m.num_inst)
        assert type(m.sum_metric) is float          # float64, not float32
        alone = mx.metric.CrossEntropy()
        _update(alone, label, pred)
        got = (total - seen[0]) / (count - seen[1])
        assert got == pytest.approx(_value(alone), rel=2e-6)
        seen = (total, count)


def test_composite_forwards_and_f1_and_custom_stay_on_the_host():
    label, pred = _batch("ce-1d", 0)
    comp = mx.metric.CompositeEvalMetric([mx.metric.Accuracy(),
                                          mx.metric.CrossEntropy()])
    ref = mx.metric.CompositeEvalMetric([mx.metric.Accuracy(),
                                         mx.metric.CrossEntropy()])
    comp.update([_nd(label)], [_nd(pred)])
    ref.update([label], [pred])
    assert [n for n, _ in comp.get_name_value()] == ["accuracy",
                                                     "cross-entropy"]
    for (_, a), (_, b) in zip(comp.get_name_value(), ref.get_name_value()):
        assert float(a) == pytest.approx(float(b), rel=2e-6)
    assert [(c.device_updates, c.host_updates) for c in comp.metrics] == \
        [(1, 0), (1, 0)]
    comp.reset()
    assert [c.num_inst for c in comp.metrics] == [0, 0]

    binary = np.array([0.0, 1.0, 1.0, 0.0], np.float32)
    scores = np.array([[.9, .1], [.2, .8], [.6, .4], [.7, .3]], np.float32)
    f1 = mx.metric.F1()
    f1.update([_nd(binary)], [_nd(scores)])
    assert (f1.device_updates, f1.host_updates) == (0, 1)
    assert f1.get()[1] == pytest.approx(2 / 3)
    with pytest.raises(ValueError, match="binary"):
        f1.update([_nd(binary + 1)], [_nd(scores)])

    seen = []

    def feval(label, pred):
        seen.append((type(label), type(pred)))
        return float(np.abs(label - pred.argmax(1)).sum()), len(label)

    custom = mx.metric.create(feval)
    custom.update([_nd(binary)], [_nd(scores)])
    assert seen == [(np.ndarray, np.ndarray)]
    assert (custom.device_updates, custom.host_updates) == (0, 1)
    assert custom.get() == ("feval", 0.25)
    wrapped = mx.metric.np(lambda l, p: float((l == p.argmax(1)).mean()),
                           name="hit")
    wrapped.update([_nd(binary)], [_nd(scores)])
    assert wrapped.get() == ("hit", 0.75)


def test_lazy_metric_still_defers_and_replays():
    label, pred = _batch("ce-1d", 0)
    lazy = mx.metric.LazyEvalMetric(mx.metric.CrossEntropy(), sync_period=3)
    for _ in range(2):
        lazy.update([_nd(label)], [_nd(pred)])
    assert lazy._base.device_updates == 0 and len(lazy._pending) == 2
    lazy.update([_nd(label)], [_nd(pred)])
    assert lazy._base.device_updates == 3 and lazy._pending == []
    ref = mx.metric.CrossEntropy()
    ref.update([label], [pred])
    assert lazy.get()[1] == pytest.approx(ref.get()[1], rel=2e-6)


# -- through Module.fit and score -----------------------------------------

def _mlp():
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=16, name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=4, name="fc2")
    return mx.sym.SoftmaxOutput(net, name="softmax")


class _FedThroughNumpy(mx.metric.CompositeEvalMetric):
    """The same metrics, every input copied to the host first: the numpy
    bodies, as ``fit`` ran them before."""

    def update(self, labels, preds):
        super().update([l.asnumpy() for l in labels],
                       [p.asnumpy() for p in preds])


def _metrics():
    return [mx.metric.Accuracy(), mx.metric.CrossEntropy(),
            mx.metric.TopKAccuracy(top_k=2), mx.metric.Perplexity(),
            mx.metric.MSE()]


def _fit(eval_metric, context=None, **kwargs):
    rng = np.random.default_rng(5)
    x = rng.normal(size=(96, 10)).astype(np.float32)
    y = rng.integers(0, 4, 96).astype(np.float32)
    mx.random.seed(11)
    train = mx.io.NDArrayIter(x, y, batch_size=16)
    mod = mx.mod.Module(_mlp(), context=context or mx.cpu())
    mod.fit(train, num_epoch=2, eval_metric=eval_metric, optimizer="sgd",
            initializer=mx.initializer.Xavier(),
            optimizer_params={"learning_rate": 0.1}, **kwargs)
    return mod, train


def test_fit_fetches_scalars_only_and_counts_its_updates(host_traffic):
    device = mx.metric.CompositeEvalMetric(_metrics())
    mod, train = _fit(device)       # under a refused asnumpy()
    assert host_traffic and all(np.ndim(leaf) == 0
                                for got in host_traffic for leaf in got)
    fetched = len(host_traffic)
    device.get_name_value()         # fit's own epoch-end read folded all
    assert len(host_traffic) == fetched
    for child in device.metrics:
        # the second epoch's six batches, since fit's reset before it
        assert (child.device_updates, child.host_updates) == (6, 0)
        assert child.num_inst == (96 if child.name in (
            "accuracy", "cross-entropy", "top_k_accuracy_2") else 6)
    score = dict(mod.score(train, mx.metric.create(["acc", "ce"])))
    assert 0.0 <= score["accuracy"] <= 1.0 and score["cross-entropy"] > 0


def test_fit_metric_equals_the_same_fit_fed_through_numpy():
    device = mx.metric.CompositeEvalMetric(_metrics())
    _fit(device)
    host = _FedThroughNumpy(_metrics())
    _fit(host)
    assert [c.host_updates for c in host.metrics] == [6] * 5
    got, want = device.get_name_value(), host.get_name_value()
    assert [n for n, _ in got] == [n for n, _ in want]
    for (name, a), (_, b) in zip(got, want):
        assert float(a) == pytest.approx(float(b), abs=1e-6, rel=1e-6), name


def test_fit_over_a_mesh_reduces_the_sharded_output():
    """``dist_tpu_sync`` hands in one global array sharded over the
    devices; the reduction runs over the mesh and ``score``'s labels,
    which sit on one device, are moved to it."""
    contexts = [mx.cpu(i) for i in range(4)]
    device = mx.metric.CompositeEvalMetric(_metrics()[:4])
    mod, train = _fit(device, context=contexts, kvstore="dist_tpu_sync")
    out = mod.get_outputs()[0]._data
    assert len(out.sharding.device_set) == 4
    assert [(c.device_updates, c.host_updates)
            for c in device.metrics] == [(6, 0)] * 4
    single = mx.metric.CompositeEvalMetric(_metrics()[:4])
    _fit(single)
    for (name, a), (_, b) in zip(device.get_name_value(),
                                 single.get_name_value()):
        assert float(a) == pytest.approx(float(b), abs=2e-5), name
    on_mesh = dict(mod.score(train, mx.metric.create(["acc", "ce"])))
    by_numpy = dict(mod.score(train, _FedThroughNumpy(
        [mx.metric.Accuracy(), mx.metric.CrossEntropy()])))
    for name in on_mesh:
        assert float(on_mesh[name]) == pytest.approx(float(by_numpy[name]),
                                                     rel=1e-6), name
