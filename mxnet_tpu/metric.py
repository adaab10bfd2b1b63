"""Evaluation metrics (reference ``python/mxnet/metric.py``, 1,132 LoC).

Full reference family: Accuracy, TopKAccuracy, F1, Perplexity, MAE, MSE,
RMSE, CrossEntropy, Loss, Torch, Caffe, CustomMetric, CompositeEvalMetric,
np() wrapper, create() registry.

**Where a metric is computed.**  ``CrossEntropy``, ``Accuracy``,
``TopKAccuracy``, ``Perplexity``, ``Loss``, ``MAE``, ``MSE`` and ``RMSE``
reduce a prediction that is an ``NDArray`` where it lives: ``update``
dispatches one small jitted program per metric (``jit_metric_<name>`` in a
trace, beside the step's ``jit_step(...)``) and returns without waiting.
Only the reduced increments, a few scalars, ever cross to the host, and
only when somebody reads the metric: ``sum_metric``, ``num_inst``,
``num_nonfinite``, ``get()`` and ``get_name_value()`` first fetch every
increment still on the device (one ``jax.device_get`` for all of them) and
fold them, in order, into the running sums on the host (Python numbers,
float64), through the same non-finite guard as ever.  ``reset()`` discards
them unfetched.  A read sees every update made before it.  The device arithmetic is the
numpy body's: elementwise work in the dtype numpy would promote to, the
picked or differenced values raised to float32, float32 sums.  (Where
numpy accumulates a bfloat16 sum in bfloat16, ``Loss`` and the regression
metrics on bfloat16 inputs, the device sums in float32 and is the closer
of the two.)  A label outside the prediction's classes cannot raise from
the device as numpy's indexing does: ``CrossEntropy`` and ``Perplexity``
read it as NaN, so that batch is dropped and counted in ``num_nonfinite``.

A prediction that is a numpy array or a list goes down the numpy body,
unchanged.  ``F1`` (it raises from the label *values*) and
``CustomMetric`` / ``np`` (their contract is a numpy ``feval``) always
copy their inputs to the host.  ``device_updates`` and ``host_updates``
count, per (label, prediction) pair since the last ``reset()``, which way
the updates went.
"""
from __future__ import annotations

import functools
import logging
import math

import jax
import jax.numpy as jnp
import numpy as _np

from .base import MXNetError, _Registry
from .ndarray import NDArray

_logger = logging.getLogger(__name__)

__all__ = ["EvalMetric", "CompositeEvalMetric", "LazyEvalMetric",
           "Accuracy", "TopKAccuracy",
           "F1", "Perplexity", "MAE", "MSE", "RMSE", "CrossEntropy", "Loss",
           "CustomMetric", "np", "create", "register"]

_registry = _Registry("metric")


def register(klass, *names):
    for n in (names or [klass.__name__.lower()]):
        _registry.register(n, klass)
    return klass


def create(metric, *args, **kwargs):
    if callable(metric):
        return CustomMetric(metric, *args, **kwargs)
    if isinstance(metric, EvalMetric):
        return metric
    if isinstance(metric, list):
        composite = CompositeEvalMetric()
        for child in metric:
            composite.add(create(child, *args, **kwargs))
        return composite
    return _registry.get(str(metric).lower())(*args, **kwargs)


def _as_numpy(x):
    return x.asnumpy() if isinstance(x, NDArray) else _np.asarray(x)


def check_label_shapes(labels, preds, shape=0):
    if shape == 0:
        label_shape, pred_shape = len(labels), len(preds)
    else:
        label_shape, pred_shape = labels.shape, preds.shape
    if label_shape != pred_shape:
        raise ValueError(
            "Shape of labels {} does not match shape of predictions {}"
            .format(label_shape, pred_shape))


def _device_pair(label, pred):
    """The raw arrays of one (label, prediction) pair for a jitted
    reduction, the label placed where the prediction lives (``score``
    hands over the iterator's labels, which may sit on another device
    than the module's outputs; labels are small)."""
    pred = pred.todense()._data
    if not isinstance(label, NDArray):
        return _np.asarray(label), pred
    label = label.todense()._data
    where = pred.sharding
    if label.sharding.device_set != where.device_set:
        if len(where.device_set) > 1:
            from .parallel.sharding import replicated

            where = replicated(where.mesh)
        label = jax.device_put(label, where)
    return label, pred


# pending increments are folded once this many updates wait; the older
# half goes, long finished on the device, so the fold waits for nothing
_MAX_PENDING = 256


def _folded(name):
    """A running sum as an attribute: reading or assigning it first folds
    the increments still on the device, so it is always up to date."""
    private = "_" + name

    def fget(self):
        self._fold()
        return getattr(self, private)

    def fset(self, value):
        self._fold()
        setattr(self, private, value)

    return property(fget, fset)


class EvalMetric:
    sum_metric = _folded("sum_metric")
    num_inst = _folded("num_inst")
    num_nonfinite = _folded("num_nonfinite")

    def __init__(self, name, output_names=None, label_names=None, **kwargs):
        self.name = str(name)
        self.output_names = output_names
        self.label_names = label_names
        self._kwargs = kwargs
        self._increments = []
        # subclasses may override reset()
        self.num_nonfinite = 0
        self.device_updates = 0
        self.host_updates = 0
        self.reset()

    def update_dict(self, label, pred):
        if self.output_names is not None:
            pred = [pred[name] for name in self.output_names]
        else:
            pred = list(pred.values())
        if self.label_names is not None:
            label = [label[name] for name in self.label_names]
        else:
            label = list(label.values())
        self.update(label, pred)

    def update(self, labels, preds):
        raise NotImplementedError

    def _accumulate(self, sum_inc, num_inc):
        """Fold one increment into the running sums — unless it is
        non-finite.  A single NaN batch would otherwise poison
        ``sum_metric`` for the rest of the epoch (nan + x == nan), so a
        bad increment is *dropped* and counted in ``num_nonfinite``
        instead, with a throttled warning so the drop is visible."""
        if not _np.all(_np.isfinite(sum_inc)):
            self.num_nonfinite += 1
            if self.num_nonfinite == 1 or self.num_nonfinite % 100 == 0:
                _logger.warning(
                    "metric %s: dropped non-finite update #%d (value %r); "
                    "the running metric excludes these batches",
                    self.name, self.num_nonfinite, sum_inc)
            return
        self._sum_metric += sum_inc
        self._num_inst += num_inc

    def _reduce_on_device(self, labels, preds, reduce):
        """Dispatch ``reduce(label, pred) -> (sum_inc, num_inc)`` on the
        raw arrays of every pair whose prediction is an NDArray and queue
        what it returns; -> the other pairs, for the numpy body."""
        host, increments = [], []
        for label, pred in zip(labels, preds):
            if isinstance(pred, NDArray):
                increments.append(reduce(*_device_pair(label, pred)))
            else:
                host.append((label, pred))
        self.device_updates += len(increments)
        self.host_updates += len(host)
        self._defer(increments)
        return host

    def _defer(self, increments):
        """Queue one ``update`` call's device increments, a list of
        (sum_inc, num_inc) holding device scalars and Python numbers only
        (never a label or a prediction, so a step's outputs are free as
        soon as the next step replaces them)."""
        if not increments:
            return
        if len(self._increments) >= _MAX_PENDING:
            self._fold(_MAX_PENDING // 2)
        self._increments.append(increments)

    def _fold(self, count=None):
        """Fetch the ``count`` oldest pending updates (all by default) in
        ONE transfer and fold them, in order, into the running sums."""
        if not self._increments:
            return
        taken = self._increments[:count]
        del self._increments[:count]
        # a transfer of the list, not a program: no eager operation whose
        # shape follows the list's length, so nothing compiles here
        for increments in jax.device_get(taken):
            self._fold_update(increments)

    def _fold_update(self, increments):
        """Fold one ``update`` call's increments, now on the host, as
        Python numbers: the running sums are float64 (added as numpy's
        float32 scalars they would stay float32, since numpy 2 lets
        ``0.0 + float32`` be float32)."""
        for sum_inc, num_inc in increments:
            self._accumulate(float(sum_inc), int(num_inc))

    def reset(self):
        self._increments = []  # discarded, not fetched
        self.num_inst = 0
        self.sum_metric = 0.0
        self.num_nonfinite = 0
        self.device_updates = 0
        self.host_updates = 0

    def get(self):
        if self.num_inst == 0:
            return (self.name, float("nan"))
        return (self.name, self.sum_metric / self.num_inst)

    def get_name_value(self):
        name, value = self.get()
        if not isinstance(name, list):
            name = [name]
        if not isinstance(value, list):
            value = [value]
        return list(zip(name, value))

    def __str__(self):
        return "EvalMetric: {}".format(dict(self.get_name_value()))


class CompositeEvalMetric(EvalMetric):
    def __init__(self, metrics=None, name="composite", **kwargs):
        super().__init__(name, **kwargs)
        self.metrics = [create(m) for m in (metrics or [])]

    def add(self, metric):
        self.metrics.append(create(metric))

    def get_metric(self, index):
        return self.metrics[index]

    def update(self, labels, preds):
        for metric in self.metrics:
            metric.update(labels, preds)

    def reset(self):
        for metric in getattr(self, "metrics", []):
            metric.reset()

    def get(self):
        names, values = [], []
        for metric in self.metrics:
            name, value = metric.get()
            names.append(name)
            values.append(value)
        return names, values


class LazyEvalMetric(EvalMetric):
    """Deferred-sync wrapper for the pipelined training loop.

    ``F1``, ``CustomMetric`` and user metrics call ``asnumpy`` on their
    inputs in ``update`` — a host sync that blocks the dispatch thread
    until the step that produced them finishes, serializing the loop with
    the device.  (The other built-in metrics reduce on the device and need
    no wrapper: module docstring.)  This wrapper instead *buffers
    references* to the (labels, preds) device arrays, which stay alive on
    the device meanwhile (JAX arrays are immutable, so late evaluation
    sees the right values), and replays them into the wrapped metric only
    at a sync point: an explicit :meth:`flush`, any ``get``/``get_name_value``
    (which is what ``batch_end_callback`` loggers like ``Speedometer``
    call — so the sync cadence auto-aligns with the callback interval),
    or every ``sync_period`` updates as a buffer bound.

    ``Module.fit(metric_sync_period=K)`` wraps the training metric in
    this automatically for K > 1.
    """

    def __init__(self, base, sync_period=None, **kwargs):
        self._base = create(base)
        self._pending = []
        self._sync_period = sync_period
        super().__init__(self._base.name, **kwargs)

    def update(self, labels, preds):
        self._pending.append((list(labels or []), list(preds)))
        if self._sync_period and len(self._pending) >= self._sync_period:
            self.flush()

    def flush(self):
        """Replay buffered updates into the wrapped metric (the host
        sync happens here)."""
        pending, self._pending = self._pending, []
        for labels, preds in pending:
            self._base.update(labels, preds)

    def reset(self):
        self._pending = []
        self._base.reset()

    def get(self):
        self.flush()
        return self._base.get()


# -- the device reductions ---------------------------------------------
# One jitted program per metric, named so that a trace shows it as module
# ``jit_metric_<name>``.  Each is its metric's numpy body in jax.numpy and
# returns device scalars; shapes are checked while tracing, so a mismatch
# raises from ``update`` as it does on the host.

def _pick(pred, label):
    """``pred[i, label[i]]`` in ``pred``'s dtype; NaN where the label is
    no class of ``pred`` (negative labels count from the end, as numpy's
    do)."""
    check_label_shapes(label, pred[:, 0], shape=1)
    rows = jnp.arange(label.shape[0])
    return pred.at[rows, label.astype(jnp.int32)].get(
        mode="fill", fill_value=jnp.nan)


def _column_difference(label, pred):
    if label.ndim == 1:
        label = label.reshape(label.shape[0], 1)
    if pred.ndim == 1:
        pred = pred.reshape(pred.shape[0], 1)
    return (label - pred).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("axis",))
def metric_accuracy(label, pred, axis):
    if pred.ndim > label.ndim:
        pred = jnp.argmax(pred, axis=axis)  # the first maximum, as numpy
    label = label.astype(jnp.int32).ravel()
    pred = pred.astype(jnp.int32).ravel()
    check_label_shapes(label, pred, shape=1)
    return jnp.sum(pred == label)


@functools.partial(jax.jit, static_argnames=("top_k",))
def metric_top_k_accuracy(label, pred, top_k):
    """Rows whose label is among the ``top_k`` largest predictions: fewer
    than ``top_k`` classes score strictly higher than the label's.  No
    sort, one pass over ``pred``.  Tied scores count for the label, where
    numpy's unstable ``argsort`` leaves their order open."""
    assert pred.ndim == 2, "Predictions should be 2 dims"
    label = label.astype(jnp.int32).ravel()
    num_classes = pred.shape[1]
    # picked before the cast, so no float32 copy of ``pred`` is ever made
    score = _pick(pred, label).astype(jnp.float32)
    ahead = jnp.sum(pred.astype(jnp.float32) > score[:, None], axis=1)
    a_class = (label >= 0) & (label < num_classes)
    return jnp.sum(a_class & (ahead < min(num_classes, top_k)))


@functools.partial(jax.jit, static_argnames=("ignore_label",))
def metric_perplexity(label, pred, ignore_label):
    label = label.astype(jnp.int32).ravel()
    pred = pred.reshape(-1, pred.shape[-1])
    probs = _pick(pred, label).astype(jnp.float32)
    num = jnp.int32(label.shape[0])
    if ignore_label is not None:
        ignore = label == ignore_label
        probs = jnp.where(ignore, 1.0, probs)
        num -= jnp.sum(ignore)
    return jnp.sum(-jnp.log(jnp.maximum(1e-10, probs))), num


@jax.jit
def metric_mae(label, pred):
    return jnp.mean(jnp.abs(_column_difference(label, pred)))


@jax.jit
def metric_mse(label, pred):
    return jnp.mean(jnp.square(_column_difference(label, pred)))


@jax.jit
def metric_rmse(label, pred):
    return jnp.sqrt(jnp.mean(jnp.square(_column_difference(label, pred))))


@functools.partial(jax.jit, static_argnames=("eps",))
def metric_cross_entropy(label, pred, eps):
    label = label.ravel()
    assert label.shape[0] == pred.shape[0]
    # numpy promotes ``bf16_array + eps`` to float32 too
    prob = _pick(pred, label).astype(jnp.float32)
    return jnp.sum(-jnp.log(prob + eps))


@jax.jit
def metric_loss(pred):
    return jnp.sum(pred.astype(jnp.float32))


@register
class Accuracy(EvalMetric):
    def __init__(self, axis=1, name="accuracy", **kwargs):
        super().__init__(name, **kwargs)
        self.axis = axis

    def update(self, labels, preds):
        check_label_shapes(labels, preds)
        for label, pred in self._reduce_on_device(
                labels, preds, lambda label, pred: (
                    metric_accuracy(label, pred, axis=self.axis),
                    label.size)):
            label, pred = _as_numpy(label), _as_numpy(pred)
            if pred.ndim > label.ndim:
                pred = pred.argmax(axis=self.axis)
            label = label.astype("int32").ravel()
            pred = pred.astype("int32").ravel()
            check_label_shapes(label, pred, shape=1)
            self.sum_metric += (pred == label).sum()
            self.num_inst += len(label)


@register
class TopKAccuracy(EvalMetric):
    def __init__(self, top_k=1, name="top_k_accuracy", **kwargs):
        super().__init__(name, **kwargs)
        self.top_k = top_k
        assert self.top_k > 1, "top_k should be >1; use Accuracy for top_k=1"
        self.name += "_%d" % self.top_k

    def update(self, labels, preds):
        check_label_shapes(labels, preds)
        for label, pred in self._reduce_on_device(
                labels, preds, lambda label, pred: (
                    metric_top_k_accuracy(label, pred, top_k=self.top_k),
                    pred.shape[0])):
            label, pred = _as_numpy(label), _as_numpy(pred)
            assert pred.ndim == 2, "Predictions should be 2 dims"
            pred_idx = _np.argsort(pred.astype("float32"), axis=1)
            num_samples, num_classes = pred_idx.shape
            top_k = min(num_classes, self.top_k)
            for j in range(top_k):
                self.sum_metric += (
                    pred_idx[:, num_classes - 1 - j].ravel() ==
                    label.astype("int32").ravel()).sum()
            self.num_inst += num_samples


@register
class F1(EvalMetric):
    """Binary F1 (reference F1: predictions argmax'd, label in {0,1})."""

    def __init__(self, name="f1", **kwargs):
        super().__init__(name, **kwargs)

    def update(self, labels, preds):
        check_label_shapes(labels, preds)
        for label, pred in zip(labels, preds):
            self.host_updates += 1
            label = _as_numpy(label).ravel()
            pred = _as_numpy(pred)
            if pred.ndim > 1:
                pred = pred.argmax(axis=1)
            pred = pred.ravel()
            if not set(_np.unique(label)).issubset({0., 1.}):
                raise ValueError("F1 currently only supports binary labels")
            tp = ((pred == 1) & (label == 1)).sum()
            fp = ((pred == 1) & (label == 0)).sum()
            fn = ((pred == 0) & (label == 1)).sum()
            precision = tp / (tp + fp) if tp + fp > 0 else 0.
            recall = tp / (tp + fn) if tp + fn > 0 else 0.
            if precision + recall > 0:
                self.sum_metric += 2 * precision * recall / (precision + recall)
            self.num_inst += 1


@register
class Perplexity(EvalMetric):
    def __init__(self, ignore_label=None, axis=-1, name="perplexity", **kwargs):
        super().__init__(name, **kwargs)
        self.ignore_label = ignore_label
        self.axis = axis

    def update(self, labels, preds):
        check_label_shapes(labels, preds)
        # one increment for the whole call, so the call goes one way
        if preds and all(isinstance(pred, NDArray) for pred in preds):
            self.device_updates += len(preds)
            self._defer([metric_perplexity(*_device_pair(label, pred),
                                           ignore_label=self.ignore_label)
                         for label, pred in zip(labels, preds)])
            return
        self.host_updates += len(preds)
        loss, num = 0., 0
        for label, pred in zip(labels, preds):
            label, pred = _as_numpy(label), _as_numpy(pred)
            label = label.astype("int32").ravel()
            pred = pred.reshape(-1, pred.shape[-1])
            probs = pred[_np.arange(label.shape[0]), label]
            if self.ignore_label is not None:
                ignore = (label == self.ignore_label)
                probs = _np.where(ignore, 1.0, probs)
                num -= ignore.sum()
            loss += -_np.log(_np.maximum(1e-10, probs)).sum()
            num += label.shape[0]
        self._accumulate_perplexity(loss, num)

    def _fold_update(self, increments):
        self._accumulate_perplexity(
            sum(float(loss) for loss, _ in increments),
            sum(int(num) for _, num in increments))

    def _accumulate_perplexity(self, loss, num):
        try:
            ppl = math.exp(loss / max(1, num))
        except OverflowError:  # exp(huge finite loss) — treat as inf
            ppl = float("inf")
        self._accumulate(ppl, 1)


@register
class MAE(EvalMetric):
    def __init__(self, name="mae", **kwargs):
        super().__init__(name, **kwargs)

    def update(self, labels, preds):
        check_label_shapes(labels, preds)
        for label, pred in self._reduce_on_device(
                labels, preds, lambda label, pred: (metric_mae(label, pred), 1)):
            label, pred = _as_numpy(label), _as_numpy(pred)
            if label.ndim == 1:
                label = label.reshape(label.shape[0], 1)
            if pred.ndim == 1:
                pred = pred.reshape(pred.shape[0], 1)
            self._accumulate(_np.abs(label - pred).mean(), 1)


@register
class MSE(EvalMetric):
    def __init__(self, name="mse", **kwargs):
        super().__init__(name, **kwargs)

    def update(self, labels, preds):
        check_label_shapes(labels, preds)
        for label, pred in self._reduce_on_device(
                labels, preds, lambda label, pred: (metric_mse(label, pred), 1)):
            label, pred = _as_numpy(label), _as_numpy(pred)
            if label.ndim == 1:
                label = label.reshape(label.shape[0], 1)
            if pred.ndim == 1:
                pred = pred.reshape(pred.shape[0], 1)
            self._accumulate(((label - pred) ** 2.0).mean(), 1)


@register
class RMSE(EvalMetric):
    def __init__(self, name="rmse", **kwargs):
        super().__init__(name, **kwargs)

    def update(self, labels, preds):
        check_label_shapes(labels, preds)
        for label, pred in self._reduce_on_device(
                labels, preds, lambda label, pred: (metric_rmse(label, pred), 1)):
            label, pred = _as_numpy(label), _as_numpy(pred)
            if label.ndim == 1:
                label = label.reshape(label.shape[0], 1)
            if pred.ndim == 1:
                pred = pred.reshape(pred.shape[0], 1)
            self._accumulate(_np.sqrt(((label - pred) ** 2.0).mean()), 1)


@register
class CrossEntropy(EvalMetric):
    def __init__(self, eps=1e-12, name="cross-entropy", **kwargs):
        super().__init__(name, **kwargs)
        self.eps = eps

    def update(self, labels, preds):
        check_label_shapes(labels, preds)
        for label, pred in self._reduce_on_device(
                labels, preds, lambda label, pred: (
                    metric_cross_entropy(label, pred, eps=self.eps),
                    label.size)):
            label, pred = _as_numpy(label), _as_numpy(pred)
            label = label.ravel()
            assert label.shape[0] == pred.shape[0]
            prob = pred[_np.arange(label.shape[0]), _np.int64(label)]
            self._accumulate((-_np.log(prob + self.eps)).sum(),
                             label.shape[0])


@register
class Loss(EvalMetric):
    """Mean of raw outputs (for MakeLoss-style heads)."""

    def __init__(self, name="loss", **kwargs):
        super().__init__(name, **kwargs)

    def update(self, _, preds):
        # no labels: each prediction stands in for its own
        for _, pred in self._reduce_on_device(
                preds, preds, lambda _, pred: (metric_loss(pred), pred.size)):
            pred = _as_numpy(pred)
            self._accumulate(pred.sum(), pred.size)


@register
class CustomMetric(EvalMetric):
    def __init__(self, feval, name=None, allow_extra_outputs=False, **kwargs):
        if name is None:
            name = feval.__name__
            if name.find("<") != -1:
                name = "custom(%s)" % name
        super().__init__(name, **kwargs)
        self._feval = feval
        self._allow_extra_outputs = allow_extra_outputs

    def update(self, labels, preds):
        if not self._allow_extra_outputs:
            check_label_shapes(labels, preds)
        for pred, label in zip(preds, labels):
            self.host_updates += 1
            label, pred = _as_numpy(label), _as_numpy(pred)
            reval = self._feval(label, pred)
            if isinstance(reval, tuple):
                sum_metric, num_inst = reval
                self._accumulate(sum_metric, num_inst)
            else:
                self._accumulate(reval, 1)


def np(numpy_feval, name=None, allow_extra_outputs=False):
    """Wrap a numpy feval into a metric (reference ``metric.np``)."""

    def feval(label, pred):
        return numpy_feval(label, pred)

    feval.__name__ = name if name else numpy_feval.__name__
    return CustomMetric(feval, name, allow_extra_outputs)


register(CrossEntropy, "ce", "crossentropy", "cross-entropy")
register(Accuracy, "acc")
register(TopKAccuracy, "top_k_accuracy", "top_k_acc")
