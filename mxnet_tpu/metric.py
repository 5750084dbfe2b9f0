"""Evaluation metrics.

API parity with the reference ``python/mxnet/metric.py:44-1167`` (EvalMetric
base, registry + ``create``, Accuracy/TopK/F1/Perplexity/regression-error/
CrossEntropy/Pearson/Loss/Custom families). Independent design: most metrics
derive from ``_PairAccumulator``, which owns the per-(label, pred) iteration
and running-sum bookkeeping; each concrete metric contributes a single
``measure(label, pred) -> (value, count)`` function on numpy arrays.
"""
from __future__ import annotations

import math

import numpy as _np

from .base import Registry
from . import ndarray as nd
from . import telemetry as _tel
from .ndarray import NDArray

__all__ = ["EvalMetric", "CompositeEvalMetric", "Accuracy", "TopKAccuracy",
           "F1", "Perplexity", "MAE", "MSE", "RMSE", "CrossEntropy",
           "NegativeLogLikelihood", "PearsonCorrelation", "Loss", "Torch",
           "Caffe", "CustomMetric", "np", "create", "check_label_shapes"]

_REG = Registry("metric")


def check_label_shapes(labels, preds, shape=False):
    """Raise when label/pred list lengths (or array shapes) disagree."""
    got = (labels.shape, preds.shape) if shape else (len(labels), len(preds))
    if got[0] != got[1]:
        raise ValueError("Shape of labels {} does not match shape of "
                         "predictions {}".format(got[0], got[1]))


def _numpy(x):
    """A metric's input on the host.  While spans record, the one
    ``asnumpy`` is split into its two halves so that a trace tells them
    apart: ``metric_wait`` (blocked until the array is ready — the device
    is still busy, this is overlap) and ``metric_fetch`` (the copy — the
    device has nothing queued).  The copy is requested before the wait, as
    the unsplit call does, so that it follows the array's last op without
    a second wake-up of the host in between (on the chip that wake-up left
    the device idle ~0.45 ms a batch more than an untraced run)."""
    if not isinstance(x, NDArray):
        return _np.asarray(x)
    if _tel.trace_active():
        with _tel.span("metric_wait", cat="host"):
            request_copy = getattr(x._data, "copy_to_host_async", None)
            if request_copy is not None:
                request_copy()
            x.wait_to_read()
        with _tel.span("metric_fetch", cat="host"):
            return x.asnumpy()
    return x.asnumpy()


def _finite_contribution(value):
    """Gate one accumulator contribution: a NaN/Inf value would poison
    the running sum FOREVER (every later ``get()`` reports NaN, long
    after the sick batch scrolled off the log).  Nonfinite updates are
    excluded and booked as ``metric_nonfinite_updates`` so the exclusion
    is visible instead of silent."""
    if math.isfinite(value):
        return True
    _tel.bump("metric_nonfinite_updates")
    return False


def _column(arr):
    """Ensure a 2-D (n, k) view for regression metrics."""
    a = _numpy(arr)
    return a.reshape(-1, 1) if a.ndim == 1 else a


class EvalMetric:
    """Running-average metric base (ref metric.py:44).

    State is a (sum_metric, num_inst) pair; ``get`` reports their ratio.
    """

    def __init__(self, name, output_names=None, label_names=None, **kwargs):
        self.name = str(name)
        self.output_names, self.label_names = output_names, label_names
        self._kwargs = kwargs
        self.reset()

    def __str__(self):
        return "EvalMetric: %s" % dict(self.get_name_value())

    def get_config(self):
        cfg = dict(self._kwargs,
                   metric=type(self).__name__, name=self.name,
                   output_names=self.output_names,
                   label_names=self.label_names)
        return cfg

    def update_dict(self, label, pred):
        """Update from name→array dicts, selecting declared names if any."""
        preds = [pred[n] for n in self.output_names] \
            if self.output_names is not None else list(pred.values())
        labels = [label[n] for n in self.label_names] \
            if self.label_names is not None else list(label.values())
        self.update(labels, preds)

    def update(self, labels, preds):
        raise NotImplementedError()

    def reset(self):
        self.sum_metric, self.num_inst = 0.0, 0

    def get(self):
        if not self.num_inst:
            return self.name, float("nan")
        return self.name, self.sum_metric / self.num_inst

    def get_name_value(self):
        names, values = self.get()
        if not isinstance(names, list):
            names, values = [names], [values]
        return list(zip(names, values))


class _PairAccumulator(EvalMetric):
    """Template for metrics that reduce each (label, pred) pair to a
    (contribution, count) tuple via :meth:`measure`."""

    check_shapes = True

    def update(self, labels, preds):
        if self.check_shapes:
            check_label_shapes(labels, preds)
        for label, pred in zip(labels, preds):
            value, count = self.measure(_numpy(label), _numpy(pred))
            if not _finite_contribution(float(value)):
                continue
            self.sum_metric += value
            self.num_inst += count

    def measure(self, label, pred):
        raise NotImplementedError()


_ALIASES = {
    "Accuracy": ["acc"], "TopKAccuracy": ["top_k_accuracy", "top_k_acc"],
    "CrossEntropy": ["ce"], "NegativeLogLikelihood": ["nll_loss"],
    "PearsonCorrelation": ["pearsonr"], "CompositeEvalMetric": ["composite"],
}


def register(klass):
    _REG.register(klass, klass.__name__,
                  aliases=_ALIASES.get(klass.__name__, ()))
    return klass


def create(metric, *args, **kwargs):
    """Build a metric from a callable, instance, list, or registered name."""
    if callable(metric):
        return CustomMetric(metric, *args, **kwargs)
    if isinstance(metric, EvalMetric):
        return metric
    if isinstance(metric, list):
        bundle = CompositeEvalMetric()
        for item in metric:
            bundle.add(create(item, *args, **kwargs))
        return bundle
    return _REG.get(metric)(*args, **kwargs)


@register
class CompositeEvalMetric(EvalMetric):
    """Fan-out wrapper reporting every child metric's name/value."""

    def __init__(self, metrics=None, name="composite", output_names=None,
                 label_names=None):
        super().__init__(name, output_names, label_names)
        self.metrics = [create(m) for m in (metrics or [])]

    def add(self, metric):
        self.metrics.append(create(metric))

    def get_metric(self, index):
        return self.metrics[index]

    def update_dict(self, labels, preds):
        for child in self.metrics:
            child.update_dict(labels, preds)

    def update(self, labels, preds):
        for child in self.metrics:
            child.update(labels, preds)

    def reset(self):
        for child in getattr(self, "metrics", ()):
            child.reset()

    def get(self):
        names, values = [], []
        for child in self.metrics:
            n, v = child.get()
            names += n if isinstance(n, list) else [n]
            values += v if isinstance(v, list) else [v]
        return names, values


@register
class Accuracy(_PairAccumulator):
    """Top-1 classification accuracy; argmaxes preds when ranks differ."""

    def __init__(self, axis=1, name="accuracy", output_names=None,
                 label_names=None):
        super().__init__(name, output_names, label_names, axis=axis)
        self.axis = axis

    def measure(self, label, pred):
        # argmax whenever SHAPES differ, not just ranks: 2D sequence
        # labels (batch, seq) vs (batch*seq, vocab) scores must reduce
        # too (ref python/mxnet/metric.py:391-392)
        if pred.shape != label.shape:
            pred = pred.argmax(axis=self.axis)
        check_label_shapes(label.ravel(), pred.ravel(), shape=True)
        hits = pred.astype("int64").ravel() == label.astype("int64").ravel()
        return int(hits.sum()), hits.size


@register
class TopKAccuracy(_PairAccumulator):
    """Fraction of rows whose label lands in the top-k scored classes."""

    def __init__(self, top_k=1, name="top_k_accuracy", output_names=None,
                 label_names=None):
        super().__init__(name, output_names, label_names, top_k=top_k)
        if top_k <= 1:
            raise ValueError("use Accuracy for top_k <= 1")
        self.top_k = top_k
        self.name = "%s_%d" % (self.name, top_k)

    def measure(self, label, pred):
        if pred.ndim > 2:
            raise ValueError("Predictions should be no more than 2 dims")
        label = label.astype("int64").ravel()
        if pred.ndim == 1:
            return int((pred.astype("int64") == label).sum()), label.size
        k = min(self.top_k, pred.shape[1])
        # indices of the k best classes per row
        ranked = _np.argsort(pred.astype("float32"), axis=1)[:, -k:]
        hits = (ranked == label[:, None]).any(axis=1)
        return int(hits.sum()), label.size


@register
class F1(_PairAccumulator):
    """Binary F1 over argmaxed predictions, one score per batch."""

    def __init__(self, name="f1", output_names=None, label_names=None):
        super().__init__(name, output_names, label_names)

    def measure(self, label, pred):
        label = label.astype("int64").ravel()
        if _np.unique(label).size > 2:
            raise ValueError("F1 currently only supports binary classification.")
        decided = pred.argmax(axis=1)
        tp = float(((decided == 1) & (label == 1)).sum())
        fp = float(((decided == 1) & (label == 0)).sum())
        fn = float(((decided == 0) & (label == 1)).sum())
        prec = tp / (tp + fp) if tp + fp else 0.0
        rec = tp / (tp + fn) if tp + fn else 0.0
        score = 2 * prec * rec / (prec + rec) if prec + rec else 0.0
        return score, 1


@register
class Perplexity(EvalMetric):
    """exp(mean negative log prob of the target class), with an optional
    ignored label id (padding)."""

    def __init__(self, ignore_label, axis=-1, name="perplexity",
                 output_names=None, label_names=None):
        super().__init__(name, output_names, label_names,
                         ignore_label=ignore_label, axis=axis)
        self.ignore_label = ignore_label
        self.axis = axis

    def update(self, labels, preds):
        if len(labels) != len(preds):
            raise ValueError("label/pred list length mismatch")
        for label, pred in zip(labels, preds):
            if label.size != pred.size // pred.shape[-1]:
                raise ValueError("shape mismatch: %s vs. %s"
                                 % (label.shape, pred.shape))
            flat = label.as_in_context(pred.context).reshape((label.size,))
            target_p = _numpy(nd.pick(pred, flat.astype(dtype="int32"),
                                      axis=self.axis))
            lab = _numpy(flat)
            count = target_p.size
            if self.ignore_label is not None:
                masked = lab == self.ignore_label
                count -= int(masked.sum())
                target_p = _np.where(masked, 1.0, target_p)
            value = -float(_np.log(_np.maximum(target_p, 1e-10)).sum())
            if not _finite_contribution(value):
                continue
            self.sum_metric += value
            self.num_inst += count

    def get(self):
        if not self.num_inst:
            return self.name, float("nan")
        return self.name, math.exp(self.sum_metric / self.num_inst)


@register
class MAE(_PairAccumulator):
    def __init__(self, name="mae", output_names=None, label_names=None):
        super().__init__(name, output_names, label_names)

    def measure(self, label, pred):
        return float(_np.abs(_column(label) - _column(pred)).mean()), 1


@register
class MSE(_PairAccumulator):
    def __init__(self, name="mse", output_names=None, label_names=None):
        super().__init__(name, output_names, label_names)

    def measure(self, label, pred):
        return float(((_column(label) - _column(pred)) ** 2).mean()), 1


@register
class RMSE(_PairAccumulator):
    def __init__(self, name="rmse", output_names=None, label_names=None):
        super().__init__(name, output_names, label_names)

    def measure(self, label, pred):
        return float(_np.sqrt(((_column(label) - _column(pred)) ** 2).mean())), 1


@register
class CrossEntropy(_PairAccumulator):
    """Mean -log p(target) given per-class probability rows."""

    def __init__(self, eps=1e-12, name="cross-entropy", output_names=None,
                 label_names=None):
        super().__init__(name, output_names, label_names, eps=eps)
        self.eps = eps

    def measure(self, label, pred):
        idx = label.ravel().astype("int64")
        if idx.shape[0] != pred.shape[0]:
            raise ValueError("label/pred row mismatch")
        target_p = pred[_np.arange(idx.shape[0]), idx]
        return float(-_np.log(target_p + self.eps).sum()), idx.shape[0]


@register
class NegativeLogLikelihood(CrossEntropy):
    def __init__(self, eps=1e-12, name="nll-loss", output_names=None,
                 label_names=None):
        super().__init__(eps=eps, name=name, output_names=output_names,
                         label_names=label_names)


@register
class PearsonCorrelation(_PairAccumulator):
    def __init__(self, name="pearsonr", output_names=None, label_names=None):
        super().__init__(name, output_names, label_names)

    def measure(self, label, pred):
        check_label_shapes(label, pred, shape=True)
        return float(_np.corrcoef(pred.ravel(), label.ravel())[0, 1]), 1


@register
class Loss(EvalMetric):
    """Mean of raw outputs — pair with loss-valued heads."""

    def __init__(self, name="loss", output_names=None, label_names=None):
        super().__init__(name, output_names, label_names)

    def update(self, _, preds):
        for pred in preds:
            value = float(_numpy(pred).sum())
            if not _finite_contribution(value):
                continue
            self.sum_metric += value
            self.num_inst += pred.size


@register
class Torch(Loss):
    def __init__(self, name="torch", output_names=None, label_names=None):
        super().__init__(name, output_names, label_names)


@register
class Caffe(Loss):
    def __init__(self, name="caffe", output_names=None, label_names=None):
        super().__init__(name, output_names, label_names)


@register
class CustomMetric(EvalMetric):
    """Adapter for a user ``feval(label, pred)`` returning a value or a
    (sum, count) tuple."""

    def __init__(self, feval, name=None, allow_extra_outputs=False,
                 output_names=None, label_names=None):
        if name is None:
            name = feval.__name__
            if "<" in name:
                name = "custom(%s)" % name
        super().__init__(name, output_names, label_names, feval=feval,
                         allow_extra_outputs=allow_extra_outputs)
        self._feval = feval
        self._allow_extra_outputs = allow_extra_outputs

    def update(self, labels, preds):
        if not self._allow_extra_outputs:
            check_label_shapes(labels, preds)
        for pred, label in zip(preds, labels):
            result = self._feval(_numpy(label), _numpy(pred))
            if isinstance(result, tuple):
                self.sum_metric += result[0]
                self.num_inst += result[1]
            else:
                self.sum_metric += result
                self.num_inst += 1


def np(numpy_feval, name=None, allow_extra_outputs=False):
    """Wrap a numpy eval function as a CustomMetric (ref metric.np)."""
    def feval(label, pred):
        return numpy_feval(label, pred)
    feval.__name__ = numpy_feval.__name__
    return CustomMetric(feval, name, allow_extra_outputs)
