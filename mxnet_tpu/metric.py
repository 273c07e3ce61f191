"""Evaluation metrics.

API parity with reference python/mxnet/metric.py — ``update(labels,
preds)`` over lists of NDArrays, ``get() -> (name, value)``, the
``asnumpy()`` inside update being the training step's only host sync —
rebuilt around a name registry and shared label/pred normalization
helpers instead of the reference's per-class plumbing. Regression
metrics share one base class with an ``_error`` hook.
"""
from __future__ import annotations

import math

import numpy as _np

from .ndarray import NDArray

__all__ = ["EvalMetric", "Accuracy", "TopKAccuracy", "F1", "Perplexity",
           "MAE", "MSE", "RMSE", "CrossEntropy", "CustomMetric",
           "CompositeEvalMetric", "np", "create", "check_label_shapes"]

_REGISTRY: dict = {}


def _register(*names):
    def deco(cls):
        for n in names:
            _REGISTRY[n] = cls
        return cls
    return deco


def _host(x):
    """NDArray/array-like -> numpy array on host (the sync point)."""
    return x.asnumpy() if isinstance(x, NDArray) else _np.asarray(x)


def check_label_shapes(labels, preds, shape=0):
    """Raise if the label/pred batch lists (or shapes) disagree."""
    got = (len(labels), len(preds)) if shape == 0 \
        else (labels.shape, preds.shape)
    if got[0] != got[1]:
        raise ValueError(
            f"labels {got[0]} and predictions {got[1]} do not match")


def _each(labels, preds, check=True):
    """Yield (label, pred) numpy pairs for one update call."""
    if check:
        check_label_shapes(labels, preds)
    for label, pred in zip(labels, preds):
        yield _host(label), _host(pred)


def _device_pair(lab, pred):
    """(lab_jax, pred_jax) when both live on the same device — the
    device-side metric fast path (no per-batch host pull); else None."""
    if isinstance(pred, NDArray) and isinstance(lab, NDArray):
        pj, lj = pred.asjax(), lab.asjax()
        if pj.devices() == lj.devices():
            return lj, pj
    return None


class EvalMetric:
    """Base class: a running (sum, count) with named readout.

    ``sum_metric`` / ``num_inst`` keep the reference's attribute names —
    downstream code (and the reference's own tests) poke them directly.
    They are flushing properties: reading either drains any queued
    device-side accumulations first, so direct reads never undercount.
    """

    def __init__(self, name, num=None):
        self.name = name
        self.num = num
        self._pending = []        # before reset(): subclasses override it
        self.reset()

    def reset(self):
        if self.num is None:
            self._sum_metric, self._num_inst = 0.0, 0
        else:
            self._sum_metric = [0.0] * self.num
            self._num_inst = [0] * self.num
        self._pending = []        # device-lazy (total, count) pairs

    # reference-parity attributes; reads flush queued device scalars
    @property
    def sum_metric(self):
        self._flush()
        return self._sum_metric

    @sum_metric.setter
    def sum_metric(self, value):
        # manual pokes DISCARD queued device batches: flushing here would
        # fold the queued counts into both accumulators and then
        # overwrite only this one — a half-applied state (ADVICE r5).
        # Reference-style code that zeroes both attributes gets a clean
        # slate either way.
        self._pending = []
        self._sum_metric = value

    @property
    def num_inst(self):
        self._flush()
        return self._num_inst

    @num_inst.setter
    def num_inst(self, value):
        self._pending = []        # same discard semantics as sum_metric
        self._num_inst = value

    def _accumulate(self, total, count, index=None):
        if index is None:
            self._sum_metric += total
            self._num_inst += count
        else:
            self._sum_metric[index] += total
            self._num_inst[index] += count

    def _accumulate_device(self, total_dev, count):
        """Accumulate a device-resident scalar WITHOUT synchronizing.

        The reference's metrics are host numpy, so every update is a
        device->host pull — through an accelerator runtime that makes
        the metric the training loop's per-batch sync point. Device-side
        metrics queue the async scalar instead; only reading the metric
        (``get``) synchronizes, once, fetching all queued scalars in a
        single transfer batch.
        """
        assert self.num is None, (
            "_accumulate_device supports single-output metrics only "
            "(multi-output sum_metric is a list; use _accumulate)")
        self._pending.append((total_dev, count))

    def _flush(self):
        if not getattr(self, "_pending", None):
            return
        import jax
        pend, self._pending = self._pending, []
        # one pull for everything queued; counts may themselves be
        # device scalars (e.g. Perplexity's ignore-label keep count)
        for total, count in jax.device_get(pend):
            self._accumulate(float(total), int(count))

    def update(self, labels, preds):
        raise NotImplementedError

    @staticmethod
    def _ratio(total, count):
        return total / count if count else float("nan")

    def get(self):
        self._flush()
        if self.num is None:
            return self.name, self._ratio(self.sum_metric, self.num_inst)
        return ([f"{self.name}_{i}" for i in range(self.num)],
                [self._ratio(s, c)
                 for s, c in zip(self.sum_metric, self.num_inst)])

    def get_name_value(self):
        names, values = self.get()
        if not isinstance(names, list):
            names, values = [names], [values]
        return list(zip(names, values))

    def __str__(self):
        return f"EvalMetric: {dict(self.get_name_value())}"


class CompositeEvalMetric(EvalMetric):
    """Fan an update out to several child metrics."""

    def __init__(self, metrics=None, name="composite"):
        super().__init__(name)
        self.metrics = [create(m) for m in (metrics or [])]

    def add(self, metric):
        self.metrics.append(create(metric))

    def get_metric(self, index):
        if index >= len(self.metrics):
            return ValueError(f"no metric at index {index}")
        return self.metrics[index]

    def update(self, labels, preds):
        for m in self.metrics:
            m.update(labels, preds)

    def reset(self):
        for m in getattr(self, "metrics", []):
            m.reset()

    def get(self):
        out = [m.get() for m in self.metrics]
        return [n for n, _ in out], [v for _, v in out]


@_register("acc", "accuracy")
class Accuracy(EvalMetric):
    """Fraction of argmax predictions equal to the integer label."""

    def __init__(self):
        super().__init__("accuracy")

    def update(self, labels, preds):
        check_label_shapes(labels, preds)
        for lab, pred in zip(labels, preds):
            dp = _device_pair(lab, pred)
            if dp is not None:
                # device-side argmax + compare: no per-batch host sync
                import jax.numpy as jnp
                l, p = dp
                l = l.astype(jnp.int32).ravel()
                if p.ndim > 1 and p.shape != dp[0].shape:
                    p = jnp.argmax(p, axis=-1)
                correct = jnp.sum(p.astype(jnp.int32).ravel() == l)
                self._accumulate_device(correct, int(l.size))
                continue
            lab, pred = _host(lab), _host(pred)
            if pred.ndim > 1 and pred.shape != lab.shape:
                pred = pred.argmax(axis=-1)
            lab = lab.astype(_np.int32).ravel()
            pred = pred.astype(_np.int32).ravel()
            self._accumulate(int((pred == lab).sum()), lab.size)


@_register("top_k_accuracy", "top_k_acc")
class TopKAccuracy(EvalMetric):
    """Label within the k highest-scoring classes.

    Tie-breaking caveat: both paths select exactly k entries, but on
    inputs with *tied* scores the device path (``jax.lax.top_k``) and
    the host path (``np.argpartition``) may pick different tied members,
    so device/host parity is only guaranteed for tie-free scores
    (softmax probabilities from continuous inputs never tie in
    practice). An all-equal row, e.g. uniform zeros, can therefore count
    as a hit on one path and a miss on the other.
    """

    def __init__(self, top_k=1):
        if top_k <= 1:
            raise ValueError("top_k must exceed 1 (use Accuracy otherwise)")
        super().__init__(f"top_k_accuracy_{top_k}")
        self.top_k = top_k

    def update(self, labels, preds):
        check_label_shapes(labels, preds)
        for lab, pred in zip(labels, preds):
            dp = _device_pair(lab, pred)
            if dp is not None and dp[1].ndim == 2 \
                    and dp[0].size == dp[1].shape[0]:
                import jax
                import jax.numpy as jnp
                l, p = dp
                k = min(self.top_k, p.shape[1])
                _, top = jax.lax.top_k(p, k)
                li = l.astype(jnp.int32).ravel()   # (N,1) labels too
                hits = jnp.sum(jnp.any(top == li[:, None], axis=1))
                self._accumulate_device(hits, int(li.size))
                continue
            lab, pred = _host(lab), _host(pred)
            lab = lab.astype(_np.int32).ravel()
            if pred.ndim == 1:
                hits = int((pred.astype(_np.int32) == lab).sum())
            else:
                k = min(self.top_k, pred.shape[1])
                top = _np.argpartition(pred, -k, axis=1)[:, -k:]
                hits = int((top == lab[:, None]).any(axis=1).sum())
            self._accumulate(hits, lab.size)


@_register("f1")
class F1(EvalMetric):
    """Binary F1 over argmax predictions, averaged per batch."""

    def __init__(self):
        super().__init__("f1")

    def update(self, labels, preds):
        for lab, pred in _each(labels, preds):
            lab = lab.astype(_np.int32).ravel()
            if set(_np.unique(lab)) - {0, 1}:
                raise ValueError("F1 is defined for binary labels {0,1}")
            hat = pred.argmax(axis=-1).ravel()
            tp = int(((hat == 1) & (lab == 1)).sum())
            fp = int(((hat == 1) & (lab == 0)).sum())
            fn = int(((hat == 0) & (lab == 1)).sum())
            denom = 2 * tp + fp + fn
            self._accumulate(2.0 * tp / denom if denom else 0.0, 1)


@_register("perplexity")
class Perplexity(EvalMetric):
    """exp(mean negative log-prob of the target), with an optional
    ignored padding label."""

    def __init__(self, ignore_label, axis=-1):
        super().__init__("Perplexity")
        self.ignore_label = ignore_label
        self.axis = axis

    def update(self, labels, preds):
        assert len(labels) == len(preds)
        nll, count = 0.0, 0
        for lab_in, prob_in in zip(labels, preds):
            dp = _device_pair(lab_in, prob_in)
            # same size guard as CrossEntropy: a mismatched gather would
            # clamp silently on device; fall to the loud host path
            if dp is not None and \
                    dp[0].size == dp[1].size // dp[1].shape[self.axis]:
                import jax.numpy as jnp
                l, p = dp
                li = l.astype(jnp.int32).ravel()
                ncls = p.shape[self.axis]
                p2 = jnp.moveaxis(p, self.axis, -1).reshape(-1, ncls)
                p_t = p2[jnp.arange(li.shape[0]), li]
                if self.ignore_label is not None:
                    keep = li != self.ignore_label
                    p_t = jnp.where(keep, p_t, 1.0)
                    cnt = jnp.sum(keep)          # device count: flushed
                else:                             # with the total
                    cnt = li.shape[0]
                self._accumulate_device(
                    -jnp.sum(jnp.log(jnp.maximum(p_t, 1e-10))), cnt)
                continue
            lab, prob = _host(lab_in), _host(prob_in)
            lab = lab.astype(_np.int64).ravel()
            ncls = prob.shape[self.axis]
            prob = _np.moveaxis(prob, self.axis, -1).reshape(-1, ncls)
            p_target = prob[_np.arange(lab.size), lab]
            if self.ignore_label is not None:
                keep = lab != self.ignore_label
                p_target = _np.where(keep, p_target, 1.0)
                count += int(keep.sum())
            else:
                count += lab.size
            nll -= float(_np.log(_np.maximum(p_target, 1e-10)).sum())
        # Accumulate raw nll/count so get() returns exp(total_nll/total
        # count) — averaging per-batch perplexities would be biased high
        # (Jensen; reference metric.py Perplexity.get). A fully-ignored
        # batch contributes nothing.
        self._accumulate(nll, count)

    def get(self):
        if self.num_inst == 0:
            return self.name, float("nan")
        return self.name, math.exp(self.sum_metric / self.num_inst)


class _RegressionMetric(EvalMetric):
    """Shared shell for elementwise-error metrics (one hook to fill in;
    ``_error`` must be written in array operators + the ``_xp`` module
    handle so the same body runs on numpy (host) and jnp (device))."""

    def _error(self, xp, lab, pred):
        raise NotImplementedError

    def update(self, labels, preds):
        check_label_shapes(labels, preds)
        for lab, pred in zip(labels, preds):
            dp = _device_pair(lab, pred)
            if dp is not None:
                import jax.numpy as jnp
                l, p = dp
                if l.ndim == 1:
                    l = l[:, None]
                if p.shape != l.shape:
                    p = p.reshape(l.shape)
                self._accumulate_device(self._error(jnp, l, p), 1)
                continue
            lab, pred = _host(lab), _host(pred)
            if lab.ndim == 1:
                lab = lab[:, None]
            if pred.shape != lab.shape:
                pred = pred.reshape(lab.shape)
            self._accumulate(float(self._error(_np, lab, pred)), 1)


@_register("mae")
class MAE(_RegressionMetric):
    def __init__(self):
        super().__init__("mae")

    def _error(self, xp, lab, pred):
        return xp.abs(lab - pred).mean()


@_register("mse")
class MSE(_RegressionMetric):
    def __init__(self):
        super().__init__("mse")

    def _error(self, xp, lab, pred):
        return ((lab - pred) ** 2).mean()


@_register("rmse")
class RMSE(_RegressionMetric):
    def __init__(self):
        super().__init__("rmse")

    def _error(self, xp, lab, pred):
        return xp.sqrt(((lab - pred) ** 2).mean())


@_register("ce", "cross-entropy")
class CrossEntropy(EvalMetric):
    """Mean -log p(target) given per-class probability rows."""

    def __init__(self, eps=1e-8):
        super().__init__("cross-entropy")
        self.eps = eps

    def update(self, labels, preds):
        check_label_shapes(labels, preds)
        for lab, prob in zip(labels, preds):
            dp = _device_pair(lab, prob)
            if dp is not None and dp[1].ndim == 2 \
                    and dp[0].size == dp[1].shape[0]:
                # NOTE: like every XLA gather, out-of-range label values
                # clamp instead of raising — run the host path (numpy
                # inputs) to surface label-range bugs loudly
                import jax.numpy as jnp
                l, p = dp
                li = l.astype(jnp.int32).ravel()
                p_t = p[jnp.arange(li.shape[0]), li]
                self._accumulate_device(-jnp.sum(jnp.log(p_t + self.eps)),
                                        int(li.shape[0]))
                continue
            lab, prob = _host(lab), _host(prob)
            lab = lab.astype(_np.int64).ravel()
            assert lab.shape[0] == prob.shape[0]
            p_target = prob[_np.arange(lab.size), lab]
            self._accumulate(float(-_np.log(p_target + self.eps).sum()),
                             lab.size)


class CustomMetric(EvalMetric):
    """Adapt a python ``feval(label, pred)`` into the metric protocol."""

    def __init__(self, feval, name=None, allow_extra_outputs=False):
        if name is None:
            name = feval.__name__
            if "<" in name:  # lambdas
                name = f"custom({name})"
        super().__init__(name)
        self._feval = feval
        self._allow_extra_outputs = allow_extra_outputs

    def update(self, labels, preds):
        for lab, pred in _each(labels, preds,
                               check=not self._allow_extra_outputs):
            res = self._feval(lab, pred)
            if isinstance(res, tuple):
                self._accumulate(*res)
            else:
                self._accumulate(res, 1)


def np(numpy_feval, name=None, allow_extra_outputs=False):
    """Wrap a bare numpy function as a metric."""
    def feval(label, pred):
        return numpy_feval(label, pred)
    feval.__name__ = numpy_feval.__name__
    return CustomMetric(feval, name, allow_extra_outputs)


def create(metric, **kwargs):
    """Resolve a metric from a name, callable, instance, or list."""
    if isinstance(metric, EvalMetric):
        return metric
    if callable(metric):
        return CustomMetric(metric)
    if isinstance(metric, list):
        out = CompositeEvalMetric()
        for m in metric:
            out.add(create(m, **kwargs))
        return out
    try:
        return _REGISTRY[metric.lower()](**kwargs)
    except KeyError:
        raise ValueError(
            f"unknown metric {metric!r}; registered: {sorted(_REGISTRY)}")
