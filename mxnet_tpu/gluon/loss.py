"""Gluon losses.

Parity target: `python/mxnet/gluon/loss.py` (1046 LoC) — Loss base with
weight/batch_axis, L1/L2, SigmoidBCE, SoftmaxCE, KLDiv, CTC, Huber, Hinge,
SquaredHinge, Logistic, Triplet, Cosine. Semantics preserved: per-example
mean over non-batch axes, optional sample_weight broadcast.
"""
from __future__ import annotations

from .block import HybridBlock

__all__ = ["Loss", "L2Loss", "L1Loss", "SigmoidBinaryCrossEntropyLoss",
           "SigmoidBCELoss", "SoftmaxCrossEntropyLoss", "SoftmaxCELoss",
           "CausalLMLoss", "KLDivLoss", "CTCLoss", "HuberLoss", "HingeLoss",
           "SquaredHingeLoss", "LogisticLoss", "TripletLoss", "CosineEmbeddingLoss"]


def _apply_weighting(F, loss, weight=None, sample_weight=None):
    """parity: gluon/loss.py:34 _apply_weighting."""
    if sample_weight is not None:
        loss = F.invoke("broadcast_mul", loss, sample_weight)
    if weight is not None:
        assert isinstance(weight, (int, float)), "weight must be a number"
        loss = loss * weight
    return loss


def _reshape_like(F, pred, label):
    return label.reshape(pred.shape) if pred.shape != label.shape else label


class Loss(HybridBlock):
    """Base loss (parity: gluon/loss.py:54)."""

    def __init__(self, weight, batch_axis, **kwargs):
        super().__init__(**kwargs)
        self._weight = weight
        self._batch_axis = batch_axis

    def __repr__(self):
        return f"{type(self).__name__}(batch_axis={self._batch_axis}, w={self._weight})"

    def _mean_all_but_batch(self, F, loss):
        axes = tuple(i for i in range(loss.ndim) if i != self._batch_axis)
        return loss.mean(axis=axes) if axes else loss


class L2Loss(Loss):
    """0.5 * (pred - label)^2 (parity: loss.py:130)."""

    def __init__(self, weight=1.0, batch_axis=0, **kwargs):
        super().__init__(weight, batch_axis, **kwargs)

    def hybrid_forward(self, F, pred, label, sample_weight=None):
        label = _reshape_like(F, pred, label)
        loss = F.invoke("square", pred - label)
        loss = _apply_weighting(F, loss, self._weight / 2, sample_weight)
        return self._mean_all_but_batch(F, loss)


class L1Loss(Loss):
    def __init__(self, weight=None, batch_axis=0, **kwargs):
        super().__init__(weight, batch_axis, **kwargs)

    def hybrid_forward(self, F, pred, label, sample_weight=None):
        label = _reshape_like(F, pred, label)
        loss = (pred - label).abs()
        loss = _apply_weighting(F, loss, self._weight, sample_weight)
        return self._mean_all_but_batch(F, loss)


class SigmoidBinaryCrossEntropyLoss(Loss):
    """parity: loss.py:231 — numerically-stable logits form by default."""

    def __init__(self, from_sigmoid=False, weight=None, batch_axis=0, **kwargs):
        super().__init__(weight, batch_axis, **kwargs)
        self._from_sigmoid = from_sigmoid

    def hybrid_forward(self, F, pred, label, sample_weight=None, pos_weight=None):
        label = _reshape_like(F, pred, label)
        if not self._from_sigmoid:
            # max(x,0) - x*z + log(1+exp(-|x|))
            relu_p = pred.relu()
            abs_p = pred.abs()
            softplus = F.invoke("Activation", -abs_p, act_type="softrelu")
            if pos_weight is None:
                loss = relu_p - pred * label + softplus
            else:
                log_wt = (pos_weight - 1) * label + 1
                loss = relu_p - pred * label + softplus * log_wt
        else:
            eps = 1e-12
            if pos_weight is None:
                loss = -((pred + eps).log() * label
                         + (1.0 - pred + eps).log() * (1.0 - label))
            else:
                loss = -((pred + eps).log() * label * pos_weight
                         + (1.0 - pred + eps).log() * (1.0 - label))
        loss = _apply_weighting(F, loss, self._weight, sample_weight)
        return self._mean_all_but_batch(F, loss)


SigmoidBCELoss = SigmoidBinaryCrossEntropyLoss


class SoftmaxCrossEntropyLoss(Loss):
    """parity: loss.py:348 — sparse labels by default; axis softmax."""

    def __init__(self, axis=-1, sparse_label=True, from_logits=False,
                 weight=None, batch_axis=0, **kwargs):
        super().__init__(weight, batch_axis, **kwargs)
        self._axis = axis
        self._sparse_label = sparse_label
        self._from_logits = from_logits

    def hybrid_forward(self, F, pred, label, sample_weight=None):
        if not self._from_logits:
            pred = F.invoke("log_softmax", pred, axis=self._axis)
        if self._sparse_label:
            loss = -F.invoke("pick", pred, label, axis=self._axis,
                             keepdims=True)
        else:
            label = _reshape_like(F, pred, label)
            loss = -(pred * label).sum(axis=self._axis, keepdims=True)
        loss = _apply_weighting(F, loss, self._weight, sample_weight)
        return self._mean_all_but_batch(F, loss)


SoftmaxCELoss = SoftmaxCrossEntropyLoss


class CausalLMLoss(Loss):
    """Next-token cross-entropy of a language model (beyond the
    reference): logits (B, S, V) against labels (B, S), the ids that
    follow each position (the caller shifts them), per sequence the mean
    over its tokens; the softmax statistics in float32 whatever the
    logits' type."""

    def __init__(self, weight=None, batch_axis=0, **kwargs):
        super().__init__(weight, batch_axis, **kwargs)

    def hybrid_forward(self, F, pred, label, sample_weight=None):
        import jax

        with jax.named_scope("lm.head_loss"):
            loss = F.invoke("_contrib_lm_cross_entropy", pred, label)
        return _apply_weighting(F, loss, self._weight, sample_weight)


class KLDivLoss(Loss):
    """parity: loss.py:442."""

    def __init__(self, from_logits=True, axis=-1, weight=None, batch_axis=0,
                 **kwargs):
        super().__init__(weight, batch_axis, **kwargs)
        self._from_logits = from_logits
        self._axis = axis

    def hybrid_forward(self, F, pred, label, sample_weight=None):
        if not self._from_logits:
            pred = F.invoke("log_softmax", pred, axis=self._axis)
        loss = label * ((label + 1e-12).log() - pred)
        loss = _apply_weighting(F, loss, self._weight, sample_weight)
        return self._mean_all_but_batch(F, loss)


class CTCLoss(Loss):
    """parity: loss.py:512 — layout TNC/NTC, optional lengths."""

    def __init__(self, layout="NTC", label_layout="NT", weight=None, **kwargs):
        assert layout in ("NTC", "TNC")
        assert label_layout in ("NT", "TN")
        self._layout = layout
        self._label_layout = label_layout
        super().__init__(weight, label_layout.find("N"), **kwargs)

    def hybrid_forward(self, F, pred, label, pred_lengths=None,
                       label_lengths=None, sample_weight=None):
        if self._layout == "NTC":
            pred = pred.swapaxes(0, 1)
        if self._batch_axis == 1:
            label = label.swapaxes(0, 1)
        args = [pred, label]
        kwargs = {"use_data_lengths": pred_lengths is not None,
                  "use_label_lengths": label_lengths is not None,
                  "blank_label": "last"}
        if pred_lengths is not None:
            args.append(pred_lengths)
        if label_lengths is not None:
            args.append(label_lengths)
        loss = F.invoke("CTCLoss", *args, **kwargs)
        return _apply_weighting(F, loss, self._weight, sample_weight)


class HuberLoss(Loss):
    """parity: loss.py:600."""

    def __init__(self, rho=1, weight=None, batch_axis=0, **kwargs):
        super().__init__(weight, batch_axis, **kwargs)
        self._rho = rho

    def hybrid_forward(self, F, pred, label, sample_weight=None):
        label = _reshape_like(F, pred, label)
        loss = (pred - label).abs()
        loss = F.invoke("where", (loss > self._rho), loss - 0.5 * self._rho,
                        (0.5 / self._rho) * F.invoke("square", loss))
        loss = _apply_weighting(F, loss, self._weight, sample_weight)
        return self._mean_all_but_batch(F, loss)


class HingeLoss(Loss):
    """parity: loss.py:660 — labels in {-1, 1}."""

    def __init__(self, margin=1, weight=None, batch_axis=0, **kwargs):
        super().__init__(weight, batch_axis, **kwargs)
        self._margin = margin

    def hybrid_forward(self, F, pred, label, sample_weight=None):
        label = _reshape_like(F, pred, label)
        loss = (self._margin - pred * label).relu()
        loss = _apply_weighting(F, loss, self._weight, sample_weight)
        return self._mean_all_but_batch(F, loss)


class SquaredHingeLoss(Loss):
    def __init__(self, margin=1, weight=None, batch_axis=0, **kwargs):
        super().__init__(weight, batch_axis, **kwargs)
        self._margin = margin

    def hybrid_forward(self, F, pred, label, sample_weight=None):
        label = _reshape_like(F, pred, label)
        loss = F.invoke("square", (self._margin - pred * label).relu())
        loss = _apply_weighting(F, loss, self._weight, sample_weight)
        return self._mean_all_but_batch(F, loss)


class LogisticLoss(Loss):
    """parity: loss.py:770 — binary/signed label formats."""

    def __init__(self, weight=None, batch_axis=0, label_format="signed",
                 **kwargs):
        super().__init__(weight, batch_axis, **kwargs)
        assert label_format in ("signed", "binary")
        self._label_format = label_format

    def hybrid_forward(self, F, pred, label, sample_weight=None):
        label = _reshape_like(F, pred, label)
        if self._label_format == "signed":
            label = (label + 1.0) / 2.0
        loss = pred.relu() - pred * label + \
            F.invoke("Activation", -pred.abs(), act_type="softrelu")
        loss = _apply_weighting(F, loss, self._weight, sample_weight)
        return self._mean_all_but_batch(F, loss)


class TripletLoss(Loss):
    """parity: loss.py:833."""

    def __init__(self, margin=1, weight=None, batch_axis=0, **kwargs):
        super().__init__(weight, batch_axis, **kwargs)
        self._margin = margin

    def hybrid_forward(self, F, pred, positive, negative, sample_weight=None):
        positive = _reshape_like(F, pred, positive)
        negative = _reshape_like(F, pred, negative)
        sq = F.invoke("square", positive - pred) - \
            F.invoke("square", negative - pred)
        axes = tuple(range(1, pred.ndim))
        loss = (sq.sum(axis=axes) + self._margin).relu()
        return _apply_weighting(F, loss, self._weight, sample_weight)


class CosineEmbeddingLoss(Loss):
    """parity: loss.py:905 — label 1 (similar) / -1 (dissimilar)."""

    def __init__(self, weight=None, batch_axis=0, margin=0, **kwargs):
        super().__init__(weight, batch_axis, **kwargs)
        self._margin = margin

    def hybrid_forward(self, F, input1, input2, label, sample_weight=None):
        def cos_sim(a, b):
            num = (a * b).sum(axis=-1)
            den = a.norm(axis=-1) * b.norm(axis=-1) + 1e-12
            return num / den

        sim = cos_sim(input1, input2)
        label = label.reshape(sim.shape)
        pos = 1.0 - sim
        neg = (sim - self._margin).relu()
        loss = F.invoke("where", label == 1.0, pos, neg)
        return _apply_weighting(F, loss, self._weight, sample_weight)
