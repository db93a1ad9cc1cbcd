"""Estimator: Keras-style train/evaluate facade over Gluon
(parity: `python/mxnet/gluon/contrib/estimator/estimator.py:42` —
fit :326, evaluate :272, handler dispatch :423)."""
from __future__ import annotations

import logging

from .... import autograd, metric as metric_mod
from ... import Trainer
from .event_handler import (BatchBegin, BatchEnd, EpochBegin, EpochEnd,
                            LoggingHandler, StoppingHandler, TrainBegin,
                            TrainEnd)

__all__ = ["Estimator"]


class Estimator:
    """parity: estimator.py:42."""

    def __init__(self, net, loss, train_metrics=None, val_metrics=None,
                 initializer=None, trainer=None, context=None,
                 val_net=None, val_loss=None):
        self.net = net
        self.loss = loss
        self.val_net = val_net or net
        self.val_loss = val_loss or loss
        self.logger = logging.getLogger("Estimator")
        self.logger.setLevel(logging.INFO)
        from ....context import cpu, num_tpus, tpu

        if context is None:
            context = tpu() if num_tpus() > 0 else cpu()
        self.context = context if isinstance(context, (list, tuple)) \
            else [context]
        self.logger.info("Estimator context %s runs on %s", self.context,
                         [c.jax_device() for c in self.context])
        self.train_metrics = [metric_mod.create(m)
                              for m in (train_metrics or ["accuracy"])]
        self.val_metrics = [metric_mod.create(m)
                            for m in (val_metrics or ["accuracy"])]
        self.train_loss_metric = metric_mod.Loss("train_loss")
        self.val_loss_metric = metric_mod.Loss("val_loss")
        if initializer is not None or not self._is_initialized():
            from .... import initializer as init_mod

            self.net.initialize(initializer or init_mod.Xavier(),
                                ctx=self.context[0], force_reinit=False)
        self.trainer = trainer or Trainer(
            self.net.collect_params(), "adam", {"learning_rate": 1e-3})
        self.stop_training = False

    def _is_initialized(self):
        for p in self.net.collect_params().values():
            try:
                p.data()
            except Exception:
                return False
        return True

    def _get_data_and_label(self, batch):
        ctx = self.context[0]
        if hasattr(batch, "data"):  # DataBatch
            return batch.data[0].as_in_context(ctx), \
                batch.label[0].as_in_context(ctx)
        data, label = batch
        return data.as_in_context(ctx), label.as_in_context(ctx)

    def evaluate_batch(self, batch):
        data, label = self._get_data_and_label(batch)
        pred = self.val_net(data)
        loss = self.val_loss(pred, label)
        self.val_loss_metric.update(None, [loss])
        for metric in self.val_metrics:
            metric.update([label], [pred])

    def evaluate(self, val_data, batch_axis=0, event_handlers=None):
        """parity: estimator.py:272."""
        for metric in self.val_metrics + [self.val_loss_metric]:
            metric.reset()
        for batch in val_data:
            self.evaluate_batch(batch)
        if hasattr(val_data, "reset"):
            val_data.reset()
        return {m.get()[0]: m.get()[1]
                for m in self.val_metrics + [self.val_loss_metric]}

    def fit_batch(self, batch):
        data, label = self._get_data_and_label(batch)
        with autograd.record():
            pred = self.net(data)
            loss = self.loss(pred, label)
        loss.backward()
        self.trainer.step(data.shape[0])
        self.train_loss_metric.update(None, [loss])
        for metric in self.train_metrics:
            metric.update([label], [pred])
        return data, label, pred, loss

    def fit(self, train_data, val_data=None, epochs=None,
            event_handlers=None, batches=None, batch_axis=0):
        """parity: estimator.py:326.

        Preemption-aware: with the :mod:`mxnet_tpu.preempt` handlers
        installed (explicitly or via ``MXNET_TPU_PREEMPT``), a SIGTERM
        lets the in-flight batch finish, writes a final mid-epoch
        checkpoint through every :class:`CheckpointHandler` among the
        event handlers, and exits with the reschedule code (default 75).
        """
        from .... import preempt as _preempt

        _preempt.maybe_install_from_env()
        if epochs is None and batches is None:
            epochs = 1
        handlers = self._prepare_handlers(epochs, batches, event_handlers)
        self.stop_training = False
        for h in handlers:
            if isinstance(h, TrainBegin):
                h.train_begin(self)
        while not self.stop_training:
            for metric in self.train_metrics + [self.train_loss_metric]:
                metric.reset()
            for h in handlers:
                if isinstance(h, EpochBegin):
                    h.epoch_begin(self)
            for batch in train_data:
                for h in handlers:
                    if isinstance(h, BatchBegin):
                        h.batch_begin(self, batch=batch)
                data, label, pred, loss = self.fit_batch(batch)
                for h in handlers:
                    if isinstance(h, BatchEnd):
                        h.batch_end(self, batch=batch,
                                    batch_size=data.shape[0])
                if _preempt.requested():
                    self._drain(handlers, _preempt)
                if self.stop_training:
                    break
            if hasattr(train_data, "reset"):
                train_data.reset()
            if val_data is not None:
                self.evaluate(val_data)
            for h in handlers:
                if isinstance(h, EpochEnd):
                    h.epoch_end(self)
        for h in handlers:
            if isinstance(h, TrainEnd):
                h.train_end(self)

    def _drain(self, handlers, _preempt):
        """Graceful preemption drain: save a final mid-epoch checkpoint
        through every handler that supports it, then exit for reschedule
        (SystemExit with preempt.exit_code(), default 75)."""
        self.logger.warning(
            "preemption drain requested (%s): writing final checkpoint "
            "and exiting for reschedule",
            (_preempt.event() or {}).get("signal") or "api")
        saved = False
        for h in handlers:
            if hasattr(h, "drain_save"):
                h.drain_save(self)
                saved = True
        # saved=True: the handlers checkpointed; skip the last-resort hook
        _preempt.drain(save=False if saved else None)

    def _prepare_handlers(self, epochs, batches, event_handlers):
        handlers = list(event_handlers or [])
        if not any(isinstance(h, StoppingHandler) for h in handlers):
            handlers.append(StoppingHandler(max_epoch=epochs,
                                            max_batch=batches))
        if not any(isinstance(h, LoggingHandler) for h in handlers):
            handlers.append(LoggingHandler(
                metrics=self.train_metrics + [self.train_loss_metric]))
        return handlers
