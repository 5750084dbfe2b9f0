"""BaseModule: the abstract train/score/predict interface.

API parity with the reference ``python/mxnet/module/base_module.py``
(``fit`` :376-530, ``score`` :212, ``predict`` :272, ``forward_backward``
:189), independently organised: the epoch loop is factored into
``_train_one_epoch`` and callback dispatch into a shared helper.
"""
from __future__ import annotations

import logging
import time

from .. import metric as metric_mod
from .. import ndarray as nd
from .. import telemetry as _tel
from ..checkpoint import hooks as _ckpt_hooks
from ..initializer import Uniform
from ..model import BatchEndParam

__all__ = ["BaseModule"]


def _fire(callbacks, payload):
    """Invoke a callback, or each callback in a list, with *payload*."""
    if callbacks is None:
        return
    if not isinstance(callbacks, (list, tuple)):
        callbacks = (callbacks,)
    for cb in callbacks:
        cb(payload)


def _fire_epoch(callbacks, epoch, sym, arg, aux):
    if callbacks is None:
        return
    if not isinstance(callbacks, (list, tuple)):
        callbacks = (callbacks,)
    for cb in callbacks:
        cb(epoch, sym, arg, aux)


def _coerce_metric(m):
    return m if isinstance(m, metric_mod.EvalMetric) else metric_mod.create(m)


def _subclass_must_implement(what):
    return NotImplementedError("subclass responsibility: " + what)


def _check_input_names(symbol, names, typename, throw):
    """Warn (or raise) when a declared data/label name is not a symbol arg."""
    known = symbol.list_arguments()
    weightish = ("_weight", "_bias", "_gamma", "_beta")
    for name in names:
        if name in known:
            continue
        suggestions = [a for a in known
                       if not any(a.endswith(suf) for suf in weightish)]
        msg = ("\033[91mYou created Module with Module(..., %s_names=%s) but "
               "input with name '%s' is not found in symbol.list_arguments(). "
               "Did you mean one of:\n\t%s\033[0m"
               % (typename, str(names), name, "\n\t".join(suggestions)))
        if throw:
            raise ValueError(msg)
        logging.warning(msg)


def _trim_pad(outputs, pad):
    """Drop the last *pad* rows (batch padding) from each output array."""
    if not pad:
        return list(outputs)
    return [out[: out.shape[0] - pad] for out in outputs]


class BaseModule:
    """Shared state flags + the generic training/eval loops.

    Concrete subclasses (Module, BucketingModule, ...) implement the
    computation primitives (bind/forward/backward/update/...); everything
    here is expressed in terms of those primitives only.
    """

    def __init__(self, logger=logging):
        self.logger = logger
        self.binded = self.params_initialized = self.optimizer_initialized = False
        self.for_training = self.inputs_need_grad = False
        self._symbol, self._total_exec_bytes = None, 0

    # ---- high-level driver API ----

    def forward_backward(self, data_batch):
        """One fused fwd+bwd pass (ref base_module.py:189)."""
        self.forward(data_batch, True)
        self.backward()

    def _fit_step(self, data_batch):
        """One fit-loop step: fwd+bwd+update. Subclasses may fuse all
        three into a single compiled program (Module does, when update
        placement allows).

        Returns None, or a handle on the step's outputs where they are
        fresh buffers that the next step neither rewrites nor donates.
        The fit loop then enqueues the next step before it reads this
        one's metric, and reads it inside :meth:`_outputs_read_as`."""
        self.forward_backward(data_batch)
        self.update()

    def _outputs_read_as(self, held):
        """Context manager: while open, ``update_metric`` and
        ``get_outputs`` read the outputs of the step whose ``_fit_step``
        returned *held*, whatever step has run since.  Only a module
        whose ``_fit_step`` returns a handle needs it."""
        raise _subclass_must_implement("_outputs_read_as")

    def fit(self, train_data, eval_data=None, eval_metric="acc",
            epoch_end_callback=None, batch_end_callback=None, kvstore="local",
            optimizer="sgd", optimizer_params=None,
            eval_end_callback=None, eval_batch_end_callback=None,
            initializer=None, arg_params=None, aux_params=None,
            allow_missing=False, force_rebind=False, force_init=False,
            begin_epoch=0, num_epoch=None, validation_metric=None,
            monitor=None):
        """Train for ``num_epoch - begin_epoch`` epochs (ref :376-530).

        Sequence per the reference contract: bind → (monitor) → init_params →
        init_optimizer → per-epoch {train pass, epoch callbacks, validation}.
        """
        if num_epoch is None:
            raise ValueError("fit() requires num_epoch")
        self.bind(data_shapes=train_data.provide_data,
                  label_shapes=train_data.provide_label,
                  for_training=True, force_rebind=force_rebind)
        if monitor is not None:
            self.install_monitor(monitor)
        self.init_params(initializer=initializer or Uniform(0.01),
                         arg_params=arg_params, aux_params=aux_params,
                         allow_missing=allow_missing, force_init=force_init)
        self.init_optimizer(
            kvstore=kvstore, optimizer=optimizer,
            optimizer_params=optimizer_params or (("learning_rate", 0.01),))

        train_metric = _coerce_metric(eval_metric)
        val_metric = validation_metric if validation_metric is not None \
            else train_metric

        for epoch in range(begin_epoch, num_epoch):
            started = time.time()
            self._train_one_epoch(train_data, train_metric, epoch,
                                  batch_end_callback, monitor)
            for name, val in train_metric.get_name_value():
                self.logger.info("Epoch[%d] Train-%s=%f", epoch, name, val)
            self.logger.info("Epoch[%d] Time cost=%.3f",
                             epoch, time.time() - started)

            # Sync trained params back into the module's canonical copies so
            # epoch callbacks (checkpointing) observe the latest values.
            arg_now, aux_now = self.get_params()
            self.set_params(arg_now, aux_now)
            _fire_epoch(epoch_end_callback, epoch, self.symbol, arg_now, aux_now)

            if eval_data:
                scored = self.score(eval_data, val_metric, epoch=epoch,
                                    batch_end_callback=eval_batch_end_callback,
                                    score_end_callback=eval_end_callback)
                for name, val in scored:
                    self.logger.info("Epoch[%d] Validation-%s=%f",
                                     epoch, name, val)
            train_data.reset()

    def _train_one_epoch(self, train_data, train_metric, epoch,
                         batch_end_callback, monitor):
        """Inner loop of one training epoch over *train_data*.

        A one-deep pipeline.  Where ``_fit_step`` hands back its outputs
        (see there), the batch's metric and callback are *owed*: the next
        iteration enqueues its own step first and settles them after, so
        the device runs step N+1 while the host waits for, fetches and
        folds in step N's outputs; the epoch's end settles the last
        batch.  Where it hands back nothing (any module but a fused
        ``Module``, and every step under a monitor) the batch is settled
        at once: step, metric, callback, as the reference's loop."""
        train_metric.reset()

        def settle(nbatch, batch, held, rec):
            # batch *nbatch*'s metric, from its own outputs and labels, and
            # then its callback: in batch order, each after its own step
            with _tel.span("fit_update_metric", cat="host") \
                    if rec else _tel.NO_SPAN:
                if held is None:
                    self.update_metric(train_metric, batch.label)
                else:
                    with self._outputs_read_as(held):
                        self.update_metric(train_metric, batch.label)
            if monitor is not None:
                monitor.toc_print()
            with _tel.span("fit_callback", cat="host") \
                    if rec else _tel.NO_SPAN:
                _fire(batch_end_callback,
                      BatchEndParam(epoch=epoch, nbatch=nbatch,
                                    eval_metric=train_metric,
                                    locals=locals()))

        owed = None             # (nbatch, batch, held) not settled yet
        # the iterator's next() runs between two fit_batch spans (io.py
        # books it as data_batch), so it carries neither batch's id
        for nbatch, batch in enumerate(train_data):
            # nbatch names the batch whose step this iteration enqueues;
            # the metric and callback under it are the owed batch's
            with _tel.span("fit_batch", cat="batch",
                           args={"epoch": epoch, "nbatch": nbatch}):
                # the root asked whether anything records; its children
                # here read the answer (off: one bool test each)
                rec = _tel.trace_active()
                self.prepare(batch)
                if monitor is None:
                    held = self._fit_step(batch)
                else:
                    monitor.tic()
                    self.forward_backward(batch)
                    self.update()
                    held = None
                # step boundary (see gluon/trainer.py): checkpoint snapshot
                # point + pending-SIGTERM honor.  Noted with the step the
                # module's parameters now hold and the cursor of its batch,
                # whichever batch's callback comes next
                _ckpt_hooks.note_step_boundary(epoch=epoch, batch=nbatch)
                if owed is not None:
                    _tel.bump("fit_step_overlapped")
                    settle(*owed, rec)
                owed = (nbatch, batch, held)
                if held is None:
                    settle(*owed, rec)
                    owed = None
        if owed is not None:
            # the drain: a root of its own, with no step under it
            with _tel.span("fit_batch", cat="batch",
                           args={"epoch": epoch, "nbatch": owed[0],
                                 "drain": True}):
                settle(*owed, _tel.trace_active())

    def score(self, eval_data, eval_metric, num_batch=None,
              batch_end_callback=None, score_end_callback=None, reset=True,
              epoch=0):
        """Evaluate the metric over *eval_data* (ref base_module.py:212)."""
        self._require_ready()
        if reset:
            eval_data.reset()
        eval_metric = _coerce_metric(eval_metric)
        eval_metric.reset()

        seen = 0
        for nbatch, batch in enumerate(eval_data):
            if num_batch is not None and nbatch >= num_batch:
                break
            self.forward(batch, is_train=False)
            self.update_metric(eval_metric, batch.label)
            _fire(batch_end_callback,
                  BatchEndParam(epoch=epoch, nbatch=nbatch,
                                eval_metric=eval_metric, locals=locals()))
            seen += 1
        _fire(score_end_callback,
              BatchEndParam(epoch=epoch, nbatch=seen,
                            eval_metric=eval_metric, locals=locals()))
        return eval_metric.get_name_value()

    def iter_predict(self, eval_data, num_batch=None, reset=True):
        """Yield ``(padded-trimmed outputs, i, batch)`` per batch."""
        self._require_ready()
        if reset:
            eval_data.reset()
        for nbatch, batch in enumerate(eval_data):
            if num_batch is not None and nbatch >= num_batch:
                break
            self.forward(batch, is_train=False)
            yield (_trim_pad(self.get_outputs(), batch.pad or 0),
                   nbatch, batch)

    def predict(self, eval_data, num_batch=None, merge_batches=True,
                reset=True, always_output_list=False):
        """Collect forward outputs over *eval_data* (ref base_module.py:272).

        With ``merge_batches`` the per-batch output lists are concatenated
        along axis 0 into one array per output head.
        """
        per_batch = [[o.copy() for o in outs] for outs, _, _
                     in self.iter_predict(eval_data, num_batch, reset)]
        if not per_batch or not merge_batches:
            return per_batch
        heads = len(per_batch[0])
        if any(len(outs) != heads for outs in per_batch):
            raise ValueError(
                "cannot merge: per-batch output counts differ "
                "(bucketing produces variable head counts)")
        merged = [nd.concatenate([outs[i] for outs in per_batch])
                  for i in range(heads)]
        if heads == 1 and not always_output_list:
            return merged[0]
        return merged

    # ---- parameter management ----

    @property
    def symbol(self):
        return self._symbol

    def get_params(self):
        raise _subclass_must_implement("get_params")

    def init_params(self, initializer=None, arg_params=None, aux_params=None,
                    allow_missing=False, force_init=False, allow_extra=False):
        raise _subclass_must_implement("init_params")

    def set_params(self, arg_params, aux_params, allow_missing=False,
                   force_init=True, allow_extra=False):
        self.init_params(initializer=None, arg_params=arg_params,
                         aux_params=aux_params, allow_missing=allow_missing,
                         force_init=force_init, allow_extra=allow_extra)

    def save_params(self, fname):
        """Write params to *fname* in the ``arg:``/``aux:`` dict format."""
        arg_params, aux_params = self.get_params()
        blob = {}
        for prefix, group in (("arg:", arg_params), ("aux:", aux_params)):
            for name, array in group.items():
                blob[prefix + name] = array
        nd.save(fname, blob)

    def load_params(self, fname):
        """Read params written by :meth:`save_params`."""
        arg_params, aux_params = {}, {}
        for key, array in nd.load(fname).items():
            kind, _, name = key.partition(":")
            if kind == "arg":
                arg_params[name] = array
            elif kind == "aux":
                aux_params[name] = array
            else:
                raise ValueError("unrecognised key %r in %s" % (key, fname))
        self.set_params(arg_params, aux_params)

    def get_states(self, merge_multi_context=True):
        self._require_ready()
        return []

    def set_states(self, states=None, value=None):
        self._require_ready()

    def install_monitor(self, mon):
        raise _subclass_must_implement("install_monitor")

    def prepare(self, data_batch):
        """Hook called before each training batch (sparse row-id prefetch
        in the reference); default no-op."""

    def _require_ready(self):
        if not (self.binded and self.params_initialized):
            raise AssertionError("module must be binded and initialized")

    # ---- abstract properties ----

    @property
    def data_names(self):
        raise _subclass_must_implement("data_names")

    @property
    def output_names(self):
        raise _subclass_must_implement("output_names")

    @property
    def data_shapes(self):
        raise _subclass_must_implement("data_shapes")

    @property
    def label_shapes(self):
        raise _subclass_must_implement("label_shapes")

    @property
    def output_shapes(self):
        raise _subclass_must_implement("output_shapes")

    # ---- abstract computation primitives ----

    def forward(self, data_batch, is_train=None):
        raise _subclass_must_implement("forward")

    def backward(self, out_grads=None):
        raise _subclass_must_implement("backward")

    def get_outputs(self, merge_multi_context=True):
        raise _subclass_must_implement("get_outputs")

    def get_input_grads(self, merge_multi_context=True):
        raise _subclass_must_implement("get_input_grads")

    def update(self):
        raise _subclass_must_implement("update")

    def update_metric(self, eval_metric, labels):
        raise _subclass_must_implement("update_metric")

    def bind(self, data_shapes, label_shapes=None,
             for_training=True, inputs_need_grad=False, force_rebind=False,
             shared_module=None, grad_req="write"):
        raise _subclass_must_implement("bind")

    def init_optimizer(self, kvstore="local", optimizer="sgd",
                       optimizer_params=(("learning_rate", 0.01),),
                       force_init=False):
        raise _subclass_must_implement("init_optimizer")
