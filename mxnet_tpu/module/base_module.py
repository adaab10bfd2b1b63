"""BaseModule — the training-loop contract.

Reference: ``python/mxnet/module/base_module.py`` (``fit`` epoch loop at
``:376,:476-496``: forward_backward → update → update_metric; ``score``,
``predict``, param get/set, checkpointing hooks).  Semantics preserved;
the compute under it is XLA instead of engine-pushed closures.
"""
from __future__ import annotations

import logging
import signal
import sys
import threading
import time

from ..base import MXNetError, StepHung, TrainingDiverged, TrainingPreempted
from .. import metric as metric_mod
from .. import io as io_mod
from ..ndarray import NDArray
from ..profiler import span as _span

__all__ = ["BaseModule"]


def _as_metric(m):
    return m if isinstance(m, metric_mod.EvalMetric) else metric_mod.create(m)


class _PreemptionGuard:
    """SIGTERM/SIGINT watcher for the duration of one ``fit``.

    The handler only records the signal (the async-signal-safe minimum);
    the training loop polls ``fired`` at batch boundaries, where params/
    optimizer state are consistent, drains the prefetch pipeline, writes
    the final checkpoint, and raises :class:`TrainingPreempted`.  Python
    only allows signal handlers on the main thread, so installation is a
    no-op elsewhere (a fit running on a worker thread trains exactly as
    before).  Previous handlers are restored on exit."""

    SIGNALS = (signal.SIGTERM, signal.SIGINT)

    def __init__(self, enabled=True):
        self.fired = None
        self._prev = {}
        self._enabled = enabled and \
            threading.current_thread() is threading.main_thread()

    def __enter__(self):
        if self._enabled:
            for sig in self.SIGNALS:
                try:
                    self._prev[sig] = signal.signal(sig, self._record)
                except (ValueError, OSError):  # embedded interpreter etc.
                    pass
        return self

    def _record(self, signum, frame):
        self.fired = signum

    def __exit__(self, exc_type, exc, tb):
        for sig, prev in self._prev.items():
            try:
                signal.signal(sig, prev)
            except (ValueError, OSError):
                pass
        return False


class BaseModule:
    def __init__(self, logger=logging):
        self.logger = logger
        self.binded = False
        self.for_training = False
        self.params_initialized = False
        self.optimizer_initialized = False
        self._symbol = None

    # -- things subclasses implement -----------------------------------
    def forward(self, data_batch, is_train=None):
        raise NotImplementedError

    def backward(self, out_grads=None):
        raise NotImplementedError

    def update(self):
        raise NotImplementedError

    def _epoch_end_sync(self):
        """Epoch-boundary synchronization hook (dist_async averaging
        round); default no-op."""

    def get_outputs(self, merge_multi_context=True):
        raise NotImplementedError

    def update_metric(self, eval_metric, labels):
        raise NotImplementedError

    def bind(self, *args, **kwargs):
        raise NotImplementedError

    def init_params(self, *args, **kwargs):
        raise NotImplementedError

    def init_optimizer(self, *args, **kwargs):
        raise NotImplementedError

    def get_params(self):
        raise NotImplementedError

    # -- shared conveniences -------------------------------------------
    def forward_backward(self, data_batch):
        self.forward(data_batch, is_train=True)
        self.backward()

    @property
    def symbol(self):
        return self._symbol

    def set_params(self, arg_params, aux_params, allow_missing=False,
                   force_init=True, allow_extra=False):
        self.init_params(initializer=None, arg_params=arg_params,
                         aux_params=aux_params, allow_missing=allow_missing,
                         force_init=force_init, allow_extra=allow_extra)

    def score(self, eval_data, eval_metric, num_batch=None,
              batch_end_callback=None, score_end_callback=None, reset=True,
              epoch=0):
        """Evaluate on a data iterator (reference ``BaseModule.score``)."""
        assert self.binded and self.params_initialized
        eval_metric = _as_metric(eval_metric)
        eval_metric.reset()
        if reset:
            eval_data.reset()
        actual_num_batch = 0
        for nbatch, eval_batch in enumerate(eval_data):
            if num_batch is not None and nbatch == num_batch:
                break
            self.forward(eval_batch, is_train=False)
            self.update_metric(eval_metric, eval_batch.label)
            if batch_end_callback is not None:
                for cb in _as_list(batch_end_callback):
                    cb(BatchEndParam(epoch=epoch, nbatch=nbatch,
                                     eval_metric=eval_metric, locals=locals()))
            actual_num_batch += 1
        if score_end_callback is not None:
            for cb in _as_list(score_end_callback):
                cb(BatchEndParam(epoch=epoch, nbatch=actual_num_batch,
                                 eval_metric=eval_metric, locals=locals()))
        return eval_metric.get_name_value()

    def predict(self, eval_data, num_batch=None, merge_batches=True,
                reset=True, always_output_list=False):
        """Run forward over an iterator, concatenating outputs (reference
        ``BaseModule.predict``)."""
        from ..ndarray import concat

        assert self.binded and self.params_initialized
        if reset:
            eval_data.reset()
        output_list = []
        for nbatch, eval_batch in enumerate(eval_data):
            if num_batch is not None and nbatch == num_batch:
                break
            self.forward(eval_batch, is_train=False)
            pad = eval_batch.pad or 0
            outputs = [out[0:out.shape[0] - pad]
                       for out in self.get_outputs()]
            output_list.append(outputs)
        if not output_list:
            return []
        if merge_batches:
            num_outputs = len(output_list[0])
            for out in output_list:
                if len(out) != num_outputs:
                    raise MXNetError(
                        "Cannot merge batches: different number of outputs")
            merged = [concat([out[i] for out in output_list], dim=0)
                      for i in range(num_outputs)]
            if num_outputs == 1 and not always_output_list:
                return merged[0]
            return merged
        return output_list

    def fit(self, train_data, eval_data=None, eval_metric="acc",
            epoch_end_callback=None, batch_end_callback=None,
            kvstore="local", optimizer="sgd",
            optimizer_params=(("learning_rate", 0.01),),
            eval_end_callback=None, eval_batch_end_callback=None,
            initializer=None, arg_params=None, aux_params=None,
            allow_missing=False, force_rebind=False, force_init=False,
            begin_epoch=0, num_epoch=None, validation_metric=None,
            monitor=None, param_sharding=None, compute_dtype=None,
            prefetch_to_device=None, prefetch_depth=2,
            metric_sync_period=None, steps_per_call=None,
            checkpoint=None, checkpoint_period=1, resume_from=None,
            health=None, loss_scale=None, step_timeout_s=None,
            zero=None, plan=None, elastic=None):
        """The training loop (reference ``BaseModule.fit``,
        ``base_module.py:376``), pipelined: by default the train iterator
        is wrapped in :class:`~mxnet_tpu.io.DevicePrefetchIter` so batch
        ``n+1`` stages host→device while batch ``n``'s step executes, and
        the loop itself never blocks on device results between steps (JAX
        async dispatch) except where a metric value is actually read.

        extra knobs (all also settable by env var):

        * ``prefetch_to_device`` — wrap ``train_data`` for background
          device staging (default: ``MXNET_FIT_PIPELINE``, on).  Pass an
          already-wrapped ``DevicePrefetchIter`` as ``train_data`` to
          control staging parameters yourself.
        * ``prefetch_depth`` — staging ring depth (≥2 for double
          buffering).
        * ``metric_sync_period`` — accumulate (label, pred) device refs
          and fold them into the metric every N batches instead of every
          batch (``MXNET_METRIC_SYNC_PERIOD``); a ``Speedometer`` reading
          the metric still sees up-to-date values (reads force a flush).
          Only ``F1``, ``CustomMetric`` and user metrics gain from it: the
          other built-in metrics reduce on the device (``metric.py``).
        * ``steps_per_call`` — dispatch K optimizer steps as one device
          call (``lax.scan`` over a packed super-batch staged by the
          prefetcher); requires the fused step (``MXNET_STEPS_PER_CALL``).

        fault tolerance (see ``docs/fault_tolerance.md``):

        * ``checkpoint`` — a
          :class:`~mxnet_tpu.checkpoint.CheckpointManager` (or a
          directory path for one with defaults).  Epoch-end checkpoints
          are written every ``checkpoint_period`` epochs, and a SIGTERM/
          SIGINT arriving mid-run stops the loop at the next batch
          boundary, writes a final mid-epoch checkpoint, and raises
          :class:`~mxnet_tpu.base.TrainingPreempted`.
        * ``resume_from`` — a ``CheckpointState``/``CheckpointManager``/
          prefix string/``(prefix, epoch)`` pair (see
          :func:`~mxnet_tpu.checkpoint.resolve_resume`): params, aux,
          optimizer states and update counters are restored and the data
          stream is fast-forwarded to the recorded position, so the run
          continues the uninterrupted trajectory.

        run health (see ``docs/health_monitoring.md``):

        * ``health`` — enable the run-health sentinel: True, a policy
          string ('warn'/'skip'/'rollback'), or a configured
          :class:`~mxnet_tpu.health.HealthMonitor`
          (``MXNET_HEALTH_MONITOR=1``).  The fused step then computes a
          global grad norm + non-finite flag on-device, skips poisoned
          steps bit-exactly, and — under the 'rollback' policy with a
          ``checkpoint`` manager — reloads last-good and backs off the
          learning rate on sustained divergence, raising
          :class:`~mxnet_tpu.base.TrainingDiverged` when recovery is
          exhausted.
        * ``loss_scale`` — 'dynamic', a fixed scale, or a
          :class:`~mxnet_tpu.health.DynamicLossScaler` for low-precision
          ``compute_dtype`` runs (``MXNET_LOSS_SCALE``).
        * ``step_timeout_s`` — arm a step watchdog
          (``MXNET_STEP_TIMEOUT_S``): a step making no progress for this
          long dumps all-thread stacks + health stats to an artifact and
          raises :class:`~mxnet_tpu.base.StepHung` instead of hanging.
        * ``zero`` — 'auto' | 'on' | 'off' | '3': ZeRO-style sharding of
          the optimizer state and the weight update over the mesh's
          data axis; '3' additionally keeps the parameters themselves
          at rest as flat 1/N tiles, re-gathered bucket by bucket
          inside each step (``MXNET_ZERO``; see
          ``docs/performance.md``).
        * ``plan`` — a :class:`~mxnet_tpu.parallel.ParallelPlan` or its
          spec string (``"data=4,model=2,zero=3"``): ONE declaration
          composing TP x PP x DP/ZeRO over a named mesh
          (``MXNET_PLAN``; see ``docs/performance.md`` "Composing
          parallelisms").
        * ``elastic`` — live elasticity: True (or ``MXNET_ELASTIC=1``,
          or a configured
          :class:`~mxnet_tpu.parallel.elastic.ElasticCoordinator`)
          polls for scale events at every batch boundary — SIGUSR1, a
          dead peer, or a ``tools/launch.py --scale-event`` manifest —
          and migrates the run in memory (quiesce / re-form / reshard /
          resume) instead of dying; a failed migration falls back to
          the last ``checkpoint``.  See ``docs/fault_tolerance.md``
          "Live elasticity".
        """
        from ..base import get_env
        from ..initializer import Uniform
        from .. import checkpoint as ckpt_mod

        assert num_epoch is not None, "please specify number of epochs"
        if initializer is None:
            initializer = Uniform(0.01)

        mgr = None
        if checkpoint is not None:
            mgr = checkpoint \
                if isinstance(checkpoint, ckpt_mod.CheckpointManager) \
                else ckpt_mod.CheckpointManager(str(checkpoint))

        resume_state = None
        if resume_from is not None:
            resume_state = ckpt_mod.resolve_resume(resume_from)
            # checkpointed params take over; whatever the caller passed
            # was the cold-start initialization this run supersedes
            arg_params = resume_state.arg_params
            aux_params = resume_state.aux_params
            force_init = True
            begin_epoch = resume_state.epoch
            self.logger.info(
                "resuming fit from %r: epoch %d, batch offset %d, "
                "num_update %d", resume_state.prefix or resume_from,
                resume_state.epoch, resume_state.nbatch,
                resume_state.num_update)

        K = max(1, int(steps_per_call if steps_per_call is not None
                       else get_env("MXNET_STEPS_PER_CALL", 1, int)))
        if K > 1 and monitor is not None:
            raise MXNetError(
                "steps_per_call > 1 is incompatible with a Monitor: the "
                "monitor needs the per-node executor path, which has no "
                "scanned multi-step form")

        self.bind(data_shapes=train_data.provide_data,
                  label_shapes=train_data.provide_label,
                  for_training=True, force_rebind=force_rebind)
        if monitor is not None:
            self.install_monitor(monitor)
        self.init_params(initializer=initializer, arg_params=arg_params,
                         aux_params=aux_params, allow_missing=allow_missing,
                         force_init=force_init)
        opt_kwargs = {}
        if param_sharding is not None:
            # only Module.init_optimizer knows this kwarg; BucketingModule
            # and PythonModule keep the base signature
            opt_kwargs["param_sharding"] = param_sharding
        if compute_dtype is not None:
            opt_kwargs["compute_dtype"] = compute_dtype
        if K > 1:
            opt_kwargs["steps_per_call"] = K
        if health is not None:
            opt_kwargs["health"] = health
        if loss_scale is not None:
            opt_kwargs["loss_scale"] = loss_scale
        if zero is not None:
            opt_kwargs["zero"] = zero
        if plan is not None:
            opt_kwargs["plan"] = plan
        self.init_optimizer(kvstore=kvstore, optimizer=optimizer,
                            optimizer_params=optimizer_params, **opt_kwargs)
        # env-driven activation (MXNET_HEALTH_MONITOR=1) happens inside
        # Module.init_optimizer; modules without health support simply
        # have no monitor
        hmon = getattr(self, "_health_monitor", None)

        from ..parallel.elastic import maybe_coordinator
        elastic = maybe_coordinator(elastic)

        if mgr is not None and mgr.kvstore is None:
            # the manager inherits rank/barrier semantics from the store
            # the fit actually trains against
            mgr.kvstore = getattr(self, "_kvstore", None)
        if resume_state is not None:
            self._restore_from(resume_state)
            # fast-forward the RAW iterator before the staging wrap: the
            # prefetch worker starts pulling batches at construction
            self._fast_forward_data(train_data, resume_state.epoch,
                                    resume_state.nbatch)

        # AOT warmup: lower+compile the fused step in the background so
        # XLA compilation overlaps the prefetch-iterator spin-up below
        # instead of landing serially inside the first step
        # (MXNET_AOT_WARMUP=0 restores the lazy first-call compile)
        compile_thread = None
        if get_env("MXNET_AOT_WARMUP", True, bool) and \
                hasattr(self, "prepare_compiled"):
            import threading

            def _warmup():
                try:
                    self.prepare_compiled()
                except Exception as e:
                    # warmup is an optimization: the lazy path compiles
                    # on the first step exactly as before
                    self.logger.debug("AOT warmup unavailable: %s", e)

            compile_thread = threading.Thread(
                target=_warmup, name="mxtpu-aot-compile", daemon=True)
            compile_thread.start()

        # wrap AFTER init_optimizer: staging placement follows the mesh
        # the optimizer decided on (kvstore type → mesh)
        pipeline = prefetch_to_device
        if pipeline is None:
            pipeline = get_env("MXNET_FIT_PIPELINE", True, bool)
        fit_data = train_data
        if pipeline or K > 1:
            # packed super-batches only exist via the staging iter, so
            # K > 1 forces the wrap even if pipelining was switched off
            ctx = getattr(self, "_context", None)
            if isinstance(ctx, (list, tuple)):  # BucketingModule keeps a bare Context
                ctx = ctx[0] if ctx else None
            fit_data = io_mod.prefetch_to_device(
                train_data, prefetch_depth=prefetch_depth,
                mesh=getattr(self, "_mesh", None), context=ctx,
                steps_per_call=K)

        if validation_metric is None:
            validation_metric = eval_metric
        eval_metric = _as_metric(eval_metric)
        sync = int(metric_sync_period if metric_sync_period is not None
                   else get_env("MXNET_METRIC_SYNC_PERIOD", 1, int))
        if sync > 1:
            eval_metric = metric_mod.LazyEvalMetric(eval_metric,
                                                    sync_period=sync)

        timeout = float(step_timeout_s if step_timeout_s is not None
                        else get_env("MXNET_STEP_TIMEOUT_S", 0.0, float))
        watchdog = None
        if timeout > 0:
            from ..health import StepWatchdog

            watchdog = StepWatchdog(
                timeout,
                stats_cb=hmon.snapshot if hmon is not None else None)
            watchdog.start()

        if compile_thread is not None:
            # the first step needs the compiled executable anyway; a
            # bounded join keeps a wedged compile from hanging fit
            # silently (the watchdog covers the in-step hang case)
            compile_thread.join(
                get_env("MXNET_AOT_WARMUP_TIMEOUT_S", 600.0, float))

        try:
            self._fit_epochs(fit_data, eval_data, eval_metric,
                             validation_metric, monitor,
                             batch_end_callback, epoch_end_callback,
                             eval_end_callback, eval_batch_end_callback,
                             begin_epoch, num_epoch, K,
                             mgr=mgr, checkpoint_period=checkpoint_period,
                             resume_nbatch=resume_state.nbatch
                             if resume_state is not None else 0,
                             hmon=hmon, watchdog=watchdog, elastic=elastic)
            if mgr is not None:
                # drain the async checkpoint writer before declaring the
                # fit done: a failed background write must fail the fit,
                # not vanish with the daemon thread
                mgr.flush()
        except StepHung as e:
            # the watchdog delivers a BARE StepHung through
            # PyThreadState_SetAsyncExc (the C API cannot pass
            # arguments); rehydrate the message and artifact path it
            # recorded before raising
            if e.args and e.args[0]:
                raise
            from ..health import last_hang_details

            d = last_hang_details()
            raise StepHung(
                d.get("msg") or "training step made no progress (step "
                "watchdog fired)", note=d.get("note"),
                dump_path=d.get("dump_path")) from None
        finally:
            if watchdog is not None:
                watchdog.stop()
            if fit_data is not train_data:
                # the staging worker must not outlive fit: it would keep
                # consuming the caller's iterator (stealing the batches a
                # follow-up fit/score would read) and can sit inside a
                # device_put when the interpreter tears the runtime down
                in_flight = sys.exc_info()[0] is not None
                try:
                    fit_data.close()
                except Exception:
                    # close() re-raises worker errors the loop never saw;
                    # surface them on a clean exit, but never let them
                    # mask the exception already propagating
                    if not in_flight:
                        raise
                    self.logger.exception(
                        "prefetch close() failed during fit teardown; "
                        "keeping the original error")
                train_data.reset()

    def _fit_epochs(self, fit_data, eval_data, eval_metric,
                    validation_metric, monitor, batch_end_callback,
                    epoch_end_callback, eval_end_callback,
                    eval_batch_end_callback, begin_epoch, num_epoch, K,
                    mgr=None, checkpoint_period=1, resume_nbatch=0,
                    hmon=None, watchdog=None, elastic=None):
        from ..testing import faults

        period = max(1, int(checkpoint_period))
        with _PreemptionGuard() as guard:
            for epoch in range(begin_epoch, num_epoch):
                tic = time.time()
                eval_metric.reset()
                # a resumed mid-epoch run keeps counting from its recorded
                # offset so a second preemption checkpoints the true
                # position (the metric only covers the replayed remainder)
                nbatch = resume_nbatch if epoch == begin_epoch else 0
                data_iter = iter(fit_data)
                end_of_batch = False
                try:
                    next_data_batch = next(data_iter)
                except StopIteration:
                    # a resume checkpoint taken right after an epoch's
                    # final batch fast-forwards past the whole epoch;
                    # run the epoch tail and move on
                    end_of_batch = True
                while not end_of_batch:
                    with _span("fit.batch", epoch=epoch, nbatch=nbatch):
                        data_batch = next_data_batch
                        if watchdog is not None:
                            watchdog.kick("epoch %d batch %d"
                                          % (epoch, nbatch))
                        faults.inject("step")
                        if monitor is not None:
                            monitor.tic()
                        with _span("fit.forward_backward"):
                            self.forward_backward(data_batch)
                        with _span("fit.update"):
                            self.update()
                        if hmon is not None:
                            # dispatch boundary: feed the monitor this
                            # step's device stats refs; it realizes LAGGED
                            # entries (already finished on device — free
                            # reads) and may request a rollback
                            self._health_tick(hmon, mgr, epoch, nbatch)
                        # lookahead next() AFTER dispatch: pulling batch
                        # n+1 off the staging queue (and refilling it)
                        # overlaps the step that is still executing
                        # asynchronously on device
                        with _span("fit.next_batch"):
                            try:
                                next_data_batch = next(data_iter)
                            except StopIteration:
                                end_of_batch = True
                        with _span("fit.update_metric"):
                            if K > 1:
                                outs = self.get_outputs()
                                labels = data_batch.label or []
                                for k in range(K):
                                    self.update_metric(
                                        eval_metric, [l[k] for l in labels],
                                        outputs=[o[k] for o in outs])
                            else:
                                self.update_metric(eval_metric,
                                                   data_batch.label)
                        if monitor is not None:
                            monitor.toc_print()
                        if batch_end_callback is not None:
                            with _span("fit.callbacks"):
                                for cb in _as_list(batch_end_callback):
                                    cb(BatchEndParam(
                                        epoch=epoch, nbatch=nbatch,
                                        eval_metric=eval_metric,
                                        locals=locals()))
                        nbatch += K
                        if guard.fired is not None:
                            # batch boundary: params/optimizer state
                            # consistent
                            self._preempt(guard.fired, fit_data, mgr,
                                          epoch, nbatch)
                        if elastic is not None:
                            event = elastic.poll()
                            if event is not None:
                                self._elastic_migrate(
                                    elastic, event, mgr, fit_data, epoch,
                                    nbatch)
                                # the stream was re-seeked to this
                                # boundary (migration) or left in place
                                # (fallback); either way the lookahead
                                # batch fetched above predates the move —
                                # refetch
                                data_iter = iter(fit_data)
                                end_of_batch = False
                                try:
                                    next_data_batch = next(data_iter)
                                except StopIteration:
                                    end_of_batch = True

                if watchdog is not None:
                    # the epoch tail (eval pass, checkpoint write,
                    # callbacks) is not step progress; the first kick of
                    # the next epoch rearms the timer
                    watchdog.pause()
                if hmon is not None:
                    # drain the lag queue BEFORE the epoch checkpoint: a
                    # pending rollback must not see a freshly saved
                    # diverged state as "last good"
                    self._health_tick(hmon, mgr, epoch, nbatch,
                                      flush=True)

                for name, val in eval_metric.get_name_value():
                    self.logger.info("Epoch[%d] Train-%s=%f", epoch, name,
                                     val)
                self.logger.info("Epoch[%d] Time cost=%.3f", epoch,
                                 time.time() - tic)

                self._epoch_end_sync()
                arg_params_, aux_params_ = self.get_params()
                self.set_params(arg_params_, aux_params_)

                if mgr is not None and ((epoch + 1) % period == 0
                                        or epoch + 1 == num_epoch):
                    mgr.save(self, epoch=epoch + 1, nbatch=0)

                if epoch_end_callback is not None:
                    for cb in _as_list(epoch_end_callback):
                        cb(epoch, self.symbol, arg_params_, aux_params_)

                if guard.fired is not None:
                    # signal landed in the epoch tail: skip eval and stop
                    # at the epoch boundary (tag = completed epochs)
                    self._preempt(guard.fired, fit_data, mgr, epoch + 1, 0)

                if eval_data is not None:
                    res = self.score(
                        eval_data, validation_metric,
                        score_end_callback=eval_end_callback,
                        batch_end_callback=eval_batch_end_callback,
                        epoch=epoch)
                    for name, val in res:
                        self.logger.info("Epoch[%d] Validation-%s=%f",
                                         epoch, name, val)
                fit_data.reset()

    # -- fault tolerance hooks ------------------------------------------
    def _preempt(self, signum, fit_data, mgr, epoch, nbatch):
        """Shut the pipeline down, write the final checkpoint, and raise
        :class:`TrainingPreempted` carrying the checkpointed position."""
        self.logger.warning(
            "signal %d received: stopping training at epoch %d, batch %d%s",
            signum, epoch, nbatch,
            "" if mgr is None else "; writing final checkpoint")
        close = getattr(fit_data, "close", None)
        if close is not None:
            try:
                # drain the staging worker first so the checkpoint write
                # does not race an in-flight device_put
                close()
            except Exception:
                self.logger.exception(
                    "prefetch teardown failed during preemption; "
                    "continuing to the checkpoint write")
        if mgr is not None:
            mgr.save(self, epoch=epoch, nbatch=nbatch)
            # the preemption latch is the last code to run before the
            # process exits: drain the async writer so the final
            # checkpoint is on disk (and its errors surfaced) before
            # TrainingPreempted unwinds
            mgr.flush()
        raise TrainingPreempted(
            "training preempted by signal %d at epoch %d, batch %d%s"
            % (signum, epoch, nbatch,
               "; checkpoint written under %r" % mgr.prefix
               if mgr is not None else " (no checkpoint manager "
               "configured — pass fit(checkpoint=...) to save on "
               "preemption)"),
            epoch=epoch, nbatch=nbatch, signum=signum)

    def _elastic_migrate(self, elastic, event, mgr, fit_data, epoch,
                         nbatch):
        """Run one live plan migration at the batch boundary
        ``(epoch, nbatch)``; any mid-migration failure falls back to the
        last good checkpoint so the job is always either migrated or
        resumable — never wedged half-moved.  A retirement
        (:class:`TrainingPreempted` from a shrink) propagates: that rank
        is leaving on purpose, with its quiesce checkpoint written."""
        try:
            return elastic.migrate(self, event, epoch=epoch, nbatch=nbatch,
                                   train_data=fit_data, checkpoint=mgr)
        except (TrainingPreempted, KeyboardInterrupt):
            raise
        except Exception as e:
            if mgr is None or mgr.latest() is None:
                raise
            self.logger.warning(
                "elastic: migration failed mid-flight (%s: %s); falling "
                "back to the last good checkpoint", type(e).__name__, e)
            state = mgr.load()
            self.set_params(state.arg_params, state.aux_params)
            self._restore_from(state)
            # _health_rollback semantics: the restored trajectory
            # continues from the CURRENT stream boundary — the stream
            # itself never moved, only the lookahead batch is refetched.
            # The module may sit on EITHER plan here (a resume-phase
            # failure lands after the reshard), so repoint the staging
            # mesh at whatever the module actually runs now
            if hasattr(fit_data, "mesh"):
                fit_data.mesh = getattr(self, "_mesh", None)
            self._fast_forward_data(fit_data, epoch, nbatch)
            elastic.record_fallback(event, e, epoch=epoch, nbatch=nbatch)
            return None

    def _restore_from(self, state):
        """Apply the optimizer side of a resume after ``init_optimizer``:
        load the states file, then pin the update counters on EVERY
        optimizer copy (the module's, the worker-side updater's, and the
        kvstore's pickled clone) so lr schedules and bias correction
        continue from the checkpointed step instead of restarting — on
        both the split path (counts via ``_index_update_count``) and the
        fused path (reads ``num_update`` directly)."""
        if state.states_path is not None and \
                hasattr(self, "load_optimizer_states"):
            self.load_optimizer_states(state.states_path)
        elif getattr(state, "opt_states", None) and \
                hasattr(self, "set_fused_optimizer_states"):
            # ZeRO-sharded states come back from the v2 piece-window
            # format as canonical weight-shaped trees, already assembled
            # across whatever topology wrote them
            self.set_fused_optimizer_states(state.opt_states)
        n = int(state.num_update)
        for o in self._optimizer_copies():
            o.begin_num_update = n
            o.num_update = n
            # lazily refilled from begin_num_update on the next update,
            # which makes the next step number n + 1 on every path
            o._index_update_count = {}

    def _optimizer_copies(self):
        """Every live optimizer object a state change must reach: the
        module's, the worker-side updater's, and the kvstore's pickled
        clone (deduped by identity)."""
        kv = getattr(self, "_kvstore", None)
        opts = []
        for o in (getattr(self, "_optimizer", None),
                  getattr(getattr(self, "_updater", None), "optimizer",
                          None),
                  getattr(kv, "_optimizer", None),
                  getattr(getattr(kv, "updater", None), "optimizer", None)):
            if o is not None and not any(o is seen for seen in opts):
                opts.append(o)
        return opts

    # -- run-health hooks -----------------------------------------------
    def _health_tick(self, hmon, mgr, epoch, nbatch, flush=False):
        """Feed the health monitor at a dispatch boundary and act on its
        verdict.  'skip' needs no action here — the device already kept
        the old params bit-exactly; 'rollback' reloads last-good."""
        stats = getattr(self, "_last_health_stats", None)
        self._last_health_stats = None
        try:
            if flush:
                if stats is not None:
                    hmon.tick(stats, step=(epoch, nbatch))
                action = hmon.flush()
            else:
                action = hmon.tick(stats, step=(epoch, nbatch))
        except TrainingDiverged as e:
            e.epoch, e.nbatch = epoch, nbatch
            raise
        if action == "rollback":
            self._health_rollback(hmon, mgr, epoch, nbatch)

    def _health_rollback(self, hmon, mgr, epoch, nbatch):
        """Reload the last-good checkpoint, back the learning rate off,
        and continue from the CURRENT stream position — the poison
        window is consumed, not replayed (replaying it would diverge
        identically).  No manager or no checkpoint on disk means there
        is nothing to roll back to: typed :class:`TrainingDiverged`."""
        reason = getattr(hmon, "_last_anomaly", "sustained divergence")
        if mgr is None or mgr.latest() is None:
            raise TrainingDiverged(
                "health policy requested a rollback at epoch %d batch %d "
                "(%s) but no checkpoint is available — pass "
                "fit(checkpoint=...) so there is a last-good state to "
                "reload" % (epoch, nbatch, reason),
                epoch=epoch, nbatch=nbatch, reason=reason)
        state = mgr.load()
        hmon.note_rollback(step=(epoch, nbatch))
        factor = hmon.lr_backoff
        self.logger.warning(
            "health: rollback %d/%d at epoch %d batch %d (%s) — "
            "restoring checkpoint epoch %d (num_update %d), learning "
            "rate x%g", hmon.consecutive_rollbacks, hmon.max_rollbacks,
            epoch, nbatch, reason, state.epoch, state.num_update, factor)
        self.set_params(state.arg_params, state.aux_params)
        self._restore_from(state)
        for o in self._optimizer_copies():
            o.lr *= factor
            sch = getattr(o, "lr_scheduler", None)
            if sch is not None:
                # FactorScheduler reads base_lr; Poly/Cosine recompute
                # from base_lr_orig — back both off so every schedule
                # family honors the reduction
                if getattr(sch, "base_lr", None) is not None:
                    sch.base_lr *= factor
                if getattr(sch, "base_lr_orig", None) is not None:
                    sch.base_lr_orig *= factor
        # the restored trajectory has different statistics; the stale
        # EMA/lag state must not re-trigger on it
        hmon.soft_reset()

    def _fast_forward_data(self, train_data, epochs, nbatch):
        """Fast-forward the raw data stream to a mid-run position.

        Seekable pipelines (seeded :class:`~mxnet_tpu.io.NDArrayIter`,
        the data service, seeded :class:`~mxnet_tpu.image.ImageIter`,
        and any prefetch wrapper over them) jump in O(1):
        ``seek(epochs, nbatch)`` recomputes the epoch permutation from
        the seed and places the cursor — no decode, no replay, bit-exact
        at any process count.  Everything else falls back to O(steps)
        replay: one ``reset()`` per completed epoch reproduces the
        shuffle-RNG draw sequence an uninterrupted run performs at its
        epoch boundaries (given the same process-level seeding — see
        ``docs/fault_tolerance.md``), then ``nbatch`` batches are drawn
        and discarded."""
        can_seek = getattr(train_data, "seekable", None)
        if can_seek is not None and can_seek():
            train_data.seek(int(epochs), int(nbatch))
            self.logger.info(
                "resume fast-forward: O(1) seek to epoch %d batch %d",
                int(epochs), int(nbatch))
            return
        for _ in range(int(epochs)):
            train_data.reset()
        for skipped in range(int(nbatch)):
            try:
                train_data.next()
            except StopIteration:
                self.logger.warning(
                    "resume fast-forward exhausted the epoch after %d of "
                    "%d batches; continuing from the epoch boundary",
                    skipped, nbatch)
                break

    def install_monitor(self, monitor):
        raise NotImplementedError

    # -- introspection --------------------------------------------------
    @property
    def data_names(self):
        raise NotImplementedError

    @property
    def output_names(self):
        raise NotImplementedError

    @property
    def data_shapes(self):
        raise NotImplementedError

    @property
    def label_shapes(self):
        raise NotImplementedError

    @property
    def output_shapes(self):
        raise NotImplementedError


class BatchEndParam:
    """Callback payload (reference namedtuple ``BatchEndParam``)."""

    def __init__(self, epoch, nbatch, eval_metric, locals=None):
        self.epoch = epoch
        self.nbatch = nbatch
        self.eval_metric = eval_metric
        self.locals = locals


def _as_list(obj):
    if isinstance(obj, (list, tuple)):
        return obj
    return [obj]
