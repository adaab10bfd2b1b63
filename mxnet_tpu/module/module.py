"""Module — Symbol + Executor + Optimizer + KVStore.

Reference: ``python/mxnet/module/module.py`` (bind ``:351``,
init_optimizer ``:460``, forward ``:556``, backward ``:598``, update
``:615``) over ``DataParallelExecutorGroup``.

TPU-native difference: there is no per-device executor group.  One
executor holds the whole bound graph as a single XLA program; *device*
parallelism is SPMD — the batch is sharded over the mesh's 'data' axis
and XLA replicates the program and inserts the gradient all-reduce
(kvstore types containing 'dist'/'device' activate this via
``mxnet_tpu.parallel``).  ``update()`` keeps the reference's
push-then-pull kvstore protocol with ``priority=-index`` ordering.
"""
from __future__ import annotations

import logging

from ..base import MXNetError
from .. import optimizer as opt
from .. import kvstore as kvs
from ..initializer import InitDesc
from ..ndarray import NDArray, zeros
from ..profiler import span as _span
from .base_module import BaseModule

__all__ = ["Module"]


class Module(BaseModule):
    def __init__(self, symbol, data_names=("data",),
                 label_names=("softmax_label",), logger=logging,
                 context=None, work_load_list=None, fixed_param_names=None,
                 state_names=None, pipeline_stages=0,
                 pipeline_microbatches=None, pipeline_schedule="1f1b"):
        """``pipeline_stages=S`` trains through pipeline parallelism:
        the symbol is cut into S heterogeneous stages
        (``parallel.pipeline.split_symbol``), parameters/optimizer
        states shard over the active mesh's 'pipe' axis, and ``fit``
        runs the ``pipeline_schedule`` ('1f1b' or 'gpipe') microbatch
        wave — requires a mesh with ``{'pipe': S}`` and a dist kvstore.
        """
        super().__init__(logger=logger)
        from ..context import current_context

        if context is None:
            context = [current_context()]
        if not isinstance(context, (list, tuple)):
            context = [context]
        self._context = list(context)
        self._symbol = symbol
        self._pipeline_stages = int(pipeline_stages)
        self._pipeline_microbatches = pipeline_microbatches
        self._pipeline_schedule = pipeline_schedule
        self._pipeline_stale = False
        self._data_names = list(data_names or [])
        self._label_names = list(label_names or [])
        self._fixed_param_names = list(fixed_param_names or [])
        arg_names = symbol.list_arguments()
        self._param_names = [n for n in arg_names
                             if n not in self._data_names
                             and n not in self._label_names]
        self._aux_names = symbol.list_auxiliary_states()
        self._exec = None
        self._optimizer = None
        self._kvstore = None
        self._update_on_kvstore = False
        self._updater = None
        self._preload_opt_states = None
        self._grad_req = None

    # -- introspection --------------------------------------------------
    @property
    def data_names(self):
        return self._data_names

    @property
    def label_names(self):
        return self._label_names

    @property
    def output_names(self):
        return self._symbol.list_outputs()

    @property
    def data_shapes(self):
        assert self.binded
        return self._data_shapes

    @property
    def label_shapes(self):
        assert self.binded
        return self._label_shapes

    @property
    def output_shapes(self):
        assert self.binded
        return [o.shape for o in self._exec.outputs] if self._exec.outputs \
            else self._symbol._infer_outputs(
                {d.name: d.shape for d in self._data_shapes +
                 (self._label_shapes or [])})

    # -- bind -----------------------------------------------------------
    def bind(self, data_shapes, label_shapes=None, for_training=True,
             inputs_need_grad=False, force_rebind=False, shared_module=None,
             grad_req="write"):
        if self.binded and not force_rebind:
            self.logger.warning("Already bound, ignoring bind()")
            return
        self.for_training = for_training
        self.inputs_need_grad = inputs_need_grad
        self._grad_req = grad_req

        self._data_shapes = [_as_desc(d) for d in data_shapes]
        self._label_shapes = [_as_desc(l) for l in (label_shapes or [])]

        shapes = {d.name: d.shape for d in self._data_shapes}
        shapes.update({l.name: l.shape for l in self._label_shapes})

        req = grad_req
        if isinstance(req, str) and not for_training:
            req = "null"
        if isinstance(req, str) and self._fixed_param_names:
            req = {n: ("null" if n in self._fixed_param_names else grad_req)
                   for n in self._param_names}
        if inputs_need_grad and isinstance(req, dict):
            for n in self._data_names:
                req[n] = grad_req
        elif inputs_need_grad and isinstance(req, str):
            req = {n: grad_req for n in
                   self._param_names + self._data_names}

        shared_exec = shared_module._exec if shared_module is not None else None
        self._exec = self._symbol.simple_bind(
            self._context[0], grad_req=req, shared_exec=shared_exec,
            **shapes)
        self.binded = True
        if shared_module is not None and shared_module.params_initialized:
            self.params_initialized = True

    # -- params ---------------------------------------------------------
    def init_params(self, initializer=None, arg_params=None, aux_params=None,
                    allow_missing=False, force_init=False, allow_extra=False):
        if self.params_initialized and not force_init:
            return
        assert self.binded, "call bind before initializing parameters"
        if initializer is None:
            # reference Module.init_params default (module.py:246):
            # leaving params at their simple_bind zeros would dead-relu
            # every net whose caller skipped the initializer argument
            from ..initializer import Uniform

            initializer = Uniform(0.01)
        for name in self._param_names:
            arr = self._exec.arg_dict[name]
            if arg_params is not None and name in arg_params:
                arg_params[name].copyto(arr)
            elif arg_params is not None and not allow_missing:
                raise MXNetError("parameter %s missing from arg_params" % name)
            else:
                # covers both no-arg_params and allow_missing fine-tune
                # flows: missing params get the initializer, never zeros
                desc = InitDesc(name, self._symbol.attr_dict().get(name, {}))
                initializer(desc, arr)
        for name in self._aux_names:
            arr = self._exec.aux_dict[name]
            if aux_params is not None and name in aux_params:
                aux_params[name].copyto(arr)
            elif initializer is not None:
                desc = InitDesc(name, self._symbol.attr_dict().get(name, {}))
                initializer(desc, arr)
        self.params_initialized = True
        # a live pipelined step caches params/states in packed
        # stage-sharded buffers; newly set params must invalidate them
        # (optimizer states carry over) or the next step trains on
        # stale weights.  When arg_dict is already in sync
        # (_pipeline_stale False — e.g. fit's per-epoch
        # get_params/set_params round-trip just ran _sync_pipeline),
        # the states dict is current too and the device unpack is
        # skipped; the one repack on the next step is the price of
        # honoring a potential external write.
        fused = getattr(self, "_fused", None)
        if fused is not None and \
                getattr(fused, "_packed_params", None) is not None:
            from ..parallel.pipeline import PipelineTrainStep

            if isinstance(fused, PipelineTrainStep):
                if getattr(self, "_pipeline_stale", False):
                    self._fused_states = fused.unpack_states()
                # newly set params/aux win over the packed buffers (the
                # same stance as arg_dict: external writes are honored,
                # the next step repacks all three)
                fused._packed_params = None
                fused._packed_states = None
                fused._packed_aux = None
                self._pipeline_stale = False
        # same stance for ZeRO-3 at-rest tiles: external writes to
        # arg_dict win; the next step repacks from the canonical dict
        if getattr(self, "_zero3_params", None) is not None:
            self._zero3_params = None
            self._zero3_stale = False

    def _sync_zero3(self):
        """Unpack ZeRO-3 at-rest parameter tiles back into the executor
        arg_dict (lazy sync point, mirroring ``_sync_pipeline``)."""
        if not getattr(self, "_zero3_stale", False):
            return
        import jax.numpy as jnp

        live = self._fused.unpack_params(self._zero3_params)
        for n, v in live.items():
            self._exec.arg_dict[n]._set_data(jnp.asarray(v))
        self._zero3_stale = False

    def _export_zero_params(self):
        """Flat ZeRO-3 parameter tiles for elastic checkpointing, or
        ``None`` when params are not sharded at rest."""
        fused = getattr(self, "_fused", None)
        if fused is None or not getattr(fused, "zero3", False):
            return None
        if getattr(self, "_zero3_params", None) is None:
            return None
        from ..parallel import zero as _zero_mod

        return _zero_mod.export_params(self._zero3_params, fused._zero_lay)

    def _sync_pipeline(self):
        """Gather live packed pipeline params/states back into the
        executor dicts (lazy sync point for the stage-sharded step)."""
        if not getattr(self, "_pipeline_stale", False):
            return
        import jax.numpy as jnp

        live = self._fused.unpack_params()
        for n, v in live.items():
            self._exec.arg_dict[n]._set_data(jnp.asarray(v))
        for n, v in self._fused.unpack_aux().items():
            self._exec.aux_dict[n]._set_data(jnp.asarray(v))
        self._fused_states = self._fused.unpack_states()
        self._pipeline_stale = False

    def get_params(self):
        assert self.binded and self.params_initialized
        self._sync_pipeline()
        self._sync_zero3()
        arg_params = {n: self._exec.arg_dict[n].copy()
                      for n in self._param_names}
        aux_params = {n: self._exec.aux_dict[n].copy()
                      for n in self._aux_names}
        return arg_params, aux_params

    # -- optimizer ------------------------------------------------------
    def init_optimizer(self, kvstore="local", optimizer="sgd",
                       optimizer_params=(("learning_rate", 0.01),),
                       force_init=False, param_sharding=None,
                       compute_dtype=None, steps_per_call=None,
                       health=None, loss_scale=None, zero=None,
                       plan=None):
        """``param_sharding``: 'replicated' (default), 'fsdp', 'tp', or a
        rule list (see ``parallel.sharding.param_sharding_rules``) —
        applied to the fused step's parameter/optimizer-state layouts
        over the active mesh.  This is the working equivalent of the
        reference's ``group2ctx`` model parallelism
        (``graph_executor.cc:395`` PlaceDevice) plus the ZeRO-style
        sharded-optimizer layout the reference approximated with
        parameter-server key sharding (``kvstore_dist.h:431``).  Also
        settable via ``MXNET_PARAM_SHARDING``.

        ``steps_per_call=K``: multi-step dispatch — the fused step scans
        K donated updates over a packed (K, batch, …) super-batch per
        device call (``fit`` packs via ``DevicePrefetchIter``).  Also
        settable via ``MXNET_STEPS_PER_CALL``.

        ``health``: run-health sentinel — True / a policy string / a
        :class:`~mxnet_tpu.health.HealthMonitor` (also via
        ``MXNET_HEALTH_MONITOR=1``); ``loss_scale``: 'dynamic', a fixed
        number, or a :class:`~mxnet_tpu.health.DynamicLossScaler` for
        low-precision runs (also via ``MXNET_LOSS_SCALE``).  See
        docs/health_monitoring.md.

        ``zero``: 'auto' (default) | 'on' | 'off' — ZeRO-style sharding
        of the optimizer state and the weight update across the data
        axis (``MXNET_ZERO``; see docs/performance.md).

        ``plan``: a :class:`~mxnet_tpu.parallel.ParallelPlan` (or its
        ``"data=4,model=2,zero=3"`` spec string, also via
        ``MXNET_PLAN``) — ONE declaration composing TP x PP x DP/ZeRO;
        it replaces ``param_sharding``/``zero`` and, for ``pipe>1``
        plans, routes training through ``PipelineTrainStep`` (see
        docs/performance.md "Composing parallelisms")."""
        from ..base import get_env
        from ..health import DynamicLossScaler, resolve_monitor
        from ..parallel import zero as _zero_mod

        assert self.binded and self.params_initialized
        if self.optimizer_initialized and not force_init:
            return
        if plan is None:
            plan = get_env("MXNET_PLAN", "", str).strip() or None
        if plan is not None:
            from ..parallel.plan import ParallelPlan

            plan = ParallelPlan.parse(plan)
            if plan.pipe > 1:
                if self._pipeline_stages and \
                        self._pipeline_stages != plan.pipe:
                    raise MXNetError(
                        "plan pipe=%d conflicts with Module("
                        "pipeline_stages=%d)"
                        % (plan.pipe, self._pipeline_stages))
                self._pipeline_stages = plan.pipe
                self._pipeline_schedule = plan.schedule
                if plan.n_microbatches:
                    self._pipeline_microbatches = plan.n_microbatches
        self._plan = plan
        self._health_monitor = resolve_monitor(health)
        if loss_scale is None:
            loss_scale = get_env("MXNET_LOSS_SCALE", "", str) or None
        self._loss_scaler = DynamicLossScaler.from_spec(loss_scale)
        self._last_health_stats = None
        if param_sharding is None:
            param_sharding = get_env("MXNET_PARAM_SHARDING", "", str) \
                or None
        self._param_sharding = param_sharding
        if steps_per_call is None:
            steps_per_call = get_env("MXNET_STEPS_PER_CALL", 1, int)
        self._steps_per_call = max(1, int(steps_per_call))
        # mixed precision for the fused step: bf16 activations over fp32
        # master weights (also via MXNET_COMPUTE_DTYPE=bfloat16)
        if compute_dtype is None:
            compute_dtype = get_env("MXNET_COMPUTE_DTYPE", "", str) or None
        self._compute_dtype = compute_dtype
        # normalized to auto|on|off (explicit arg wins over MXNET_ZERO);
        # a plan that pins zero owns the mode when the arg is unset —
        # without this the plan's zero=3 would silently degrade to the
        # MXNET_ZERO default on the Module path
        if zero is None and plan is not None and plan.zero is not None:
            zero = plan.zero
        self._zero = _zero_mod.zero_mode(zero)
        kvstore_inst, update_on_kvstore = _create_kvstore(
            kvstore, len(self._context), self._exec.arg_dict)

        batch_size = self._data_shapes[0].shape[0]
        rescale_grad = 1.0 / batch_size
        if kvstore_inst and "dist" in kvstore_inst.type and \
                "_sync" in kvstore_inst.type:
            rescale_grad /= kvstore_inst.num_workers

        if isinstance(optimizer, str):
            idx2name = {i: n for i, n in enumerate(self._param_names)}
            optimizer_params = dict(optimizer_params)
            if "rescale_grad" not in optimizer_params:
                optimizer_params["rescale_grad"] = rescale_grad
            optimizer = opt.create(optimizer, sym=self._symbol,
                                   param_idx2name=idx2name,
                                   **optimizer_params)
        else:
            assert isinstance(optimizer, opt.Optimizer)

        self._optimizer = optimizer
        self._kvstore = kvstore_inst
        self._update_on_kvstore = update_on_kvstore
        optimizer.set_lr_mult({})
        optimizer.set_wd_mult({})
        self._mesh = self._decide_mesh(kvstore_inst)

        if kvstore_inst:
            # init keys: index -> weight
            for i, name in enumerate(self._param_names):
                kvstore_inst.init(i, self._exec.arg_dict[name])
            if update_on_kvstore:
                kvstore_inst.set_optimizer(optimizer)
            if getattr(kvstore_inst, "_is_async", False):
                # hosts must start from one common point; one averaging
                # round over the (identically- or differently-) seeded
                # initial params establishes it
                kvstore_inst.sync_params(self._async_params())
        if not update_on_kvstore:
            self._updater = opt.get_updater(optimizer)

        self._maybe_compile_fused()
        self.optimizer_initialized = True
        if self._preload_opt_states is not None:
            self.load_optimizer_states(self._preload_opt_states)
            self._preload_opt_states = None

    def _decide_mesh(self, kvstore_inst):
        """Choose the device mesh for this fit (reference: kvstore type
        selects the comm layer, ``src/kvstore/kvstore.cc:34-62``; here
        'device'/'dist*' types select SPMD over a ``jax.sharding.Mesh``
        and XLA inserts the gradient all-reduce over ICI)."""
        plan = getattr(self, "_plan", None)
        if plan is None:
            if kvstore_inst is None:
                return None
            if not ("dist" in kvstore_inst.type
                    or "device" in kvstore_inst.type):
                return None
        import jax

        from ..parallel import current_mesh, create_mesh

        mesh = current_mesh()
        if mesh is None and plan is not None:
            # the plan declares its own topology over this host's devices
            # (a plan needs no kvstore: GSPMD owns every collective)
            mesh = plan.mesh()
        elif mesh is not None and plan is not None:
            plan.validate_mesh(mesh)
        if mesh is None:
            # meshes stay process-LOCAL: in-jit collectives ride ICI
            # within this host's slice; cross-process traffic goes
            # through the kvstore DCN branch (sync) or the averaging
            # rounds (async)
            devices = [c.jax_device for c in self._context] \
                if len(self._context) > 1 else list(jax.local_devices())
            if len(devices) <= 1:
                return None
            mesh = create_mesh({"data": len(devices)}, devices=devices)
        # the global batch must divide over the data axis
        axis = mesh.shape.get("data", 1)
        batch = self._data_shapes[0].shape[0]
        if axis > 1 and batch % axis != 0:
            if plan is not None:
                raise MXNetError(
                    "batch size %d not divisible by the plan's data axis "
                    "%d (plan=%r)" % (batch, axis, plan))
            self.logger.warning(
                "batch size %d not divisible by mesh data axis %d; "
                "running replicated", batch, axis)
            return None
        if kvstore_inst is not None:
            kvstore_inst._mesh = mesh
        return mesh

    def _maybe_compile_fused(self):
        """Compile fwd+bwd+allreduce+update into ONE XLA program.

        This is the TPU analogue of the reference's bulk-exec segments
        (``InitOpSegs``, env ``MXNET_EXEC_BULK_EXEC_TRAIN``) taken to its
        limit: the whole train step — including the optimizer and, under a
        mesh, the gradient all-reduce — is a single device call per batch,
        which removes the per-op dispatches and host round-trips of the
        split path.  Works for every optimizer with
        a ``fused_update`` (the whole built-in family); per-param lr/wd
        multipliers and fixed params are honored.  Set MXNET_FUSED_STEP=0
        to disable (falls back to forward/backward/update calls)."""
        from ..base import get_env

        self._fused = None
        self._fused_states = None
        self._fused_ran = False

        def _bail(reason):
            # an EXPLICIT mixed-precision request must not silently train
            # fp32 through the split path (same stance as param_sharding)
            if getattr(self, "_compute_dtype", None) is not None:
                raise MXNetError(
                    "compute_dtype=%r was requested but the fused step is "
                    "unavailable: %s" % (self._compute_dtype, reason))
            # likewise an explicit multi-step dispatch request: the split
            # path has no scanned form
            if getattr(self, "_steps_per_call", 1) > 1:
                raise MXNetError(
                    "steps_per_call=%d was requested but the fused step "
                    "is unavailable: %s" % (self._steps_per_call, reason))
            # and loss scaling: the split path cannot thread scaler state
            # through per-parameter updates, so silently training
            # unscaled would defeat the overflow protection asked for
            if getattr(self, "_loss_scaler", None) is not None:
                raise MXNetError(
                    "loss_scale was requested but the fused step is "
                    "unavailable: %s" % (reason,))
            # an explicit ZeRO request only exists inside the fused step
            if getattr(self, "_zero", None) in ("on", "3"):
                raise MXNetError(
                    "zero=%s was requested but the fused step is "
                    "unavailable: %s" % (self._zero, reason))
            # likewise a composed plan: the split path has no TP/ZeRO
            # composition, so training replicated would silently ignore it
            if getattr(self, "_plan", None) is not None:
                raise MXNetError(
                    "plan=%r was requested but the fused step is "
                    "unavailable: %s" % (self._plan, reason))

        if self._pipeline_stages > 1:
            if getattr(self, "_steps_per_call", 1) > 1:
                raise MXNetError(
                    "steps_per_call cannot combine with pipeline_stages "
                    "(the pipelined step already runs its own microbatch "
                    "wave per call)")
            if getattr(self, "_loss_scaler", None) is not None:
                raise MXNetError(
                    "loss_scale cannot combine with pipeline_stages (the "
                    "pipelined step does not thread scaler state)")
            if getattr(self, "_health_monitor", None) is not None:
                # the pipelined step computes no in-step stats; the
                # liveness side (watchdog, heartbeats) still applies
                self.logger.warning(
                    "health monitor: in-step numerics are unavailable "
                    "with pipeline_stages — disabling the monitor "
                    "(step watchdog and heartbeats remain active)")
                self._health_monitor = None
            # an EXPLICIT pipeline request never falls back silently
            from ..parallel.pipeline import PipelineTrainStep

            if self._kvstore is not None and \
                    getattr(self._kvstore, "_is_async", False):
                raise MXNetError(
                    "pipeline_stages cannot combine with dist_async "
                    "(packed stage-sharded params have no averaging "
                    "round); use a sync kvstore")
            if self.inputs_need_grad:
                raise MXNetError(
                    "pipeline_stages cannot serve inputs_need_grad "
                    "(the pipelined step does not populate data input "
                    "gradients); use the non-pipelined module")
            if self._mesh is None or \
                    self._mesh.shape.get("pipe") != self._pipeline_stages:
                raise MXNetError(
                    "pipeline_stages=%d needs a dist kvstore under an "
                    "active mesh with {'pipe': %d} (parallel.mesh_scope)"
                    % (self._pipeline_stages, self._pipeline_stages))
            self._fused = PipelineTrainStep(
                self._symbol, optimizer=self._optimizer, mesh=self._mesh,
                n_microbatches=self._pipeline_microbatches,
                data_names=self._data_names,
                label_names=self._label_names,
                schedule=self._pipeline_schedule,
                fixed_param_names=self._fixed_param_names,
                plan=getattr(self, "_plan", None))
            return
        if not get_env("MXNET_FUSED_STEP", True, bool):
            _bail("MXNET_FUSED_STEP=0")
            return
        import jax

        if jax.process_count() > 1 and self._kvstore is not None and \
                "dist" in self._kvstore.type and \
                not getattr(self._kvstore, "_is_async", False):
            # multi-process SYNC training reduces gradients over DCN in
            # the kvstore push path; the fused in-jit step only covers
            # this host's mesh, so it would silently skip the
            # cross-process merge — use the split path
            _bail("multi-process sync kvstore uses the split push/pull "
                  "path for the DCN gradient merge")
            return
        if self.inputs_need_grad:
            # the fused step does not populate grad_dict for data inputs;
            # get_input_grads needs the split executor path
            _bail("inputs_need_grad requires the split executor")
            return
        o = self._optimizer
        if not o.supports_fused:
            self.logger.debug("optimizer %s has no fused form; using the "
                              "split update path", type(o).__name__)
            _bail("optimizer %s has no fused form" % type(o).__name__)
            return
        req = self._grad_req
        if isinstance(req, str):
            ok = req == "write"
        else:  # dict: fixed params null, everything else write
            ok = all(v == "write" or (k in self._fixed_param_names and
                                      v == "null")
                     for k, v in req.items())
        if not ok:
            _bail("grad_req %r is not fusable" % (req,))
            return
        # every declared reason to use the split path bailed above; from
        # here an exception building the fused step is a fault and
        # raises (it would otherwise silently change what trains)
        from ..fused import TrainStep
        from ..health import StepHealth

        remat = "full" if get_env("MXNET_BACKWARD_DO_MIRROR", False,
                                  bool) else None
        scaler = getattr(self, "_loss_scaler", None)
        step_health = None
        if scaler is not None or \
                getattr(self, "_health_monitor", None) is not None:
            step_health = StepHealth(scaler=scaler)
        self._fused = TrainStep(
            self._symbol, optimizer=o, mesh=self._mesh,
            data_names=self._data_names, label_names=self._label_names,
            fixed_param_names=self._fixed_param_names, remat=remat,
            param_sharding=getattr(self, "_param_sharding", None),
            compute_dtype=getattr(self, "_compute_dtype", None),
            steps_per_call=getattr(self, "_steps_per_call", 1),
            health=step_health,
            zero=getattr(self, "_zero", None),
            plan=getattr(self, "_plan", None))
        # the sharded-update dispatch attaches the kvstore's peer
        # diagnosis to bounded-collective timeouts
        self._fused._kvstore = self._kvstore

    def _init_fused_states(self):
        """Seed fused optimizer states, honoring any states preloaded into
        the updater (checkpoint resume) or handed over canonically by the
        elastic ZeRO restore.  Under the sharded update every seed —
        fresh, updater-preloaded, or canonical — lands in the flat 1/N
        zero layout (re-tiling is bit-exact: padding lanes are zeros)."""
        o = self._optimizer
        fused = getattr(self, "_fused", None)
        lay = None
        if fused is not None and getattr(fused, "zero_axis", None):
            pdict = {n: self._exec.arg_dict[n]._data
                     for n in self._param_names}
            lay = fused.zero_layout(pdict)
        states = {}
        preloaded = self._updater.states if self._updater is not None else \
            (self._kvstore.updater.states
             if self._kvstore is not None and self._kvstore.updater else {})
        canonical = getattr(self, "_preloaded_zero_states", None) or {}
        for i, n in enumerate(self._param_names):
            if n in canonical:
                st = canonical[n]
            elif i in preloaded and preloaded[i] is not None:
                st = o.fused_state_from_nd(preloaded[i])
            else:
                st = None
            if lay is not None:
                from ..parallel import zero as _zero

                if st is None:
                    states[n] = _zero.init_state(
                        o, pdict[n], lay[n], fused.mesh, fused.zero_axis)
                else:
                    states[n] = _zero.shard_state(
                        st, lay[n], fused.mesh, fused.zero_axis)
            else:
                states[n] = st if st is not None else \
                    o.init_fused_state(self._exec.arg_dict[n]._data)
        self._preloaded_zero_states = None
        return states

    def set_fused_optimizer_states(self, states):
        """Hand the fused step canonical (weight-shaped, by-name) fused
        optimizer states in memory — the elastic checkpoint's ZeRO
        restore path.  Applied (and re-tiled to the live layout) when the
        fused step next seeds its states."""
        assert self.binded
        self._preloaded_zero_states = dict(states)
        self._fused_states = None

    def _export_zero_states(self):
        """v2-checkpoint export descriptor of the live ZeRO-sharded fused
        states (``parallel.zero.export_states``), or None when the fused
        step is not running the sharded update."""
        fused = getattr(self, "_fused", None)
        if fused is None or not getattr(fused, "zero_axis", None) or \
                getattr(self, "_fused_states", None) is None:
            return None
        from ..parallel import zero as _zero

        pdict = {n: self._exec.arg_dict[n]._data
                 for n in self._param_names}
        return _zero.export_states(self._fused_states,
                                   fused.zero_layout(pdict))

    def reconfigure_plan(self, plan):
        """Rebuild the mesh + fused step under a NEW
        :class:`~mxnet_tpu.parallel.ParallelPlan` without re-running
        ``init_optimizer`` — the reshard half of the in-memory plan
        migration (``parallel/elastic.py``).  The live optimizer object
        is kept, so ``num_update`` and the lr schedule continue
        uninterrupted; the caller is responsible for capturing the fused
        optimizer states BEFORE this call (the rebuild drops them) and
        re-installing the canonical trees afterwards via
        :meth:`set_fused_optimizer_states`."""
        from ..parallel.plan import ParallelPlan
        from ..parallel import zero as _zero_mod

        assert self.binded and self.optimizer_initialized, \
            "reconfigure_plan needs a bound, optimizer-initialized module"
        plan = ParallelPlan.parse(plan)
        if plan.pipe > 1:
            raise MXNetError(
                "live migration onto a pipe>1 plan is not supported — "
                "the pipelined step packs state per stage, which has no "
                "in-memory reshard path yet (restart from a checkpoint)")
        if self._pipeline_stages > 1:
            raise MXNetError(
                "live migration off a pipelined module is not supported")
        old_plan = getattr(self, "_plan", None)
        self._plan = plan
        if plan.zero is not None:
            self._zero = _zero_mod.zero_mode(plan.zero)
        try:
            self._mesh = self._decide_mesh(self._kvstore)
            self._zero3_params = None
            self._zero3_stale = False
            self._preloaded_zero_states = None
            self._maybe_compile_fused()
            if self._fused is None:
                raise MXNetError(
                    "plan=%r was requested but the fused step is "
                    "unavailable after the rebuild" % (plan,))
        except Exception:
            # leave the module describing the plan it actually runs
            self._plan = old_plan
            raise
        return self._fused

    def prepare_compiled(self, dtype="float32"):
        """AOT warmup: lower-and-compile the fused train step for the
        bound shapes NOW instead of inside the first ``forward_backward``
        (``Module.fit`` runs this in a background thread that overlaps
        ``DevicePrefetchIter`` spin-up; see docs/compilation.md).

        Returns the compile stats dict (also on
        ``self._fused.compile_stats``), or None when no AOT-compilable
        fused step exists (split path, pipeline step, or shape-dependent
        sharding) — those paths keep their lazy first-call compile."""
        assert self.binded, "call bind before prepare_compiled"
        fused = getattr(self, "_fused", None)
        if fused is None or not hasattr(fused, "compile") or \
                (getattr(fused, "_jit_step", None) is None and
                 not getattr(fused, "_aot_capable", False)):
            return None
        shapes = {d.name: d.shape for d in self._data_shapes}
        shapes.update({l.name: l.shape
                       for l in (self._label_shapes or [])})
        # single-device modules compile for THEIR device: jit's default
        # is the accelerator, which a cpu-context module on a TPU host
        # would then refuse at the first step
        device = self._context[0].jax_device if self._mesh is None \
            else None
        stats = fused.compile(shapes, dtype=dtype, device=device)
        self.logger.debug("AOT compile %s: %.2fs%s", stats.get("name"),
                          stats.get("duration_s", 0.0),
                          " (persistent-cache hit)"
                          if stats.get("cache_hit") else "")
        return stats

    def _fused_forward_backward_update(self, data_batch):
        import jax.numpy as jnp

        from .. import random as _rnd
        from ..ndarray import NDArray

        o = self._optimizer
        z3 = getattr(self._fused, "zero3", False)
        if z3 and getattr(self, "_zero3_params", None) is not None:
            # ZeRO-3 steady state: params live step-side as flat 1/N
            # tiles; arg_dict is synced lazily (_sync_zero3) on read
            params = self._zero3_params
        else:
            params = {n: self._exec.arg_dict[n]._data
                      for n in self._param_names}
            if z3:
                # first step (or after an external arg_dict write): tile
                # the canonical params into the at-rest layout — this is
                # also the canonical-shape seeding point for the cached
                # zero layout
                params = self._fused.pack_params(params)
                self._zero3_params = params
        aux = {n: self._exec.aux_dict[n]._data for n in self._aux_names}
        if self._fused_states is None:
            self._fused_states = self._init_fused_states()
        K = getattr(self._fused, "_steps_per_call", 1)
        with _span("trainstep.stage"):
            batch = {}
            for name, arr in zip(self._data_names, data_batch.data):
                batch[name] = arr._data if isinstance(arr, NDArray) else \
                    jnp.asarray(arr)
            for name, arr in zip(self._label_names, data_batch.label or []):
                batch[name] = arr._data if isinstance(arr, NDArray) else \
                    jnp.asarray(arr)
            if getattr(data_batch, "staged", False):
                # the DevicePrefetchIter staging thread already placed this
                # batch (device or NamedSharding) — re-placing would be a
                # synchronous no-op at best and an axis-0 re-shard at worst
                # for packed super-batches
                pass
            elif self._mesh is not None:
                from ..parallel.sharding import shard_batch

                lead = 1 if K > 1 else 0
                batch = {k: shard_batch(self._mesh, v, leading=lead)
                         for k, v in batch.items()}
            else:
                # load_data semantics: batches follow the module's device,
                # not the default platform (a cpu-context module on a TPU
                # host gets NDArrayIter batches materialized on the
                # accelerator)
                import jax

                dev = self._context[0].jax_device
                batch = {k: jax.device_put(v, dev) for k, v in batch.items()}
        from ..testing import faults

        poison = faults.inject("numerics")
        if poison is not None:
            # poison one element of the first data tensor: the NaN/Inf
            # flows through forward AND backward, exercising the on-step
            # non-finite sentinel end to end (deterministic via
            # MXNET_FAULT_INJECT=numerics:nan:after=N)
            name = self._data_names[0]
            v = batch[name]
            v = v.at[(0,) * v.ndim].set(poison)
            batch = dict(batch)
            batch[name] = v
        # split-path parity: the scheduler is consulted at the
        # PRE-increment num_update (Optimizer.update calls _get_lr before
        # _update_count); bias-correction t is the POST-increment count.
        # A multi-step call advances the count by K (lr holds for the K
        # inner steps; t increments per step inside the scan).
        lr = o.lr_scheduler(o.num_update) if o.lr_scheduler else o.lr
        for _ in range(K):
            for i in range(len(self._param_names)):
                o._update_count(i)
        t = o.num_update - K + 1
        new_params, new_aux, self._fused_states, outs = self._fused(
            params, aux, self._fused_states, batch, _rnd.next_key(), lr, t)
        self._last_health_stats = getattr(self._fused, "last_health", None)
        from ..parallel.pipeline import PipelineTrainStep

        with _span("fit.adopt"):
            if isinstance(self._fused, PipelineTrainStep):
                # params/states live as packed stage-sharded buffers inside
                # the step; arg_dict is synced lazily (_sync_pipeline) when
                # something reads it (eval forward, get_params, checkpoint)
                self._pipeline_stale = True
            elif z3:
                # at-rest tiles stay step-side; aux (batchnorm stats) are
                # canonical-shaped and land in aux_dict as usual
                self._zero3_params = new_params
                self._zero3_stale = True
                for n, v in new_aux.items():
                    self._exec.aux_dict[n]._set_data(v)
            else:
                for n, v in new_params.items():
                    self._exec.arg_dict[n]._set_data(v)
                for n, v in new_aux.items():
                    self._exec.aux_dict[n]._set_data(v)
            self._exec.outputs = [NDArray(o, self._context[0]) for o in outs]
        self._fused_ran = True

    # -- compute --------------------------------------------------------
    def forward(self, data_batch, is_train=None):
        assert self.binded and self.params_initialized
        self._sync_pipeline()
        self._sync_zero3()
        if is_train is None:
            is_train = self.for_training
        inputs = {}
        for name, arr in zip(self._data_names, data_batch.data):
            inputs[name] = arr
        if self._label_names and data_batch.label:
            for name, arr in zip(self._label_names, data_batch.label):
                inputs[name] = arr
        # rebind on batch-size change (reference reshapes executors)
        cur = self._exec.arg_dict[self._data_names[0]].shape
        new = inputs[self._data_names[0]].shape
        if cur != new:
            self._exec = self._exec.reshape(
                **{k: v.shape for k, v in inputs.items()})
        self._exec.forward(is_train=is_train, **inputs)

    def backward(self, out_grads=None):
        assert self.binded and self.params_initialized
        self._exec.backward(out_grads=out_grads)

    def forward_backward(self, data_batch):
        if getattr(self, "_fused", None) is not None and \
                self._exec._monitor_callback is None:
            # an installed Monitor needs the per-node executor path; the
            # fused one-program step has no node boundaries to observe
            self._fused_forward_backward_update(data_batch)
            return
        self.forward(data_batch, is_train=True)
        self.backward()

    def update(self):
        """Push gradients / pull weights (reference ``Module.update`` →
        ``_update_params_on_kvstore``, priority=-index for comm overlap)."""
        assert self.binded and self.params_initialized and \
            self.optimizer_initialized
        if getattr(self, "_fused_ran", False):
            self._fused_ran = False  # fused step already applied the update
            self._async_tick()
            return
        o = self._optimizer
        if o is not None and (getattr(o, "clip_global_norm", None)
                              or getattr(self, "_health_monitor", None)
                              is not None):
            self._split_health_pass()
        if self._kvstore:
            # one batched push in priority order (priority=-i: earliest
            # layers first, the reference's overlap hint order,
            # model.py:105-116); the kvstore reduces the whole batch in
            # a single DCN round trip instead of one per key
            live = [(i, name) for i, name in enumerate(self._param_names)
                    if self._exec.grad_dict.get(name) is not None]
            keys = [i for i, _ in live]
            grads = [self._exec.grad_dict[name] for _, name in live]
            self._kvstore.push(keys, grads, priority=0)
            if self._update_on_kvstore:
                self._kvstore.pull(
                    keys, [self._exec.arg_dict[name] for _, name in live])
            else:
                merged = [zeros(g.shape, g.context) for g in grads]
                self._kvstore.pull(keys, merged)
                for (i, name), m in zip(live, merged):
                    self._updater(i, m, self._exec.arg_dict[name])
        else:
            for i, name in enumerate(self._param_names):
                w = self._exec.arg_dict[name]
                g = self._exec.grad_dict.get(name)
                if g is not None:
                    self._updater(i, g, w)
        self._async_tick()

    def _split_health_pass(self):
        """Split-path analogue of the in-step sentinel: one lazy pass
        over ``grad_dict`` computing the global norm, applying
        ``clip_global_norm``, and zeroing the gradients on a non-finite
        batch so the update is skipped.  All ops trace asynchronously —
        no host sync.  Unlike the fused path the skip is APPROXIMATE:
        momentum still decays and weight decay still applies over the
        zeroed gradients (the bit-exact guarantee is the fused path's)."""
        import jax.numpy as jnp

        o = self._optimizer
        names = [n for n in self._param_names
                 if self._exec.grad_dict.get(n) is not None]
        if not names:
            return
        grads = {n: self._exec.grad_dict[n]._data for n in names}
        gnorm = opt.global_grad_norm(grads, o.rescale_grad)
        finite = jnp.isfinite(gnorm)
        factor = jnp.asarray(1.0, "float32")
        if getattr(o, "clip_global_norm", None):
            factor = opt.global_norm_scale(gnorm, o.clip_global_norm)
        zero_bad = getattr(self, "_health_monitor", None) is not None
        if zero_bad:
            self._last_health_stats = {"grad_norm": gnorm,
                                       "nonfinite": ~finite}
        for n in names:
            g = grads[n] * factor.astype(grads[n].dtype)
            if zero_bad:
                # 0 * NaN is NaN — a multiplicative skip would leak the
                # poison into the optimizer state, so select instead
                g = jnp.where(finite, g, jnp.zeros_like(g))
            self._exec.grad_dict[n]._set_data(g)

    def _async_params(self):
        # aux states (BN moving stats) average too — per-shard moving
        # stats would diverge without bound otherwise
        return [self._exec.arg_dict[n] for n in self._param_names] + \
               [self._exec.aux_dict[n] for n in self._aux_names]

    def _async_tick(self):
        kv = self._kvstore
        if kv is not None and getattr(kv, "_is_async", False):
            kv._async_tick(self._async_params)

    def _epoch_end_sync(self):
        """dist_async: epoch-boundary parameter-averaging round (the
        always-on bounded-staleness sync point)."""
        kv = self._kvstore
        if kv is not None and getattr(kv, "_is_async", False):
            kv.sync_params(self._async_params())

    def get_outputs(self, merge_multi_context=True):
        assert self.binded
        return self._exec.outputs

    def get_input_grads(self, merge_multi_context=True):
        assert self.binded and self.inputs_need_grad
        return [self._exec.grad_dict.get(n) for n in self._data_names]

    def update_metric(self, eval_metric, labels, outputs=None):
        from ..executor_manager import pair_metric_outputs

        outs = self._exec.outputs if outputs is None else outputs
        eval_metric.update(labels, pair_metric_outputs(
            self._symbol, self._label_names, labels, outs))

    def install_monitor(self, monitor):
        assert self.binded
        monitor.install(self._exec)

    # -- checkpoint -----------------------------------------------------
    def save_checkpoint(self, prefix, epoch, save_optimizer_states=False):
        """Reference format contract: ``prefix-symbol.json`` +
        ``prefix-%04d.params`` (``module.py:152``)."""
        from ..model import save_checkpoint

        arg_params, aux_params = self.get_params()
        save_checkpoint(prefix, epoch, self._symbol, arg_params, aux_params)
        if save_optimizer_states:
            self.save_optimizer_states("%s-%04d.states" % (prefix, epoch))

    @staticmethod
    def load(prefix, epoch, load_optimizer_states=False, **kwargs):
        from ..model import load_checkpoint

        sym, args, auxs = load_checkpoint(prefix, epoch)
        mod = Module(symbol=sym, **kwargs)
        mod._arg_params = args
        mod._aux_params = auxs
        mod._preloaded_params = (args, auxs)
        if load_optimizer_states:
            mod._preload_opt_states = "%s-%04d.states" % (prefix, epoch)
        # defer set_params until bind; stash for init_params
        orig_init = mod.init_params

        def init_with_loaded(initializer=None, arg_params=None,
                             aux_params=None, **kw):
            orig_init(initializer=initializer,
                      arg_params=arg_params or args,
                      aux_params=aux_params or auxs, **kw)
        mod.init_params = init_with_loaded
        return mod

    def save_optimizer_states(self, fname):
        assert self.optimizer_initialized
        if getattr(self, "_fused_states", None) is not None:
            # sync live fused states back into the updater structure so
            # the on-disk format is identical to the split path's
            import pickle

            o = self._optimizer
            src = self._fused_states
            fused = getattr(self, "_fused", None)
            if fused is not None and getattr(fused, "zero_axis", None):
                import jax

                if jax.process_count() > 1:
                    raise MXNetError(
                        "save_optimizer_states cannot pickle ZeRO-sharded "
                        "state in a multi-process run (remote shards are "
                        "not addressable from this host); save through "
                        "the v2 elastic CheckpointManager instead")
                from ..parallel import zero as _zero

                pdict = {n: self._exec.arg_dict[n]._data
                         for n in self._param_names}
                lay = fused.zero_layout(pdict)
                src = {n: _zero.unshard_state(src[n], lay[n])
                       for n in src}
            states = {i: o.fused_state_to_nd(src[n], self._context[0])
                      for i, n in enumerate(self._param_names)}
            with open(fname, "wb") as f:
                f.write(pickle.dumps(states))
            return
        if self._update_on_kvstore:
            self._kvstore.save_optimizer_states(fname)
        else:
            with open(fname, "wb") as f:
                f.write(self._updater.get_states())

    def load_optimizer_states(self, fname):
        assert self.optimizer_initialized
        if self._update_on_kvstore and self._kvstore.updater is not None:
            self._kvstore.load_optimizer_states(fname)
        else:
            with open(fname, "rb") as f:
                self._updater.set_states(f.read())
        # force the fused path to re-seed from the freshly loaded states
        # (and drop any stale canonical ZeRO handover)
        self._preloaded_zero_states = None
        self._fused_states = None

    def reshape(self, data_shapes, label_shapes=None):
        assert self.binded
        self._data_shapes = [_as_desc(d) for d in data_shapes]
        self._label_shapes = [_as_desc(l) for l in (label_shapes or [])]
        shapes = {d.name: d.shape for d in self._data_shapes}
        shapes.update({l.name: l.shape for l in self._label_shapes})
        self._exec = self._exec.reshape(**shapes)


def _as_desc(d):
    from ..io import DataDesc

    if isinstance(d, DataDesc):
        return d
    name, shape = d[0], d[1]
    return DataDesc(name, shape)


def _create_kvstore(kvstore, num_device, arg_params):
    """Reference ``model.py:57`` ``_create_kvstore``: decide the store and
    whether updates run on it."""
    if kvstore is None:
        return None, False
    if isinstance(kvstore, kvs.KVStore):
        kv = kvstore
    elif isinstance(kvstore, str):
        if num_device == 1 and "dist" not in kvstore:
            return None, False
        kv = kvs.create(kvstore)
    else:
        raise TypeError("kvstore must be KVStore, str or None")
    # update_on_kvstore: the reference defaults True unless explicitly
    # disabled via MXNET_UPDATE_ON_KVSTORE=0 (env_var.md) — then the
    # worker-side updater runs on pulled merged gradients instead
    from ..base import get_env

    update_on_kvstore = get_env("MXNET_UPDATE_ON_KVSTORE", True, bool)
    if getattr(kv, "_is_async", False):
        # dist_async updates are LOCAL by design; pulling weights from
        # the store's private copies would undo the averaging rounds
        # (sync_params rewrites the executor arrays, not the store)
        update_on_kvstore = False
    return kv, update_on_kvstore
