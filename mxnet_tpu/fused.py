"""Fused train step — the TPU performance path.

The reference's fastest path pushes per-node cached engine ops plus
separate optimizer-update ops (SURVEY.md §3.1, fused update ops in
``src/operator/optimizer_op.cc``).  On TPU the whole thing — forward,
backward, optimizer update, and (under a mesh) the gradient all-reduce —
compiles into ONE XLA program with donated parameter buffers: zero host
round-trips per step and maximal fusion (measured on the single real
chip).  Under a multi-chip mesh the single-program form additionally
lets XLA's scheduler overlap the gradient collectives with backward
compute — design intent pending real-ICI measurement (this environment
has one chip); the pod-side check is a profiler trace confirming
all-reduce slots hide under the backward convolutions
(docs/distributed.md "pending hardware" list).  This is what ``Module``
uses when ``fit`` runs with a compiled step, and what bench.py measures.

Any registered :class:`~mxnet_tpu.optimizer.Optimizer` that implements
``fused_update`` (all of the built-in family) compiles in; per-parameter
``lr_mult``/``wd_mult`` (symbol ``__lr_mult__``/``__wd_mult__`` attrs and
the no-decay-for-bias default) are honored exactly like the split
``Optimizer._get_lr/_get_wd`` path.

Extra TPU-first knobs the reference exposes differently:

* ``compute_dtype='bfloat16'`` — mixed precision: parameters stay fp32
  (master weights, the reference's ``mp_sgd_*`` contract) and are cast to
  bf16 for the forward/backward so matmuls/convs hit the MXU at full
  rate; gradients come back fp32 for the update.
* ``remat`` — gradient checkpointing (the reference's
  ``MXNET_BACKWARD_DO_MIRROR`` / ``__force_mirroring__``,
  ``src/executor/graph_executor.cc:273-296``): ``'full'`` recomputes all
  activations in the backward, or pass a named jax checkpoint policy
  (e.g. ``'dots_with_no_batch_dims_saveable'``).
* ``steps_per_call=K`` — multi-step dispatch: ``__call__`` takes a
  ``(K, batch, …)`` super-batch and ``lax.scan``s K donated updates in
  ONE device call, amortizing Python dispatch for small models (fed by
  ``io.DevicePrefetchIter(steps_per_call=K)``; see docs/performance.md).
* ``zero='auto'|'on'|'off'|'3'`` (``MXNET_ZERO``) — ZeRO-style sharded
  weight update (arXiv 2004.13336): gradients reduce-scatter over the
  data axis, optimizer state and the update live on the local 1/N flat
  tile, fresh params all-gather — ~1/N optimizer-state memory and
  update FLOPs per replica (see ``parallel/zero.py`` and
  docs/performance.md).  ``auto`` engages on a ≥2-device data axis with
  replicated params; composes with the DDP grad overlap (the bucketed
  psum becomes a bucketed psum_scatter), ``steps_per_call``, health
  guards, the dynamic loss scaler, and AOT ``compile()``.  ``'3'``
  (ZeRO-3) additionally keeps the PARAMS at rest as those 1/N tiles:
  forward gathers them layer-bucket by layer-bucket
  (``MXNET_ZERO_GATHER_BUCKET_MB``), backward re-gathers via remat, the
  update writes tiles, and the trailing full all-gather disappears —
  per-replica param residency ~1/N, live full params O(max bucket).
  Callers feed at-rest params from ``init_state`` or
  ``pack_params(...)`` (Module does this itself).
* ``health=StepHealth(...)`` — run-health sentinel: the step
  additionally returns a global gradient norm, an all-params non-finite
  flag, and (with a :class:`~mxnet_tpu.health.DynamicLossScaler`) the
  scaler state — all computed on-device and fused into the program; a
  non-finite step keeps the old params bit-exactly via ``jnp.where``
  (see docs/health_monitoring.md).  ``__call__`` keeps the 4-tuple
  return; the stats land on ``self.last_health`` as device refs.
"""
from __future__ import annotations

import functools

from .base import MXNetError, logger
from .compile_cache import signature_of as _signature_of
from .profiler import span as _span

__all__ = ["compile_train_step", "TrainStep"]


def _loss_from_outputs(outs):
    """Seed the backward exactly like Executor.backward with ones head
    grads: sum of outputs (loss heads carry custom vjp that ignores the
    cotangent's value)."""
    total = None
    for o in outs:
        s = o.astype("float32").sum()
        total = s if total is None else total + s
    return total


def _buffer_key(x):
    """Identity of the underlying device buffer (best effort)."""
    try:
        return ("ptr", x.unsafe_buffer_pointer())
    except Exception:
        return ("id", id(x))


def _place(tree, shardings):
    """Place ``tree`` per ``shardings`` (one sharding broadcast over the
    tree, or a {name: sharding-or-subtree} dict), multiprocess-safe via
    :func:`parallel.zero.put`."""
    import jax

    from .parallel.zero import put

    if shardings is None:
        return tree
    if isinstance(shardings, dict):
        return {n: jax.tree.map(put, tree[n], shardings[n])
                for n in tree}
    return jax.tree.map(lambda x: put(x, shardings), tree)


def _resolve_remat(remat):
    import jax

    if remat is None or remat is False:
        return None
    if remat is True or remat == "full":
        return "full"
    if isinstance(remat, str):
        policy = getattr(jax.checkpoint_policies, remat, None)
        if policy is None:
            raise MXNetError("unknown remat policy %r" % remat)
        return policy
    return remat  # a jax checkpoint policy callable


_Z3_TAG = "zero3_gather"


def _z3_tag(x):
    """Name a gathered full parameter for the ZeRO-3 remat policy."""
    try:
        from jax.ad_checkpoint import checkpoint_name
    except ImportError:  # ancient jax: no names, params stay residuals
        return x
    return checkpoint_name(x, _Z3_TAG)


def _z3_remat_policy():
    """Save every forward residual EXCEPT the tagged gathered params, so
    backward re-issues the bucket all-gathers (deterministic, bit-exact)
    instead of holding O(model) full params alive across the step."""
    import jax

    pol = getattr(jax.checkpoint_policies,
                  "save_anything_except_these_names", None)
    return pol(_Z3_TAG) if pol is not None else None


class TrainStep:
    """Compiled (params, aux, opt_states, batch) -> updated state step."""

    def __init__(self, symbol, optimizer="sgd", optimizer_params=None,
                 mesh=None, data_names=("data",),
                 label_names=("softmax_label",), dtype="float32",
                 batch_sharding_axis="data", compute_dtype=None,
                 remat=None, fixed_param_names=(), param_sharding=None,
                 steps_per_call=1, health=None, zero=None, plan=None):
        import jax
        import jax.numpy as jnp

        from .base import get_env
        from .executor import _trace_fn
        from . import optimizer as opt_mod
        from .compile_cache import ensure_initialized, registry
        from .health import StepHealth

        # first jit owner in the hot path: wire the persistent XLA cache
        # before anything lowers, so this process's compiles are
        # reusable by the next one
        ensure_initialized()
        # composed parallel plan (parallel/plan.py): ONE declaration of
        # the (data, model, pipe, seq) split replacing the per-dimension
        # mesh/param_sharding/zero kwargs.  MXNET_PLAN is the env
        # surface, same "data=4,model=2,zero=3" grammar.
        from .parallel.plan import ParallelPlan

        if plan is None:
            env_plan = get_env("MXNET_PLAN", "", str).strip()
            plan = env_plan or None
        if plan is not None:
            plan = ParallelPlan.parse(plan)
            if plan.pipe > 1:
                raise MXNetError(
                    "plan has a %d-stage pipe axis: pipeline schedules "
                    "run through parallel.pipeline.PipelineTrainStep "
                    "(Module.init_optimizer routes there when given a "
                    "pipe plan)" % plan.pipe)
            if param_sharding not in (None, "replicated"):
                raise MXNetError(
                    "plan=%r owns parameter placement; drop "
                    "param_sharding=%r" % (plan, param_sharding))
            if mesh is None:
                mesh = plan.mesh()
            else:
                plan.validate_mesh(mesh)
            if zero is None:
                zero = plan.zero
        self.plan = plan
        self._plan_tp = plan is not None and plan.model_size(mesh) > 1
        plan_tp = self._plan_tp
        # cached autotune knobs (MXNET_AUTOTUNE=1) arm their env vars
        # BEFORE anything traces — the ops read them at trace time
        from . import autotune as _autotune

        self._autotune_applied = _autotune.apply_train_env(symbol, mesh,
                                                           plan=plan)
        self.symbol = symbol
        self._fwd_fn, self._arg_names, self._aux_names = _trace_fn(
            symbol, is_train=True)
        self.data_names = list(data_names)
        self.label_names = list(label_names)
        self.param_names = [n for n in self._arg_names
                            if n not in self.data_names
                            and n not in self.label_names]
        self.mesh = mesh

        opt_params = dict(optimizer_params or {})
        fixed = frozenset(fixed_param_names) | frozenset(
            opt_params.pop("fixed_param_names", ()))
        if isinstance(optimizer, str):
            optimizer = opt_mod.create(optimizer, **opt_params)
        elif isinstance(optimizer, opt_mod.Optimizer):
            if opt_params:
                raise MXNetError(
                    "optimizer_params must not be set when passing an "
                    "Optimizer instance (got %r); configure the instance "
                    "instead" % sorted(opt_params))
        else:
            raise MXNetError("optimizer must be a name or Optimizer")
        if not optimizer.supports_fused:
            raise MXNetError("optimizer %s has no fused form"
                             % type(optimizer).__name__)
        self.optimizer = optimizer
        self.lr = optimizer.lr

        # static per-parameter multipliers, resolved by name exactly like
        # Optimizer._get_lr/_get_wd
        lr_mults = {n: optimizer.lr_mult.get(n, 1.0)
                    for n in self.param_names}
        wd_mults = {n: optimizer.wd_mult.get(n, 1.0)
                    for n in self.param_names}
        base_wd = optimizer.wd

        fwd_fn = self._fwd_fn
        remat_policy = _resolve_remat(remat)
        if remat_policy == "full":
            fwd_fn = jax.checkpoint(fwd_fn)
        elif remat_policy is not None:
            fwd_fn = jax.checkpoint(fwd_fn, policy=remat_policy)
        cdtype = compute_dtype
        self._compute_dtype = compute_dtype
        frozen = fixed

        if health is not None and not isinstance(health, StepHealth):
            raise MXNetError("health must be a StepHealth (got %r)"
                             % (health,))
        self._health = health
        self._hstate = None
        self.last_health = None
        scaler = health.scaler if health is not None else None
        # scaler semantics REQUIRE the skip: an overflowed step must not
        # reach the weights, whatever skip_nonfinite says
        skip_on_bad = health is not None and (
            health.skip_nonfinite or scaler is not None)
        from . import quantize as _quantize

        # fp8 training compute (MXNET_FP8): per-site amax histories ride
        # the carried hstate exactly like the dynamic loss scaler, so an
        # armed fp8 build uses the 8-arg/6-output step form even when no
        # StepHealth is configured.  Site count is discovered lazily
        # (first compile/call) from an abstract forward trace.
        fp8_on = _quantize.fp8_enabled()
        self._fp8 = fp8_on
        self._fp8_sites = None
        use_hstate = health is not None or fp8_on
        self._use_hstate = use_hstate
        clip_gnorm = optimizer.clip_global_norm
        rescale = optimizer.rescale_grad

        # compute/collective overlap: under a pure data-parallel mesh
        # the gradient reduction runs as explicit bucketed all-reduces
        # (shard_map) issued in reverse production order so they hide
        # under backward compute; the latency-hiding scheduler flags
        # arm here (best effort — first TrainStep in the process, before
        # the backend initializes)
        from .parallel import overlap as _overlap
        from .parallel import zero as _zero

        _overlap.arm_latency_hiding()
        # decline warnings scope to THIS step: a rebuilt TrainStep with a
        # different config re-reports its own decline reasons
        self._overlap_warner = warner = _overlap.DeclineWarner()
        if plan_tp:
            # composed TP plan: gradient reduction belongs to GSPMD —
            # per-group psum_scatter over the data axis for tiled grads,
            # the model-axis all-reduce where the TP math needs it.  The
            # explicit shard_map DDP path cannot express the joint
            # (model, data) layout, so it stands down without a decline
            # warning (this is the designed path, not a fallback).
            ddp_ax = None
        else:
            ddp_ax = _overlap.ddp_axis(mesh, batch_sharding_axis,
                                       param_sharding, warner=warner,
                                       param_names=self.param_names)
        ddp_bucket = _overlap.grad_bucket_bytes()
        # reverse graph-construction order approximates the order
        # backward produces gradients in
        ddp_order = tuple(reversed(self.param_names))
        self.grad_overlap_axis = ddp_ax

        # ZeRO sharded update (arXiv 2004.13336): optimizer state and the
        # weight update tile 1/N over the data axis — gradients arrive
        # reduce-scattered, the update runs on the local flat tile, fresh
        # params all-gather for the next forward.  Stage 3 keeps the
        # params themselves at rest as those flat tiles and gathers them
        # bucket-by-bucket on demand inside forward (re-gathered by the
        # rematerialized backward), with no trailing full all-gather.
        zmode = _zero.zero_mode(zero)
        zax = _zero.zero_axis(mesh, batch_sharding_axis, param_sharding,
                              mode=zmode, warn=warner.warn,
                              param_names=self.param_names)
        self.zero_axis = zax
        zero_n = int(mesh.shape[zax]) if zax is not None else 0
        zero_min = _zero.min_param_bytes()
        self._zero_n = zero_n
        self._zero_min_bytes = zero_min
        self._frozen = frozen
        self.zero3 = z3_mode = zax is not None and zmode == "3"
        # the tiling layout, cached from CANONICAL shapes the first time
        # it is computed (init_state / compile / pack_params): under
        # ZeRO-3 the live params are flat tiles, so recomputing from
        # them would mis-tile — every later caller reads the cache
        self._zero_lay = None
        z3_bucket = _zero.gather_bucket_bytes()
        if z3_mode and ddp_ax is None and not plan_tp \
                and _overlap.overlap_mode() != "off":
            warner.warn(
                "zero3-gather",
                "zero=3: the bucketed gather prefetch needs the explicit "
                "DDP path (pure data-parallel mesh, MXNET_GRAD_OVERLAP); "
                "params stay sharded at rest with GSPMD-scheduled "
                "gathers instead")
        # set by Module when it drives this step, so the bounded sharded-
        # update dispatch can attach the kvstore's peer diagnosis
        self._kvstore = None

        def cast_compute(x):
            return x.astype(cdtype) if jnp.issubdtype(
                x.dtype, jnp.floating) else x

        def core_step(params, aux, states, batch, rng, lr, t, hstate):
            # delayed scaling: realize this step's per-site (x, w) scales
            # from the carried amax history before the forward traces
            fp8_scales = None
            if fp8_on and "fp8_hist" in hstate:
                fp8_scales = _quantize.fp8_realize_scales(
                    hstate["fp8_hist"])

            def loss_fn(p, b, r):
                args = dict(p)
                args.update(b)
                a = aux
                if cdtype is not None:
                    args = {k: cast_compute(v) for k, v in args.items()}
                    a = {k: cast_compute(v) for k, v in aux.items()}
                if fp8_scales is not None:
                    with _quantize.fp8_trace(fp8_scales) as tr:
                        outs, new_aux = fwd_fn(args, a, r)
                    amax = jnp.stack(tr.amax) if tr.amax else None
                else:
                    outs, new_aux = fwd_fn(args, a, r)
                    amax = None
                if cdtype is not None:
                    new_aux = {k: v.astype(aux[k].dtype)
                               for k, v in new_aux.items()}
                if amax is not None:
                    # fresh amaxes leave the grad transform as an aux
                    # output under a reserved key (a Python-side record
                    # would leak tracers); popped right after the vag
                    new_aux = dict(new_aux)
                    new_aux["__fp8_amax__"] = amax
                loss = _loss_from_outputs(outs)
                if scaler is not None:
                    # scale the loss BEFORE the backward: gradients come
                    # back scaled out of the underflow-prone range
                    loss = loss * hstate["loss_scale"]
                return loss, (outs, new_aux)

            # ZeRO tiling decision: the canonical-shape layout, cached
            # (under ZeRO-3 the traced params are flat at-rest tiles, so
            # recomputing here from live shapes would mis-tile)
            zlay = self.zero_layout(params) if zax is not None else None
            z3 = z3_mode and zlay is not None
            if z3:
                # ZeRO-3 on-demand gather: layer buckets in FORWARD
                # (graph-construction) order, one schedulable collective
                # per bucket, issued back-to-back ahead of the compute
                # that consumes them.  Each gathered full param is
                # tagged; the remat policy below refuses to save tagged
                # values as residuals, so backward re-issues the bucket
                # gathers in reverse order as it needs them — live full
                # params stay O(max bucket), not O(model).
                z3_names = [p for p in self.param_names
                            if zlay[p].sharded]
                z3_sizes = {p: zlay[p].padded * zlay[p].dtype.itemsize
                            for p in z3_names}
                z3_buckets = (_overlap.bucket_partition(
                    z3_names, z3_sizes, z3_bucket) if z3_names else [])
                base_loss_fn = loss_fn

                def z3_loss_fn(p, b, r):
                    full = dict(p)
                    for bucket in z3_buckets:
                        gathered = _zero.gather_bucket(
                            [p[q] for q in bucket],
                            [zlay[q] for q in bucket], mesh, zax)
                        for q, fp in zip(bucket, gathered):
                            full[q] = _z3_tag(fp)
                    return base_loss_fn(full, b, r)

                # The re-gather policy applies to fp32 compute only.
                # Under a compute dtype the matmuls consume the CAST copy,
                # which carries no tag: the policy saved it whole (on the
                # chip the compiled bf16 step holds one gather per
                # parameter either way), and that program stopped
                # learning on four v5e chips while this plain form tracks
                # one chip exactly (PERF.md, Findings PR 21).
                policy = _z3_remat_policy() if cdtype is None else None
                loss_fn = (jax.checkpoint(z3_loss_fn, policy=policy)
                           if policy is not None else z3_loss_fn)
            vag = None
            if ddp_ax is not None:
                # None = this trace can't run the DDP path (indivisible
                # batch, non-batch-leading outputs); GSPMD fallback below
                vag = _overlap.ddp_value_and_grad(
                    loss_fn, params, batch, rng, mesh, ddp_ax,
                    frozen=frozen, order=ddp_order,
                    bucket_bytes=ddp_bucket, warner=warner,
                    zero_layout=zlay if ddp_ax == zax else None,
                    zero_rest=z3)
            if vag is None:
                vag = jax.value_and_grad(
                    lambda p: loss_fn(p, batch, rng),
                    has_aux=True)(params)
            (loss, (outs, new_aux)), grads = vag
            fp8_amax = None
            if fp8_scales is not None:
                new_aux = dict(new_aux)
                fp8_amax = new_aux.pop("__fp8_amax__", None)
            if zlay is not None:
                # normalize: sharded grads still at full shape came from
                # the GSPMD fallback (or a declined DDP trace) — the
                # sharding constraint on the flat form IS the
                # reduce-scatter (DDP-path grads arrive already flat).
                # ZeRO-3 grads are born flat everywhere (the gather's
                # transpose reduce-scatters); pin their tile layout so
                # the GSPMD fallback lands them scattered, not summed
                # full-size first.
                grads = dict(grads)
                for k, ent in zlay.items():
                    if not ent.sharded or k not in grads:
                        continue
                    if tuple(grads[k].shape) == ent.shape:
                        grads[k] = _zero.shard_flat(grads[k], ent, mesh,
                                                    zax)
                    elif z3 and tuple(grads[k].shape) == (ent.padded,):
                        grads[k] = jax.lax.with_sharding_constraint(
                            grads[k], _zero.flat_sharding(mesh, zax, ent))
            live = [k for k in sorted(grads) if k not in frozen]
            if scaler is not None:
                inv = 1.0 / hstate["loss_scale"]
                loss = loss * inv
                grads = dict(grads)
                for k in live:
                    grads[k] = grads[k] * inv.astype(grads[k].dtype)
            # health sentinel: one extra reduction per parameter, fused
            # into compute that already reads every gradient.  A single
            # NaN/Inf anywhere poisons the sum of squares, so the
            # norm's finiteness doubles as the all-params flag.
            gnorm = opt_mod.global_grad_norm(
                [grads[k] for k in live], rescale)
            nonfinite = ~(jnp.isfinite(loss) & jnp.isfinite(gnorm))
            if clip_gnorm is not None:
                factor = opt_mod.global_norm_scale(gnorm, clip_gnorm)
                grads = dict(grads)
                for k in live:
                    grads[k] = grads[k] * factor.astype(grads[k].dtype)
            def run_updates(_):
                new_params, new_states = {}, {}
                for i, k in enumerate(sorted(grads)):
                    g = grads[k]
                    if k in frozen:
                        new_params[k] = params[k]
                        new_states[k] = states[k]
                        continue
                    if zlay is not None and zlay[k].sharded:
                        # stage 1 slices the replicated weight down to
                        # its tile and gathers the fresh param back;
                        # stage 3 runs on the at-rest tile directly and
                        # returns it still tiled — the next forward's
                        # bucket gather replaces the trailing all-gather
                        driver = (opt_mod.sharded_fused_update_at_rest
                                  if z3 else opt_mod.sharded_fused_update)
                        new_params[k], new_states[k] = driver(
                            optimizer, params[k], g, states[k],
                            lr * lr_mults[k], base_wd * wd_mults[k],
                            t, jax.random.fold_in(rng, i + 1),
                            mesh, zax, zlay[k])
                        continue
                    new_params[k], new_states[k] = optimizer.fused_update(
                        params[k], g, states[k],
                        lr * lr_mults[k], base_wd * wd_mults[k], t,
                        jax.random.fold_in(rng, i + 1))
                return new_params, new_states, new_aux

            if skip_on_bad:
                # the skip happens IN-PROGRAM: a conditional keeps the
                # old buffers bit-exactly, so a poisoned batch is
                # consumed with a zero update and async dispatch never
                # stalls.  lax.cond (not jnp.where): the clean path
                # executes only the update branch, so the sentinel adds
                # no parameter-sized select pass to healthy steps.
                new_params, new_states, new_aux = jax.lax.cond(
                    nonfinite,
                    lambda _: (params, states, aux),
                    run_updates, None)
            else:
                new_params, new_states, new_aux = run_updates(None)
            if scaler is not None:
                good = jnp.where(nonfinite, 0,
                                 hstate["good_steps"] + 1)
                grow = good >= scaler.growth_interval
                scale = jnp.where(
                    nonfinite,
                    jnp.maximum(hstate["loss_scale"] * scaler.backoff,
                                scaler.min_scale),
                    jnp.where(
                        grow,
                        jnp.minimum(hstate["loss_scale"] * scaler.growth,
                                    scaler.max_scale),
                        hstate["loss_scale"]))
                new_hstate = {
                    "loss_scale": scale.astype("float32"),
                    "good_steps": jnp.where(grow, 0, good).astype("int32"),
                }
            else:
                new_hstate = hstate
            if fp8_amax is not None:
                # roll the amax history forward even on skipped steps —
                # but a nonfinite forward amax must not poison it
                safe = jnp.where(jnp.isfinite(fp8_amax), fp8_amax, 0.0)
                new_hstate = dict(new_hstate)
                new_hstate["fp8_hist"] = _quantize.fp8_update_hist(
                    hstate["fp8_hist"], safe)
            stats = {"loss": loss.astype("float32"), "grad_norm": gnorm,
                     "nonfinite": nonfinite}
            if scaler is not None:
                stats["loss_scale"] = hstate["loss_scale"]
            # all outputs come back (multi-loss symbols run fused too);
            # a batch-sharded prefix sharding covers the whole tuple
            return new_params, new_aux, new_states, outs, new_hstate, stats

        if use_hstate:
            step = core_step
        else:
            # legacy 7-arg / 4-output form: the discarded loss value,
            # norm, and flag trace dead and XLA DCEs them — the compiled
            # clean path is unchanged (clip_global_norm, if set, is live
            # through the grads and survives)
            def step(params, aux, states, batch, rng, lr, t):
                p, a, s, outs, _, _ = core_step(
                    params, aux, states, batch, rng, lr, t, {})
                return p, a, s, outs

        K = int(steps_per_call)
        if K < 1:
            raise MXNetError("steps_per_call must be >= 1, got %d" % K)
        self._steps_per_call = K
        if K > 1:
            # multi-step dispatch: one device call scans K donated
            # updates over a (K, batch, …) super-batch — Python dispatch
            # and launch overhead amortize K-fold (the win for small
            # models where per-step host work rivals device time).  lr is
            # held constant across the K inner steps (the scheduler is
            # consulted once per call); t advances per inner step so
            # bias-corrected optimizers stay exact; the per-call rng is
            # folded with the inner step index so dropout masks differ
            # per step.  Outputs come back stacked (K, batch, …); the
            # health stats likewise carry one (K,) entry per inner step.
            base_step = step

            if use_hstate:
                def step(params, aux, states, batch, rng, lr, t, hstate):
                    def body(carry, xs):
                        p, a, s, tk, h = carry
                        bk, k = xs
                        p, a, s, outs, h, stats = base_step(
                            p, a, s, bk, jax.random.fold_in(rng, k), lr,
                            tk, h)
                        return (p, a, s, tk + 1, h), (outs, stats)

                    (params, aux, states, _, hstate), (outs, stats) = \
                        jax.lax.scan(body,
                                     (params, aux, states, t, hstate),
                                     (batch, jnp.arange(K)))
                    return params, aux, states, outs, hstate, stats
            else:
                def step(params, aux, states, batch, rng, lr, t):
                    def body(carry, xs):
                        p, a, s, tk = carry
                        bk, k = xs
                        p, a, s, outs = base_step(
                            p, a, s, bk, jax.random.fold_in(rng, k), lr,
                            tk)
                        return (p, a, s, tk + 1), outs

                    (params, aux, states, _), outs = jax.lax.scan(
                        body, (params, aux, states, t),
                        (batch, jnp.arange(K)))
                    return params, aux, states, outs

        self._step_fn = step
        self._batch_sharding_axis = batch_sharding_axis
        self._param_sharding = param_sharding
        if param_sharding not in (None, "replicated"):
            if mesh is None:
                raise MXNetError(
                    "param_sharding=%r needs a mesh (pass mesh=... or run "
                    "under a dist kvstore)" % (param_sharding,))
            if isinstance(param_sharding, str):
                # validate the style NOW: a typo must fail at construction
                # (inside Module's fused-fallback handling), not on the
                # first training batch
                from .parallel.sharding import param_sharding_rules

                param_sharding_rules(param_sharding)
        # AOT compile() works everywhere except shape-dependent
        # param_sharding (fsdp resolves against concrete shapes)
        self._aot_capable = not (
            mesh is not None and param_sharding not in (None, "replicated"))
        if mesh is not None and param_sharding not in (None, "replicated"):
            # FSDP's largest-dim rule needs concrete parameter SHAPES, so
            # the jitted step is built lazily on the first call
            self._jit_step = None
        elif zax is not None or plan_tp:
            # ZeRO state shardings resolve against the optimizer-state
            # pytree structure — lazily from the first call's concrete
            # states, or from compile()'s abstract ones.  A zero-off TP
            # plan likewise resolves its per-parameter specs against
            # concrete shapes (the divisibility fallback needs them).
            self._jit_step = None
        elif mesh is not None:
            self._jit_step = self._build_jit()
        else:
            self._jit_step = jax.jit(step, donate_argnums=(0, 1, 2))
        self._t = 0
        # recompile guardrail: one guard per symbol name, shared across
        # rebuilt instances so a per-batch reconstruction storm is
        # visible as one counter
        self._recompile_guard = registry.guard(
            "TrainStep(%s)" % (getattr(symbol, "name", None) or "graph"))
        # AOT state (compile()): the ready executable, its input
        # signature, and the recorded stats
        self._aot = None
        self._aot_sig = None
        self.compile_stats = None

    def _build_jit(self, pshard=None, sshard=None):
        """jit the step with parameter/state shardings resolved.

        ``pshard``: {name: NamedSharding} (or None → replicate all);
        ``sshard``: a pytree prefix for the optimizer states (or None).
        Gradients need no annotation: GSPMD propagates shardings and
        inserts the collectives (all-gather for fsdp params,
        all-reduce/reduce-scatter for grads — the TPU form of the
        reference's push/pull).
        """
        import jax

        from .parallel.sharding import (batch_axes, named_sharding,
                                        replicated)

        mesh = self.mesh
        repl = replicated(mesh)
        # batch sharding mirrors shard_batch exactly (data axis plus
        # fsdp when present); pure SP/EP/pipe meshes carry no batch
        # axis, so the batch stays replicated and the mesh axes are
        # consumed inside the ops (ring attention, MoE all_to_all)
        baxes = batch_axes(mesh, self._batch_sharding_axis)
        # a packed super-batch carries an unsharded leading K axis; the
        # batch dim (and the stacked outputs' step dim) sits behind it
        lead = [None] if self._steps_per_call > 1 else []
        bshard = named_sharding(mesh, *(lead + [baxes])) if baxes else repl
        if pshard is None:
            pshard = repl
        if sshard is None:
            sshard = repl if not isinstance(pshard, dict) else pshard
        bdict = {n: bshard for n in self.data_names + self.label_names}
        # __call__ re-places host inputs onto these when the mesh spans
        # processes (jit cannot auto-commit to non-addressable devices)
        self._in_bshard = bdict
        self._in_repl = repl
        in_sh = (pshard, repl, sshard, bdict, repl, None, None)
        out_sh = (pshard, repl, sshard, bshard)
        if self._use_hstate:
            # + scaler/fp8 state in, + new state / health stats out —
            # scalars and small histories, replicated everywhere
            in_sh = in_sh + (repl,)
            out_sh = out_sh + (repl, repl)
        return jax.jit(self._step_fn, in_shardings=in_sh,
                       out_shardings=out_sh, donate_argnums=(0, 1, 2))

    def _build_sharded_jit(self, params, states):
        """Resolve param_sharding rules against concrete shapes and jit.

        Optimizer state leaves follow their parameter's sharding when
        shaped like the weight (momentum/adam moments), else replicate
        (scalars, schedules) — the ZeRO contract that sharded params
        carry sharded optimizer states.
        """
        import jax

        from .parallel.sharding import (apply_rules, param_sharding_rules,
                                        replicated)

        if self._plan_tp and self._param_sharding in (None, "replicated"):
            pshard = self.plan.param_shardings(self.mesh, params)
        else:
            rules = self._param_sharding
            if isinstance(rules, str):
                rules = param_sharding_rules(rules)
            pshard = apply_rules(self.mesh, params, rules)
        repl = replicated(self.mesh)
        sshard = {
            n: jax.tree.map(
                lambda leaf, _n=n: pshard[_n]
                if tuple(leaf.shape) == tuple(params[_n].shape) else repl,
                states[n])
            for n in states
        }
        self._in_pshard = pshard
        self._in_sshard = sshard
        return self._build_jit(pshard, sshard)

    def _build_zero_jit(self, params, states):
        """jit with the ZeRO state layout resolved: flat ``(padded,)``
        state leaves tile over the data axis (group-locally
        ``P((model, data))`` for a composed plan's TP entries), scalars
        and unsharded params' states replicate.  Stage 1 keeps the
        params at their canonical placement (replicated, or the plan's
        TP specs — the all-gather lives inside the program); stage 3
        pins the at-rest flat params to their tile sharding in AND out —
        fresh tiles leave the step still sharded.  Under a plan, a
        parameter too small for tiling stays at its canonical TP
        sharding, weight-shaped state leaves included."""
        import jax

        from .parallel import zero as _zero
        from .parallel.sharding import named_sharding, replicated

        mesh = self.mesh
        zax = self.zero_axis
        lay = self.zero_layout(params)
        repl = replicated(mesh)
        canon = None
        if self._plan_tp:
            canon = {n: named_sharding(
                        mesh, *self.plan.param_spec(n, lay[n].shape, mesh))
                     for n in lay}

        def state_shard(n):
            if canon is not None and not lay[n].sharded:
                # canonical TP placement: moments follow the weight
                return jax.tree.map(
                    lambda leaf, _n=n: canon[_n]
                    if tuple(getattr(leaf, "shape", ())) == lay[_n].shape
                    else repl, states[n])
            return _zero.state_sharding(states[n], lay[n], mesh, zax)

        sshard = {n: state_shard(n) for n in states}
        pshard = None
        if self.zero3:
            pshard = {n: (_zero.flat_sharding(mesh, zax, lay[n])
                          if lay[n].sharded
                          else (canon[n] if canon is not None else repl))
                      for n in params}
        elif canon is not None:
            pshard = dict(canon)
        self._in_pshard = (pshard if pshard is not None
                           else replicated(self.mesh))
        self._in_sshard = sshard
        return self._build_jit(pshard, sshard)

    def _spans_processes(self):
        """True when the step's mesh holds devices this process cannot
        address (a multi-controller pod run)."""
        cached = getattr(self, "_spans_cache", None)
        if cached is None:
            import jax

            mesh = self.mesh
            cached = self._spans_cache = bool(
                mesh is not None
                and any(d.process_index != jax.process_index()
                        for d in mesh.devices.flat))
        return cached

    def zero_layout(self, params):
        """{name: ZeroParam} tiling decision for this step, or None when
        the sharded update is off/declined.  Deterministic in parameter
        shapes/dtypes (works on ShapeDtypeStructs too).  Cached on first
        computation — which must see CANONICAL shapes (``init_state``,
        ``compile``, ``pack_params`` all qualify), because under ZeRO-3
        the live params are flat tiles the tiling cannot be derived
        from."""
        if self.zero_axis is None:
            return None
        if self._zero_lay is not None:
            return self._zero_lay
        from .parallel import zero as _zero

        if self._plan_tp:
            # composed plan: TP params get group-local shard-major
            # tiles, everything else the classic data-axis tiling
            self._zero_lay = _zero.plan_layout(
                params, self.mesh, self.zero_axis,
                self.plan.param_specs(params, self.mesh),
                min_bytes=self._zero_min_bytes, frozen=self._frozen)
        else:
            self._zero_lay = _zero.layout(params, self._zero_n,
                                          self._zero_min_bytes,
                                          self._frozen)
        return self._zero_lay

    def pack_params(self, params):
        """Canonical full params -> this step's at-rest layout: under
        ZeRO-3 sharded entries become flat 1/N tiles placed ``P(axis)``
        (bit-exact round trip — padding is zeros); identity otherwise.
        Module calls this before the first fused step; direct ZeRO-3
        callers must feed ``__call__`` packed params (``init_state``
        already returns them packed)."""
        lay = self.zero_layout(params)
        if not self.zero3 or lay is None:
            return params
        from .parallel import zero as _zero

        return _zero.pack_params(params, lay, self.mesh, self.zero_axis)

    def unpack_params(self, params):
        """At-rest params -> canonical host numpy dict (identity unless
        ZeRO-3).  Requires the tiles to be addressable."""
        lay = self._zero_lay
        if not self.zero3 or lay is None:
            return params
        from .parallel import zero as _zero

        return _zero.unpack_params(params, lay)

    def memory_report(self, params=None, states=None):
        """Bench accounting, labeled per column: ``opt_state_bytes`` and
        ``params_bytes_per_replica`` are what ONE replica holds at rest
        (read from the live arrays' shardings — full-model params under
        zero=off/stage-1, ~1/N tiles under ZeRO-3), and their sum is
        ``total_state_bytes_per_replica`` — params included, so the
        stage-1-vs-3 A/B compares like with like.
        ``update_gather_bytes`` is the stage-1 trailing fresh-param
        all-gather (0 under ZeRO-3 — there is none);
        ``gather_bytes_per_step`` is the per-step param-gather traffic
        whichever stage moves it (stage 1: the trailing gather; ZeRO-3:
        forward bucket gathers + the backward re-gather).  AOT
        ``memory_analysis`` numbers ride along when compiled."""
        from .parallel import zero as _zero

        out = {"zero": self.zero_axis is not None, "zero3": self.zero3}
        if states is not None:
            out["opt_state_bytes"] = _zero.state_bytes_per_replica(states)
        if params is not None:
            out["params_bytes_per_replica"] = \
                _zero.params_bytes_per_replica(params)
            if states is not None:
                out["total_state_bytes_per_replica"] = (
                    out["opt_state_bytes"]
                    + out["params_bytes_per_replica"])
        lay = self._zero_lay
        if lay is None and params is not None:
            lay = self.zero_layout(params)
        if lay is None:
            out["update_gather_bytes"] = 0
            out["gather_bytes_per_step"] = 0
        elif self.zero3:
            out["update_gather_bytes"] = 0
            out["gather_bytes_per_step"] = _zero.zero3_gather_bytes(lay)
        else:
            out["update_gather_bytes"] = _zero.update_gather_bytes(lay)
            out["gather_bytes_per_step"] = out["update_gather_bytes"]
        if self._aot is not None:
            try:
                mem = self._aot.memory_analysis()
                out["aot_argument_bytes"] = int(
                    mem.argument_size_in_bytes)
                out["aot_temp_bytes"] = int(mem.temp_size_in_bytes)
            except Exception:
                pass
        return out

    def _abstract_inputs(self, shapes, dtype="float32", device=None):
        """Abstract (params, aux, states, batch, rng, lr, t[, hstate])
        matching what ``__call__`` dispatches for per-step ``shapes``
        (placed on ``device`` when given, else wherever jit defaults):
        parameter/aux avals from the shape-inference pass, optimizer
        states via ``eval_shape``, the super-batch leading K axis when
        ``steps_per_call > 1``, a concrete rng key, the python-float lr
        (weak type, exactly like the live call), and the int32 step."""
        import jax
        import jax.numpy as jnp

        from .symbol.symbol import _infer_param_shapes

        shapes = {k: tuple(v) for k, v in dict(shapes).items()}
        all_shapes = _infer_param_shapes(self.symbol, dict(shapes))
        S = jax.ShapeDtypeStruct
        if device is not None:
            S = functools.partial(
                S, sharding=jax.sharding.SingleDeviceSharding(device))
        params = {n: S(tuple(all_shapes[n]), jnp.dtype(dtype))
                  for n in self.param_names}
        aux = {n: S(tuple(all_shapes[n]), jnp.dtype("float32"))
               for n in self._aux_names}
        lay = self.zero_layout(params)
        states = {}
        for n in self.param_names:
            w = params[n]
            if lay is not None and lay[n].sharded:
                # ZeRO layout: every weight-shaped leaf is born flat
                w = S((lay[n].padded,), jnp.dtype(dtype))
            states[n] = jax.eval_shape(self.optimizer.init_fused_state, w)
        if self.zero3 and lay is not None:
            # ZeRO-3: the step's param arguments are the at-rest tiles
            params = {n: (S((lay[n].padded,), jnp.dtype(dtype))
                          if lay[n].sharded else params[n])
                      for n in params}
        K = self._steps_per_call
        batch = {}
        for n in self.data_names + self.label_names:
            if n not in shapes:
                raise MXNetError("compile(shapes) is missing a shape "
                                 "for input %r" % n)
            shp = ((K,) + shapes[n]) if K > 1 else shapes[n]
            batch[n] = S(shp, jnp.dtype("float32"))
        args = (params, aux, states, batch, jax.random.PRNGKey(0),
                float(self.lr), jnp.asarray(1, "int32"))
        if self._use_hstate:
            self._fp8_site_count(params, aux, batch)
            args = args + (self._init_hstate(),)
        return args

    def compile(self, shapes, dtype="float32", device=None):
        """AOT warmup: lower and compile the step for ``shapes`` NOW.
        ``device``: the single device the live arrays sit on when it is
        not jit's default (a cpu-context module on a TPU host) — the
        executable is compiled for it, not for the default device.

        ``shapes`` maps each data/label name to its per-step shape (the
        same dict ``init_state`` takes); the leading ``steps_per_call``
        axis is added internally.  The resulting executable is kept and
        used directly by ``__call__`` whenever the live inputs match the
        compiled signature, so the first training step pays zero
        compile; a mismatch falls back to the lazily-jitted path (which
        still hits the persistent cache).  Compile wall time, FLOPs, and
        executable size are recorded as a profiler compile event and
        returned (also kept on ``self.compile_stats``)."""
        import time

        from . import profiler
        from .compile_cache import cache_stats

        if self._jit_step is None and not self._aot_capable:
            raise MXNetError(
                "AOT compile is unavailable with shape-dependent "
                "param_sharding=%r: the sharded jit resolves against "
                "concrete parameters on the first call"
                % (self._param_sharding,))
        args = self._abstract_inputs(shapes, dtype=dtype, device=device)
        if self._jit_step is None:
            if self.zero_axis is not None:
                # ZeRO: the abstract states carry the flat layout, which
                # is all the sharding resolution needs
                self._jit_step = self._build_zero_jit(args[0], args[2])
            else:
                # zero-off TP plan: specs resolve from abstract shapes
                self._jit_step = self._build_sharded_jit(args[0], args[2])
        hits_before = cache_stats()["hits"]
        t0 = time.perf_counter()
        lowered = self._jit_step.lower(*args)
        lower_s = time.perf_counter() - t0
        compiled = lowered.compile()
        compile_s = time.perf_counter() - t0
        flops = None
        try:
            ca = compiled.cost_analysis()
            ca = ca[0] if isinstance(ca, list) else ca
            flops = float(ca.get("flops", 0.0)) or None
        except Exception:
            pass
        exe_bytes = None
        try:
            mem = compiled.memory_analysis()
            exe_bytes = int(getattr(mem, "generated_code_size_in_bytes",
                                    0)) or None
        except Exception:
            pass
        cache_hit = cache_stats()["hits"] > hits_before
        sig = _signature_of(*args)
        self._aot = compiled
        self._aot_sig = sig
        # seed the guard so the first matching live call is not counted
        # as a second trace
        self._recompile_guard.observe(sig)
        self.compile_stats = profiler.compile_event(
            self._recompile_guard.name, compile_s, flops=flops,
            executable_bytes=exe_bytes, cache_hit=cache_hit,
            lower_s=round(lower_s, 6), aot=True)
        return self.compile_stats

    def __call__(self, params, aux, states, batch, rng, lr=None, t=None):
        import jax
        import jax.numpy as jnp

        K = self._steps_per_call
        if t is None:
            self._t += K
            t = self._t - K + 1  # first inner step's post-increment count
        else:
            self._t = int(t) + K - 1
        with _span("trainstep.hygiene"):
            # Two input hygiene passes before the donated call:
            # 1. commit uncommitted arrays (jnp.zeros products) so the jit
            #    signature is identical on every step — no recompiles;
            # 2. donated pytrees must not alias each other (some optimizers
            #    seed state from the weight buffer; XLA may also alias
            #    identical outputs) — copy duplicates.
            seen = set()

            def dedupe(x):
                if not getattr(x, "committed", True):
                    x = jax.device_put(x, next(iter(x.devices())))
                k = _buffer_key(x)
                if k in seen:
                    return jnp.copy(x)
                seen.add(k)
                return x

            params, aux, states = jax.tree.map(
                dedupe, (params, aux, states))
            if self._jit_step is None:
                if self.zero_axis is not None:
                    self._jit_step = self._build_zero_jit(params, states)
                else:
                    self._jit_step = self._build_sharded_jit(params, states)
            if getattr(self, "_in_pshard", None) is not None:
                # committed single-device arrays cannot be auto-resharded to
                # a non-trivial layout by jit; place them explicitly (no-op
                # once the donated outputs carry the sharding)
                params = _place(params, self._in_pshard)
                states = _place(states, self._in_sshard)
            lr = self.lr if lr is None else lr
            t = jnp.asarray(t, "int32")
            if self._spans_processes():
                # pod run: EVERY array argument must be a global jax.Array —
                # jit cannot place host batches/rng/scalars across processes
                # itself.  The host batch is read as the GLOBAL batch (each
                # rank materializes its own rows), matching the
                # single-process semantics bit for bit.
                repl = self._in_repl
                aux = _place(aux, repl)
                batch = _place(dict(batch), self._in_bshard)
                rng = _place(rng, repl)
                lr = _place(jnp.asarray(lr, "float32"), repl)
                t = _place(t, repl)
                if self._use_hstate and self._hstate is None:
                    self._fp8_site_count(params, aux, batch)
                    self._hstate = self._init_hstate()
                if self._hstate is not None:
                    self._hstate = _place(self._hstate, repl)
            if not self._use_hstate:
                call_args = (params, aux, states, batch, rng, lr, t)
            else:
                if self._hstate is None:
                    self._fp8_site_count(params, aux, batch)
                    self._hstate = self._init_hstate()
                call_args = (params, aux, states, batch, rng, lr, t,
                             self._hstate)
            sig = _signature_of(*call_args)
            self._recompile_guard.observe(sig)

        def dispatch():
            out = None
            if self._aot is not None and sig == self._aot_sig:
                try:
                    out = self._aot(*call_args)
                except (TypeError, ValueError) as e:
                    # A compiled executable validates avals, layouts and
                    # shardings before it runs (donation has not happened
                    # yet) and refuses with one of these; the lazy jit
                    # re-specializes for what arrived.  Drop the AOT
                    # executable for good rather than re-failing every
                    # step, and say so.  A runtime error of the step
                    # itself (device fault, out of memory) is neither
                    # type and raises.
                    logger.warning(
                        "%s: AOT executable refused the live arguments "
                        "(%s); using the lazily-jitted step from here",
                        self._recompile_guard.name, e)
                    self._aot = None
                    out = None
            if out is None:
                out = self._jit_step(*call_args)
            return out

        with _span("trainstep.launch"):
            if self.zero_axis is not None:
                from .parallel import zero as _zero
                from .testing import faults

                def dispatch_zero():
                    # host-side boundaries of the in-program collectives:
                    # before dispatch = the gradient reduce-scatter (and,
                    # under ZeRO-3, the forward bucket all-gathers), after
                    # the result = the stage-1 fresh-param all-gather
                    faults.inject("zero_update")
                    if self.zero3:
                        faults.inject("zero_gather")
                    res = dispatch()
                    faults.inject("zero_update")
                    return res

                what = None
                active = None
                if self.zero3 and faults.active("zero_gather"):
                    active = True
                    what = ("ZeRO-3 bucketed parameter all-gather (forward "
                            "bucket gathers + backward re-gather)")
                out = _zero.bounded_dispatch(dispatch_zero,
                                             kvstore=self._kvstore,
                                             active=active, what=what)
            else:
                out = dispatch()
        if not self._use_hstate:
            return out
        (params, aux, states, outs, self._hstate,
         self.last_health) = out
        return params, aux, states, outs

    def _init_hstate(self):
        import jax.numpy as jnp

        scaler = self._health.scaler if self._health is not None else None
        h = {}
        if scaler is not None:
            h["loss_scale"] = jnp.asarray(scaler.init_scale, "float32")
            h["good_steps"] = jnp.asarray(0, "int32")
        if self._fp8 and self._fp8_sites:
            from . import quantize as _quantize

            h["fp8_hist"] = _quantize.fp8_hist_init(self._fp8_sites)
        return h

    def export_hstate(self):
        """Host snapshot of the carried step health state — the dynamic
        loss scale, its good-step streak, and the fp8 delayed-scaling
        amax history — or None when this step carries none.  The capture
        side of the in-memory plan migration (``parallel/elastic.py``);
        checkpoint-free, bit-exact."""
        import numpy as np

        if self._hstate is None:
            return None
        return {k: np.asarray(v) for k, v in self._hstate.items()}

    def load_hstate(self, hstate):
        """Install a captured :meth:`export_hstate` snapshot onto THIS
        step (the reshard side of the in-memory migration, or a restore
        without a disk round trip).  Dtypes are pinned to the carried
        contract (f32 scale/history, i32 streak) so the jit signature
        matches a fresh :meth:`_init_hstate`; an fp8 history also pins
        the site count, which is topology-independent."""
        import numpy as np

        import jax.numpy as jnp

        if hstate is None:
            return
        if not self._use_hstate:
            raise MXNetError(
                "cannot install a migrated hstate: this TrainStep "
                "carries no health state (no loss scaler and fp8 off) — "
                "the new plan's step must be armed like the old one")
        h = {}
        if "loss_scale" in hstate:
            h["loss_scale"] = jnp.asarray(float(hstate["loss_scale"]),
                                          "float32")
            h["good_steps"] = jnp.asarray(
                int(hstate.get("good_steps", 0)), "int32")
        if "fp8_hist" in hstate:
            hist = np.asarray(hstate["fp8_hist"])
            h["fp8_hist"] = jnp.asarray(hist, "float32")
            self._fp8_sites = int(hist.shape[0])
        self._hstate = h or None

    def _fp8_site_count(self, params, aux, batch):
        """Count the fp8 matmul sites one forward claims (once, via an
        abstract trace) — the leading dim of the carried amax history.

        Works from avals only, so live arrays and ShapeDtypeStructs both
        serve.  Under ZeRO-3 the live params are flat at-rest tiles; the
        cached layout recovers their canonical shapes.  The super-batch
        leading K axis is stripped when ``steps_per_call > 1``."""
        if not self._fp8 or self._fp8_sites is not None:
            return self._fp8_sites
        import jax
        import jax.numpy as jnp

        from . import quantize as _quantize

        S = jax.ShapeDtypeStruct
        lay = self._zero_lay if self.zero3 else None
        cparams = {}
        for n, v in dict(params).items():
            shp, dt = tuple(v.shape), v.dtype
            if lay is not None and n in lay and lay[n].sharded:
                shp, dt = tuple(lay[n].shape), lay[n].dtype
            cparams[n] = S(shp, jnp.dtype(dt))
        K = self._steps_per_call
        abatch = {n: S(tuple(v.shape)[1:] if K > 1 else tuple(v.shape),
                       jnp.dtype(v.dtype))
                  for n, v in dict(batch).items()}
        aaux = {n: S(tuple(v.shape), jnp.dtype(v.dtype))
                for n, v in dict(aux).items()}
        fwd = self._fwd_fn
        rng = jax.random.PRNGKey(0)

        def probe(p, a, b):
            args = dict(p)
            args.update(b)
            return fwd(args, a, rng)

        with _quantize.fp8_trace() as tr:
            jax.eval_shape(probe, cparams, aaux, abatch)
        self._fp8_sites = len(tr.names)
        return self._fp8_sites

    @property
    def loss_scale(self):
        """Current dynamic loss scale as a float (host sync), or None
        when no scaler is configured."""
        if self._hstate is None or "loss_scale" not in self._hstate:
            return None
        return float(self._hstate["loss_scale"])

    def init_state(self, shapes, dtype="float32", seed=0):
        """Allocate params/aux/optimizer-states as raw jax arrays via the
        shape inference pass + Xavier-ish scaling (bench/profiling
        convenience; real training initializes through Module)."""
        import jax
        import jax.numpy as jnp
        import numpy as np

        from .symbol.symbol import _infer_param_shapes

        all_shapes = _infer_param_shapes(self.symbol, dict(shapes))
        key = jax.random.PRNGKey(seed)
        params, aux, states = {}, {}, {}
        for n in self.param_names:
            shp = all_shapes[n]
            key, sub = jax.random.split(key)
            if n.endswith(("_gamma",)):
                params[n] = jnp.ones(shp, dtype)
            elif n.endswith(("_bias", "_beta")):
                params[n] = jnp.zeros(shp, dtype)
            else:
                fan_in = int(np.prod(shp[1:])) if len(shp) > 1 else shp[0]
                scale = (2.0 / max(1, fan_in)) ** 0.5
                params[n] = scale * jax.random.normal(sub, shp, dtype)
        lay = self.zero_layout(params)
        if lay is not None:
            from .parallel import zero as _zero
        for n in self.param_names:
            if lay is not None and lay[n].sharded:
                states[n] = _zero.init_state(
                    self.optimizer, params[n], lay[n], self.mesh,
                    self.zero_axis)
            else:
                states[n] = self.optimizer.init_fused_state(params[n])
        for n in self._aux_names:
            shp = all_shapes[n]
            aux[n] = jnp.ones(shp, "float32") if n.endswith("_var") \
                else jnp.zeros(shp, "float32")
        if lay is not None and self.zero3:
            # ZeRO-3: hand back the params already at rest (flat 1/N
            # tiles), matching what __call__ expects and returns
            params = _zero.pack_params(params, lay, self.mesh,
                                       self.zero_axis)
        return params, aux, states


def compile_train_step(symbol, **kwargs):
    return TrainStep(symbol, **kwargs)
