"""Compute/collective overlap for the data-parallel gradient reduction.

The fused train step compiles forward+backward+update into one XLA
program; under a data-parallel mesh the cross-replica gradient sum is
the largest exposed collective.  Under plain ``jit``+GSPMD the gradient
tree is a *logical global value* — the per-replica partial sums never
appear in the program we write, so there is nothing to bucket or
reorder, and whether the all-reduce hides under backward compute is
entirely up to the compiler.  This module makes the reduction explicit,
DDP-style: ``shard_map`` the loss/grad computation over the batch axis
so each replica's local gradients exist as values, then issue the
cross-replica sum as a sequence of bucket-sized tuple all-reduces in
*reverse production order* (``MXNET_GRAD_BUCKET_MB`` per bucket).  Each
bucket's collective depends only on its own gradients, so it becomes
schedulable the moment backward emits the bucket's last tensor and
XLA's latency-hiding scheduler (armed by :func:`arm_latency_hiding` for
the TPU build) can overlap it with the rest of the backward — instead
of one step-ending all-reduce over every parameter at once.

Semantics: gradients, the loss value, and the stacked outputs match the
GSPMD path (the loss is a sum over batch elements, so the bucketed psum
of local grads IS the global gradient).  Ops whose math depends on the
*global* batch read the trace context set by
:func:`ddp_value_and_grad` — SoftmaxOutput's ``normalization="batch"``
/``"valid"`` gradient scale widens by :func:`ddp_batch_factor` /
:func:`ddp_psum`, and BatchNorm training statistics ``pmean`` their
local moments (exact sync-BN, equal to the GSPMD global-batch stats) —
so the DDP path stays numerically equivalent, not approximately so.
The per-replica RNG is folded with the replica index so stochastic ops
(dropout) decorrelate across replicas.

Eligibility is checked at trace time; anything unsupported (non-batch
mesh axes, sharded params, outputs whose leading dim is not the batch)
declines with a one-time warning and the step falls back to the GSPMD
reduction — never wrong answers, only a missed optimization.
"""
from __future__ import annotations

import importlib.util
import os
import warnings

from ..base import get_env, logger

__all__ = ["arm_latency_hiding", "bucket_partition", "ddp_axis",
           "ddp_batch_factor", "ddp_pmean", "ddp_psum",
           "ddp_value_and_grad", "grad_bucket_bytes", "lhs_flags_present",
           "overlap_mode"]

# the MaxText-standard trio: latency-hiding scheduler + async collective
# fusion.  Delivered via LIBTPU_INIT_ARGS, NOT XLA_FLAGS: only libtpu
# reads it (at TPU client init), while XLA_FLAGS is parsed strictly by
# every backend build and unknown --xla_tpu_* flags abort a CPU/GPU
# process outright.
_LHS_FLAGS = (
    "--xla_tpu_enable_latency_hiding_scheduler=true",
    "--xla_tpu_enable_async_collective_fusion=true",
    "--xla_tpu_enable_async_collective_fusion_fuse_all_gather=true",
)

class DeclineWarner:
    """One-shot decline reporter with an explicit scope.

    Decline warnings must fire once per *consumer*, not once per
    process: a second ``TrainStep`` built with a different config in
    the same process has its own decline reasons to report, so each
    step owns a :class:`DeclineWarner` and passes it down.  The
    module-level default (``_warned``) keeps the old once-per-process
    behavior for direct callers."""

    def __init__(self):
        self.seen = set()

    def warn(self, key, msg):
        if key not in self.seen:
            self.seen.add(key)
            warnings.warn(msg, RuntimeWarning, stacklevel=3)

    def discard(self, key):
        self.seen.discard(key)


_default_warner = DeclineWarner()
# back-compat alias: tests/pre-existing callers reach the process-wide
# key set through ``overlap._warned``
_warned = _default_warner.seen

# (axis_name, replica_count) while the DDP local step is being traced,
# else None.  Batch-global ops consult this: under shard_map they see
# only the local batch shard, so anything whose math depends on the
# global batch — SoftmaxOutput's normalization="batch"/"valid" gradient
# scale, BatchNorm's training statistics — must widen its reduction by
# the replica count (or a psum) to keep the DDP path numerically equal
# to the GSPMD one.
_ddp_ctx = None


def ddp_batch_factor():
    """Replica count of the active DDP reduction (1 outside the trace)."""
    return _ddp_ctx[1] if _ddp_ctx else 1


def ddp_psum(x):
    """Sum ``x`` across the active DDP replicas (identity outside)."""
    if _ddp_ctx is None:
        return x
    from jax import lax

    return lax.psum(x, _ddp_ctx[0])


def ddp_pmean(x):
    """Mean of ``x`` across the active DDP replicas (identity outside)."""
    if _ddp_ctx is None:
        return x
    from jax import lax

    return lax.pmean(x, _ddp_ctx[0])


def _warn_once(key, msg, warner=None):
    (warner or _default_warner).warn(key, msg)


def overlap_mode():
    """``MXNET_GRAD_OVERLAP``: ``auto`` (default) | ``on`` | ``off``."""
    raw = str(get_env("MXNET_GRAD_OVERLAP", "auto")).strip().lower()
    if raw in ("0", "off", "false", "no"):
        return "off"
    if raw in ("1", "on", "true", "yes"):
        return "on"
    return "auto"


def grad_bucket_bytes():
    """Bucket size for the explicit reduction (``MXNET_GRAD_BUCKET_MB``,
    default 4 MB; 0 = one collective per parameter)."""
    mb = get_env("MXNET_GRAD_BUCKET_MB", 4.0)
    return max(0, int(mb * (1 << 20)))


_late_logged = False


def _backend_initialized():
    # jax has no public name for "has a backend client been created
    # yet"; this private one is what jax.distributed.initialize itself
    # checks to refuse a late call
    from jax._src import xla_bridge

    return xla_bridge.backends_are_initialized()


def lhs_flags_present():
    """Whether every scheduler flag is in ``LIBTPU_INIT_ARGS`` now."""
    flags = os.environ.get("LIBTPU_INIT_ARGS", "")
    return all(f.split("=")[0] in flags for f in _LHS_FLAGS)


def arm_latency_hiding():
    """Append the latency-hiding-scheduler flags to ``LIBTPU_INIT_ARGS``
    (idempotent) — while that can still take effect.

    libtpu reads the variable once, when the TPU client initializes.
    So the flags are appended only if no backend exists yet; a call
    that arrives later (anything resolved a device first — a
    ``Context.jax_device`` lookup, ``jax.devices()``, an array) leaves
    the environment alone, logs once that the flags are NOT armed, and
    returns False.  ``auto`` (default) arms only when a TPU is
    plausibly present (``JAX_PLATFORMS`` mentions tpu, or libtpu is
    importable) — CPU/GPU backends never read ``LIBTPU_INIT_ARGS``;
    ``MXNET_XLA_LHS=1`` forces, ``0`` disables.  Returns True when the
    flags were in the environment before the backend initialized.
    """
    mode = str(get_env("MXNET_XLA_LHS", "auto")).strip().lower()
    if mode in ("0", "off", "false", "no"):
        return False
    tpu_hint = ("tpu" in os.environ.get("JAX_PLATFORMS", "").lower()
                or importlib.util.find_spec("libtpu") is not None)
    if mode == "auto" and not tpu_hint:
        return False
    if _backend_initialized():
        # nothing is appended from here on, so flags present now were
        # present when libtpu read the variable
        global _late_logged
        armed = lhs_flags_present()
        if not armed and not _late_logged:
            _late_logged = True
            logger.info(
                "latency-hiding scheduler flags not armed: the backend "
                "initialized before the first TrainStep was built, and "
                "libtpu reads LIBTPU_INIT_ARGS only at start-up (set "
                "them in the environment to arm: %s)",
                " ".join(_LHS_FLAGS))
        return armed
    flags = os.environ.get("LIBTPU_INIT_ARGS", "")
    missing = [f for f in _LHS_FLAGS if f.split("=")[0] not in flags]
    if missing:
        os.environ["LIBTPU_INIT_ARGS"] = " ".join([flags] + missing).strip()
    return True


def ddp_axis(mesh, batch_axis, param_sharding=None, warner=None,
             param_names=()):
    """The mesh axis the explicit DDP reduction runs over, or None.

    Eligible: a live mesh whose only non-trivial axis is the batch axis
    (pure data parallelism) with replicated parameters — sharded-param
    styles (fsdp) already reduce-scatter through GSPMD and have their
    own overlap story.  ``warner``: per-consumer decline reporter;
    ``param_names`` lets a forced-on decline name the specific blocking
    parameter.  A style whose every resolved spec is trivial on this
    mesh is effectively pure DP and stays eligible.
    """
    if overlap_mode() == "off":
        return None
    if param_sharding not in (None, "replicated"):
        from .zero import _blocking_param

        blocking = _blocking_param(mesh, param_sharding, param_names)
        if blocking is not None:
            if overlap_mode() == "on":
                name, spec = blocking
                _warn_once(
                    "params",
                    "MXNET_GRAD_OVERLAP=on but param_sharding=%r places "
                    "%s as PartitionSpec%r — sharded grads reduce "
                    "through GSPMD (compose the layouts with a "
                    "ParallelPlan instead)"
                    % (param_sharding, name, tuple(spec)), warner)
            return None
    if mesh is None or batch_axis not in mesh.shape:
        return None
    if int(mesh.shape[batch_axis]) < 2:
        return None
    if any(int(s) != 1 for ax, s in mesh.shape.items()
           if ax != batch_axis):
        if overlap_mode() == "on":
            _warn_once("mesh", "MXNET_GRAD_OVERLAP=on but the mesh has "
                       "non-batch axes %r; using the GSPMD reduction"
                       % (dict(mesh.shape),), warner)
        return None
    return batch_axis


def bucket_partition(order, sizes, bucket_bytes):
    """Greedily group ``order`` (reverse production order) into buckets
    of at most ``bucket_bytes`` each (always at least one name per
    bucket, so oversized tensors get their own collective)."""
    buckets, cur, cur_bytes = [], [], 0
    for name in order:
        sz = int(sizes[name])
        if cur and cur_bytes + sz > bucket_bytes:
            buckets.append(cur)
            cur, cur_bytes = [], 0
        cur.append(name)
        cur_bytes += sz
    if cur:
        buckets.append(cur)
    return buckets


def _shard_map(fn, mesh, in_specs, out_specs):
    import jax

    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def ddp_value_and_grad(loss_fn, params, batch, rng, mesh, axis,
                       frozen=frozenset(), order=None, bucket_bytes=None,
                       warner=None, zero_layout=None, zero_rest=False):
    """Explicit data-parallel ``value_and_grad`` with bucketed reduction.

    ``loss_fn(p, b, r) -> (loss, (outs, new_aux))`` must compute the
    *sum-over-batch* objective (the fused step's contract), so the
    global gradient is exactly the psum of per-replica local gradients.
    Returns ``((loss, (outs, new_aux)), grads)`` with global semantics
    — a drop-in for ``jax.value_and_grad(...)(params)`` — or ``None``
    when this trace cannot run the DDP path (caller falls back to the
    GSPMD reduction).  Called at trace time inside the fused step's
    ``jit``.

    ``zero_layout`` ({name: ``parallel.zero.ZeroParam``}, sharing this
    ``axis``): sharded members of each bucket come back *reduce-
    scattered* — one tuple ``psum_scatter`` per bucket instead of the
    tuple ``psum`` — as flat ``(padded,)`` arrays tiled ``P(axis)``;
    unsharded members keep the full psum.  Same overlap schedule, 1/N
    of the reduction's receive bytes.

    ``zero_rest`` (ZeRO-3): the sharded members of ``params`` are
    ALREADY the flat at-rest tiles (in_spec ``P(axis)``), ``loss_fn``
    gathers them on demand, and AD's transpose of that
    ``all_gather(tiled=True)`` is itself the ``psum_scatter`` — their
    gradients arrive pre-reduce-scattered exactly where backward
    produces them, so they are EXCLUDED from the bucketed reduction
    (summing them again would double-count).  Only the unsharded
    leftovers ride the psum buckets.
    """
    import math

    import jax
    from jax import lax
    from jax.sharding import PartitionSpec as P

    n = int(mesh.shape[axis])
    for k, b in batch.items():
        if b.ndim == 0 or b.shape[0] % n:
            _warn_once("batch", "grad-overlap declined: batch input %r "
                       "shape %r not divisible by %s=%d"
                       % (k, tuple(b.shape), axis, n), warner)
            return None

    def full_vag(p, b, r):
        return jax.value_and_grad(
            lambda q: loss_fn(q, b, r), has_aux=True)(p)

    S = jax.ShapeDtypeStruct
    local_batch = {k: S((b.shape[0] // n,) + b.shape[1:], b.dtype)
                   for k, b in batch.items()}
    g_abs = jax.eval_shape(full_vag, params, batch, rng)
    l_abs = jax.eval_shape(full_vag, params, local_batch, rng)
    (_, (g_outs, g_aux)), g_grads = g_abs
    (_, (l_outs, _)), _ = l_abs

    # classify outputs: every leaf must carry the batch on its leading
    # dim so shard_map can stitch the global value back (out_spec
    # P(axis)).  Anything else (scalar MakeLoss heads, reductions) has
    # replica-dependent values with no inferable global semantics.
    out_specs_leaves = []
    for gl, ll in zip(jax.tree.leaves(g_outs), jax.tree.leaves(l_outs)):
        if (gl.ndim and gl.shape[0] == ll.shape[0] * n
                and gl.shape[1:] == ll.shape[1:]):
            out_specs_leaves.append(P(axis))
        else:
            _warn_once("outs", "grad-overlap declined: output leaf shape "
                       "%r does not carry the batch on its leading dim"
                       % (tuple(gl.shape),), warner)
            return None
    outs_spec = jax.tree.unflatten(jax.tree.structure(g_outs),
                                   out_specs_leaves)

    if bucket_bytes is None:
        bucket_bytes = grad_bucket_bytes()

    def _is_scattered(k):
        return (zero_layout is not None and k in zero_layout
                and zero_layout[k].sharded)

    live = [k for k in (order if order is not None else sorted(g_grads))
            if k in g_grads and k not in frozen
            and not (zero_rest and _is_scattered(k))]
    sizes = {k: math.prod(g_grads[k].shape) * g_grads[k].dtype.itemsize
             for k in live}
    buckets = bucket_partition(live, sizes, bucket_bytes) if live else []

    def local_step(p, b, r):
        from . import zero as _zero

        # decorrelate stochastic ops (dropout) across replicas
        r = jax.random.fold_in(r, lax.axis_index(axis))
        (loss, (outs, new_aux)), grads = full_vag(p, b, r)
        grads = dict(grads)
        # one tuple all-reduce per bucket, reverse production order:
        # bucket i's collective depends only on its own gradients, so
        # the scheduler can issue it while backward still computes the
        # earlier layers' buckets.  Under the zero layout the bucket's
        # sharded members flatten/pad first and reduce-SCATTER instead:
        # each replica keeps only its 1/N tile of the summed gradient.
        for bucket in buckets:
            plain = [k for k in bucket if not _is_scattered(k)]
            scat = [k for k in bucket if _is_scattered(k)]
            if plain:
                summed = lax.psum(tuple(grads[k] for k in plain), axis)
                for k, g in zip(plain, summed):
                    grads[k] = g
            if scat:
                tiles = lax.psum_scatter(
                    tuple(_zero.flat_pad(grads[k], zero_layout[k])
                          for k in scat),
                    axis, scatter_dimension=0, tiled=True)
                for k, g in zip(scat, tiles):
                    grads[k] = g
        loss = lax.psum(loss, axis)
        new_aux = lax.pmean(new_aux, axis)
        return (loss, (outs, new_aux)), grads

    bspec = {k: P(axis) for k in batch}
    gspec = {k: (P(axis) if _is_scattered(k) else P())
             for k in g_grads}
    # ZeRO-3 at-rest tiles enter sharded P(axis); everything else
    # (full params, zero-1 replicated weights) enters replicated
    pspec = ({k: (P(axis) if _is_scattered(k) else P()) for k in params}
             if zero_rest else P())
    spec_tree = ((P(), (outs_spec, jax.tree.map(lambda _: P(), g_aux))),
                 gspec)
    fn = _shard_map(local_step, mesh, (pspec, bspec, P()), spec_tree)
    # trace the local step under the DDP context so batch-global ops
    # (SoftmaxOutput normalization, BatchNorm training stats) widen
    # their reductions to the global batch
    global _ddp_ctx
    prev, _ddp_ctx = _ddp_ctx, (axis, n)
    try:
        return fn(params, batch, rng)
    finally:
        _ddp_ctx = prev
