"""Pipeline parallelism — microbatch schedules over the 'pipe' mesh axis.

The reference's only pipelining is manual ``group2ctx`` staging
(``example/model-parallel-lstm``, SURVEY.md §2.3 "Model parallelism"):
layers pinned to devices, activations copied at boundaries, no
microbatching.  This module is the fresh TPU-first design, in two tiers:

* :func:`pipeline_apply` — homogeneous stages (every stage shares one
  ``stage_fn``), forward-only GPipe schedule: stage parameters stacked
  on a leading axis sharded over 'pipe', a ``lax.fori_loop`` runs the
  fill/drain wave, activations hop between ring neighbors on ICI via
  ``ppermute``.
* :class:`PipelineTrainStep` — the first-class training form:
  **heterogeneous** stages (embed → N blocks → head) derived from a
  Symbol via :func:`split_symbol`, per-stage parameters flat-packed into
  one ``(S, L)`` buffer sharded over 'pipe' (each device physically
  holds only its stage's weights + optimizer state), and a choice of
  schedules:

  - ``schedule='gpipe'`` — all-forward wave stashing every stage
    input (M slots — the GPipe memory profile), then an explicit
    validity-gated all-backward wave that recomputes each stage
    forward under ``jax.vjp`` (gating matters: differentiating the
    whole forward scan would let the loss heads' custom vjp — which
    ignores its cotangent per the reference contract — emit junk
    gradients for the fill/drain ticks);
  - ``schedule='1f1b'`` — interleaved one-forward-one-backward: each
    stage keeps a bounded ring of at most ``2S`` stage-input
    activations and **recomputes** the stage forward during its
    backward tick (remat, the TPU-idiomatic trade — XLA already
    offers it as ``jax.checkpoint``), so peak activation memory is
    O(S), independent of M.  Gradients accumulate locally on each
    stage's device; no cross-stage gradient collective is needed
    because every parameter lives on exactly one stage.

  Both schedules move activations forward (and 1F1B moves cotangents
  backward) with ``lax.ppermute`` between mesh ring neighbors — the
  ICI-friendly hop — and compile to ONE XLA program including the
  optimizer update (donated buffers), the same single-program stance as
  ``fused.TrainStep``.
"""
from __future__ import annotations

import functools

from ..base import MXNetError
from ..compile_cache import track_lru
from .mesh import current_mesh

__all__ = ["pipeline_apply", "split_symbol", "PipelineTrainStep"]


def pipeline_apply(stage_fn, stage_params, microbatches, mesh=None,
                   axis="pipe"):
    """Run ``microbatches`` through a pipeline of stages.

    ``stage_fn(params, x) -> y``: one stage's computation; every stage
    shares this code (same shapes in = shapes out, the homogeneous-stage
    form — e.g. a transformer block).

    ``stage_params``: pytree whose leaves have a leading STAGE axis of
    size ``mesh.shape[axis]``; it is sharded so each device holds its
    stage's slice.

    ``microbatches``: (M, micro_batch, ...) array; returns the stacked
    outputs (M, micro_batch, ...), replicated over the pipe axis.
    """
    import jax

    mesh = mesh or current_mesh()
    if mesh is None or axis not in mesh.shape:
        raise MXNetError("pipeline_apply needs a mesh with a %r axis"
                         % axis)
    n_stages = mesh.shape[axis]
    leaves = jax.tree.leaves(stage_params)
    for leaf in leaves:
        if leaf.shape[0] != n_stages:
            raise MXNetError(
                "stage_params leading dim %d != pipe axis size %d"
                % (leaf.shape[0], n_stages))
    return _pipeline_fn(mesh, axis, stage_fn,
                        jax.tree.structure(stage_params))(
        stage_params, microbatches)


@track_lru("parallel._pipeline_fn")
@functools.lru_cache(maxsize=32)
def _pipeline_fn(mesh, axis, stage_fn, params_treedef):
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    n_stages = mesh.shape[axis]

    def body(params, micro):
        # params leaves: (1, ...) local stage slice; micro: (M, mb, ...)
        local_params = jax.tree.map(lambda p: p[0], params)
        stage = lax.axis_index(axis)
        m = micro.shape[0]
        ticks = m + n_stages - 1
        perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

        carry0 = jnp.zeros_like(micro[0])   # activation arriving from prev
        out0 = jnp.zeros_like(micro)

        def tick(t, state):
            carry, out = state
            feed = micro[jnp.minimum(t, m - 1)]
            x = jnp.where(stage == 0, feed, carry)
            y = stage_fn(local_params, x)
            # last stage emits microbatch t-(S-1) at tick t
            emit_idx = t - (n_stages - 1)
            is_emit = (stage == n_stages - 1) & (emit_idx >= 0)
            out = lax.cond(
                is_emit,
                lambda o: o.at[jnp.maximum(emit_idx, 0)].set(y),
                lambda o: o, out)
            carry = lax.ppermute(y, axis, perm)
            return carry, out

        _, out = lax.fori_loop(0, ticks, tick, (carry0, out0))
        # outputs live on the last stage; replicate over the pipe axis
        out = lax.psum(
            jnp.where(stage == n_stages - 1, out, jnp.zeros_like(out)),
            axis)
        return out

    pspec = jax.tree.unflatten(
        params_treedef,
        [P(axis)] * params_treedef.num_leaves)
    fn = jax.shard_map(body, mesh=mesh, in_specs=(pspec, P()),
                       out_specs=P(), check_vma=False)
    return jax.jit(fn)


# ---------------------------------------------------------------------------
# heterogeneous stages from a Symbol
# ---------------------------------------------------------------------------

def split_symbol(sym, n_stages, data_names=("data",),
                 label_names=("softmax_label",), input_shapes=None):
    """Cut a Symbol into ``n_stages`` stage symbols at graph positions
    where a fixed-size set of live tensors crosses (the pipeline
    boundary contract: every hop carries the same pytree of
    activations).

    The reference analogue is manual ``group2ctx`` staging
    (``/root/reference/example/model-parallel-lstm/lstm.py:65-129``)
    where the user assigns layers to devices by hand; here the cut
    points are found automatically: the smallest boundary width K with
    enough single-width positions is chosen, and the S-1 cuts are
    placed at even quantiles of the op-node order (transformer blocks
    are uniform, so this balances compute).

    Returns ``stage_syms``: stage k consumes boundary Variables
    ``pipe_in0..pipe_in{K-1}`` (except stage 0, which consumes the data
    variables) and outputs the K live tensors at its cut (the last
    stage outputs the original symbol heads).
    """
    from ..symbol.symbol import Symbol, _Node

    if n_stages < 2:
        raise MXNetError("split_symbol needs n_stages >= 2")
    topo = sym._topo()
    op_nodes = [n for n in topo if not n.is_variable]
    if len(op_nodes) < n_stages:
        raise MXNetError("symbol has %d op nodes, cannot make %d stages"
                         % (len(op_nodes), n_stages))
    # DFS order visits whole output chains one at a time, which strands
    # side chains (e.g. a running aux-loss sum) at the end and hides the
    # narrow boundaries; re-order by longest-path level (a valid topo
    # order — every edge goes to a strictly higher level) so each node
    # sits right after its inputs
    level = {}
    for n in topo:
        level[id(n)] = 0 if n.is_variable else 1 + max(
            (level[id(s)] for (s, _) in n.inputs), default=0)
    dfs_pos = {id(n): i for i, n in enumerate(op_nodes)}
    op_nodes.sort(key=lambda n: (level[id(n)], dfs_pos[id(n)]))
    pos = {id(n): i for i, n in enumerate(op_nodes)}

    # nodes computable from data/label variables alone (no parameters)
    # are "feed-local": cheap to recompute in whichever stage consumes
    # them (e.g. a label reshape feeding the loss head), so they never
    # ride the inter-stage hop
    feed_names = set(data_names) | set(label_names)
    replicable = {}

    def _replicable(n):
        if id(n) in replicable:
            return replicable[id(n)]
        if n.is_variable:
            r = n.name in feed_names
        else:
            r = all(_replicable(s) for (s, _) in n.inputs)
        replicable[id(n)] = r
        return r

    for n in topo:
        _replicable(n)

    # last consumer position of every op-node output entry
    consumed_at = {}
    for n in op_nodes:
        for (src, idx) in n.inputs:
            if not src.is_variable:
                key = (id(src), idx)
                consumed_at[key] = max(consumed_at.get(key, -1),
                                       pos[id(n)])
    out_entries = [(id(n), i) for (n, i) in sym._outputs]
    for key in out_entries:
        consumed_at[key] = len(op_nodes)  # live to the very end

    # live entries after each op position p (ordered by producer, idx)
    def live_after(p):
        live = []
        for n in op_nodes[:p + 1]:
            if replicable[id(n)]:
                continue
            for i in range(n.num_outputs):
                key = (id(n), i)
                if consumed_at.get(key, -1) > p:
                    live.append(key)
        return live

    lives = [live_after(p) for p in range(len(op_nodes) - 1)]

    # group candidate positions by boundary signature.  With
    # ``input_shapes`` the signature is the sorted multiset of live
    # tensor shapes (every hop must carry the same buffer set); without
    # shapes it degrades to the live width alone.
    if input_shapes is not None:
        entry_shapes = _entry_shapes(sym, topo, dict(input_shapes))

        def signature(lv):
            return tuple(sorted(str(entry_shapes[key]) for key in lv))
    else:
        def signature(lv):
            return (len(lv),)

    groups = {}
    for p, lv in enumerate(lives):
        groups.setdefault((len(lv), signature(lv)), []).append(p)

    # pick the smallest boundary width whose candidate positions cover
    # every quantile cut (a group with candidates only near one end
    # would produce wildly unbalanced stages)
    targets = [k * len(op_nodes) / n_stages for k in range(1, n_stages)]
    tol = max(1.0, len(op_nodes) / (2.0 * n_stages))
    cand = None
    for (width, _sig), c in sorted(groups.items()):
        if len(c) >= n_stages - 1 and all(
                any(abs(p - t) <= tol for p in c) for t in targets):
            cand = c
            break
    if cand is None:
        for (width, _sig), c in sorted(groups.items()):
            if len(c) >= n_stages - 1:
                cand = c
                break
    if cand is None:
        raise MXNetError(
            "no boundary signature offers %d cut points; this symbol "
            "does not decompose into a fixed-width pipeline (try fewer "
            "stages; %d op nodes, boundary groups: %s)"
            % (n_stages - 1, len(op_nodes),
               sorted((k[0], len(v)) for k, v in groups.items())))

    # even quantiles over the op order -> nearest candidate (distinct)
    cuts = []
    for k in range(1, n_stages):
        target = k * len(op_nodes) / n_stages
        best = min((c for c in cand if c not in cuts),
                   key=lambda c: abs(c - target), default=None)
        if best is None:
            raise MXNetError("not enough distinct cut candidates for %d "
                             "stages" % n_stages)
        cuts.append(best)
    cuts.sort()
    if len(set(cuts)) != len(cuts):
        raise MXNetError("cut positions collide; reduce n_stages")

    node_by_id = {id(n): n for n in topo}
    stage_syms = []
    prev_cut = -1
    in_entries = []        # boundary entries feeding the current stage
    for k in range(n_stages):
        end = cuts[k] if k < n_stages - 1 else len(op_nodes) - 1
        segment = op_nodes[prev_cut + 1:end + 1]
        bvars = {entry: _Node(None, "pipe_in%d" % i, {}, [])
                 for i, entry in enumerate(in_entries)}
        mapping = {}

        def remap(src, idx):
            if (id(src), idx) in bvars:
                return (bvars[(id(src), idx)], 0)
            if id(src) in mapping:
                return (mapping[id(src)], idx)
            if src.is_variable:
                return (src, idx)
            if replicable[id(src)]:
                # feed-local producer from an earlier segment: clone its
                # whole (parameter-free) subtree into this stage
                clone = _Node(src.op, src.name, src.attrs,
                              [remap(s, i) for (s, i) in src.inputs],
                              src.aux_slots)
                mapping[id(src)] = clone
                return (clone, idx)
            raise MXNetError(
                "pipeline cut is not closed: node %r (stage %d) consumes "
                "a non-boundary tensor from an earlier stage" %
                (src.name, k))

        for n in segment:
            clone = _Node(n.op, n.name, n.attrs,
                          [remap(s, i) for (s, i) in n.inputs],
                          n.aux_slots)
            mapping[id(n)] = clone

        if k < n_stages - 1:
            out_keys = lives[cuts[k]]
        else:
            out_keys = out_entries
        outs = []
        for (nid, idx) in out_keys:
            if nid in mapping:
                outs.append((mapping[nid], idx))
            elif (nid, idx) in bvars:     # pass-through tensor
                outs.append((bvars[(nid, idx)], 0))
            else:
                src = node_by_id[nid]
                if src.is_variable:
                    outs.append((src, idx))
                else:
                    raise MXNetError("internal: stage %d output %r not "
                                     "in segment" % (k, src.name))
        stage_syms.append(Symbol(outs))
        in_entries = out_keys if k < n_stages - 1 else []
        prev_cut = end

    # a parameter Variable consumed by more than one stage (weight
    # tying) would pack as independent per-stage copies with partial
    # gradients — silently wrong; refuse
    feed = set(data_names) | set(label_names)
    seen_params = {}
    for k, ssym in enumerate(stage_syms):
        for a in ssym.list_arguments():
            if a in feed or a.startswith("pipe_in"):
                continue
            if a in seen_params:
                raise MXNetError(
                    "parameter %r is shared between pipeline stages %d "
                    "and %d (weight tying); tied weights cannot shard "
                    "over stages — untie them or use the dense fused "
                    "step" % (a, seen_params[a], k))
            seen_params[a] = k
    return stage_syms


def _entry_shapes(sym, topo, known_shapes):
    """Shape of every (node, out_idx) entry, given input shapes (drives
    the shape-aware boundary signatures)."""
    from ..symbol.symbol import _abstract_eval, _infer_param_shapes

    var_shapes = _infer_param_shapes(sym, known_shapes)
    env = {}
    for n in topo:
        if n.is_variable:
            env[(id(n), 0)] = tuple(var_shapes.get(n.name, ()))
            continue
        in_shapes = [env[(id(s), i)] for (s, i) in n.inputs]
        for i, shp in enumerate(_abstract_eval(n, in_shapes)):
            env[(id(n), i)] = shp
    return env


# ---------------------------------------------------------------------------
# packed stage state + the 1F1B / GPipe training step
# ---------------------------------------------------------------------------

class _Packer:
    """Static flat-packing layout for a pytree of arrays.

    Each pipeline stage has a different parameter/optimizer-state pytree;
    packing every stage into one fp32 row of a shared ``(S, L)`` buffer
    is what lets heterogeneous stages shard over the 'pipe' mesh axis
    (each device holds exactly its stage's row).  Layout (offsets,
    shapes, dtypes) is static per stage, so unpacking inside a
    ``lax.switch`` branch is pure static slicing — XLA sees one fused
    program, no gathers."""

    def __init__(self, template):
        import jax
        import numpy as np

        leaves, self.treedef = jax.tree.flatten(template)
        self.shapes = [tuple(l.shape) for l in leaves]
        self.dtypes = [l.dtype for l in leaves]
        self.sizes = [int(np.prod(s)) if s else 1 for s in self.shapes]
        self.offsets = []
        off = 0
        for sz in self.sizes:
            self.offsets.append(off)
            off += sz
        self.total = off

    def pack(self, tree, length=None):
        """Concrete pytree -> fp32 row (padded to ``length``)."""
        import jax
        import jax.numpy as jnp

        leaves = jax.tree.leaves(tree)
        parts = [jnp.asarray(x).astype(jnp.float32).ravel()
                 for x in leaves]
        row = jnp.concatenate(parts) if parts else jnp.zeros((0,), "float32")
        length = length or self.total
        if row.shape[0] < length:
            row = jnp.pad(row, (0, length - row.shape[0]))
        return row

    def unpack(self, row):
        import jax
        import jax.numpy as jnp

        parts = []
        for shp, dt, off, sz in zip(self.shapes, self.dtypes,
                                    self.offsets, self.sizes):
            leaf = row[off:off + sz].reshape(shp).astype(dt)
            parts.append(leaf)
        return jax.tree.unflatten(self.treedef, parts)


class PipelineTrainStep:
    """Compiled pipelined train step: fwd + bwd + optimizer in ONE XLA
    program over the 'pipe' mesh axis, heterogeneous stages derived
    from a Symbol (``split_symbol``), parameters/optimizer states
    flat-packed and stage-sharded.

    ``schedule='1f1b'`` interleaves one-forward-one-backward with a
    bounded activation ring (stage inputs only; the stage forward is
    recomputed during its backward — remat); ``'gpipe'`` runs the
    all-forward wave over an M-slot stage-input stash, then an
    explicit validity-gated backward wave (same recompute strategy,
    O(M) stash instead of the O(S) ring).

    Call contract mirrors ``fused.TrainStep``:
    ``(params, aux, states, batch, rng, lr, t) -> (params, aux, states,
    outs)`` — but params/aux/states live INTERNALLY as packed
    stage-sharded buffers between steps; the dicts handed back are the
    same handles passed in (stale), and :meth:`unpack_params` /
    :meth:`unpack_aux` gather the live values for checkpointing/eval
    (``Module`` syncs lazily through them).

    Aux states (BatchNorm moving stats) thread through the schedule as a
    third stage-sharded packed buffer: each stage blends its own BN
    stats once per valid microbatch tick, so after one step the moving
    stats equal the sequential microbatch-loop semantics
    (``new = mom^M * old + (1-mom) * sum_m mom^(M-1-m) * stat_m``) —
    training-mode BN *reads* batch stats, never the aux buffer, so the
    1F1B recompute stays consistent no matter when the backward tick
    lands (reference aux-state semantics:
    ``src/operator/batch_norm.cc`` FMutateInputs).

    Rng ops (Dropout) draw a per-(stage, microbatch) key
    ``fold_in(fold_in(step_rng, m), k)``: the 1F1B backward recompute
    re-derives the same key from its tick index, so the recomputed
    dropout mask is bit-identical to the forward's.
    """

    def __init__(self, symbol, optimizer="sgd", optimizer_params=None,
                 mesh=None, n_microbatches=None,
                 data_names=("data",), label_names=("softmax_label",),
                 axis="pipe", schedule="1f1b", grad_scale=None,
                 fixed_param_names=(), plan=None):
        from .. import optimizer as opt_mod

        if plan is not None:
            # a composed ParallelPlan carries the pipe topology: the
            # mesh, the schedule and the microbatch count come from ONE
            # declaration (Module routes pipe>1 plans here)
            from .plan import ParallelPlan

            plan = ParallelPlan.parse(plan)
            if plan.pipe < 2:
                raise MXNetError(
                    "PipelineTrainStep got a plan without a >=2-stage "
                    "pipe axis: %r (use fused.TrainStep)" % (plan,))
            if mesh is None:
                mesh = plan.mesh()
            else:
                plan.validate_mesh(mesh)
            schedule = plan.schedule
            if n_microbatches is None:
                n_microbatches = plan.n_microbatches
        self.plan = plan
        mesh = mesh if mesh is not None else current_mesh()
        if mesh is None or axis not in mesh.shape:
            raise MXNetError(
                "PipelineTrainStep needs a mesh with a %r axis" % axis)
        if mesh.shape[axis] < 2:
            raise MXNetError("pipeline needs >= 2 stages")
        self.mesh = mesh
        self.axis = axis
        self.n_stages = mesh.shape[axis]
        if schedule not in ("1f1b", "gpipe"):
            raise MXNetError("schedule must be '1f1b' or 'gpipe', got %r"
                             % (schedule,))
        self.schedule = schedule
        self.symbol = symbol
        self.data_names = list(data_names)
        self.label_names = list(label_names)
        self.n_micro = n_microbatches or 2 * self.n_stages

        if isinstance(optimizer, str):
            optimizer = opt_mod.create(optimizer, **(optimizer_params or {}))
        if not optimizer.supports_fused:
            raise MXNetError("optimizer %s has no fused form"
                             % type(optimizer).__name__)
        self.optimizer = optimizer
        self.lr = optimizer.lr

        # the split itself is deferred to the first batch (_build) where
        # input shapes make the boundary signatures shape-aware
        feed_set = set(self.data_names) | set(self.label_names)
        self.param_names = [a for a in symbol.list_arguments()
                            if a not in feed_set]
        self.aux_names = list(symbol.list_auxiliary_states())
        self._frozen = frozenset(fixed_param_names)

        # default grad scale: per-microbatch losses sum over M; 'batch'-
        # normalized heads (grad ~ 1/mb per micro) need 1/M for parity
        # with the dense full-batch step
        if grad_scale is None:
            loss_heads = [n for n in symbol._topo() if not n.is_variable
                          and n.op.name in
                          ("SoftmaxOutput", "Softmax", "SVMOutput",
                           "LinearRegressionOutput",
                           "LogisticRegressionOutput",
                           "MAERegressionOutput")]
            batch_heads = [n for n in loss_heads
                           if n.attrs.get("normalization") == "batch"]
            sum_heads = [n for n in loss_heads if n not in batch_heads]
            if batch_heads and sum_heads:
                raise MXNetError(
                    "symbol mixes batch-normalized and sum-normalized "
                    "loss heads; one grad scale cannot match both under "
                    "microbatching — pass grad_scale explicitly")
            grad_scale = 1.0 / self.n_micro if batch_heads else 1.0
        self.grad_scale = float(grad_scale)

        self._built = None      # lazy: needs concrete batch shapes
        self._packed_params = None
        self._packed_states = None
        self._packed_aux = None
        self._t = 0

    # -- layout build (first call) ---------------------------------------
    def _build(self, batch):
        import jax
        import numpy as np

        from ..executor import _trace_fn
        from ..symbol.symbol import _infer_param_shapes

        S, M = self.n_stages, self.n_micro
        full_shapes = {k: tuple(v.shape) for k, v in batch.items()}
        nbatch = full_shapes[self.data_names[0]][0]
        if nbatch % M:
            raise MXNetError(
                "batch size %d not divisible by n_microbatches=%d"
                % (nbatch, M))
        mb = nbatch // M
        micro_shapes = {k: (mb,) + s[1:] for k, s in full_shapes.items()}
        micro_dtypes = {k: v.dtype for k, v in batch.items()}

        # shape-aware split: every boundary carries an identical buffer
        # set (the micro-batch shapes, not the full batch, cross hops)
        self._stage_syms = split_symbol(
            self.symbol, S, self.data_names, self.label_names,
            input_shapes=micro_shapes)
        self._stage_fns = []
        self._stage_args = []
        self._stage_param_names = []
        self._stage_aux_names = []
        feed_set = set(self.data_names) | set(self.label_names)
        for k, ssym in enumerate(self._stage_syms):
            fn, args, auxn = _trace_fn(ssym, is_train=True)
            self._stage_fns.append(fn)
            self._stage_args.append(args)
            self._stage_param_names.append(
                [a for a in args if a not in feed_set
                 and not a.startswith("pipe_in")])
            self._stage_aux_names.append(list(auxn))

        pshapes = _infer_param_shapes(self.symbol, dict(full_shapes))
        # microbatch-sized shape inference for the boundary templates
        param_tpls = []
        for pnames in self._stage_param_names:
            param_tpls.append({n: jax.ShapeDtypeStruct(pshapes[n],
                                                       np.float32)
                               for n in pnames})
        self._param_packers = [_Packer(t) for t in param_tpls]
        self._lp = max(max(p.total for p in self._param_packers), 1)

        state_tpls = []
        for tpl in param_tpls:
            state_tpls.append({
                n: jax.eval_shape(self.optimizer.init_fused_state,
                                  tpl[n])
                for n in tpl})
        self._state_packers = [_Packer(t) for t in state_tpls]
        self._ls = max(max(p.total for p in self._state_packers), 1)

        # per-stage aux states (BatchNorm moving stats) pack into a
        # third stage-sharded buffer; fp32, like the Module aux dicts
        aux_tpls = []
        for auxn in self._stage_aux_names:
            aux_tpls.append({n: jax.ShapeDtypeStruct(pshapes[n],
                                                     np.float32)
                             for n in auxn})
        self._aux_packers = [_Packer(t) for t in aux_tpls]
        self._la = max(max(p.total for p in self._aux_packers), 1)
        self._aux_tpls = aux_tpls

        # chain eval_shape through stages for boundary templates + the
        # canonical (shape-sorted) slot permutation per boundary
        rngspec = jax.ShapeDtypeStruct((2,), np.uint32)
        feed_spec = {k: jax.ShapeDtypeStruct(micro_shapes[k],
                                             micro_dtypes[k])
                     for k in micro_shapes}
        self._boundary_perm = []   # perm[i] = out position of slot i
        carry_tpl = None
        cur = None
        for k, (fn, args) in enumerate(zip(self._stage_fns,
                                           self._stage_args)):
            argspec = {}
            for a in args:
                if a.startswith("pipe_in"):
                    argspec[a] = cur[int(a[7:])]
                elif a in feed_spec:
                    argspec[a] = feed_spec[a]
                else:
                    argspec[a] = param_tpls[k][a]
            outs, _ = jax.eval_shape(
                lambda ar, ax: fn(ar, ax, jax.random.PRNGKey(0)),
                argspec, aux_tpls[k])
            cur = list(outs)
            if k < S - 1:
                order = sorted(
                    range(len(cur)),
                    key=lambda i: (str(cur[i].shape), str(cur[i].dtype),
                                   i))
                tpl = [jax.ShapeDtypeStruct(cur[i].shape, cur[i].dtype)
                       for i in order]
                if carry_tpl is None:
                    carry_tpl = tpl
                elif [(t.shape, t.dtype) for t in tpl] != \
                        [(t.shape, t.dtype) for t in carry_tpl]:
                    raise MXNetError(
                        "pipeline boundaries carry different tensor "
                        "sets (%r vs %r); choose a different n_stages"
                        % (tpl, carry_tpl))
                self._boundary_perm.append(order)
        self._carry_tpl = carry_tpl
        self._out_tpl = cur          # last stage outputs (per micro)
        self._micro_shapes = micro_shapes
        self._mb = mb
        self._full_shapes = full_shapes
        self._built = True
        self._jit_step = self._make_jit()

    # -- the compiled step -----------------------------------------------
    def _make_jit(self):
        import jax
        import jax.numpy as jnp
        from jax import lax
        from jax.sharding import NamedSharding, PartitionSpec as P

        S, M, axis = self.n_stages, self.n_micro, self.axis
        R = 2 * S
        mesh = self.mesh
        carry_tpl = self._carry_tpl
        out_tpl = self._out_tpl
        opt = self.optimizer
        lr_mults = {n: opt.lr_mult.get(n, 1.0) for n in self.param_names}
        wd_mults = {n: opt.wd_mult.get(n, 1.0) for n in self.param_names}
        base_wd = opt.wd
        gscale = self.grad_scale
        perm_f = [(i, (i + 1) % S) for i in range(S)]
        perm_b = [(i, (i - 1) % S) for i in range(S)]

        def zeros_carry():
            return tuple(jnp.zeros(t.shape, t.dtype) for t in carry_tpl)

        def zeros_emit():
            return tuple(jnp.zeros(t.shape, t.dtype) for t in out_tpl)

        la = self._la

        def stage_fwd(k):
            """fwd branch for stage k: (p_row, a_row, carry, feed, key)
            -> (carry_out, emits, new_a_row).  ``key`` is the
            per-microbatch key; the per-stage fold keeps rng streams of
            different stages independent."""
            fn = self._stage_fns[k]
            args_k = self._stage_args[k]
            packer = self._param_packers[k]
            apacker = self._aux_packers[k]
            in_perm = self._boundary_perm[k - 1] if k > 0 else None
            out_perm = self._boundary_perm[k] if k < S - 1 else None

            def branch(p_row, a_row, carry, feed, key):
                params = packer.unpack(p_row[:packer.total])
                aux = apacker.unpack(a_row[:apacker.total])
                ar = {}
                for a in args_k:
                    if a.startswith("pipe_in"):
                        want = int(a[7:])
                        # carry slot holding the boundary's out position
                        slot = in_perm.index(want)
                        ar[a] = carry[slot]
                    elif a in feed:
                        ar[a] = lax.stop_gradient(feed[a])
                    else:
                        ar[a] = params[a]
                outs, new_aux = fn(ar, aux, jax.random.fold_in(key, k))
                outs = list(outs)
                new_a_row = lax.stop_gradient(apacker.pack(new_aux, la))
                if k < S - 1:
                    carry_out = tuple(outs[i] for i in out_perm)
                    return carry_out, zeros_emit(), new_a_row
                return zeros_carry(), tuple(outs), new_a_row

            return branch

        fwd_branches = [stage_fwd(k) for k in range(S)]

        def stage_bwd(k):
            """bwd branch for stage k (recompute + vjp): (p_row, a_row,
            x, feed, g_in, key) -> (g_p_row, g_carry_out).  ``key`` is
            re-derived from the backward tick's microbatch index, so
            the recomputed rng ops (dropout masks) are bit-identical to
            the forward's; training-mode BN reads batch stats only, so
            the recompute is aux-timing independent."""
            branch_f = fwd_branches[k]

            def branch(p_row, a_row, x, feed, g_in, key):
                def f(pr, c):
                    c_out, emits, _na = branch_f(pr, a_row, c, feed, key)
                    return c_out, emits

                (c_out, emits), vjp_fn = jax.vjp(f, p_row, x)
                if k == S - 1:
                    cts = (zeros_carry(),
                           tuple(jnp.ones(t.shape, t.dtype)
                                 for t in out_tpl))
                else:
                    cts = (g_in, zeros_emit())
                g_pr, g_c = vjp_fn(cts)
                return g_pr, g_c

            return branch

        bwd_branches = [stage_bwd(k) for k in range(S)]

        def upd_branch(k):
            ppk = self._param_packers[k]
            spk = self._state_packers[k]
            names = self._stage_param_names[k]

            frozen = self._frozen

            def branch(p_row, s_row, g_row, lr, t, rng):
                params = ppk.unpack(p_row[:ppk.total])
                grads = ppk.unpack(g_row[:ppk.total])
                states = spk.unpack(s_row[:spk.total])
                new_p, new_s = {}, {}
                for i, n in enumerate(names):
                    if n in frozen:
                        new_p[n], new_s[n] = params[n], states[n]
                        continue
                    new_p[n], new_s[n] = opt.fused_update(
                        params[n], grads[n] * gscale, states[n],
                        lr * lr_mults[n], base_wd * wd_mults[n], t,
                        jax.random.fold_in(rng, k * 1000 + i))
                return (ppk.pack(new_p, self._lp),
                        spk.pack(new_s, self._ls))

            return branch

        upd_branches = [upd_branch(k) for k in range(S)]

        def feed_at(micro, m):
            m = jnp.clip(m, 0, M - 1)
            return {k: v[m] for k, v in micro.items()}

        def micro_key(rng, m):
            # per-microbatch key; fwd and bwd recompute derive the SAME
            # key from their own tick indices, keeping dropout masks
            # bit-identical across the 1F1B recompute
            return jax.random.fold_in(rng, jnp.clip(m, 0, M - 1))

        def body_1f1b(pp, ps, pa, micro, rng, lr, t):
            p_row = pp[0]
            s_row = ps[0]
            a_row = pa[0]
            sidx = lax.axis_index(axis)
            ring = tuple(jnp.zeros((R,) + tp.shape, tp.dtype)
                         for tp in carry_tpl)
            outs_buf = tuple(jnp.zeros((M,) + tp.shape, tp.dtype)
                             for tp in out_tpl)
            grad_acc = jnp.zeros_like(p_row)
            carry_f = zeros_carry()
            g_carry = zeros_carry()

            def tick(state, t_idx):
                carry_f, g_carry, ring, grad_acc, outs_buf, a_row = state
                m_f = t_idx - sidx
                valid_f = (m_f >= 0) & (m_f < M)
                feed_f = feed_at(micro, m_f)
                c_out, emits, a_new = lax.switch(
                    sidx, fwd_branches, p_row, a_row, carry_f, feed_f,
                    micro_key(rng, m_f))
                # BN moving stats blend once per VALID microbatch tick
                a_row = jnp.where(valid_f, a_new, a_row)
                slot_f = jnp.mod(m_f, R)
                ring = tuple(
                    lax.dynamic_update_index_in_dim(r, v, slot_f, 0)
                    for r, v in zip(ring, carry_f))
                emit_gate = jnp.where(valid_f & (sidx == S - 1), 1.0, 0.0)
                m_safe = jnp.clip(m_f, 0, M - 1)
                outs_buf = tuple(
                    lax.dynamic_update_index_in_dim(
                        b, jnp.where(emit_gate > 0, v,
                                     lax.dynamic_index_in_dim(
                                         b, m_safe, 0, keepdims=False)),
                        m_safe, 0)
                    for b, v in zip(outs_buf, emits))
                carry_next = tuple(lax.ppermute(v, axis, perm_f)
                                   for v in c_out)

                m_b = t_idx - 2 * (S - 1) + sidx
                valid_b = (m_b >= 0) & (m_b < M)
                slot_b = jnp.mod(m_b, R)
                x_b = tuple(lax.dynamic_index_in_dim(r, slot_b, 0,
                                                     keepdims=False)
                            for r in ring)
                feed_b = feed_at(micro, m_b)
                g_pr, g_c = lax.switch(sidx, bwd_branches, p_row, a_row,
                                       x_b, feed_b, g_carry,
                                       micro_key(rng, m_b))
                grad_acc = grad_acc + jnp.where(valid_b, 1.0, 0.0) * g_pr
                g_next = tuple(lax.ppermute(
                    jnp.where(valid_b, v, jnp.zeros_like(v)), axis,
                    perm_b) for v in g_c)
                return (carry_next, g_next, ring, grad_acc, outs_buf,
                        a_row), None

            ticks = jnp.arange(M + 2 * (S - 1))
            (carry_f, g_carry, ring, grad_acc, outs_buf, a_row), _ = \
                lax.scan(tick, (carry_f, g_carry, ring, grad_acc,
                                outs_buf, a_row), ticks)

            outs_rep = tuple(
                lax.psum(jnp.where(sidx == S - 1, b, jnp.zeros_like(b)),
                         axis) for b in outs_buf)
            new_p_row, new_s_row = lax.switch(
                sidx, upd_branches, p_row, s_row, grad_acc, lr, t, rng)
            return new_p_row[None], new_s_row[None], a_row[None], outs_rep

        def body_gpipe(pp, ps, pa, micro, rng, lr, t):
            # All-forward wave stashing every stage INPUT (M slots — the
            # GPipe memory profile), then an explicit all-backward wave
            # over the stash.  The backward is validity-GATED per tick:
            # differentiating the whole forward scan instead would let
            # the loss heads' custom vjp (which by the reference
            # contract ignores its cotangent) emit junk gradients for
            # the fill/drain ticks.
            p_row = pp[0]
            s_row = ps[0]
            a_row = pa[0]
            sidx = lax.axis_index(axis)
            stash = tuple(jnp.zeros((M,) + tp.shape, tp.dtype)
                          for tp in carry_tpl)
            outs_buf = tuple(jnp.zeros((M,) + tp.shape, tp.dtype)
                             for tp in out_tpl)
            carry_f = zeros_carry()

            def tick_f(state, t_idx):
                carry_f, stash, outs_buf, a_row = state
                m_f = t_idx - sidx
                valid_f = (m_f >= 0) & (m_f < M)
                m_safe = jnp.clip(m_f, 0, M - 1)
                feed_f = feed_at(micro, m_f)
                c_out, emits, a_new = lax.switch(
                    sidx, fwd_branches, p_row, a_row, carry_f,
                    feed_f, micro_key(rng, m_f))
                a_row = jnp.where(valid_f, a_new, a_row)
                stash = tuple(
                    lax.dynamic_update_index_in_dim(b, v, m_safe, 0)
                    for b, v in zip(stash, carry_f))
                emit_gate = valid_f & (sidx == S - 1)
                outs_buf = tuple(
                    lax.dynamic_update_index_in_dim(
                        b, jnp.where(emit_gate, v,
                                     lax.dynamic_index_in_dim(
                                         b, m_safe, 0, keepdims=False)),
                        m_safe, 0)
                    for b, v in zip(outs_buf, emits))
                carry_next = tuple(lax.ppermute(v, axis, perm_f)
                                   for v in c_out)
                return (carry_next, stash, outs_buf, a_row), None

            (carry_f, stash, outs_buf, a_row), _ = lax.scan(
                tick_f, (carry_f, stash, outs_buf, a_row),
                jnp.arange(M + S - 1))

            grad_acc = jnp.zeros_like(p_row)
            g_carry = zeros_carry()

            def tick_b(state, t_idx):
                g_carry, grad_acc = state
                m_b = t_idx - (S - 1 - sidx)
                valid_b = (m_b >= 0) & (m_b < M)
                m_safe = jnp.clip(m_b, 0, M - 1)
                x_b = tuple(lax.dynamic_index_in_dim(b, m_safe, 0,
                                                     keepdims=False)
                            for b in stash)
                feed_b = feed_at(micro, m_b)
                g_pr, g_c = lax.switch(sidx, bwd_branches, p_row, a_row,
                                       x_b, feed_b, g_carry,
                                       micro_key(rng, m_b))
                grad_acc = grad_acc + jnp.where(valid_b, 1.0, 0.0) * g_pr
                g_next = tuple(lax.ppermute(
                    jnp.where(valid_b, v, jnp.zeros_like(v)), axis,
                    perm_b) for v in g_c)
                return (g_next, grad_acc), None

            (g_carry, grad_acc), _ = lax.scan(
                tick_b, (g_carry, grad_acc), jnp.arange(M + S - 1))

            outs_rep = tuple(
                lax.psum(jnp.where(sidx == S - 1, b, jnp.zeros_like(b)),
                         axis) for b in outs_buf)
            new_p_row, new_s_row = lax.switch(
                sidx, upd_branches, p_row, s_row, grad_acc, lr, t, rng)
            return new_p_row[None], new_s_row[None], a_row[None], outs_rep

        body = body_1f1b if self.schedule == "1f1b" else body_gpipe
        pspec = P(axis)
        specs = dict(
            in_specs=(pspec, pspec, pspec, P(), P(), P(), P()),
            out_specs=(pspec, pspec, pspec, P()))
        fn = jax.shard_map(body, mesh=mesh, check_vma=False, **specs)
        row_sh = NamedSharding(mesh, P(axis))
        repl = NamedSharding(mesh, P())
        return jax.jit(
            fn,
            in_shardings=(row_sh, row_sh, row_sh, repl, repl, repl, repl),
            out_shardings=(row_sh, row_sh, row_sh, repl),
            donate_argnums=(0, 1, 2))

    # -- packing interface -----------------------------------------------
    def pack_params(self, params):
        """{name: array} -> stage-sharded (S, Lp) packed buffer."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        rows = []
        for k, pk in enumerate(self._param_packers):
            sub = {n: params[n] for n in self._stage_param_names[k]}
            rows.append(pk.pack(sub, self._lp))
        stacked = jnp.stack(rows)
        return jax.device_put(stacked,
                              NamedSharding(self.mesh, P(self.axis)))

    def pack_states(self, states):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        rows = []
        for k, pk in enumerate(self._state_packers):
            sub = {n: states[n] for n in self._stage_param_names[k]}
            rows.append(pk.pack(sub, self._ls))
        stacked = jnp.stack(rows)
        return jax.device_put(stacked,
                              NamedSharding(self.mesh, P(self.axis)))

    def pack_aux(self, aux):
        """{name: array} aux states -> stage-sharded (S, La) buffer."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        rows = []
        for k, pk in enumerate(self._aux_packers):
            sub = {n: aux[n] for n in self._stage_aux_names[k]}
            rows.append(pk.pack(sub, self._la))
        stacked = jnp.stack(rows)
        return jax.device_put(stacked,
                              NamedSharding(self.mesh, P(self.axis)))

    def unpack_params(self):
        """Gather the live packed parameters back to a {name: array}
        dict (replicated) — the checkpoint/eval sync point."""
        import numpy as np

        out = {}
        if self._packed_params is None:
            return out
        host = np.asarray(self._packed_params)
        for k, pk in enumerate(self._param_packers):
            sub = pk.unpack(host[k][:pk.total])
            out.update(sub)
        return out

    def unpack_states(self):
        import numpy as np

        out = {}
        if self._packed_states is None:
            return out
        host = np.asarray(self._packed_states)
        for k, pk in enumerate(self._state_packers):
            out.update(pk.unpack(host[k][:pk.total]))
        return out

    def unpack_aux(self):
        """Gather the live packed aux states (BN moving stats) back to
        a replicated {name: array} dict."""
        import numpy as np

        out = {}
        if getattr(self, "_packed_aux", None) is None:
            return out
        host = np.asarray(self._packed_aux)
        for k, pk in enumerate(self._aux_packers):
            out.update(pk.unpack(host[k][:pk.total]))
        return out

    # -- call -------------------------------------------------------------
    def __call__(self, params, aux, states, batch, rng, lr=None, t=None):
        import jax.numpy as jnp

        if t is None:
            self._t += 1
            t = self._t
        else:
            self._t = int(t)
        if self._built is None:
            self._build(batch)
        if self._packed_params is None:
            self._packed_params = self.pack_params(params)
            self._packed_states = self.pack_states(states)
            self._packed_aux = self.pack_aux(aux)
        micro = {}
        for k, v in batch.items():
            arr = jnp.asarray(v)
            micro[k] = arr.reshape((self.n_micro, self._mb)
                                   + tuple(arr.shape[1:]))
        (self._packed_params, self._packed_states, self._packed_aux,
         outs) = self._jit_step(
            self._packed_params, self._packed_states, self._packed_aux,
            micro, rng,
            jnp.asarray(self.lr if lr is None else lr, "float32"),
            jnp.asarray(t, "int32"))
        # un-microbatch the outputs: (M, mb, ...) -> (N, ...)
        flat_outs = tuple(
            o.reshape((o.shape[0] * o.shape[1],) + tuple(o.shape[2:]))
        if o.ndim >= 2 else o for o in outs)
        return params, aux, states, flat_outs

    def init_state(self, shapes, dtype="float32", seed=0):
        """Allocate params/aux/states directly (bench convenience;
        Module initializes through its own initializer path).  Returns
        ``(params, aux, states)`` — the same triple as
        ``fused.TrainStep.init_state``."""
        import jax
        import jax.numpy as jnp
        import numpy as np

        from ..symbol.symbol import _infer_param_shapes

        all_shapes = _infer_param_shapes(self.symbol, dict(shapes))
        key = jax.random.PRNGKey(seed)
        params, states = {}, {}
        for n in self.param_names:
            shp = all_shapes[n]
            key, sub = jax.random.split(key)
            if n.endswith("_gamma"):
                params[n] = jnp.ones(shp, dtype)
            elif n.endswith(("_bias", "_beta")):
                params[n] = jnp.zeros(shp, dtype)
            else:
                fan_in = int(np.prod(shp[1:])) if len(shp) > 1 else shp[0]
                scale = (2.0 / max(1, fan_in)) ** 0.5
                params[n] = scale * jax.random.normal(sub, shp, dtype)
            states[n] = self.optimizer.init_fused_state(params[n])
        aux = {}
        for n in self.aux_names:
            shp = all_shapes[n]
            aux[n] = jnp.ones(shp, "float32") if n.endswith("_var") \
                else jnp.zeros(shp, "float32")
        return params, aux, states
