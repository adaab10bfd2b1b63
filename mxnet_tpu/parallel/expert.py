"""Expert parallelism — mixture-of-experts FFN over the 'expert' axis.

Nothing to port (the reference predates MoE; SURVEY.md §2.3 lists EP as
a fresh first-class design).  Two forms:

* :func:`moe_ffn` — dense dispatch: expert weights sharded on their
  leading EXPERT axis, tokens replicated; each device runs ALL tokens
  through its experts and one ``psum`` combines.  Exact for any gating,
  simple, but the FLOPs are not top-k sparse — the correctness
  reference.
* :func:`routed_moe_ffn` — the first-class training form: tokens are
  sharded over the 'expert' axis, each token is routed to its top-k
  experts through capacity-bounded ``all_to_all`` dispatch/return hops
  riding ICI (the GShard/Switch design), per-device FFN compute is
  ``k/E``-sparse, and the Switch-style load-balancing auxiliary loss
  comes back with the output so the trainer can add it to the
  objective.
"""
from __future__ import annotations

import functools

from ..base import MXNetError
from ..compile_cache import track_lru
from .mesh import current_mesh

__all__ = ["moe_ffn", "routed_moe_ffn"]


def moe_ffn(x, gate_w, w1, w2, top_k=None, mesh=None, axis="expert"):
    """Mixture-of-experts feed-forward.

    ``x`` (B, D) tokens; ``gate_w`` (D, E); ``w1`` (E, D, H);
    ``w2`` (E, H, D) — w1/w2 sharded over ``axis``.  Gating is softmax
    over experts, optionally masked to the ``top_k`` largest (weights
    renormalized), and each expert runs relu(x@w1_e)@w2_e.
    Returns (B, D), replicated over the expert axis.
    """
    mesh = mesh or current_mesh()
    if mesh is None or axis not in mesh.shape:
        raise MXNetError("moe_ffn needs a mesh with a %r axis" % axis)
    n_exp = w1.shape[0]
    if gate_w.shape[1] != n_exp:
        raise MXNetError(
            "gate_w has %d expert columns but w1 has %d experts — a "
            "mismatch would silently drop/duplicate gate mass"
            % (gate_w.shape[1], n_exp))
    if n_exp % mesh.shape[axis] != 0:
        raise MXNetError("num experts %d not divisible by %s=%d"
                         % (n_exp, axis, mesh.shape[axis]))
    return _moe_fn(mesh, axis, top_k)(x, gate_w, w1, w2)


@track_lru("parallel._moe_fn")
@functools.lru_cache(maxsize=32)
def _moe_fn(mesh, axis, top_k):
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    n_dev = mesh.shape[axis]

    def body(x, gate_w, w1, w2):
        # w1/w2: local expert slices (E_local, D, H) / (E_local, H, D)
        e_local = w1.shape[0]
        rank = lax.axis_index(axis)
        logits = x @ gate_w                       # (B, E) replicated
        if top_k is not None:
            kth = lax.top_k(logits, top_k)[0][:, -1:]
            logits = jnp.where(logits >= kth, logits, -jnp.inf)
        probs = jax.nn.softmax(logits, axis=-1)   # renormalized over mask
        # this device's gate columns
        local_probs = lax.dynamic_slice_in_dim(
            probs, rank * e_local, e_local, axis=1)  # (B, E_local)
        h = jnp.einsum("bd,edh->ebh", x, w1)
        h = jnp.maximum(h, 0.0)
        y = jnp.einsum("ebh,ehd->ebd", h, w2)     # (E_local, B, D)
        out = jnp.einsum("ebd,be->bd", y, local_probs)
        return lax.psum(out, axis)

    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(P(), P(), P(axis), P(axis)),
                       out_specs=P(), check_vma=False)
    return jax.jit(fn)


# ---------------------------------------------------------------------------
# routed top-k MoE (GShard-style all-to-all dispatch)
# ---------------------------------------------------------------------------

def routed_moe_ffn(x, gate_w, w1, w2, top_k=2, capacity_factor=1.25,
                   mesh=None, axis="expert"):
    """Top-k routed mixture-of-experts feed-forward.

    ``x`` (B, D) tokens; ``gate_w`` (D, E); ``w1`` (E, D, H);
    ``w2`` (E, H, D).  Each token is dispatched to its ``top_k``
    highest-gate experts, subject to a per-expert capacity of
    ``ceil(capacity_factor * top_k * B_group / E)`` tokens per source
    group (overflow tokens are dropped from that expert, the standard
    capacity contract).  Combine weights are the softmax of the top-k
    masked gate logits, so with ample capacity the result equals the
    dense :func:`moe_ffn` with the same ``top_k``.

    Under a mesh with an ``axis`` ('expert') dimension, tokens shard
    over the axis, expert weights shard on their leading expert dim, and
    two ``lax.all_to_all`` hops carry tokens to their experts and back —
    per-device FFN compute is ``k/E``-sparse, unlike the dense form.
    With ``mesh=None`` (and no active mesh) the identical math runs on
    one device.

    Returns ``(y, aux_loss)``: ``y`` (B, D) and the scalar Switch-style
    load-balancing loss ``E * sum_e(f_e * P_e)`` (1.0 at perfect
    balance), which the caller scales and adds to the objective.

    ``mesh=None`` auto-discovers the active mesh (like
    :func:`moe_ffn`); pass ``mesh=False`` to force the single-device
    path even under an active mesh.
    """
    if mesh is False:
        mesh = None
    elif mesh is None:
        mesh = current_mesh()
    if mesh is not None and axis not in mesh.shape:
        mesh = None
    n_exp = w1.shape[0]
    if gate_w.shape[1] != n_exp:
        raise MXNetError(
            "gate_w has %d expert columns but w1 has %d experts"
            % (gate_w.shape[1], n_exp))
    if mesh is not None:
        n_dev = mesh.shape[axis]
        if n_exp % n_dev != 0:
            raise MXNetError("num experts %d not divisible by %s=%d"
                             % (n_exp, axis, n_dev))
        if x.shape[0] % n_dev != 0:
            raise MXNetError(
                "token count %d not divisible by %s=%d (tokens shard "
                "over the expert axis)" % (x.shape[0], axis, n_dev))
        b_group = x.shape[0] // n_dev
    else:
        n_dev = 1
        b_group = x.shape[0]
    import math

    capacity = max(1, math.ceil(capacity_factor * top_k * b_group
                                / n_exp))
    if top_k > n_exp:
        raise MXNetError("top_k=%d exceeds num experts %d"
                         % (top_k, n_exp))
    if mesh is None:
        return _routed_local_fn(int(top_k), capacity)(x, gate_w, w1, w2)
    return _routed_fn(mesh, axis, int(top_k), capacity)(x, gate_w, w1, w2)


def _routed_body(x, gate_w, w1_local, w2_local, top_k, capacity, n_dev,
                 axis):
    """The dispatch→expert→combine math for one token group.

    ``w1_local``/``w2_local`` hold this group's ``E_local = E/n_dev``
    experts; with ``axis`` set, two ``all_to_all`` hops exchange the
    capacity-bounded per-expert buffers between groups.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    e_local = w1_local.shape[0]
    n_exp = e_local * n_dev
    b, d = x.shape

    logits = (x @ gate_w).astype(jnp.float32)          # (B, E)
    _, top_idx = lax.top_k(logits, top_k)              # (B, k)
    sel = jax.nn.one_hot(top_idx, n_exp, dtype=logits.dtype)  # (B, k, E)
    mask = sel.sum(axis=1)                             # (B, E) 0/1
    masked = jnp.where(mask > 0, logits, -jnp.inf)
    probs = jax.nn.softmax(masked, axis=-1)            # combine weights

    # Switch-style load balance: f_e = dispatch fraction, P_e = mean
    # full-softmax router prob; globally averaged when sharded
    full_probs = jax.nn.softmax(logits, axis=-1)
    f_e = mask.sum(axis=0) / (b * top_k)       # dispatch fraction, sums to 1
    p_e = full_probs.mean(axis=0)
    if axis is not None:
        f_e = lax.pmean(f_e, axis)
        p_e = lax.pmean(p_e, axis)
    aux = n_exp * jnp.sum(f_e * p_e)

    # position of each (token, choice) inside its expert's buffer;
    # entries past capacity get an all-zero one-hot row (dropped)
    flat_sel = sel.reshape(b * top_k, n_exp).astype(jnp.int32)
    pos = jnp.cumsum(flat_sel, axis=0) - flat_sel
    my_pos = (pos * flat_sel).sum(-1).reshape(b, top_k)     # (B, k)
    pos_oh = jax.nn.one_hot(my_pos, capacity, dtype=x.dtype)
    dm = jnp.einsum("bke,bkc->bec", sel.astype(x.dtype), pos_oh)

    expert_in = jnp.einsum("bec,bd->ecd", dm, x)       # (E, C, D)
    if axis is not None:
        buf = expert_in.reshape(n_dev, e_local, capacity, d)
        recv = lax.all_to_all(buf, axis, 0, 0)         # (n_dev, E_l, C, D)
        xin = recv.transpose(1, 0, 2, 3).reshape(
            e_local, n_dev * capacity, d)
    else:
        xin = expert_in                                # (E, C, D)

    h = jnp.maximum(jnp.einsum("ecd,edh->ech", xin, w1_local), 0.0)
    y = jnp.einsum("ech,ehd->ecd", h, w2_local)

    if axis is not None:
        yb = y.reshape(e_local, n_dev, capacity, d).transpose(1, 0, 2, 3)
        back = lax.all_to_all(yb, axis, 0, 0)          # (n_dev, E_l, C, D)
        ye = back.reshape(n_exp, capacity, d)
    else:
        ye = y
    out = jnp.einsum("bec,ecd->bd",
                     dm * probs.astype(x.dtype)[..., None], ye)
    return out, aux.astype(jnp.float32)


@track_lru("parallel._routed_local_fn")
@functools.lru_cache(maxsize=32)
def _routed_local_fn(top_k, capacity):
    import jax

    def fn(x, gate_w, w1, w2):
        return _routed_body(x, gate_w, w1, w2, top_k, capacity, 1, None)

    return jax.jit(fn)


@track_lru("parallel._routed_fn")
@functools.lru_cache(maxsize=32)
def _routed_fn(mesh, axis, top_k, capacity):
    import jax
    from jax.sharding import PartitionSpec as P

    n_dev = mesh.shape[axis]

    def body(x, gate_w, w1, w2):
        out, aux = _routed_body(x, gate_w, w1, w2, top_k, capacity,
                                n_dev, axis)
        return out, aux

    specs = dict(in_specs=(P(axis), P(), P(axis), P(axis)),
                 out_specs=(P(axis), P()))
    fn = jax.shard_map(body, mesh=mesh, check_vma=False, **specs)
    return jax.jit(fn)
