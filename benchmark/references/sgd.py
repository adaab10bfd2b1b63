"""Plain reference: SGD with momentum as MXNet defines it, and the
numbers the correctness check compares.

    mom <- momentum * mom - lr * (scale * grad + wd * w);   w <- w + mom

(Sutskever et al. 2013 in the form of MXNet's ``sgd_mom_update``).  Weight
decay applies to parameters whose name ends in ``_weight`` or ``_gamma``,
MXNet's default.  Imports nothing from the program under test.
"""
import jax
import jax.numpy as jnp


def decays(name):
    return name.endswith("_weight") or name.endswith("_gamma")


def leaf_norms(tree):
    """{name: float l2 norm}, computed on the device in one call."""
    norms = jax.jit(lambda t: {k: jnp.sqrt(jnp.sum(jnp.square(
        v.astype(jnp.float32)))) for k, v in t.items()})(tree)
    return {k: float(v) for k, v in norms.items()}


def follow(loss_and_grads, params, aux, batches, lr, momentum, wd,
           grad_scale):
    """Take ``len(batches)`` steps from ``params``.

    ``loss_and_grads(params, aux, data, label)`` returns the mean loss,
    its gradient and the new auxiliary state.  Returns the loss of every
    step, the per-leaf norm of the first gradient as the optimizer gets
    it (``grad_scale * grad + wd * w``) and the per-leaf norm of the
    parameters' change over all the steps."""

    def update(p, g, m, name):
        eff = grad_scale * g + (wd * p if decays(name) else 0.0)
        m = momentum * m - lr * eff
        return p + m, m, jnp.sqrt(jnp.sum(jnp.square(eff)))

    step = jax.jit(lambda p, g, m: {
        k: update(p[k], g[k], m[k], k) for k in p}, donate_argnums=(1, 2))
    start = params
    mom = jax.jit(lambda p: jax.tree.map(jnp.zeros_like, p))(params)
    losses, grad_norms = [], None
    for data, label in batches:
        loss, grads, aux = loss_and_grads(params, aux, data, label)
        losses.append(float(loss))
        out = step(params, grads, mom)
        params = {k: v[0] for k, v in out.items()}
        mom = {k: v[1] for k, v in out.items()}
        if grad_norms is None:
            grad_norms = {k: float(v[2]) for k, v in out.items()}
    delta = leaf_norms(jax.jit(lambda a, b: jax.tree.map(
        jnp.subtract, a, b))(params, start))
    return losses, grad_norms, delta
