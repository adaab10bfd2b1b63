"""The gated delta rule with one decay a head (``ops/gdn.py``): its two
forms against the definition, token by token, which is the reference's
(``benchmark/references/qwen3_next_lm.py``: nothing of the program's)."""
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu.ops import gdn, kda

_REF = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "benchmark", "references", "qwen3_next_lm.py")
_spec = importlib.util.spec_from_file_location("qwen3_next_lm_reference",
                                               _REF)
reference = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(reference)


def _layer_inputs(seed, t, heads=3, width=16, low=-3.0):
    """q, k at their lengths, v, log-decays between ``low`` and 0, rates,
    and a non-zero entering state."""
    rs = np.random.RandomState(seed)
    f = lambda *shape: jnp.asarray(rs.randn(*shape).astype(np.float32))
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    return (unit(f(t, heads, width)) * width ** -0.5,
            unit(f(t, heads, width)), f(t, heads, width),
            low * jax.nn.sigmoid(3.0 * f(t, heads)),
            jax.nn.sigmoid(f(t, heads)), f(heads, width, width))


def _near(got, want, what, rel=2e-5):
    scale = float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(got - want))) <= rel * scale, what


@pytest.mark.parametrize("rows, chunk, real", [
    (24, 8, 24),     # three whole chunks: two boundaries crossed
    (24, 8, 13),     # bucket padding from the middle of the second chunk
    (21, 8, 21),     # not whole chunks: the form pads with identities
    (192, 64, 150),  # the served chunk, padding from its third chunk on
    (16, 64, 9)])    # one chunk wider than the bucket
def test_chunked_form_is_the_recurrence(rows, chunk, real):
    """Across chunk boundaries, from a non-zero carried state, with
    padded rows: outputs of the real rows and the state after the last
    real row are the token-by-token definition's (the reference's scan)."""
    q, k, v, g, beta, state0 = _layer_inputs(rows + chunk, rows)
    pad = (jnp.arange(rows) < real)[:, None]
    g, beta = jnp.where(pad, g, 0.0), jnp.where(pad, beta, 0.0)
    o, state = gdn.gdn_chunked(q, k, v, g, beta, state0, chunk)
    want_o, want_state = reference.gdn_recurrence(
        q[:real], k[:real], v[:real], jnp.exp(g[:real]), beta[:real], state0)
    _near(o[:real], want_o, "outputs")
    _near(state, want_state, "the state after the last real row")
    # the carried state matters: from zero the same rows read otherwise
    cold, _ = gdn.gdn_chunked(q, k, v, g, beta, 0 * state0, chunk)
    assert float(jnp.max(jnp.abs(cold[:real] - want_o))) > 1e-2


@pytest.mark.parametrize("low", [-5.0, -40.0])
def test_a_chunk_of_64_holds_at_any_log_decay(low):
    """Log-decays down to -40 a token at the published chunk of 64, where
    ``ops/kda.py``'s factored form refuses a chunk over 32 rows at -5: no
    exponent here is positive, so everything is finite and still the
    definition's to rounding.  Half the heads sit at the floor, where a
    token forgets the state whole."""
    q, k, v, g, beta, state0 = _layer_inputs(7, 128, heads=4, low=low)
    g = g.at[:, ::2].set(low)
    o, state = gdn.gdn_chunked(q, k, v, g, beta, state0, 64)
    want_o, want_state = reference.gdn_recurrence(q, k, v, jnp.exp(g), beta,
                                                  state0)
    assert bool(jnp.isfinite(o).all()) and bool(jnp.isfinite(state).all())
    _near(o, want_o, "outputs")
    _near(state, want_state, "state")


def test_one_step_is_the_definition():
    q, k, v, g, beta, state0 = _layer_inputs(5, 6)
    want_o, want_state = reference.gdn_recurrence(
        q, k, v, jnp.exp(g), beta, state0)
    state, outs = state0, []
    for i in range(6):
        o, state = gdn.gdn_step(q[i:i + 1], k[i:i + 1], v[i:i + 1],
                                g[i:i + 1], beta[i:i + 1], state[None])
        outs.append(o[0])
        state = state[0]
    _near(jnp.stack(outs), want_o, "outputs")
    _near(state, want_state, "state")


def test_the_step_is_kdas_with_the_decay_broadcast_over_a_heads_channels():
    """One decay a head is a decay a channel with every channel the same:
    ``kda_step`` over the broadcast decay is the scalar-decay step bit for
    bit (the same sums in the same order), so ling's decode step and this
    one are one function."""
    q, k, v, g, beta, state0 = _layer_inputs(11, 4)
    state = jnp.broadcast_to(state0, (4,) + state0.shape)
    want_o, want_state = kda.kda_step(
        q, k, v, jnp.broadcast_to(g[..., None], q.shape), beta, state)
    o, new = gdn.gdn_step(q, k, v, g, beta, state)
    np.testing.assert_array_equal(np.asarray(o), np.asarray(want_o))
    np.testing.assert_array_equal(np.asarray(new), np.asarray(want_state))


def test_chunked_form_loops_over_chunks_not_tokens():
    """Prefill's form is matmul-shaped: the one sequential pass is over
    the chunks (``lax.scan`` of length rows / chunk); what else loops is
    the triangular solve's own blocks, never the rows."""
    args = _layer_inputs(0, 256)

    def loops(jaxpr):
        found = []
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "scan":
                found.append(eqn.params.get("length"))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                found += loops(sub)
        return found

    jaxpr = jax.make_jaxpr(lambda *a: gdn.gdn_chunked(*a, chunk=64))(*args)
    assert loops(jaxpr.jaxpr) == [4]
