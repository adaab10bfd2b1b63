"""Seeded weights, made on the device in one jitted call.

Both sides of the correctness check get their weights from here, so the
plain reference takes nothing the program has made.  The rule is by name:
``*_gamma`` and ``*_moving_var`` are ones; ``*_beta``, ``*_bias`` and
``*_moving_mean`` zeros; everything else is normal with the
configuration's ``init_std`` (GPT-2's 0.02 for the language models) or,
where it has none, He et al.'s sqrt(2 / fan_in).
"""
import math
import zlib

import jax
import jax.numpy as jnp

ONES = ("_gamma", "_moving_var")
ZEROS = ("_beta", "_bias", "_moving_mean")


def seed_words(seed):
    """A seed of any size as two 31-bit words (int32 holds each)."""
    seed = int(seed)
    if seed < 0:
        raise ValueError("--seed must not be negative: %d" % seed)
    return jnp.asarray([seed & 0x7FFFFFFF, (seed >> 31) & 0x7FFFFFFF],
                       jnp.int32)


def _leaf(key, name, shape, init_std, dtype):
    """One parameter; its stream is folded from its NAME, so a leaf is
    the same whatever else is made beside it."""
    if name.endswith(ONES):
        return jnp.ones(shape, dtype)
    if name.endswith(ZEROS):
        return jnp.zeros(shape, dtype)
    std = init_std or math.sqrt(2.0 / math.prod(shape[1:]))
    return (std * jax.random.normal(jax.random.fold_in(
        key, zlib.crc32(name.encode()) & 0x7FFFFFFF), shape,
                                    jnp.float32)).astype(dtype)


def maker(spec, init_std=None, dtype=jnp.float32):
    """-> jitted f(seed_words) = {name: array} for ``spec`` ({name:
    shape}).  The seed is an argument of the compiled program, so one
    compilation serves every seed."""
    def make(words):
        key = jax.random.fold_in(jax.random.PRNGKey(words[0]), words[1])
        return {n: _leaf(key, n, tuple(shape), init_std, dtype)
                for n, shape in spec.items()}

    return jax.jit(make)


def change_norms(spec, init_std=None):
    """-> jitted f(seed_words, params) = {name: ||params[name] - its
    seeded start||}; the start is made again inside the call, leaf by
    leaf, so no second copy of the weights is ever held."""
    def norms(words, params):
        key = jax.random.fold_in(jax.random.PRNGKey(words[0]), words[1])
        return {n: jnp.sqrt(jnp.sum(jnp.square(
            params[n].astype(jnp.float32)
            - _leaf(key, n, tuple(shape), init_std, jnp.float32))))
            for n, shape in spec.items()}

    return jax.jit(norms)
