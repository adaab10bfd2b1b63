"""Functional transformer decoder for the serving runtime.

The training side runs the symbolic graph (``models/transformer.py`` →
``ops/nn_ops.py``); serving needs the same network as a *pure function*
it can specialize three ways — full-context reference forward, bucketed
prefill (full forward + KV page writes), and the O(1) single-token
decode step — over one parameter dict.  This module is that function,
written against the exact op semantics of the training kernels
(``FullyConnected``'s ``(out, in)`` weight layout, ``LayerNorm`` at
eps 1e-5 in rsqrt form, the MHA in/out projection einsums, head split
``(n, t, h, d) -> (n, h, t, d)``, ``jax.nn.gelu``) and parameterized by
the training graph's own parameter names (``tok_embed_weight``,
``blk{i}_attn_in_weight`` …), so a ``CheckpointManager`` restore of a
training run drops straight in.

Bit-exactness contract (the serving acceptance criterion): with
``exact=True`` every matmul uses the M-invariant broadcast-multiply-
reduce form and attention runs the ``mi=True`` flash/decode kernels, so
a token decoded through the paged KV cache is bit-identical to the same
position of a full-context forward.  XLA's gemm accumulation order
depends on the M dimension (a 1-row projection differs from row T of a
T-row projection by ~1 ulp), which is why plain einsums cannot make
that guarantee; ``exact=False`` restores them for production serving
where ulp-level drift is acceptable and gemm throughput matters.
``MXNET_SERVE_EXACT`` picks the default.

The oracle for that contract is :func:`reference_last_logits`: a jitted
full-context forward padded to the next ``page_size`` multiple, so the
reference runs the *same attention-block geometry* as the serving
executables (whole-program XLA fusion is itself shape-dependent — an
unpadded T=9 forward and a padded T=16 one differ by ~1 ulp at some
widths, so the reference must share the padded shape family; causal
masking makes the pad positions exact no-ops).
"""
from __future__ import annotations

import contextlib
import contextvars
import dataclasses
import functools
import importlib

from ..base import MXNetError, get_env
from ..ops.attention import (decode_attention, flash_attention,
                             paged_decode_attention, paged_prefill_attention)
from .kv_cache import append_rows, read_context, read_ring

__all__ = ["ModelConfig", "BLOCKS", "block_of", "exact_mode", "init_params",
           "config_from_params", "full_forward", "prefill_forward",
           "decode_step", "verify_step", "draft_propose",
           "reference_last_logits"]

# ``ModelConfig.block`` -> the module of this package that provides the
# block.  Every such module has the same surface (docs/serving.md, "Adding
# a block", names it; tests/test_serve_blocks.py holds the modules to
# it).  This module is the GPT-2 block.
BLOCKS = {"gpt2": "model", "deepseek_v3": "latent_moe",
          "granitemoehybrid": "granite_hybrid", "laguna": "laguna",
          "bailing_hybrid": "bailing_hybrid", "lfm2_moe": "lfm2_moe",
          "sdar_moe": "sdar_moe", "phi4flash": "phi4flash",
          "qwen3_next": "qwen3_next"}


def block_of(cfg):
    """The module that provides ``cfg.block``, imported on first use."""
    mod = BLOCKS.get(cfg.block)
    if mod is None:
        raise MXNetError("unknown block %r (%s)"
                         % (cfg.block, " or ".join(BLOCKS)))
    if isinstance(mod, str):
        mod = BLOCKS[cfg.block] = importlib.import_module("." + mod,
                                                          __package__)
    return mod


def exact_mode():
    """Default for the ``exact`` knob (``MXNET_SERVE_EXACT``, default 1):
    bit-exact M-invariant matmuls vs plain gemms."""
    return get_env("MXNET_SERVE_EXACT", True, bool)


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    """Static decoder geometry (everything the traced functions close
    over).

    A layer's kind is stated once, here: ``layer_types`` names every
    layer in the block's own words and :attr:`kinds` maps them to the
    cache's (``"full"`` owns pages, ``"window"`` a ring, ``"ssm"`` only
    slot-private state, ``"shared"`` nothing: it reads what another layer
    owns).  No ``ServeConfig`` field and no environment variable says
    what a layer is.

    ``block`` names the architecture, a key of :data:`BLOCKS`.  ``"gpt2"``
    (learned positions, LayerNorm, GELU, biased fused-QKV heads) is what
    :func:`config_from_params` infers from a parameter dict, every layer
    full attention; a stack with sliding-window layers states
    ``layer_types`` of ``"full_attention"`` | ``"sliding_attention"`` and
    ``sliding_window`` (ring-buffered K/V, the last ``sliding_window``
    keys; every layer keeps the ``attn_in`` / ``attn_out`` weights, so any
    such checkpoint hosts any such stack);
    ``"deepseek_v3"`` (``latent_moe.py``: RMSNorm, RoPE, latent
    attention, SwiGLU, routed and shared experts, no bias, no position
    table) cannot be inferred from shapes, so it is stated: the fields
    after ``block`` are its head split, its latent rank, its norms and
    RoPE, and its expert layer, under the names of the published
    ``config.json`` where the repo had none.  ``"granitemoehybrid"``
    (``granite_hybrid.py``: Mamba-2 and grouped-query attention layers in
    the published ``layer_types`` order, no positions, a shared SwiGLU of
    ``d_ff``, four multipliers, a tied head) is stated the same way, by
    the group of fields from ``num_key_value_heads`` on.
    ``"bailing_hybrid"`` (``bailing_hybrid.py``: KDA linear-attention and
    gated latent-attention layers, a dense SwiGLU then group-limited
    routed experts of which this chip may hold a share) takes the latent
    block's fields, ``layer_types`` of ``"kda"`` | ``"mla"`` (the tuple
    states the layers kept) and the group from ``n_group`` on: the
    router's group limit, the range of experts held here, and the KDA
    mixer's heads, head width, taps, gate bound and prefill chunk.
    ``"laguna"`` (``laguna.py``: sliding-window and full grouped-query
    attention layers, a query-head count a layer, two rotary embeddings,
    a sigmoid gate a head, softmax-routed experts of which this chip may
    hold a share) takes the expert fields, ``num_key_value_heads``,
    ``layer_types`` of ``"full_attention"`` | ``"sliding_attention"`` and
    the group from ``attn_head_dim`` on (``rope_parameters``: a mapping a
    kind of layer, kept as sorted tuples, which hash).  ``"lfm2_moe"``
    (``lfm2_moe.py``: gated short convolutions, QK-normed grouped-query
    attention layers, sigmoid-routed experts with a selection bias, a
    tied head) takes the same fields, ``layer_types`` of ``"conv"`` |
    ``"full_attention"`` and the convolution's taps, ``conv_L_cache``.
    ``"sdar_moe"`` (``sdar_moe.py``: generation by diffusion over blocks of
    ``block_length`` tokens, QK-normed grouped-query attention that sees
    both ways inside a block, softmax-routed experts, an untied head)
    takes the expert fields, ``num_key_value_heads``, ``attn_head_dim`` and
    the last group: the block's length, the mask token, and the passes and
    the confidence threshold of its unmasking.  ``"phi4flash"``
    (``phi4flash.py``: Mamba-1 and window differential attention in the
    first half, one full-attention layer whose pages every later
    cross-attention layer reads, gated memory units on one Mamba layer's
    scan output, LayerNorm, a tied head, no positions) counts
    DIFFERENTIAL heads, pairs of the published ones: ``num_heads`` 20 of
    2 x 64 over ``num_key_value_heads`` 10; ``layer_types`` of ``"mamba"``
    | ``"sliding_attention"`` | ``"full_attention"`` | ``"gmu"`` |
    ``"cross_attention"``, ``sliding_window``, ``d_ff``, ``mamba_d_state``,
    ``mamba_d_conv`` and the three fields from ``mamba_expand`` on.
    ``"qwen3_next"`` (``qwen3_next.py``: Gated DeltaNet layers with one
    decay a head, gated grouped-query attention with a zero-centred norm a
    query and key head and a partial rotation, softmax-routed experts of
    which this chip may hold a share beside a shared expert behind a
    sigmoid gate, an untied head) takes the expert fields (softmax
    scores, ``shared_expert_gate``), ``num_key_value_heads``,
    ``attn_head_dim``, ``partial_rotary_factor``, ``layer_types`` of
    ``"linear_attention"`` | ``"full_attention"`` and the ``linear_*``
    group with ``gdn_chunk_size``.
    """
    vocab_size: int
    num_layers: int
    d_model: int
    num_heads: int
    max_len: int          # the context ceiling (gpt2: pos_embed rows)
    block: str = "gpt2"
    qk_nope_head_dim: int = 0   # per-head query/key width without RoPE
    qk_rope_head_dim: int = 0   # rotated width; one shared key a token
    v_head_dim: int = 0
    kv_lora_rank: int = 0   # the latent c's width (+ the rope part, cached)
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-6
    d_ff: int = 0               # the dense layers' SwiGLU width
    first_k_dense: int = 0      # leading layers with a dense FFN
    moe_d_ff: int = 0           # one expert's SwiGLU width
    n_routed_experts: int = 0
    num_experts_per_tok: int = 0
    n_shared_experts: int = 0   # one SwiGLU of n_shared * moe_d_ff
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True
    num_key_value_heads: int = 0    # 0: as many as query heads
    layer_types: tuple = ()     # per layer "mamba" | "attention", or
    #                             "kda" | "mla" (bailing_hybrid), or
    #                             "full_attention" | "sliding_attention"
    #                             (laguna, gpt2; gpt2: () = all full), or
    mamba_n_heads: int = 0      # "conv" | "full_attention" (lfm2_moe)
    mamba_d_head: int = 0
    mamba_d_state: int = 0
    mamba_n_groups: int = 1     # heads that share one B and one C
    mamba_d_conv: int = 4       # taps of the causal depthwise convolution
    mamba_chunk_size: int = 256     # rows a chunk of the prefill scan
    embedding_multiplier: float = 1.0
    attention_multiplier: float = 0.0   # the score scale, stated
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    tie_word_embeddings: bool = False
    n_group: int = 1            # the router's experts, in equal groups
    topk_group: int = 1         # groups a token may choose experts from
    experts_held: tuple = ()    # (first, count): the routed experts this
    #                             chip holds of n_routed_experts; () = all
    kda_n_heads: int = 0        # 0: as many as query heads
    kda_head_dim: int = 0       # key and value width of a KDA head
    kda_d_conv: int = 4         # taps of the short causal convolution
    kda_lower_bound: float = -5.0   # the safe gate: log-decay a token in
    #                                 (kda_lower_bound, 0)
    kda_chunk_size: int = 32    # rows a chunk of the prefill form
    attn_head_dim: int = 0      # a head's width, stated (the published
    #                             head_dim); 0: d_model // num_heads
    num_attention_heads_per_layer: tuple = ()   # (): num_heads in each
    sliding_window: int = 0     # keys a "sliding_attention" layer's query
    #                             sees, itself included
    rope_parameters: tuple = ()  # {layer type: {rope_theta, rope_type,
    #                              partial_rotary_factor, YaRN's keys}}
    mlp_only_layers: tuple = ()  # the layers with a dense FFN: leading ones
    shared_expert_intermediate_size: int = 0    # one shared SwiGLU's width
    scoring_func: str = "sigmoid"   # the router's scores: "sigmoid" (+ a
    #                                 selection bias) | "softmax"
    conv_L_cache: int = 0       # taps of an lfm2_moe short convolution
    block_length: int = 0       # tokens a block of an sdar_moe generation
    mask_token_id: int = -1     # what a row not yet unmasked holds
    denoising_steps: int = 0    # denoise passes a block takes at most
    confidence_threshold: float = 0.0   # a masked row over it is unmasked
    mamba_expand: int = 2       # a Mamba-1 mixer's d_inner over d_model
    mamba_dt_rank: int = 0      # the rank of its step's projection; 0:
    #                             ceil(d_model / 16)
    layer_norm_eps: float = 1e-5    # of a LayerNorm block's norms
    partial_rotary_factor: float = 1.0  # the share of a head's values that
    #                                     a qwen3_next layer rotates
    shared_expert_gate: bool = False    # the shared expert's result times
    #                                     sigmoid(w_s . u)
    linear_num_key_heads: int = 0       # a Gated DeltaNet mixer's query /
    linear_num_value_heads: int = 0     # key heads and its value heads,
    linear_key_head_dim: int = 0        # their widths,
    linear_value_head_dim: int = 0
    linear_conv_kernel_dim: int = 4     # the taps of its convolution
    gdn_chunk_size: int = 64            # and the rows a chunk of its prefill

    def __post_init__(self):
        if isinstance(self.rope_parameters, dict):
            object.__setattr__(self, "rope_parameters", tuple(sorted(
                (kind, tuple(sorted(group.items())))
                for kind, group in self.rope_parameters.items())))

    @property
    def head_dim(self):
        return self.attn_head_dim or self.d_model // self.num_heads

    @property
    def kv_heads(self):
        return self.num_key_value_heads or self.num_heads

    @property
    def kinds(self):
        """Per-layer kinds in the cache's words, ``num_layers`` entries:
        ``"full"`` owns pages, ``"window"`` a ring, ``"ssm"`` nothing but
        the slot-private state its block's ``state_shapes`` names,
        ``"shared"`` nothing at all (a gated memory unit reads a scan's
        output of the same step, a cross-attention layer another layer's
        pages)."""
        if self.layer_types:
            return tuple({"attention": "full", "mamba": "ssm", "mla": "full",
                          "kda": "ssm", "conv": "ssm",
                          "linear_attention": "ssm",
                          "full_attention": "full",
                          "sliding_attention": "window",
                          "gmu": "shared",
                          "cross_attention": "shared"}.get(t, t)
                         for t in self.layer_types)
        return ("full",) * self.num_layers

    @property
    def hybrid(self):
        return any(k != "full" for k in self.kinds)

    def validate(self):
        return block_of(self).validate(self)


# -- the GPT-2 block's surface, beside the step functions below ----------
REFUSES = ()      # ServeConfig features a session over this block refuses
REFUSES_WHY = ""


def validate(cfg):
    if cfg.d_model % cfg.num_heads:
        raise MXNetError("d_model %d not divisible by num_heads %d"
                         % (cfg.d_model, cfg.num_heads))
    if cfg.layer_types:
        bad = set(cfg.layer_types) - {"full_attention", "sliding_attention"}
        if bad:
            raise MXNetError("block 'gpt2' runs full_attention and "
                             "sliding_attention layers, not %r" % sorted(bad))
        if len(cfg.layer_types) != cfg.num_layers:
            raise MXNetError("layer_types %r does not cover %d layers"
                             % (cfg.layer_types, cfg.num_layers))
        if "sliding_attention" in cfg.layer_types and cfg.sliding_window < 1:
            raise MXNetError("sliding_attention layers need sliding_window "
                             ">= 1 (got %d)" % cfg.sliding_window)
    return cfg


def check_params(params, cfg):
    """The parameter dict has the architecture's shapes: all of it that
    shapes can tell (``num_heads`` appears in none)."""
    got = config_from_params(params, cfg.num_heads)
    for name in ("vocab_size", "num_layers", "d_model", "max_len"):
        if getattr(got, name) != getattr(cfg, name):
            raise MXNetError("the parameters say %s %d, the architecture "
                             "says %d" % (name, getattr(got, name),
                                          getattr(cfg, name)))


_TRACE_NOTES = contextvars.ContextVar("serve_trace_notes", default=None)


@contextlib.contextmanager
def trace_notes():
    """-> a dict of what a block's functions note (:func:`note_traced`)
    while they are traced inside the ``with``: facts of the program that
    was built, such as which kernel a layer was traced with, that no
    device counter can hold.  The session builds each executable under
    one and keeps the dict beside it."""
    notes = {}
    token = _TRACE_NOTES.set(notes)
    try:
        yield notes
    finally:
        _TRACE_NOTES.reset(token)


def note_traced(name, count):
    """Add ``count`` under ``name`` to the notes of the trace under way;
    nothing outside :func:`trace_notes`."""
    notes = _TRACE_NOTES.get()
    if notes is not None:
        notes[name] = notes.get(name, 0) + count


def check_param_shapes(params, shapes, block):
    """``params`` has exactly ``shapes`` ({name: shape}; a quantized
    entry keeps the canonical shape on its codes): what a block whose
    architecture is stated, not inferred, asks of its parameter dict."""
    def _shape(v):
        return tuple(v["q"].shape if isinstance(v, dict) else v.shape)

    for name, shape in shapes.items():
        if name not in params:
            raise MXNetError("ModelConfig(block=%r) needs parameter %s %r"
                             % (block, name, shape))
        if _shape(params[name]) != tuple(shape):
            raise MXNetError("parameter %s is %r, the architecture says %r"
                             % (name, _shape(params[name]), tuple(shape)))


def latent_dim(cfg):
    """Values the cache holds a token a layer in ONE latent pool; 0 for
    this block's per-head K and V pools."""
    return 0


def state_shapes(cfg):
    """Slot-private recurrent state beside the pages, as the cache
    builds it: name -> (layers, one slot's shape a layer, dtype).  None:
    this block's layers are attention, over pages or over a ring."""
    return {}


def init_counters(cfg):
    """The block's own device state, which every executable takes and
    returns beside the cache's pools: none."""
    return {}


def compiler_options(backend):
    return None


def report(counters, cfg):
    """``InferenceSession.block_report()``: this block counts nothing
    on the device."""
    return None


def decode_pages_visited(lengths, page_size, table_width, per_slot):
    """Pages of a full layer's pools that one decode step's
    :func:`~mxnet_tpu.ops.attention.paged_decode_attention` visits, from
    the host's ``lengths`` (every slot's rows before the step, which
    appends one; 0 for an idle slot): ``per_slot`` (the kernel) the sum of
    the slots' own pages; else (the loop) the longest context's pages for
    every slot."""
    import numpy as np

    pages = np.minimum(-(-(np.asarray(lengths) + 1) // page_size),
                       table_width)
    return int(pages.sum() if per_slot else pages.max() * pages.size)


def decode_report(stats, table_width):
    """``InferenceSession.decode_report()`` from the session's host-side
    counts: every full-attention layer of :func:`decode_step` runs
    :func:`~mxnet_tpu.ops.attention.paged_decode_attention`, whose loop
    ends at the longest live context and whose kernel at each slot's
    own."""
    rep = dict(stats)
    rep["blocks_capacity"] = rep["steps"] * table_width
    rep["visited_share"] = (
        rep["blocks_visited"] / float(rep["blocks_capacity"])
        if rep["blocks_capacity"] else 0.0)
    return rep


def prefill_block(max_pages, page_size, exact):
    """Key block of :func:`prefill_forward`'s attention scan over the
    slot's gathered table: a page, whatever the table's width.  The scan
    visits the blocks up to the chunk's furthest horizon, ``offset +
    bucket`` (``InferenceSession.prefill_report()`` counts them from
    this)."""
    return page_size


def ring_pages(cfg, serve):
    """Pages of a slot's ring in every windowed layer, under the
    ``ServeConfig`` ``serve``.

    A dispatch writes up to ``span`` rows (the largest prefill chunk, or
    the speculative window) before its queries read, so a ring must hold
    the window plus the whole span minus the row that overlaps
    (``sliding_window + span - 1`` rows) for no visible key to be
    overwritten mid-dispatch — plus one extra page because the rotated
    gather (:func:`_ring_gather`) is page-granular: the newest page may
    be only one row full, yet the gather must still reach
    ``sliding_window + span - 1`` rows below that row."""
    span = max(max(serve.buckets), serve.spec_window if serve.spec_k else 1)
    return -(-(cfg.sliding_window + span - 1) // serve.page_size) + 1


def guard_tag(cfg):
    """What the recompile guard's name must tell apart beyond the widths:
    a stack with windowed layers adds ring pool avals (and a window
    length baked into every trace), so it must never share a guard with
    the classic stack — window length plus the per-layer kind initials
    (f/w)."""
    if not cfg.hybrid:
        return ""
    return "-w%d%s" % (cfg.sliding_window,
                       "".join(k[0] for k in cfg.kinds))


def _resolve_params(params):
    """See through a weight-only quantized params tree (name ->
    ``{"q", "s"}``, see ``mxnet_tpu.quantize``): dequantize to float32
    *inside* the traced function, so the executable's arguments stay
    1-byte codes while every matmul runs full precision.  Dequantization
    is an elementwise convert + multiply, so the resolved weight VALUES
    are identical across executables — which is why the M-invariant
    bit-exactness contract below holds per precision (quantized serial
    decode == quantized batched verify)."""
    if any(isinstance(v, dict) for v in params.values()):
        from ..quantize import dequantize_params

        return dequantize_params(params)
    return params


def _mm(x, w, exact):
    """``x (..., C) @ w (F, C)^T -> (..., F)`` — the ``FullyConnected``/
    MHA-projection contraction.  ``exact`` selects the M-invariant
    reduce form (each output element sums over C in an order independent
    of the leading dims)."""
    if exact:
        return (x[..., None, :] * w).sum(axis=-1)
    import jax.numpy as jnp

    return jnp.einsum("...c,fc->...f", x, w)


def _layer_norm(x, gamma, beta):
    """Training ``LayerNorm`` semantics: axis -1, eps 1e-5, rsqrt form.
    Row-wise, so it is M-invariant as-is."""
    import jax.numpy as jnp
    from jax import lax

    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) * lax.rsqrt(var + 1e-5) * gamma + beta


def init_params(cfg, seed=0, scale=0.02):
    """Fresh float32 parameters under the training graph's names (for
    benches/tests; real deployments restore a checkpoint)."""
    import jax
    import jax.numpy as jnp

    cfg.validate()
    theirs = block_of(cfg).init_params
    if theirs is not init_params:
        return theirs(cfg, seed=seed, scale=scale)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed),
                                 4 * cfg.num_layers + 4))

    def normal(shape):
        return (scale * jax.random.normal(next(keys), shape)
                ).astype(jnp.float32)

    c, v = cfg.d_model, cfg.vocab_size
    params = {
        "tok_embed_weight": normal((v, c)),
        "pos_embed": normal((1, cfg.max_len, c)),
        "final_ln_gamma": jnp.ones((c,), jnp.float32),
        "final_ln_beta": jnp.zeros((c,), jnp.float32),
        "lm_head_weight": normal((v, c)),
        "lm_head_bias": jnp.zeros((v,), jnp.float32),
    }
    for i in range(cfg.num_layers):
        params.update({
            "blk%d_ln1_gamma" % i: jnp.ones((c,), jnp.float32),
            "blk%d_ln1_beta" % i: jnp.zeros((c,), jnp.float32),
            "blk%d_attn_in_weight" % i: normal((3 * c, c)),
            "blk%d_attn_in_bias" % i: jnp.zeros((3 * c,), jnp.float32),
            "blk%d_attn_out_weight" % i: normal((c, c)),
            "blk%d_attn_out_bias" % i: jnp.zeros((c,), jnp.float32),
            "blk%d_ln2_gamma" % i: jnp.ones((c,), jnp.float32),
            "blk%d_ln2_beta" % i: jnp.zeros((c,), jnp.float32),
            "blk%d_ffn1_weight" % i: normal((4 * c, c)),
            "blk%d_ffn1_bias" % i: jnp.zeros((4 * c,), jnp.float32),
            "blk%d_ffn2_weight" % i: normal((c, 4 * c)),
            "blk%d_ffn2_bias" % i: jnp.zeros((c,), jnp.float32),
        })
    return params


def config_from_params(params, num_heads):
    """Derive the :class:`ModelConfig` from parameter shapes (everything
    except ``num_heads`` — head count does not appear in any shape)."""
    if "tok_embed_weight" not in params or "pos_embed" not in params:
        raise MXNetError(
            "not a transformer LM parameter dict (expected "
            "tok_embed_weight / pos_embed; got %s)"
            % sorted(params)[:8])

    def _shape(v):
        # quantized entries keep the canonical shape on their codes
        return v["q"].shape if isinstance(v, dict) else v.shape

    vocab, d_model = _shape(params["tok_embed_weight"])
    max_len = _shape(params["pos_embed"])[1]
    n = 0
    while "blk%d_attn_in_weight" % n in params:
        n += 1
    if n == 0:
        raise MXNetError("no blk0_attn_in_weight — zero decoder layers?")
    return ModelConfig(vocab_size=int(vocab), num_layers=n,
                       d_model=int(d_model), num_heads=int(num_heads),
                       max_len=int(max_len)).validate()


def _attn_heads(x, n, t, h, d):
    return x.reshape(n, t, h, d).transpose(0, 2, 1, 3)


def _kv_fake_quant(k, v, kv_quant):
    """Reference-side half of the per-precision bit-exactness oracle:
    quantize-dequantize the (n, H, T, D) head tensors per token with the
    exact helper the paged path scatters with, so a full-context forward
    sees the same dequantized KV VALUES the paged kernels reconstruct
    in-block (the dequant is elementwise, hence order-independent)."""
    if not kv_quant:
        return k, v
    from .. import quantize as _q

    def _fq(t):
        rows = t.transpose(0, 2, 1, 3)          # (n, T, H, D): per-token rows
        q, s = _q.kv_quantize_rows(rows, kv_quant)
        return _q.kv_dequantize(q, s).transpose(0, 2, 1, 3)

    return _fq(k), _fq(v)


def _block_attention(params, i, x, cfg, exact, block, kv_quant="",
                     window=0):
    """One pre-norm attention sublayer on (n, T, C); returns the
    residual-added activations plus this layer's (k, v) heads —
    (n, H, T, D) each, the page-writable prefill byproduct.  With
    ``kv_quant`` the keys/values are fake-quantized per token before
    attention, mirroring what a paged reader reconstructs.  ``window``
    restricts attention to the last ``window`` keys (the windowed-layer
    reference path)."""
    import jax.numpy as jnp

    n, t, c = x.shape
    h, d = cfg.num_heads, cfg.head_dim
    hdn = _layer_norm(x, params["blk%d_ln1_gamma" % i],
                      params["blk%d_ln1_beta" % i])
    qkv = _mm(hdn, params["blk%d_attn_in_weight" % i], exact) \
        + params["blk%d_attn_in_bias" % i]
    q, k, v = (_attn_heads(part, n, t, h, d)
               for part in jnp.split(qkv, 3, axis=-1))
    k, v = _kv_fake_quant(k, v, kv_quant)
    ctx = flash_attention(q, k, v, causal=True, block=block, mi=exact,
                          window=window)
    ctx = ctx.transpose(0, 2, 1, 3).reshape(n, t, c)
    out = _mm(ctx, params["blk%d_attn_out_weight" % i], exact) \
        + params["blk%d_attn_out_bias" % i]
    return x + out, (k, v)


def _ring_gather(pools, which, i, pb_max, page_size, head_dim, slot=None):
    """Gather layer ``i`` of the ring ``pools[which + "_pool"]`` (and its
    scales, where the mapping holds them) in ascending-absolute-position
    order.

    ``pb_max``: (S,) int32 — the highest absolute PAGE index written
    (the newest page).  The ring's pages are rotated so the gathered
    page ``j`` is absolute page ``pb_max - ring_pages + 1 + j``; each
    row is labeled with its absolute position (``k_positions``) so the
    windowed mask in :func:`..ops.attention.decode_attention` sees
    page-aligned blocks in exactly the reference forward's visit order —
    that alignment is what keeps ring reads bit-exact.  ``slot`` selects
    one slot's ring (prefill); otherwise all slots gather.  Returns
    (ctx (S, R, H, D), scales (S, R) or None, k_positions (S, R))."""
    import jax.numpy as jnp

    pool, scale_pool = pools[which + "_pool"], pools.get(which + "_scale")
    ring = read_ring(pool, i, head_dim) if slot is None else \
        read_ring(pool, i, head_dim, slot)[None]
    s, ring_tokens = ring.shape[0], ring.shape[1]
    ring_pages = ring_tokens // page_size
    pb = pb_max.reshape(-1, 1)                              # (S, 1)
    j = jnp.arange(ring_pages, dtype=pb.dtype)[None, :]     # (1, RP)
    gather_page = (pb + 1 + j) % ring_pages                 # ring page ids
    abs_page = pb - (ring_pages - 1) + j                    # their positions
    in_page = jnp.arange(page_size, dtype=pb.dtype)
    row_idx = (gather_page[:, :, None] * page_size
               + in_page[None, None, :]).reshape(s, ring_tokens)
    k_positions = (abs_page[:, :, None] * page_size
                   + in_page[None, None, :]).reshape(s, ring_tokens)
    ctx = jnp.take_along_axis(ring, row_idx[:, :, None, None], axis=1)
    scales = None
    if scale_pool is not None:
        sc = scale_pool[i] if slot is None else \
            jnp.take(scale_pool[i], slot, axis=0)[None]
        scales = jnp.take_along_axis(sc, row_idx, axis=1)
    return ctx, scales, k_positions


def _block_mlp(params, i, x, exact):
    import jax

    hdn = _layer_norm(x, params["blk%d_ln2_gamma" % i],
                      params["blk%d_ln2_beta" % i])
    hdn = _mm(hdn, params["blk%d_ffn1_weight" % i], exact) \
        + params["blk%d_ffn1_bias" % i]
    hdn = jax.nn.gelu(hdn)
    hdn = _mm(hdn, params["blk%d_ffn2_weight" % i], exact) \
        + params["blk%d_ffn2_bias" % i]
    return x + hdn


def full_forward(params, tokens, cfg, exact=None, block=None,
                 return_kv=False, kv_quant=""):
    """Full-context forward: (n, T) int tokens -> (n, T, V) logits.

    The O(T²)-work reference every serve-path output is checked against,
    and the compute body of the bucketed prefill (``return_kv=True``
    additionally yields each layer's (k, v) head tensors for the page
    writes)."""
    import jax.numpy as jnp

    if exact is None:
        exact = exact_mode()
    theirs = block_of(cfg).full_forward
    if theirs is not full_forward:
        if return_kv or kv_quant:
            raise MXNetError("block %r: full_forward has no return_kv and "
                             "no kv_quant" % cfg.block)
        return theirs(params, tokens, cfg, exact, block=block)
    params = _resolve_params(params)
    t = tokens.shape[-1]
    if t > cfg.max_len:
        raise MXNetError("sequence length %d > model max_len %d"
                         % (t, cfg.max_len))
    x = jnp.take(params["tok_embed_weight"], tokens.astype(jnp.int32),
                 axis=0)
    x = x + params["pos_embed"][:, :t]
    kvs = []
    for i, kind in enumerate(cfg.kinds):
        x, kv = _block_attention(
            params, i, x, cfg, exact, block, kv_quant=kv_quant,
            window=cfg.sliding_window if kind == "window" else 0)
        kvs.append(kv)
        x = _block_mlp(params, i, x, exact)
    x = _layer_norm(x, params["final_ln_gamma"], params["final_ln_beta"])
    logits = _mm(x, params["lm_head_weight"], exact) \
        + params["lm_head_bias"]
    if return_kv:
        return logits, kvs
    return logits


def prefill_forward(params, tokens, length, offset, table_row, pools,
                    counters, cfg, page_size, exact=None, kv_quant="",
                    slot=None):
    """Bucketed prefill over one suffix chunk: write the chunk's KV into
    the slot's pages and attend each row over everything at or before
    its absolute position — including KV the slot did NOT compute this
    dispatch (prefix-cache hit pages, earlier chunks of a chunked or
    resumed prefill).

    tokens: (1, Tb) chunk padded to the bucket length (a multiple of
    ``page_size``); length: () int32 real tokens in THIS chunk;
    offset: () int32 absolute position of the chunk's first token (a
    ``page_size`` multiple — chunks are page-aligned; 0 reproduces the
    classic whole-prompt prefill); table_row: (max_pages,) int32 page
    ids — entries beyond the slot's mapped pages point at the trash
    page; pools: the cache's device state (``PagedKVCache.pools``, read
    here by name); counters: the block's own device state, none for
    this block.  Returns (first_token, last_logits, pools, counters)
    where ``last_logits`` is the logits at chunk position ``length - 1``
    (absolute position ``offset + length - 1``); pools and counters are
    donate-safe.

    The body is :func:`verify_step` for one slot: per-row absolute
    positions, write-then-gather page scatter, and the shared
    online-softmax kernel with per-row validity horizons
    ``offset + j + 1`` — so row ``j`` reads the cached prefix plus
    chunk rows ``<= j`` and nothing else.  The same M-invariant
    transitivity that makes verify rows bit-identical to serial decode
    makes an offset-0 dispatch of this function bit-identical to the
    old whole-prompt flash prefill, and a suffix dispatch bit-identical
    to having prefilled the whole prompt cold.  Rows whose absolute
    page index runs past the table are routed to the trash page
    *in-graph* (a clipped index would alias the slot's LAST real page
    and corrupt it — bucket padding can overhang the mapped range when
    ``offset > 0``); their positions exceed every row's horizon, so
    nothing reads them.

    Windowed layers scatter the chunk's rows into the slot's ring
    (``kw_pool``/``vw_pool``, selected by the ``slot`` scalar) at
    ``abs_pos % ring_tokens`` and attend over the position-labeled
    rotated ring gather.  The updated ring pools come back in the same
    mapping.
    """
    import jax.numpy as jnp

    if exact is None:
        exact = exact_mode()
    params = _resolve_params(params)
    _, t_b = tokens.shape
    if t_b % page_size:
        raise MXNetError("bucket length %d not a multiple of page size %d"
                         % (t_b, page_size))
    h, d = cfg.num_heads, cfg.head_dim
    max_pages = table_row.shape[0]
    pools = dict(pools)
    trash = pools["k_pool"].shape[1] - 1  # pool row num_pages, static
    offs = jnp.arange(t_b, dtype=jnp.int32)
    abs_pos = offset + offs                               # (Tb,)
    pos = jnp.clip(abs_pos, 0, cfg.max_len - 1)
    x = jnp.take(params["tok_embed_weight"], tokens.astype(jnp.int32),
                 axis=0)
    x = x + jnp.take(params["pos_embed"][0], pos, axis=0)
    row_valid = (abs_pos + 1).reshape(1, t_b)             # keys row j sees
    idx = abs_pos // page_size
    pages = jnp.where(idx < max_pages,
                      table_row[jnp.clip(idx, 0, max_pages - 1)], trash)
    offsets = abs_pos % page_size
    fi = wi = 0  # per-kind pool indices (static)
    for i, kind in enumerate(cfg.kinds):
        hdn = _layer_norm(x, params["blk%d_ln1_gamma" % i],
                          params["blk%d_ln1_beta" % i])
        qkv = _mm(hdn, params["blk%d_attn_in_weight" % i], exact) \
            + params["blk%d_attn_in_bias" % i]
        q, k, v = jnp.split(qkv, 3, axis=-1)
        if kind == "window":
            ring_rows = abs_pos % pools["kw_pool"].shape[2]
            append_rows(pools, "kw", wi, slot, ring_rows, k.reshape(t_b, h, d),
                        kv_quant)
            append_rows(pools, "vw", wi, slot, ring_rows, v.reshape(t_b, h, d),
                        kv_quant)
            pb_max = jnp.atleast_1d((offset + t_b - 1) // page_size)
            ctx_k, ks, kp = _ring_gather(pools, "kw", wi, pb_max,
                                         page_size, d, slot=slot)
            ctx_v, vs, _ = _ring_gather(pools, "vw", wi, pb_max,
                                        page_size, d, slot=slot)
            att = decode_attention(
                q.reshape(1, t_b, h, d).transpose(0, 2, 1, 3),
                ctx_k.transpose(0, 2, 1, 3), ctx_v.transpose(0, 2, 1, 3),
                row_valid, block=page_size, mi=exact, k_scale=ks,
                v_scale=vs, window=cfg.sliding_window, k_positions=kp)
            ctx = att.transpose(0, 2, 1, 3).reshape(1, t_b, cfg.d_model)
            wi += 1
        else:
            # append the chunk's KV at its absolute rows (one vectorized
            # scatter; only trash rows can collide, nothing reads them)
            append_rows(pools, "k", fi, pages, offsets, k.reshape(t_b, h, d),
                        kv_quant)
            append_rows(pools, "v", fi, pages, offsets, v.reshape(t_b, h, d),
                        kv_quant)
            # one query head a key/value head
            ctx = paged_prefill_attention(
                q.reshape(t_b, h, 1, d), pools["k_pool"], pools["v_pool"],
                fi, table_row, abs_pos, page_size, page_size, mi=exact,
                k_scale=pools["k_scale"] if kv_quant else None,
                v_scale=pools["v_scale"] if kv_quant else None,
            ).reshape(1, t_b, cfg.d_model)
            fi += 1
        out = _mm(ctx, params["blk%d_attn_out_weight" % i], exact) \
            + params["blk%d_attn_out_bias" % i]
        x = x + out
        x = _block_mlp(params, i, x, exact)
    x = _layer_norm(x, params["final_ln_gamma"], params["final_ln_beta"])
    logits = _mm(x, params["lm_head_weight"], exact) \
        + params["lm_head_bias"]
    last = jnp.take(logits[0], length - 1, axis=0)
    first_token = jnp.argmax(last, axis=-1).astype(jnp.int32)
    return first_token, last, pools, counters


def decode_step(params, tokens, lengths, tables, pools, counters, cfg,
                page_size, exact=None, kv_quant=""):
    """One continuous-batching decode step for every slot at once.

    tokens: (S,) int32 — each slot's previous output token; lengths:
    (S,) int32 — KV rows already cached per slot (the new token's
    position); tables: (S, max_pages) int32 page tables (inactive slots:
    all-trash rows, length 0); pools and counters as in
    :func:`prefill_forward`.  Appends each slot's new KV at ``lengths``,
    attends over the slot's pages with the shared online-softmax kernel,
    and returns (next_tokens (S,), logits (S, V), pools, counters).

    Full-attention layers read their pages from the pool in place
    (:func:`~mxnet_tpu.ops.attention.paged_decode_attention`): no copy of
    a slot's page table is gathered, and the loop over pages ends at the
    longest live context, so a step's cost follows what the slots hold
    and not the table's capacity.  Every shape is fixed all the same:
    there is no tensor here whose size depends on how many tokens any
    request has generated.

    Windowed layers bound it further: they write the token's KV at
    ``lengths % ring_tokens`` in the slot's ring and attend over only
    ``ring_tokens`` rows (the rotated position-labeled gather).  Idle
    slots harmlessly re-write their own ring row 0 (prefill rewrites it
    before anything reads it) — the ring's analog of idle slots writing
    the trash page.
    """
    import jax.numpy as jnp

    if exact is None:
        exact = exact_mode()
    params = _resolve_params(params)
    s = tokens.shape[0]
    h, d = cfg.num_heads, cfg.head_dim
    max_pages = tables.shape[1]
    pools = dict(pools)
    x = jnp.take(params["tok_embed_weight"], tokens.astype(jnp.int32),
                 axis=0)
    pos = jnp.clip(lengths, 0, cfg.max_len - 1)
    x = x + jnp.take(params["pos_embed"][0], pos, axis=0)
    page_slot = jnp.clip(lengths // page_size, 0, max_pages - 1)
    page = jnp.take_along_axis(tables, page_slot[:, None], axis=1)[:, 0]
    offset = lengths % page_size
    slot_ids = jnp.arange(s)
    fi = wi = 0
    for i, kind in enumerate(cfg.kinds):
        hdn = _layer_norm(x, params["blk%d_ln1_gamma" % i],
                          params["blk%d_ln1_beta" % i])
        qkv = _mm(hdn, params["blk%d_attn_in_weight" % i], exact) \
            + params["blk%d_attn_in_bias" % i]
        q, k, v = jnp.split(qkv, 3, axis=-1)
        if kind == "window":
            ring_rows = lengths % pools["kw_pool"].shape[2]
            append_rows(pools, "kw", wi, slot_ids, ring_rows,
                        k.reshape(s, h, d), kv_quant)
            append_rows(pools, "vw", wi, slot_ids, ring_rows,
                        v.reshape(s, h, d), kv_quant)
            pb_max = lengths // page_size
            ctx_k, ks, kp = _ring_gather(pools, "kw", wi, pb_max,
                                         page_size, d)
            ctx_v, vs, _ = _ring_gather(pools, "vw", wi, pb_max,
                                        page_size, d)
            att = decode_attention(q.reshape(s, h, 1, d),
                                   ctx_k.transpose(0, 2, 1, 3),
                                   ctx_v.transpose(0, 2, 1, 3),
                                   lengths + 1, block=page_size, mi=exact,
                                   k_scale=ks, v_scale=vs,
                                   window=cfg.sliding_window,
                                   k_positions=kp)
            wi += 1
        else:
            # append this token's KV at (page, offset); inactive slots
            # write the trash page (their table rows are all-trash)
            append_rows(pools, "k", fi, page, offset, k.reshape(s, h, d),
                        kv_quant)
            append_rows(pools, "v", fi, page, offset, v.reshape(s, h, d),
                        kv_quant)
            # read the pages where they lie, up to the longest context
            att = paged_decode_attention(
                q.reshape(s, h, 1, d), pools["k_pool"], pools["v_pool"],
                fi, tables, lengths + 1, page_size, mi=exact,
                k_scale=pools.get("k_scale"), v_scale=pools.get("v_scale"))
            fi += 1
        ctx = att.transpose(0, 2, 1, 3).reshape(s, cfg.d_model)
        out = _mm(ctx, params["blk%d_attn_out_weight" % i], exact) \
            + params["blk%d_attn_out_bias" % i]
        x = x + out
        x = _block_mlp(params, i, x, exact)
    x = _layer_norm(x, params["final_ln_gamma"], params["final_ln_beta"])
    logits = _mm(x, params["lm_head_weight"], exact) \
        + params["lm_head_bias"]
    next_tokens = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return next_tokens, logits, pools, counters


def verify_step(params, tokens, lengths, tables, pools, counters, cfg,
                page_size, exact=None, kv_quant=""):
    """Speculative-decoding verify: advance every slot ``W = K + 1``
    teacher-forced positions in ONE fixed-shape step.

    tokens: (S, W) int32 — per slot, the last committed token followed
    by the draft's K proposals; lengths: (S,) int32 — committed KV rows
    per slot (position of tokens[:, 0]); tables: (S, max_pages) int32.
    Writes all W rows' KV at positions ``lengths .. lengths + W - 1``
    and attends row ``j`` over exactly ``lengths + j + 1`` keys (the
    causal horizon expressed as a per-row validity length), then
    returns (greedy (S, W), logits (S, W, V), pools, counters).

    Bit-exactness contract: with ``exact=True`` every op here is the
    M-invariant form of the matching :func:`decode_step` op, and the
    attention merge visits the same page blocks with the same masks —
    so row ``j`` of one verify step is bit-identical to the ``j``-th of
    W serial ``decode_step`` calls fed the same tokens.  That is what
    makes greedy acceptance exact: comparing the draft's proposal to
    ``greedy[:, j]`` is comparing against precisely what non-speculative
    decode would have emitted.

    Rows whose write position runs past the slot's page reservation
    land on the trash page (the session widens the table by
    ``spec_pad_pages`` all-trash columns so the page clip below can
    never alias a real page); such rows are never committed, so their
    garbage logits are dead by construction.

    A windowed layer's rollback is O(1) by construction: it writes all W
    rows into the ring at their deterministic slots ``abs_pos %
    ring_tokens``, and rejected rows need no undo — after the host rolls
    ``lengths`` back, their ring rows label as positions outside every
    future mask until the committed stream rewrites them.
    """
    import jax.numpy as jnp

    if exact is None:
        exact = exact_mode()
    params = _resolve_params(params)
    s, w = tokens.shape
    h, d = cfg.num_heads, cfg.head_dim
    max_pages = tables.shape[1]
    pools = dict(pools)
    x = jnp.take(params["tok_embed_weight"], tokens.astype(jnp.int32),
                 axis=0)
    offs = jnp.arange(w, dtype=lengths.dtype)
    abs_pos = lengths[:, None] + offs[None, :]            # (S, W)
    pos = jnp.clip(abs_pos, 0, cfg.max_len - 1)
    x = x + jnp.take(params["pos_embed"][0], pos, axis=0)
    row_valid = abs_pos + 1                               # keys row j sees
    page_slot = jnp.clip(abs_pos // page_size, 0, max_pages - 1)
    pages = jnp.take_along_axis(tables, page_slot, axis=1)  # (S, W)
    offsets = abs_pos % page_size
    slot_ids = jnp.arange(s)
    fi = wi = 0
    for i, kind in enumerate(cfg.kinds):
        hdn = _layer_norm(x, params["blk%d_ln1_gamma" % i],
                          params["blk%d_ln1_beta" % i])
        qkv = _mm(hdn, params["blk%d_attn_in_weight" % i], exact) \
            + params["blk%d_attn_in_bias" % i]
        q, k, v = jnp.split(qkv, 3, axis=-1)
        k = k.reshape(s, w, h, d)
        v = v.reshape(s, w, h, d)
        # append all W rows' KV, then attend with per-row horizons: row
        # j only ever reads rows <= j of this very step plus committed
        # context, so write-then-attend reproduces the serial interleave
        if kind == "window":
            ring_tokens = pools["kw_pool"].shape[2]
            for j in range(w):
                rr = abs_pos[:, j] % ring_tokens
                append_rows(pools, "kw", wi, slot_ids, rr, k[:, j], kv_quant)
                append_rows(pools, "vw", wi, slot_ids, rr, v[:, j], kv_quant)
            pb_max = (lengths + w - 1) // page_size
            ctx_k, ks, kp = _ring_gather(pools, "kw", wi, pb_max,
                                         page_size, d)
            ctx_v, vs, _ = _ring_gather(pools, "vw", wi, pb_max,
                                        page_size, d)
            ctx_k = ctx_k.transpose(0, 2, 1, 3)
            ctx_v = ctx_v.transpose(0, 2, 1, 3)
            win = cfg.sliding_window
            wi += 1
        else:
            for j in range(w):
                append_rows(pools, "k", fi, pages[:, j], offsets[:, j],
                            k[:, j], kv_quant)
                append_rows(pools, "v", fi, pages[:, j], offsets[:, j],
                            v[:, j], kv_quant)
            ctx_k = read_context(pools["k_pool"], fi, tables, d)
            ctx_v = read_context(pools["v_pool"], fi, tables, d)
            ks = vs = kp = None
            if kv_quant:
                ks = pools["k_scale"][fi, tables].reshape(
                    s, max_pages * page_size)
                vs = pools["v_scale"][fi, tables].reshape(
                    s, max_pages * page_size)
            win = 0
            fi += 1
        att = decode_attention(q.reshape(s, w, h, d).transpose(0, 2, 1, 3),
                               ctx_k, ctx_v, row_valid, block=page_size,
                               mi=exact, k_scale=ks, v_scale=vs,
                               window=win, k_positions=kp)
        ctx = att.transpose(0, 2, 1, 3).reshape(s, w, cfg.d_model)
        out = _mm(ctx, params["blk%d_attn_out_weight" % i], exact) \
            + params["blk%d_attn_out_bias" % i]
        x = x + out
        x = _block_mlp(params, i, x, exact)
    x = _layer_norm(x, params["final_ln_gamma"], params["final_ln_beta"])
    logits = _mm(x, params["lm_head_weight"], exact) \
        + params["lm_head_bias"]
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return greedy, logits, pools, counters


def draft_propose(params, tokens, n_feed, lengths, tables, pools, counters,
                  cfg, page_size, exact=None, kv_quant=""):
    """Draft-model K+1-step scan: one dispatch that both *ingests*
    committed tokens and *proposes* speculative continuations.

    tokens: (S, W) int32 teacher tokens; n_feed: (S,) int32 — step ``j``
    feeds ``tokens[s, j]`` while ``j < n_feed[s]`` and the draft's own
    greedy output from step ``j - 1`` after that.  ``n_feed = 1`` is
    propose mode (feed the last committed token, then autoregress);
    ``n_feed = W`` is pure teacher forcing (prompt ingestion in W-token
    chunks).  Every step appends its token's KV at ``lengths + j``, so
    the draft cache tracks exactly the positions the target cache holds.
    Returns (outs (S, W), pools, counters) where ``outs[:, j]`` is the
    greedy token after feeding position ``lengths + j`` — propose mode
    uses ``outs[:, :W-1]`` as its K proposals.

    Draft stacks may mix full and windowed layers (the ring append /
    rotated gather is scan-compatible and rollback is lengths-only).
    """
    import jax.numpy as jnp
    from jax import lax

    if exact is None:
        exact = exact_mode()
    # resolve once, outside the scan body, so the dequantized weights
    # are loop invariants XLA hoists rather than per-step work
    params = _resolve_params(params)

    def body(carry, xs):
        prev, state = carry
        teach, j = xs
        tok = jnp.where(j < n_feed, teach, prev)
        out = decode_step(params, tok, lengths + j, tables, *state,
                          cfg=cfg, page_size=page_size, exact=exact,
                          kv_quant=kv_quant)
        return (out[0], out[2:]), out[0]

    w = tokens.shape[1]
    xs = (tokens.T, jnp.arange(w, dtype=lengths.dtype))
    carry0 = (tokens[:, 0].astype(jnp.int32), (pools, counters))
    (_, state), outs = lax.scan(body, carry0, xs)
    return (outs.T,) + state


@functools.lru_cache(maxsize=None)
def _reference_fn(cfg, page_size, exact, kv_quant=""):
    import jax

    def fwd(params, tokens):
        return full_forward(params, tokens, cfg, exact=exact,
                            block=page_size, kv_quant=kv_quant)

    return jax.jit(fwd)


def reference_last_logits(params, seq, cfg, page_size, exact=None,
                          kv_quant=""):
    """Bit-exactness oracle for the serving path: full-context forward
    over ``seq`` padded to the next ``page_size`` multiple (the same
    attention-block geometry the prefill/decode executables run), logits
    at the last *real* position.  Jitted and cached per padded shape —
    eager dispatch fuses differently and is NOT bit-comparable.

    ``kv_quant`` pins the oracle to a KV precision: the reference
    fake-quantizes each token's K/V row with the same helper the paged
    path scatters with, so it certifies the quantized serving path
    bit-exactly *at that precision* (PR 13's per-precision pattern)."""
    import jax.numpy as jnp

    from ..quantize import quant_mode

    exact = exact_mode() if exact is None else bool(exact)
    seq = [int(t) for t in seq]
    if not seq:
        raise MXNetError("reference_last_logits: empty sequence")
    pad = (-len(seq)) % int(page_size)
    toks = jnp.asarray([seq + [0] * pad], jnp.int32)
    logits = _reference_fn(cfg, int(page_size), exact,
                           quant_mode(kv_quant))(params, toks)
    return logits[0, len(seq) - 1]
