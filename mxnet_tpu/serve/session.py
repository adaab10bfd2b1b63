"""InferenceSession: bucketed AOT executables over the paged KV cache.

The serving analogue of ``fused.TrainStep.compile`` (PR 4): every
executable the session will ever run is compiled up front with
``jax.jit(...).lower(*avals).compile()`` —

* one **prefill** executable per sequence-length bucket (prompts are
  right-padded to the smallest bucket that fits), and
* one fixed-shape **decode** executable advancing *all* batch slots by
  a single token against the paged KV pools.

Because every input shape is frozen (pools, page tables, token/length
vectors), the compiled-executable count is exactly
``len(buckets) + 1`` for the session's lifetime — or, with speculative
decoding enabled (``spec_k > 0``), ``len(buckets) + 3``: the same
prefill set and decode step plus one fixed-shape K+1-row **verify**
executable and one **draft** decode executable (the draft executable is
skipped for the host-side n-gram draft, giving ``len(buckets) + 2``).
Each executable gets a ``compile_cache`` recompile guard seeded at
compile time; a dispatch that would need a new trace (a bug) trips
``MXNET_RECOMPILE_WARN`` / ``RecompileStorm`` just like training steps
do.

Speculative decoding (ROADMAP 3(b)): a draft proposes ``spec_k`` tokens
per slot, the target model verifies all slots' proposals in ONE
``spec_k + 1``-row teacher-forced step, and greedy acceptance commits
the longest prefix the target agrees with — 1..K+1 tokens per step.
Because verify runs under the same M-invariant ``exact`` mode as
decode, acceptance is *exact*: row ``j`` of the verify is bit-identical
to the ``j``-th serial decode step, so spec-on output == spec-off
output token for token.  Rejected suffixes roll back through
:meth:`PagedKVCache.truncate`; the draft keeps its own cache in
lockstep.  Draft selection via ``MXNET_SERVE_DRAFT``: ``ngram`` (host
prompt-lookup, no extra params), ``layers:N`` (the target's first N
blocks — self-speculative layer skip), or a checkpoint directory.

Model load goes through the v2 elastic checkpoint restore
(:meth:`InferenceSession.from_checkpoint`), so an N-process training
run's shards serve directly in a single process.

Weight-only quantization (``ServeConfig.quant`` / ``MXNET_SERVE_QUANT``,
``int8`` or ``fp8``): eligible weights are stored as 1-byte codes with
per-channel scales (see ``mxnet_tpu.quantize``) and dequantized INSIDE
each executable — at-rest and argument bytes shrink ~4x, the executable
count stays frozen, and because dequantization is deterministic
elementwise math the M-invariant exact mode still certifies bit-
exactness per precision (quantized decode == quantized verify, so
speculative decoding composes unchanged).  Quantized and full-precision
sessions never alias recompile guards: the guard prefix grows a
``-q<mode>`` tag.

KV-cache page quantization (``ServeConfig.kv_quant`` /
``MXNET_SERVE_KV_QUANT``, ``int8`` or ``e4m3``/``fp8``): the paged KV
pools store 1-byte codes with one float32 scale per (layer, page, row)
position kept in parallel scale pools.  Every executable quantizes on
append (a token's codes+scale are a pure function of that token's K/V
values, so prefill scatter, serial decode, batched verify, prefix-hit
replay and preempt/re-prefill stay byte-identical) and dequantizes
inside the attention kernel's block scan, where XLA fuses the convert.
Pool bytes shrink ~4x — slot capacity at fixed pool bytes multiplies on
top of oversubscription — the executable count stays frozen, and the
bit-exactness oracle re-pins per precision
(``reference_last_logits(..., kv_quant=...)`` fake-quantizes its
reference KV the same way).  Guard prefixes grow a ``-kv<mode>`` tag.

Prefix caching (``prefix_pages`` / ``MXNET_SERVE_PREFIX_PAGES``): after
every prefill the slot's full prompt pages are published into the KV
cache's token-hash index; a later admission whose prompt chain hits the
index maps those pages read-only (reference-counted, copy-on-write) and
prefills only the uncached suffix.  The suffix runs through the SAME
per-bucket prefill executables — each takes a position ``offset``
argument, so a suffix chunk is just a dispatch at a non-zero offset and
the executable count stays frozen.  The offset also enables *chunked*
prefill (sequences longer than the largest bucket run as page-aligned
max-bucket chunks), which is what lets a preempted request re-prefill
its whole transcript on resume.

Oversubscription (``oversub`` / ``MXNET_SERVE_OVERSUB``): admission
reserves only the prompt's pages; every decode/verify boundary grows
active slots on demand (:meth:`InferenceSession.pages_short` is the
scheduler's shortfall probe, and the scheduler preempts requests when
the pool runs below its watermark before the growth would fail).

Windowed layers (``ModelConfig.layer_types`` + ``sliding_window``: the
model's, like the rest of the architecture below) keep a fixed ring of
pages per slot, sized by the block's ``ring_pages(model, config)``, so a
stack of them holds a slot in O(window) memory whatever its context
length.  The executable count stays frozen (rings add entries to the
cache's pool mapping and a prefill ``slot`` scalar, not executables),
speculative decoding composes (rings roll back lengths-only), and
preempt/resume re-runs prefill, which reconstructs ring contents exactly.
Prefix caching is the one subsystem such stacks opt out of: rings are
slot-private, so the only boundary at which every layer's state is
reconstructible from published pages is offset 0 — lookups miss and
nothing is published.

The architecture (``model=``): by default the session infers a GPT-2
shaped :class:`ModelConfig` from the parameter dict and ``num_heads``.
An architecture that shapes cannot tell (``ModelConfig(block=
"deepseek_v3", ...)``: latent attention over one latent page pool,
routed and shared experts, ``serve/latent_moe.py``) is passed as
``model=`` and used as given; so is ``ModelConfig(block=
"granitemoehybrid", ...)`` (``serve/granite_hybrid.py``: Mamba-2 layers,
whose slot-private state and convolution context the cache keeps beside
the pages of the few grouped-query attention layers).  It is the
model's, not the deployment's: no ``ServeConfig`` field and no
environment variable names it.  ``ModelConfig(block="laguna", ...)``
(``serve/laguna.py``) states windowed layers the same way, and its
``ring_pages`` is the model's window in whole pages.  The
session looks the block's module up once (``model.BLOCKS``,
``self.block``) and asks it for everything that depends on the
architecture: what the cache must hold, the step functions, what it
refuses, its compile options, its reports.  The executable set is the
same ``len(buckets) + 1``.

What an executable takes and returns (every block, every executable):
the parameters, the step's own arrays, then ``pools`` (the cache's
device state, :attr:`PagedKVCache.pools`: one name -> array mapping, one
pytree argument, donated) and ``counters`` (the block's own device
state, likewise: the latent block's router counts, read by
:meth:`InferenceSession.block_report`; empty for GPT-2).  After a dispatch
the session stores the two mappings that came back, and knows no pool by
name: a block with recurrent state names it in ``state_shapes(cfg)``,
the cache builds it, and ``alloc`` zeroes a slot's rows.  Not supported
for the latent block and the Mamba-2 block yet, and refused at
construction: ``spec_k``, ``kv_quant``.
Weight-only ``quant`` and ``oversub`` work for both, ``prefix_pages`` for
the latent block (a cache with recurrent state keeps no prefix index).

Fresh prompts in chunks (``ServeConfig.max_prompt``, a field only): by
default a fresh prompt may be as long as the largest bucket.  With
``max_prompt`` above it a fresh prompt up to ``max_prompt`` is admitted
and prefilled by the chunk loop above (full chunks at the largest bucket,
the rest at the smallest bucket that fits: the same ``len(buckets) + 1``
executables), and a slot's page reservation and the ``max_len`` check
follow ``max_prompt + max_new``.

A block that generates by diffusion (``ModelConfig(block="sdar_moe",
...)``, ``serve/sdar_moe.py``) has a ``block_pass`` where the others have a
``decode_step``, and the session compiles that in its place, as
``"block_pass"``: still ``len(buckets) + 1`` executables.  A slot then
holds an **open block** of ``block_length`` tokens, some of them still the
mask token: :meth:`InferenceSession.prefill` writes the K/V of the prompt's
whole blocks and yields no token (:data:`NO_TOKEN`), the tokens left over
open the first block, and :meth:`InferenceSession.step` runs ONE pass over
every live slot's open block, whatever pass of whatever block each is in:
a slot whose block still held a mask has some rows unmasked (on the
device; the host reads the block's tokens, which rows were unmasked and
their confidences, nothing of vocabulary width), a slot whose block held
none has it committed: ``lengths`` moves by the block's length, the step hands out its
tokens with the pass each was unmasked in and the confidence it was
unmasked with, and the next block opens.  A
step so commits 0 to ``block_length`` tokens a slot.  Such a block refuses
``spec_k``, ``kv_quant``, ``prefix_pages`` and ``oversub``.

A step one ahead (``InferenceSession.step(ahead=True)``): of what a
decode launch takes, only the ``(slots,)`` token vector depends on the
launch before it, and that launch's own first result holds it on the
device.  The decode executable therefore takes three token arguments (the
host's vector, the launch before's ``next_tokens`` as the device array it
is, and a mask that says which to feed a slot) and selects inside: a
call told it may run ahead launches the next step *before* it reads this
one's tokens, and leaves it in flight when it returns.  The host's state
moves where it belongs: ``lengths``, page upkeep and the page count at a
launch, ``_slot_tokens`` / ``_slot_history`` and the returned tokens at
the read.  A launch remembers the slots it carried and the epoch of each
(:meth:`InferenceSession.prefill` starts a new one); a row whose slot was
released, or released and filled again, since the launch is dropped at
the read.  A diffusion block's pass runs ahead the same way, over a token
array of ``block_length`` columns: the host has read the pass before the
unread one, which is the unread one's input, so it knows of every slot
whether the unread pass is a denoise pass of its block (the next pass
takes that pass's ``after`` rows from the device, and one more pass counts
towards its quota) or its commit pass (the next pass opens the next block,
all masks, ``block_length`` rows further on: every part of it is the
host's); which rows a pass unmasks stays the device's to decide.  There
``lengths`` and the open blocks move at the read, as the rule that commits
a block does.  A speculating session computes its next input on the host
from the read, so it never runs ahead.

Env knobs (see docs/env_vars.md): ``MXNET_SERVE_SLOTS``,
``MXNET_SERVE_PAGE``, ``MXNET_SERVE_BUCKETS``, ``MXNET_SERVE_MAX_NEW``,
``MXNET_SERVE_PAGES``, ``MXNET_SERVE_EXACT``, ``MXNET_SERVE_SPEC_K``,
``MXNET_SERVE_DRAFT``, ``MXNET_SERVE_QUANT``,
``MXNET_SERVE_KV_QUANT``, ``MXNET_SERVE_PREFIX_PAGES``,
``MXNET_SERVE_OVERSUB``,
``MXNET_SERVE_WATERMARK``, ``MXNET_SERVE_TTFT_SLO_MS``.
"""
from __future__ import annotations

import dataclasses
import json
import time

from ..base import MXNetError, get_env
from ..profiler import cpu_span as _cpu_span, span as _span
from ..quantize import quant_mode
from .kv_cache import PagedKVCache
from .model import (ModelConfig, block_of, config_from_params,
                    decode_pages_visited, exact_mode, trace_notes)

__all__ = ["ServeConfig", "InferenceSession", "NO_TOKEN"]

# what ``prefill`` returns for its first token where the block yields none
# there (a diffusion block: its first tokens come with a block's commit)
NO_TOKEN = -1


def _parse_buckets(raw):
    if isinstance(raw, str):
        parts = [p for p in raw.replace(";", ",").split(",") if p.strip()]
        raw = [int(p) for p in parts]
    buckets = tuple(sorted(set(int(b) for b in raw)))
    if not buckets:
        raise MXNetError("ServeConfig: empty bucket set")
    return buckets


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Capacity knobs for one :class:`InferenceSession`.

    ``buckets`` are padded prefill lengths (each a multiple of
    ``page_size``); ``max_new`` caps tokens generated per request;
    ``num_pages`` sizes the shared KV pool (default: full reservation
    capacity for ``slots`` worst-case requests); ``spec_k`` > 0 turns
    on speculative decoding with K draft proposals per step and
    ``draft`` picks the proposer (``""``/``"ngram"`` host prompt-lookup,
    ``"layers:N"`` target-derived truncation, else a checkpoint
    directory).
    """

    slots: int = 4
    page_size: int = 16
    buckets: tuple = (16, 32, 64)
    max_new: int = 32
    num_pages: int = 0  # 0 = slots * max_pages_per_slot
    exact: bool = True
    spec_k: int = 0  # 0 = speculative decoding off
    draft: str = ""  # "", "ngram", "layers:N", or a checkpoint dir
    quant: str = ""  # "", "int8", or "fp8" weight-only quantization
    kv_quant: str = ""  # "", "int8", or "fp8" KV-cache page quantization
    prefix_pages: int = 0  # 0 = prefix cache off; -1 = unbounded retention
    oversub: bool = False  # admit by current need, grow on demand
    watermark: int = 0  # free-pool floor that triggers preemption
    ttft_slo_ms: float = 0.0  # 0 = no TTFT budget (SLO admission off)
    max_prompt: int = 0  # longest admissible fresh prompt; 0 = max(buckets)
    # ``max_prompt`` above the largest bucket admits fresh prompts up to
    # it: prefill feeds such a prompt in chunks of the largest bucket (the
    # loop a resumed transcript runs), and a slot's page reservation and
    # the model's ``max_len`` are held to ``max_prompt + max_new``.

    @classmethod
    def from_env(cls, **overrides):
        vals = dict(
            slots=get_env("MXNET_SERVE_SLOTS", cls.slots, int),
            page_size=get_env("MXNET_SERVE_PAGE", cls.page_size, int),
            buckets=_parse_buckets(
                get_env("MXNET_SERVE_BUCKETS", "16,32,64", str)),
            max_new=get_env("MXNET_SERVE_MAX_NEW", cls.max_new, int),
            num_pages=get_env("MXNET_SERVE_PAGES", 0, int),
            exact=exact_mode(),
            spec_k=get_env("MXNET_SERVE_SPEC_K", 0, int),
            draft=get_env("MXNET_SERVE_DRAFT", "", str),
            quant=get_env("MXNET_SERVE_QUANT", "", str),
            kv_quant=get_env("MXNET_SERVE_KV_QUANT", "", str),
            prefix_pages=get_env("MXNET_SERVE_PREFIX_PAGES", 0, int),
            oversub=get_env("MXNET_SERVE_OVERSUB", False, bool),
            watermark=get_env("MXNET_SERVE_WATERMARK", 0, int),
            ttft_slo_ms=get_env("MXNET_SERVE_TTFT_SLO_MS", 0.0, float),
        )
        vals.update(overrides)
        return cls(**vals)

    def __post_init__(self):
        object.__setattr__(self, "buckets", _parse_buckets(self.buckets))
        object.__setattr__(self, "quant", quant_mode(self.quant))
        object.__setattr__(self, "kv_quant", quant_mode(self.kv_quant))
        if self.slots < 1 or self.page_size < 1 or self.max_new < 1:
            raise MXNetError("ServeConfig: slots/page_size/max_new must "
                             "be >= 1")
        if self.spec_k < 0:
            raise MXNetError("ServeConfig: spec_k must be >= 0")
        if self.prefix_pages < -1:
            raise MXNetError("ServeConfig: prefix_pages must be >= -1")
        if self.watermark < 0:
            raise MXNetError("ServeConfig: watermark must be >= 0")
        if self.ttft_slo_ms < 0:
            raise MXNetError("ServeConfig: ttft_slo_ms must be >= 0")
        if self.max_prompt and self.max_prompt < max(self.buckets):
            raise MXNetError(
                "ServeConfig: max_prompt %d is under the largest bucket %d"
                % (self.max_prompt, max(self.buckets)))
        for b in self.buckets:
            if b % self.page_size:
                raise MXNetError(
                    "ServeConfig: bucket %d is not a multiple of page_size "
                    "%d (prefill writes whole pages)" % (b, self.page_size))
    @property
    def longest_prompt(self):
        """The longest admissible fresh prompt: ``max_prompt`` where it is
        stated, else the largest bucket (so ``dataclasses.replace`` of the
        buckets moves it with them)."""
        return self.max_prompt or max(self.buckets)

    @property
    def max_pages_per_slot(self):
        worst = self.longest_prompt + self.max_new
        return -(-worst // self.page_size)

    @property
    def pool_pages(self):
        return self.num_pages or self.slots * self.max_pages_per_slot

    @property
    def spec_window(self):
        """Verify rows per speculative step: the last committed token
        plus the K proposals."""
        return self.spec_k + 1

    @property
    def spec_pad_pages(self):
        """All-trash page-table columns appended past the reservable
        range.  A verify/draft step writes up to ``spec_k`` rows beyond
        a slot's committed horizon; near the end of a request those can
        cross the reservation boundary, and the executables' page-index
        clip must then land on trash instead of aliasing the slot's
        last real page."""
        return -(-self.spec_k // self.page_size) if self.spec_k else 0


class _Executable(object):
    """One AOT-compiled entry point + its recompile guard.

    An executable's first argument is the session's parameters (the
    draft's, for ``draft``): some hundreds of leaves, assigned once.
    Their part of the call signature is described when the executable is
    built and kept here beside the tree it describes (``params``); a
    call describes only the arguments it makes anew.
    ``leaves_described`` counts the leaves described so far, at the
    build and by every call since."""

    __slots__ = ("name", "compiled", "jitted", "guard", "aval_sig",
                 "params", "params_sig", "memory", "traced", "fallbacks",
                 "leaves_described")

    def __init__(self, name, compiled, jitted, guard, params, aval_sig,
                 memory, traced=None):
        import jax

        self.name = name
        self.compiled = compiled
        self.jitted = jitted
        self.guard = guard
        self.aval_sig = aval_sig
        self.memory = memory  # dict from memory_analysis(), at compile time
        # what the block noted while it was traced (model.trace_notes)
        self.traced = traced or {}
        self.fallbacks = 0
        # the parameters lead the signature, as they lead the arguments
        self.params = params
        self.params_sig = aval_sig[:len(jax.tree.leaves(params))]
        self.leaves_described = len(aval_sig)

    def signature(self, args):
        """``signature_of(args)``, of which only what follows the
        parameters is described anew.  Parameters assigned since the
        last call are another tree: described here, once."""
        from ..compile_cache import signature_of

        if args[0] is not self.params:
            self.params = args[0]
            self.params_sig = signature_of((args[0],))
            self.leaves_described += len(self.params_sig)
        # None has no leaves, and keeps the others' paths what they are
        # in the whole tuple
        rest = signature_of((None,) + args[1:])
        self.leaves_described += len(rest)
        return self.params_sig + rest


class _OpenBlock(object):
    """The block a slot of a diffusion session is generating: its
    ``tokens`` (the mask token on the rows still masked), the denoise
    ``passes`` it has had, the pass each row was unmasked ``at`` (-1: it
    came with the prompt) and the confidence it was unmasked with
    (``conf``), how many leading rows were ``known`` from the
    prompt, how many after them are ``fresh`` (generated tokens the request
    asked for: a last block's tail is not), and the tokens the request may
    still be handed after this block (``budget``)."""

    __slots__ = ("tokens", "passes", "at", "conf", "known", "fresh",
                 "budget")

    def __init__(self, known, mask, length, budget):
        self.tokens = list(known) + [mask] * (length - len(known))
        self.passes = 0
        self.at = [-1] * length
        self.conf = [0.0] * length
        self.known = len(known)
        self.fresh = min(length - self.known, budget)
        self.budget = budget - self.fresh

    def following(self, mask):
        """The block that opens when this one is committed: all masks."""
        return _OpenBlock((), mask, len(self.tokens), self.budget)


class _Flight(object):
    """A step launched and not read yet: what the launch returned, device
    arrays that may not be ready (``tokens``, the next launch's input: a
    decode step's ``(slots,)`` next tokens, a block pass's ``(slots,
    block_length)`` rows after it, beside which the host reads a pass's
    ``unmasked`` flags and ``conf``; ``logits``), and the slots it
    ``carried``, slot -> the epoch the slot was in then."""

    __slots__ = ("tokens", "unmasked", "conf", "logits", "carried")

    def __init__(self, tokens, logits, carried, unmasked=None, conf=None):
        self.tokens, self.logits, self.carried = tokens, logits, carried
        self.unmasked, self.conf = unmasked, conf

    def read(self):
        """What the host reads of it, as numpy arrays (the wait for the
        launch); the logits stay where they are."""
        import numpy as np

        return [np.asarray(a) for a in (self.tokens, self.unmasked,
                                        self.conf) if a is not None]


class InferenceSession(object):
    """Compile-once serving session for the built-in transformer LM.

    ``params`` is a flat name->array dict (raw ``jax.numpy`` arrays,
    numpy arrays, or NDArray) under the training parameter names;
    ``num_heads`` is required unless recoverable from a checkpoint
    symbol, or unless ``model`` states the whole architecture (a
    :class:`ModelConfig`, used as given: nothing is inferred from
    shapes).  All executables are compiled in ``__init__`` — steady-state
    serving never traces.

    With ``config.spec_k > 0`` the session also hosts a draft proposer:
    pass ``draft_params`` (+ ``draft_num_heads``) explicitly, or let
    ``config.draft`` resolve one (``"ngram"``, ``"layers:N"``, or a
    checkpoint directory).  A parameterized draft gets its own
    :class:`PagedKVCache` (same slot/page geometry, draft dims) that
    the session keeps in exact lockstep with the target cache.
    """

    def __init__(self, params, num_heads=None, config=None,
                 draft_params=None, draft_num_heads=None, model=None):
        import jax
        import jax.numpy as jnp

        from .. import compile_cache, profiler

        compile_cache.ensure_initialized()
        self.config = config or ServeConfig.from_env()
        if config is None:
            # env-driven config: a cached autotune record for this
            # (model-fingerprint, backend) may override knobs (opt-in
            # via MXNET_AUTOTUNE; provenance rides the compile report)
            from .. import autotune as _autotune

            self.config = _autotune.apply_serve(self.config, params)
        cfg = self.config
        self.params = {}
        for k, v in params.items():
            if k in ("data", "softmax_label"):
                continue
            arr = getattr(v, "_data", v)
            self.params[k] = jnp.asarray(arr, jnp.float32)
        if model is not None:
            self.model = model.validate()
        elif num_heads is None:
            raise MXNetError("InferenceSession needs num_heads= (a GPT-2 "
                             "shaped parameter dict) or model=")
        else:
            self.model = config_from_params(self.params,
                                            num_heads=num_heads)
        # the block (model.BLOCKS): looked up here, once; everything the
        # session does that depends on the architecture goes through it
        self.block = block_of(self.model)
        # whether the block generates by diffusion over blocks: it then
        # has a ``block_pass`` in the place of ``decode_step``, prefill
        # yields :data:`NO_TOKEN` and a step's tokens are lists of
        # ``(token, pass, confidence)`` triples
        self.diffusion = hasattr(self.block, "block_pass")
        self._step_exe = "block_pass" if self.diffusion else "decode"
        self._check_block_support(draft_params)
        self.block.check_params(self.params, self.model)
        if cfg.longest_prompt + cfg.max_new > self.model.max_len:
            raise MXNetError(
                "ServeConfig worst case %d (prompt %d + max_new %d) exceeds "
                "the model's max_len %d"
                % (cfg.longest_prompt + cfg.max_new, cfg.longest_prompt,
                   cfg.max_new, self.model.max_len))
        self.cache = PagedKVCache(
            num_layers=self.model.num_layers,
            num_heads=self.model.kv_heads,
            head_dim=self.model.head_dim,
            page_size=cfg.page_size,
            num_pages=cfg.pool_pages,
            slots=cfg.slots,
            max_pages_per_slot=cfg.max_pages_per_slot,
            table_pad=cfg.spec_pad_pages,
            prefix_pages=cfg.prefix_pages,
            kv_quant=cfg.kv_quant,
            layer_kinds=self.model.kinds,
            window=self.model.sliding_window,
            ring_pages=self._ring_pages(self.model),
            latent_dim=self.block.latent_dim(self.model),
            state=self.block.state_shapes(self.model))
        # the block's own device state, taken and returned by every
        # executable beside the cache's pools
        self.counters = self.block.init_counters(self.model)
        # slot -> next token to feed the decoder (a diffusion block: the
        # slot's _OpenBlock)
        self._slot_tokens = {}
        self._slot_history = {}  # slot -> prompt + committed tokens
        # a slot's epoch: every prefill into it starts a new one, so a
        # decode step in flight can tell the request it carried from the
        # next one in the same slot
        self._slot_epoch = [0] * cfg.slots
        self._flight = None  # the step launched and not read yet
        # what a launch passes where every slot's tokens come from one
        # side (nearly always): on the device once, so that a launch
        # uploads neither a mask nor an array that nothing reads
        self._no_tokens = jnp.zeros(
            (cfg.slots, self.model.block_length) if self.diffusion
            else (cfg.slots,), jnp.int32)
        self._all_host = jnp.ones((cfg.slots,), jnp.bool_)
        self._none_host = jnp.zeros((cfg.slots,), jnp.bool_)
        self._slot_budget = {}  # diffusion: slot -> max_new, until prefill
        self._spec_stats = {"verify_steps": 0, "slot_steps": 0,
                            "proposed": 0, "accepted": 0, "committed": 0}
        self._decode_stats = {"steps": 0, "steps_ahead": 0,
                              "pages_visited": 0}
        self._prefill_stats = {"chunks": 0, "rows_visited": 0,
                               "rows_capacity": 0}
        # the key block of a prefill chunk's attention scan over the table
        self._scan_block = self.block.prefill_block(
            self.cache.table_width, cfg.page_size, bool(cfg.exact))
        self._resolve_draft(draft_params, draft_num_heads)
        if cfg.quant:
            # weight-only quantization of the at-rest params (the draft
            # shares the mode): eligible weights become {"q", "s"} code/
            # scale records that every executable dequantizes in-graph
            from .. import quantize as _quant

            # leaf by leaf, in place: a float32 leaf that nobody else
            # holds goes when its codes are there, so quantizing never
            # needs room for both copies of the whole model
            for name in list(self.params):
                self.params.update(_quant.quantize_params(
                    {name: self.params.pop(name)}, cfg.quant))
            if self.draft_params is not None:
                self.draft_params = _quant.quantize_params(
                    self.draft_params, cfg.quant)
        self._exes = {}
        # Recompile guards live in the process-global registry; embed the
        # model + capacity fingerprint in the guard name so two sessions
        # with different shapes (different avals) don't share a guard and
        # read each other's compiles as retraces.  Identical-config
        # sessions deliberately share: same avals -> same signature.
        # spec_k changes the table width (and adds executables), so it
        # is part of the fingerprint.
        self._guard_prefix = (
            "InferenceSession(%dL-d%d-h%d-V%d-s%d-p%d-m%d-n%d)"
            % (self.model.num_layers, self.model.d_model,
               self.model.num_heads, self.model.vocab_size, cfg.slots,
               cfg.page_size, cfg.max_pages_per_slot, cfg.pool_pages))
        if cfg.spec_k:
            self._guard_prefix += "-k%d" % cfg.spec_k
        if cfg.quant:
            # quantized avals differ from full-precision ones, so the
            # sessions must never share a guard fingerprint
            self._guard_prefix += "-q%s" % cfg.quant
        if cfg.kv_quant:
            # quantized KV pools change every executable's pool avals
            # (storage dtype + parallel scale arrays)
            self._guard_prefix += "-kv%s" % cfg.kv_quant
        self._guard_prefix += self.block.guard_tag(self.model)
        self._compile_all()

    def _ring_pages(self, model):
        """Pages of a slot's ring in each windowed layer of ``model`` (the
        target's, or the draft's): its block's rule, the one there is."""
        if "window" not in model.kinds:
            return 0
        return block_of(model).ring_pages(model, self.config)

    def _check_block_support(self, draft_params):
        """What the block cannot do yet (its ``REFUSES``) is refused
        here, by name, rather than served wrongly."""
        cfg = self.config
        asked = {"spec_k": cfg.spec_k or draft_params is not None,
                 "kv_quant": cfg.kv_quant, "prefix_pages": cfg.prefix_pages,
                 "oversub": cfg.oversub}
        refused = [name for name in self.block.REFUSES if asked[name]]
        if refused:
            raise MXNetError(
                "block %r does not support %s yet (%s)"
                % (self.model.block, ", ".join(refused),
                   self.block.REFUSES_WHY))

    def _resolve_draft(self, draft_params, draft_num_heads):
        """Pick the speculative proposer: explicit params, the host-side
        n-gram lookup, a layer-truncated copy of the target, or a
        checkpoint restore — then build its mirrored cache."""
        import jax.numpy as jnp

        cfg = self.config
        self.draft_params = None
        self.draft_model = None
        self.draft_cache = None
        self._draft_mode = "off"
        if not cfg.spec_k:
            if draft_params is not None:
                raise MXNetError(
                    "draft_params given but spec_k == 0 — set "
                    "ServeConfig.spec_k (MXNET_SERVE_SPEC_K) to enable "
                    "speculative decoding")
            return
        inherit_layers = None
        if draft_params is None:
            spec = cfg.draft or "ngram"
            if spec == "ngram":
                self._draft_mode = "ngram"
                return
            if spec.startswith("layers:"):
                n = int(spec.split(":", 1)[1])
                draft_params = _layer_truncated(self.params, n)
                draft_num_heads = draft_num_heads or self.model.num_heads
                # a layer-skip draft IS the target's first n blocks, so
                # it inherits their types (and the window) — its ring
                # writes then track the target's committed stream and
                # roll back lengths-only, exactly like the paged pools
                inherit_layers = n
            else:
                from ..checkpoint import CheckpointManager

                state = CheckpointManager(spec).load()
                if draft_num_heads is None and state.symbol is not None:
                    draft_num_heads = _num_heads_from_symbol(state.symbol)
                draft_params = dict(state.arg_params)
                draft_params.update(state.aux_params or {})
        self._draft_mode = "model"
        self.draft_params = {}
        for k, v in draft_params.items():
            if k in ("data", "softmax_label"):
                continue
            arr = getattr(v, "_data", v)
            self.draft_params[k] = jnp.asarray(arr, jnp.float32)
        self.draft_model = config_from_params(
            self.draft_params,
            num_heads=draft_num_heads or self.model.num_heads)
        if inherit_layers is not None and self.model.layer_types:
            self.draft_model = dataclasses.replace(
                self.draft_model,
                layer_types=self.model.layer_types[:inherit_layers],
                sliding_window=self.model.sliding_window).validate()
        if self.draft_model.vocab_size != self.model.vocab_size:
            raise MXNetError(
                "draft vocab %d != target vocab %d — a draft must share "
                "the target's token space"
                % (self.draft_model.vocab_size, self.model.vocab_size))
        if cfg.longest_prompt + cfg.max_new > self.draft_model.max_len:
            raise MXNetError(
                "draft max_len %d cannot cover the serve worst case %d"
                % (self.draft_model.max_len,
                   cfg.longest_prompt + cfg.max_new))
        self.draft_cache = PagedKVCache(
            num_layers=self.draft_model.num_layers,
            num_heads=self.draft_model.num_heads,
            head_dim=self.draft_model.head_dim,
            page_size=cfg.page_size,
            num_pages=cfg.pool_pages,
            slots=cfg.slots,
            max_pages_per_slot=cfg.max_pages_per_slot,
            table_pad=cfg.spec_pad_pages,
            prefix_pages=cfg.prefix_pages,
            kv_quant=cfg.kv_quant,
            layer_kinds=self.draft_model.kinds,
            window=self.draft_model.sliding_window,
            ring_pages=self._ring_pages(self.draft_model))

    # -- compilation ------------------------------------------------------
    def _aot(self, name, fn, params, avals, donate_argnums):
        """``TrainStep.compile``-style AOT build of one executable over
        ``avals``, the first of which stands for ``params``."""
        import jax

        from .. import compile_cache, profiler
        from ..compile_cache import registry, signature_of

        jitted = jax.jit(fn, donate_argnums=donate_argnums)
        hits_before = compile_cache.cache_stats()["hits"]
        t0 = time.perf_counter()
        with trace_notes() as traced:
            lowered = jitted.lower(*avals)
        compiled = lowered.compile(
            compiler_options=self.block.compiler_options(
                jax.default_backend()))
        dt = time.perf_counter() - t0
        cache_hit = compile_cache.cache_stats()["hits"] > hits_before
        flops = None
        code_bytes = None
        memory = {}
        try:
            cost = compiled.cost_analysis()
            if isinstance(cost, (list, tuple)):
                cost = cost[0] if cost else {}
            flops = float(cost.get("flops", 0.0)) or None
        except Exception:
            pass
        try:
            mem = compiled.memory_analysis()
            for attr in ("generated_code_size_in_bytes",
                         "argument_size_in_bytes",
                         "output_size_in_bytes",
                         "temp_size_in_bytes"):
                val = getattr(mem, attr, None)
                if val is not None:
                    memory[attr] = int(val)
            code_bytes = memory.get("generated_code_size_in_bytes")
        except Exception:
            pass
        profiler.compile_event("%s.%s" % (self._guard_prefix, name), dt,
                               flops=flops, executable_bytes=code_bytes,
                               cache_hit=cache_hit)
        guard = registry.guard("%s.%s" % (self._guard_prefix, name))
        sig = signature_of(avals)
        guard.observe(sig)
        self._exes[name] = _Executable(name, compiled, jitted, guard,
                                       params, sig, memory, traced)

    def _compile_all(self):
        import jax

        cfg = self.config
        i32 = jax.numpy.int32
        sds = jax.ShapeDtypeStruct

        def avals_of(tree):
            return jax.tree.map(lambda v: sds(v.shape, v.dtype), tree)

        # tree.map sees through quantized {"q", "s"} records, so the
        # executables' arguments are the 1-byte codes themselves
        param_avals = avals_of(self.params)
        # the cache's pools and the block's counters: one pytree argument
        # each, donated, and returned in the same two places.  A scalar an
        # executable has no use for (prefill's slot) is None, which has no
        # leaves: no executable gains an input.
        pools = avals_of(self.cache.pools)
        counters = avals_of(self.counters)
        # table width includes the speculative all-trash pad columns
        # (zero when spec_k == 0, so non-spec avals are unchanged)
        max_pages = self.cache.table_width
        block = self.block
        static = dict(cfg=self.model, page_size=cfg.page_size,
                      exact=bool(cfg.exact), kv_quant=cfg.kv_quant)

        def decode_fn(params, tokens, before, from_host, lengths, tables,
                      pools, counters):
            # a slot's token: the host's, or the launch before's own result
            # where that has not been read yet (``step(ahead=True)``)
            tokens = jax.numpy.where(from_host, tokens, before)
            return block.decode_step(params, tokens, lengths, tables, pools,
                                     counters, **static)

        if not self.diffusion:
            self._aot(
                "decode", decode_fn, self.params,
                (param_avals, sds((cfg.slots,), i32), sds((cfg.slots,), i32),
                 sds((cfg.slots,), jax.numpy.bool_), sds((cfg.slots,), i32),
                 sds((cfg.slots, max_pages), i32), pools, counters),
                donate_argnums=(6, 7))
        else:
            def block_pass_fn(params, tokens, before, from_host, quota,
                              fresh, lengths, tables, pools, counters):
                # a slot's open block: the host's, or the rows the pass
                # before left where that has not been read yet
                tokens = jax.numpy.where(from_host[:, None], tokens, before)
                return block.block_pass(params, tokens, quota, fresh,
                                        lengths, tables, pools, counters,
                                        **static)

            rows = sds((cfg.slots, self.model.block_length), i32)
            self._aot(
                "block_pass", block_pass_fn, self.params,
                (param_avals, rows, rows,
                 sds((cfg.slots,), jax.numpy.bool_), sds((cfg.slots,), i32),
                 sds((cfg.slots,), i32), sds((cfg.slots,), i32),
                 sds((cfg.slots, max_pages), i32), pools, counters),
                donate_argnums=(8, 9))

        # hybrid prefill takes a slot scalar (rings and SSM state are
        # slot-indexed, unlike the table-indirected pages)
        slot = sds((), i32) if self.cache.hybrid else None
        for bucket in cfg.buckets:
            def prefill_fn(params, tokens, length, offset, table_row,
                           pools, counters, slot):
                return block.prefill_forward(
                    params, tokens, length, offset, table_row, pools,
                    counters, slot=slot, **static)

            self._aot(
                "prefill_%d" % bucket, prefill_fn, self.params,
                (param_avals, sds((1, bucket), i32), sds((), i32),
                 sds((), i32), sds((max_pages,), i32), pools, counters,
                 slot),
                donate_argnums=(5, 6))

        if cfg.spec_k:
            w = cfg.spec_window

            def verify_fn(params, tokens, lengths, tables, pools, counters):
                return block.verify_step(params, tokens, lengths, tables,
                                         pools, counters, **static)

            self._aot(
                "verify", verify_fn, self.params,
                (param_avals, sds((cfg.slots, w), i32),
                 sds((cfg.slots,), i32), sds((cfg.slots, max_pages), i32),
                 pools, counters),
                donate_argnums=(4, 5))

        if self._draft_mode == "model":
            w = cfg.spec_window
            dblock = block_of(self.draft_model)
            dstatic = dict(static, cfg=self.draft_model)

            def draft_fn(params, tokens, n_feed, lengths, tables, pools,
                         counters):
                return dblock.draft_propose(params, tokens, n_feed, lengths,
                                            tables, pools, counters,
                                            **dstatic)

            self._aot(
                "draft", draft_fn, self.draft_params,
                (avals_of(self.draft_params), sds((cfg.slots, w), i32),
                 sds((cfg.slots,), i32), sds((cfg.slots,), i32),
                 sds((cfg.slots, max_pages), i32),
                 avals_of(self.draft_cache.pools), {}),
                donate_argnums=(5, 6))

    @classmethod
    def from_checkpoint(cls, directory, prefix="model", epoch=None,
                        num_heads=None, config=None):
        """Load params through the v2 elastic checkpoint restore and
        build a session.  An N-process training run's shards assemble
        in this single process; ``num_heads`` is read from the saved
        symbol when present."""
        from ..checkpoint import CheckpointManager

        state = CheckpointManager(directory, prefix=prefix).load(epoch=epoch)
        if num_heads is None and state.symbol is not None:
            num_heads = _num_heads_from_symbol(state.symbol)
        if num_heads is None:
            raise MXNetError(
                "from_checkpoint: pass num_heads= (the checkpoint symbol "
                "does not record a MultiHeadAttention op)")
        params = dict(state.arg_params)
        params.update(state.aux_params or {})
        return cls(params, num_heads=num_heads, config=config)

    # -- dispatch ---------------------------------------------------------
    def _dispatch(self, name, args):
        rec = self._exes[name]
        sig = rec.signature(args)
        rec.guard.observe(sig)
        if sig != rec.aval_sig:
            # Shape/dtype drift from the compiled avals (reported by the
            # guard above) runs through the lazy jit rather than failing
            # the request.  Nothing else does: an error of the compiled
            # executable — a device fault, out of memory — raises.
            rec.fallbacks += 1
            return rec.jitted(*args)
        return rec.compiled(*args)

    # -- request lifecycle ------------------------------------------------
    def bucket_for(self, prompt_len):
        for b in self.config.buckets:
            if prompt_len <= b:
                return b
        raise MXNetError(
            "prompt of %d tokens exceeds the largest prefill bucket %d"
            % (prompt_len, max(self.config.buckets)))

    def try_alloc(self, prompt_len, max_new=None, tokens=None,
                  resume=False):
        """Reserve a slot for a request, or return ``None`` when the
        cache can't admit it right now.

        A fresh prompt may be as long as ``config.max_prompt`` (by
        default the largest bucket).  ``tokens`` (the prompt's token ids)
        enables the prefix-cache lookup: published pages whose chain
        matches are mapped into the slot and
        :meth:`PagedKVCache.cached_len` reports the positions prefill may
        skip.  ``resume=True`` lifts the bucket-length check
        (a preempted request's re-prefill sequence — prompt plus already
        committed tokens — may exceed the largest bucket; chunked
        prefill covers it, and page capacity is still enforced because
        the resumed worst case equals the original one)."""
        if prompt_len < 1:
            raise MXNetError("empty prompt")
        if not resume and prompt_len > self.config.longest_prompt:
            # up to ``max_prompt`` a fresh prompt past the largest bucket
            # goes in chunks, as a resumed transcript does
            raise MXNetError(
                "prompt of %d tokens exceeds the longest admissible prompt "
                "%d (ServeConfig.max_prompt; the largest prefill bucket "
                "unless stated)" % (prompt_len, self.config.longest_prompt))
        max_new = self.config.max_new if max_new is None else int(max_new)
        if max_new > self.config.max_new:
            raise MXNetError("max_new %d exceeds the session cap %d"
                             % (max_new, self.config.max_new))
        toks = None
        if tokens is not None:
            toks = [int(t) for t in tokens]
            if len(toks) != int(prompt_len):
                raise MXNetError(
                    "try_alloc: tokens length %d != prompt_len %d"
                    % (len(toks), prompt_len))
        oversub = self.config.oversub
        slot = self.cache.alloc(prompt_len, max_new, tokens=toks,
                                oversub=oversub)
        if slot is not None and self.diffusion:
            self._slot_budget[slot] = max_new
        if slot is not None and self.draft_cache is not None:
            # identical geometry + identical alloc/release/publish
            # sequences keep the two caches' deterministic free lists
            # AND prefix indexes in lockstep (the draft's hit pages hold
            # draft-model KV for the same token chain)
            dslot = self.draft_cache.alloc(prompt_len, max_new,
                                           tokens=toks, oversub=oversub)
            if dslot != slot or (self.draft_cache.cached_len(dslot)
                                 != self.cache.cached_len(slot)):
                raise MXNetError(
                    "draft cache desync: target slot %r (cached %d) vs "
                    "draft slot %r (cached %d)"
                    % (slot, self.cache.cached_len(slot), dslot,
                       self.draft_cache.cached_len(dslot)
                       if dslot is not None else -1))
        return slot

    def _chunk_bucket(self, remaining):
        """Bucket for one prefill chunk: the smallest that fits, else
        the largest (a further chunk follows — max buckets are page
        multiples, so the next offset stays page-aligned)."""
        for b in self.config.buckets:
            if remaining <= b:
                return b
        return max(self.config.buckets)

    def prefill(self, slot, prompt_tokens):
        """Run the bucketed prefill for ``slot``; returns
        ``(first_token, last_logits)``: the token a host integer, the
        ``(vocab,)`` logits the executable's output as the device array
        it is (``np.asarray`` reads it; who does pays the transfer).

        Only the *uncached suffix* is computed: prompt positions covered
        by prefix-cache hit pages (``cache.cached_len``) are skipped,
        and the rest runs in page-aligned chunks through the per-bucket
        offset-taking executables — one chunk for a classic in-bucket
        prompt, several max-bucket chunks for a resumed transcript or a
        fresh prompt (``config.max_prompt``) longer than the largest
        bucket.  Afterwards the slot's full prompt pages are published
        into the prefix index for future admissions.

        A diffusion block (``serve/sdar_moe.py``) prefills the prompt's
        whole blocks and yields no token: ``(NO_TOKEN, None)``
        (:meth:`_block_prefill`)."""
        import numpy as np

        if self.diffusion:
            return self._block_prefill(slot, prompt_tokens)
        with _span("session.prefill", slot=slot) as sp:
            prompt = np.asarray(prompt_tokens, np.int32).reshape(-1)
            p = int(prompt.shape[0])
            cached = self.cache.cached_len(slot)
            sp.set(prompt=p, cached=cached)
            if not 0 <= cached < p:
                raise MXNetError("prefill: cached prefix %d outside prompt "
                                 "of %d tokens" % (cached, p))
            if self.config.kv_quant:
                # chaos site: a fault here fails THIS request before any
                # of its quantized pages/scales are written, so survivors'
                # pages and scale rows stay consistent
                from ..testing import faults

                faults.inject("kv_quant")
            if self.cache.n_window:
                # chaos site: fail before any ring row is written — the
                # slot's ring still holds only rows whose gather labels
                # fall outside every future mask, so survivors (and this
                # slot's re-admission) see a consistent ring
                from ..testing import faults

                faults.inject("kv_window")
            first, last_logits, bucket, chunks = self._prefill_chunks(
                slot, prompt, cached, p)
            sp.set(bucket=bucket, chunks=chunks)
            with _span("prefill.wait"):
                first = int(first)
            with _span("prefill.publish"):
                self._slot_epoch[slot] += 1
                self._slot_tokens[slot] = first
                self._slot_history[slot] = [int(t) for t in prompt] + [first]
                prompt_list = [int(t) for t in prompt]
                self.cache.register_prefix(slot, prompt_list)
                if self._draft_mode == "model":
                    self._draft_ingest(slot, prompt)
                    self.draft_cache.register_prefix(slot, prompt_list)
        return first, last_logits

    def _prefill_chunks(self, slot, prompt, off, end):
        """Rows ``off .. end - 1`` of ``prompt`` through the per-bucket
        executables in page-aligned chunks, a ``prefill.launch`` span each
        (its ``bucket``, and the ``largest`` there is)
        -> (the last chunk's first two results, its bucket, the chunks);
        ``lengths`` follows."""
        import numpy as np

        first = last_logits = None
        bucket = chunks = 0
        while off < end:
            with _span("prefill.launch") as sp:
                bucket = self._chunk_bucket(end - off)
                n = min(end - off, bucket)
                sp.set(bucket=bucket, largest=max(self.config.buckets))
                toks = np.zeros((1, bucket), np.int32)
                toks[0, :n] = prompt[off:off + n]
                self.cache.ensure_writable(slot, off, n)
                # host arrays: the launch uploads them, no call of its
                # own each
                args = (self.params, toks, np.int32(n), np.int32(off),
                        self.cache.table_row(slot), self.cache.pools,
                        self.counters,
                        np.int32(slot) if self.cache.hybrid else None)
                self._count_chunk(off, bucket)
                first, last_logits, self.cache.pools, self.counters = \
                    self._dispatch("prefill_%d" % bucket, args)
            off += n
            chunks += 1
            self.cache.lengths[slot] = off
        return first, last_logits, bucket, chunks

    def _block_prefill(self, slot, prompt_tokens):
        """:meth:`prefill` for a diffusion block: the prompt's whole blocks
        through the chunk loop (none for a prompt shorter than a block),
        then the slot's first open block, which the tokens left over
        begin.  -> (:data:`NO_TOKEN`, None)."""
        import numpy as np

        b = self.model.block_length
        with _span("session.prefill", slot=slot) as sp:
            prompt = np.asarray(prompt_tokens, np.int32).reshape(-1)
            p = int(prompt.shape[0])
            whole = p - p % b
            sp.set(prompt=p, cached=0)
            done, _, bucket, chunks = self._prefill_chunks(slot, prompt, 0,
                                                           whole)
            sp.set(bucket=bucket, chunks=chunks)
            with _span("prefill.wait"):
                if done is not None:
                    int(done)
            with _span("prefill.publish"):
                self._slot_epoch[slot] += 1
                self._slot_tokens[slot] = _OpenBlock(
                    prompt[whole:].tolist(), self.model.mask_token_id, b,
                    self._slot_budget.pop(slot, self.config.max_new))
        return NO_TOKEN, None

    def _count_chunk(self, offset, bucket):
        """One prefill chunk's share of ``prefill_report()``, for the
        reader its executable was traced with
        (:func:`~mxnet_tpu.ops.attention.paged_prefill_attention`).  The
        scan: in every layer that reads the slot's page table it visits
        whole key blocks up to the chunk's furthest horizon, bucket
        padding included, of the table's capacity.  The kernel: each tile
        of query rows walks whole key blocks up to its own last row's
        horizon (the row's token's position + 1); a layer counts the mean
        over the chunk's tiles."""
        stats, block = self._prefill_stats, self._scan_block
        capacity = self.cache.table_width * self.config.page_size
        traced = self._exes["prefill_%d" % bucket].traced
        by_kernel = traced.get("prefill_kernel_layers", 0)
        stats["chunks"] += 1
        stats["rows_capacity"] += self.cache.n_full * capacity
        stats["rows_visited"] += (self.cache.n_full - by_kernel) * min(
            -(-(offset + bucket) // block) * block, capacity)
        if by_kernel:
            # the notes are sums over the layers traced with the kernel
            tile, heads, keys = (traced["prefill_kernel_" + name] // by_kernel
                                 for name in ("tile_rows", "query_heads",
                                              "block_keys"))
            rows = bucket * heads
            # a tile's last row r belongs to token r // heads
            walked = [min(-(-min(offset + (min(end, rows) - 1) // heads + 1,
                                 capacity) // keys) * keys, capacity)
                      for end in range(tile, rows + tile, tile)]
            stats["rows_visited"] += by_kernel * sum(walked) / len(walked)

    def _draft_ingest(self, slot, prompt):
        """Teacher-force the prompt through the draft executable in
        W-token chunks so the draft cache holds the same positions the
        target prefill just wrote.  The single scan executable serves
        both ingest and propose (``n_feed`` switches the mode), keeping
        the executable count frozen.  Rows a chunk writes past its feed
        horizon — and the rows written for *other* active slots, whose
        ``n_feed`` is 0 — are junk beyond each slot's committed length;
        the next draft call overwrites those exact positions before any
        validity mask admits them."""
        import numpy as np

        cfg = self.config
        w = cfg.spec_window
        p = int(prompt.shape[0])
        # prefix hits skip ingestion too: the draft's hit pages already
        # hold draft-model KV for the cached positions (same chain, same
        # lockstep publication), and alloc left lengths at cached_len
        cached = self.draft_cache.cached_len(slot)
        self.draft_cache.ensure_writable(slot, cached, p - cached)
        for off in range(cached, p, w):
            chunk = prompt[off:off + w]
            toks = np.zeros((cfg.slots, w), np.int32)
            toks[slot, :len(chunk)] = chunk
            n_feed = np.zeros((cfg.slots,), np.int32)
            n_feed[slot] = len(chunk)
            self._dispatch_draft(toks, n_feed)
            self.draft_cache.lengths[slot] = off + len(chunk)

    def _dispatch_draft(self, tokens, n_feed):
        """One draft dispatch (ingest or propose); re-adopts the draft
        cache's donated pools and returns the (slots, W) greedy tokens."""
        outs, self.draft_cache.pools, _ = self._dispatch("draft", (
            self.draft_params, tokens, n_feed,
            self.draft_cache.lengths_arg(),
            self.draft_cache.device_tables(), self.draft_cache.pools, {}))
        return outs

    def step(self, ahead=False):
        """One token for every live slot from the single decode
        executable; returns ``(tokens, logits)`` where ``tokens`` maps
        slot -> emitted token id and ``logits`` is the (slots, vocab)
        array (inactive rows are garbage by design), left on the device:
        the step reads the ``(slots,)`` token vector and nothing else,
        and a caller that wants numbers converts (``np.asarray``).

        ``ahead=True`` lets the call launch the *next* step before it
        reads this one's tokens, and return with that step in flight:
        the following call reads it (and launches another, if it may
        too), so the host's work of a call runs beside the device's and
        not between two of its steps.  The next step takes its tokens
        from this one's result on the device.  What is in flight when a
        slot is released is dropped at its read: a call returns tokens
        only for the slots that the step it read carried and that have
        not been released since, so a slot prefilled while a step was in
        flight gets its first decode token one call later.  Without
        ``ahead`` the call leaves nothing in flight (it reads what an
        earlier call left, or launches and reads), which is what a caller
        that prefills and releases between steps as it pleases wants.  A
        session that speculates (``spec_k``) does not run ahead: its next
        input comes from the host.

        A diffusion block's step is one block pass and commits 0 to
        ``block_length`` tokens a slot: ``tokens`` then maps slot -> a list
        of ``(token, pass, confidence)`` triples, none for a slot whose
        block still held a mask when the pass began, every row the block
        generated for one whose block the pass committed (a last block's
        tail past the request's ``max_new`` among them: who asked for
        fewer drops it, as the scheduler does): the pass being the denoise
        pass of its block, 0-based, in which the token was unmasked, and
        the confidence the softmax's value at the token in that pass; the
        logits are (slots, block_length, vocab).  It runs ahead like a
        decode step (:meth:`_launch_pass`): the pass launched ahead takes
        the rows still masked from the unread pass's result on the device,
        and a slot prefilled while a pass was in flight gets its first
        pass one call later."""
        ahead = bool(ahead) and not self.config.spec_k
        launch = self._launch_pass if self.diffusion else self._launch
        with _cpu_span("session.step", live=len(self._slot_tokens),
                       ahead=int(ahead)) as sp:
            flight, self._flight = self._flight, None
            if flight is None or not any(
                    self._carries(flight, slot) for slot in flight.carried):
                # nothing in flight, or only rows nobody waits for
                flight = launch(None)
            if ahead:
                self._flight = launch(flight)
                self._decode_stats["steps_ahead"] += 1
            with _cpu_span("step.wait"):
                read = flight.read()
            with _span("step.commit"):
                # a slot released since the launch: dropped
                held = [slot for slot in flight.carried
                        if self._carries(flight, slot)]
                if self.diffusion:
                    out = self._commit_pass(held, sp, *read)
                else:
                    out = self._commit(held, *read)
        return out, flight.logits

    def _carries(self, flight, slot):
        """Whether ``slot`` still holds the request ``flight`` carried in
        it: not released since, nor released and prefilled again."""
        return (slot in self._slot_tokens
                and flight.carried.get(slot) == self._slot_epoch[slot])

    def _feed(self, tokens, before, host):
        """The three token arguments of a launch: the host's array, the
        unread launch's result (``before.tokens``) and the mask that says
        which a slot takes; ``host`` lists the slots whose ``tokens`` are
        the host's.  An idle slot's row is garbage either way."""
        import numpy as np

        if before is None:
            return tokens, self._no_tokens, self._all_host
        if not host:
            return self._no_tokens, before.tokens, self._none_host
        from_host = np.zeros((self.config.slots,), np.bool_)
        from_host[host] = True
        return tokens, before.tokens, from_host

    def _launch(self, before):
        """Launch one decode step for every live slot -> its
        :class:`_Flight`.  ``before`` is the step in flight whose result
        this one follows (``None``: every token is on the host): a slot it
        carried takes its token from that result on the device, any other
        (one a prefill has filled since) the host's.  What never depended
        on a read moves here: page upkeep, the page count, ``lengths``."""
        import numpy as np

        cfg = self.config
        with _span("step.prepare"):
            self._pre_dispatch(1)
            tokens = np.zeros((cfg.slots,), np.int32)
            carried, host = {}, []
            for slot, tok in self._slot_tokens.items():
                carried[slot] = self._slot_epoch[slot]
                if before is None or not self._carries(before, slot):
                    tokens[slot] = tok
                    host.append(slot)
            args = (self.params,) + self._feed(tokens, before, host) + (
                self.cache.lengths_arg(), self.cache.device_tables(),
                self.cache.pools, self.counters)
            # the pages this step's reader visits a full layer, each
            # context's new row included: the kernel every slot's own,
            # the loop the longest context's for every slot
            pages = decode_pages_visited(
                self.cache.lengths, cfg.page_size,
                self.cache.table_width, self._paged_kernel_layers() > 0)
            self._decode_stats["steps"] += 1
            self._decode_stats["pages_visited"] += pages
        with _span("step.launch"):
            next_toks, logits, self.cache.pools, self.counters = \
                self._dispatch("decode", args)
            for slot in carried:
                self.cache.lengths[slot] += 1
        return _Flight(next_toks, logits, carried)

    def _commit(self, held, next_np):
        """The read of a decode step: the token of every slot it carried
        and that is still ``held`` becomes the slot's next input.
        -> slot -> token."""
        out = {}
        for slot in held:
            tok = int(next_np[slot])
            self._slot_tokens[slot] = tok
            if slot in self._slot_history:
                self._slot_history[slot].append(tok)
            out[slot] = tok
        return out

    def _launch_pass(self, before):
        """:meth:`_launch` for a diffusion block: ONE pass of the
        block-pass executable over every live slot's open block -> its
        :class:`_Flight`.  ``before`` is the pass in flight whose result
        this one follows (``None``: every block is on the host).  The
        host's blocks are that pass's *input*, so of a slot it carried
        they say which pass it is: a denoise pass (the block holds a
        mask), and this one takes the rows it leaves from the device, its
        quota one pass further on; or the block's commit pass, and this
        one opens the block that follows, all masks, ``block_length`` rows
        further on (the read moves ``lengths`` there: here a copy is).
        Any other slot (one a prefill has filled since) feeds the host's
        block.  Page upkeep and the page count are the launch's."""
        import numpy as np

        cfg, model = self.config, self.model
        b, mask = model.block_length, model.mask_token_id
        with _span("step.prepare"):
            tokens = np.zeros((cfg.slots, b), np.int32)
            quota = np.full((cfg.slots,), -1, np.int32)
            fresh = np.zeros((cfg.slots,), np.int32)
            lengths = self.cache.lengths_arg()
            carried, host = {}, []
            for slot, blk in self._slot_tokens.items():
                carried[slot] = self._slot_epoch[slot]
                passes = blk.passes
                if before is None or not self._carries(before, slot):
                    tokens[slot] = blk.tokens
                    host.append(slot)
                elif mask in blk.tokens:
                    passes += 1     # the unread pass is one of its block's
                else:
                    blk = blk.following(mask)
                    tokens[slot], passes = blk.tokens, blk.passes
                    host.append(slot)
                    lengths[slot] += b
                quota[slot] = self.block.pass_quota(model, passes)
                fresh[slot] = blk.fresh
            self._pre_dispatch(b, lengths)
            args = (self.params,) + self._feed(tokens, before, host) + (
                quota, fresh, lengths, self.cache.device_tables(),
                self.cache.pools, self.counters)
            # the pages this pass's reader visits a layer, the block's
            # own rows included (decode_pages_visited adds one row)
            pages = decode_pages_visited(
                lengths + (b - 1), cfg.page_size,
                self.cache.table_width, self._paged_kernel_layers() > 0)
            self._decode_stats["steps"] += 1
            self._decode_stats["pages_visited"] += pages
        with _span("step.launch"):
            after, unmasked, conf, logits, self.cache.pools, \
                self.counters = self._dispatch("block_pass", args)
        return _Flight(after, logits, carried, unmasked, conf)

    def _commit_pass(self, held, sp, after, unmasked, conf):
        """The read of a block pass, for every slot it carried that is
        still ``held``: a block that went in with a mask takes the rows
        the pass unmasked (its denoise pass); one that went in with none
        is committed: ``lengths`` moves over it, its generated rows are
        handed out and the next block opens.  The step's span ``sp`` gets
        how many of each the pass held.
        -> slot -> its ``(token, pass, confidence)`` triples."""
        import numpy as np

        b, mask = self.model.block_length, self.model.mask_token_id
        out, denoise = {}, 0
        for slot in held:
            blk = self._slot_tokens[slot]
            if mask in blk.tokens:      # its denoise pass
                for row in np.flatnonzero(unmasked[slot]):
                    blk.tokens[row] = int(after[slot, row])
                    blk.at[row] = blk.passes
                    blk.conf[row] = float(conf[slot, row])
                blk.passes += 1
                denoise += 1
                out[slot] = []
                continue
            # its commit pass: the pages hold the block's rows
            self.cache.lengths[slot] += b
            out[slot] = [(blk.tokens[row], blk.at[row], blk.conf[row])
                         for row in range(blk.known, b)]
            self._slot_tokens[slot] = blk.following(mask)
        sp.set(denoise=denoise, commit=len(out) - denoise)
        return out

    def committing(self):
        """A diffusion block: slot -> the tokens the next call of
        :meth:`step` hands out for it, for every slot whose block the pass
        that call reads commits (the block holds no mask any more: the
        host has read them all).  What the host holds is the input of the
        one pass in flight, or of the pass the call launches where none
        is; a slot a prefill has filled since opens with a mask, so it is
        never among them."""
        mask = self.model.mask_token_id
        return {slot: blk.tokens[blk.known:]
                for slot, blk in self._slot_tokens.items()
                if mask not in blk.tokens}

    def spec_step(self, limits=None):
        """One speculative step for every active slot: draft proposes K
        tokens, ONE fixed-shape verify teacher-forces all ``W = K + 1``
        rows through the target, and greedy acceptance commits the
        longest agreeing prefix (1..W tokens — always at least one, the
        target's own greedy continuation, so progress is unconditional).

        ``limits`` (slot -> int) caps how many tokens a slot may commit
        this step (the scheduler passes ``max_new - emitted`` so a slot
        never overruns its page reservation).  Returns slot ->
        ``[committed tokens]``, bit-identical to what the same number of
        :meth:`step` calls would have emitted — exactness of the verify
        kernel makes acceptance a pure integer comparison.

        Both caches advance ``W`` rows then roll back the rejected
        suffix via :meth:`PagedKVCache.truncate`, so target and draft
        lengths stay equal and every retained row's KV belongs to a
        committed token.
        """
        import numpy as np

        cfg = self.config
        if not cfg.spec_k:
            raise MXNetError("spec_step on a session with spec_k == 0 — "
                             "set MXNET_SERVE_SPEC_K / ServeConfig.spec_k")
        out = {}
        if not self._slot_tokens:
            return out
        w, k = cfg.spec_window, cfg.spec_k
        active = sorted(self._slot_tokens)
        self._pre_dispatch(w)
        tokens = np.zeros((cfg.slots, w), np.int32)
        for slot, tok in self._slot_tokens.items():
            tokens[slot, 0] = tok
        if self._draft_mode == "model":
            dtoks = np.zeros((cfg.slots, w), np.int32)
            dtoks[:, 0] = tokens[:, 0]
            n_feed = np.ones((cfg.slots,), np.int32)
            tokens[:, 1:] = np.asarray(
                self._dispatch_draft(dtoks, n_feed))[:, :k]
        else:
            for slot in active:
                tokens[slot, 1:] = self._ngram_propose(slot, k)
        lims = {}
        for slot in active:
            limit = w
            if limits is not None:
                limit = max(1, min(w, int(limits.get(slot, w))))
            lims[slot] = limit
        greedy, _, self.cache.pools, self.counters = self._dispatch(
            "verify", (self.params, tokens,
                       self.cache.lengths_arg(),
                       self.cache.device_tables(), self.cache.pools,
                       self.counters))
        greedy = np.asarray(greedy)
        self._spec_stats["verify_steps"] += 1
        for slot in active:
            limit = lims[slot]
            # commit greedy[:c]: row 0 unconditionally, then one more
            # per proposal the target's previous row agreed with
            c = 1
            while c < limit and tokens[slot, c] == greedy[slot, c - 1]:
                c += 1
            committed = [int(t) for t in greedy[slot, :c]]
            self.cache.lengths[slot] += w
            self.cache.truncate(slot, w - c)
            if self.draft_cache is not None:
                self.draft_cache.lengths[slot] += w
                self.draft_cache.truncate(slot, w - c)
            self._slot_tokens[slot] = committed[-1]
            self._slot_history[slot].extend(committed)
            # proposals past the commit limit never had a chance, so
            # they don't count against the draft's acceptance rate
            self._spec_stats["slot_steps"] += 1
            self._spec_stats["proposed"] += limit - 1
            self._spec_stats["accepted"] += c - 1
            self._spec_stats["committed"] += c
            out[slot] = committed
        return out

    def _ngram_propose(self, slot, k, max_n=3):
        """Prompt-lookup draft: match the longest suffix n-gram of the
        slot's history (prompt + committed tokens, ending at the pending
        feed token) against an earlier occurrence and propose its
        continuation; shortfall pads with the last token.  Zero
        executables, zero params — the fallback draft."""
        hist = self._slot_history.get(slot) or [0]
        for n in range(min(max_n, len(hist) - 1), 0, -1):
            pat = hist[-n:]
            for start in range(len(hist) - n - 1, -1, -1):
                if hist[start:start + n] == pat:
                    cont = hist[start + n:start + n + k]
                    if cont:
                        out = list(cont)
                        while len(out) < k:
                            out.append(out[-1])
                        return out
        return [hist[-1]] * k

    def spec_report(self):
        """Speculation counters: ``acceptance_rate`` = accepted /
        proposed (proposals with a chance to commit), and
        ``tokens_per_verify_step`` = committed tokens per slot per
        verify dispatch (1..K+1 — the decode-throughput multiplier)."""
        rep = dict(self._spec_stats)
        rep["acceptance_rate"] = (
            rep["accepted"] / float(rep["proposed"])
            if rep["proposed"] else 0.0)
        rep["tokens_per_verify_step"] = (
            rep["committed"] / float(rep["slot_steps"])
            if rep["slot_steps"] else 0.0)
        return rep

    def _paged_kernel_layers(self):
        """Layers of the decode executable that were traced with the
        paged-attention kernel (``ops/paged_attention.py``); 0 where
        every full-attention layer runs the loop."""
        return self._exes[self._step_exe].traced.get("paged_kernel_layers",
                                                    0)

    def decode_report(self):
        """How much of the page tables the decode steps had to read,
        counted on the host from ``cache.lengths`` (no device read, no
        step pays for it), for the reader that was traced:
        ``paged_kernel_layers`` says which, the layers of the decode
        executable whose
        :func:`~mxnet_tpu.ops.attention.paged_decode_attention` is the
        Pallas kernel (every full-attention layer on a TPU where the call
        is eligible: ``ops/paged_attention.py:paged_attention_eligible``;
        0 on the CPU, under ``exact`` / ``kv_quant`` and for pools that
        fold their heads, where the ``fori_loop`` runs).  ``steps`` decode
        steps (a diffusion block: block passes) launched since the session
        was built, ``steps_ahead`` of them before the step in front of
        them had been read (``step(ahead=True)``); ``pages_visited`` the
        sum over
        the steps of the pages a full layer's reader visits, each context's new
        row included: with the kernel every slot's own
        ``ceil((length + 1) / page_size)``, an idle slot's one; with the
        loop the longest live context's pages for every slot (where the
        loop ends, to within the few pages that complete its last
        iteration).  ``blocks_visited`` = ``pages_visited / slots``, the
        page blocks a slot: an ``int`` under the loop (the longest
        context's), a ``float`` under the kernel (the slots' mean);
        ``blocks_capacity`` = steps x the table's width, what a reader
        that ignores the lengths would visit, and ``visited_share`` their
        ratio; ``kv_lanes`` the width of the K/V pools' last axis at rest,
        which says whether the cache folded the heads into it
        (:attr:`PagedKVCache.kv_lanes`).  ``None`` for a block whose
        decode step has no such reader (the latent block)."""
        by_kernel = self._paged_kernel_layers()
        pages, slots = self._decode_stats["pages_visited"], self.config.slots
        rep = self.block.decode_report(
            dict(self._decode_stats, blocks_visited=(
                pages / slots if by_kernel else pages // slots)),
            self.cache.table_width)
        if rep is not None:
            rep["kv_lanes"] = self.cache.kv_lanes
            rep["paged_kernel_layers"] = by_kernel
        return rep

    def prefill_report(self):
        """How much of the slots' page tables the prefill chunks' attention
        had to read, counted on the host where a chunk is launched (no
        device read, no chunk pays for it), for the reader that was
        traced: ``prefill_kernel_layers`` says which, the layers of the
        largest bucket's executable whose
        :func:`~mxnet_tpu.ops.attention.paged_prefill_attention` is the
        Pallas kernel (every full-attention layer on a TPU where the call
        is eligible, ``ops/paged_attention.py:paged_prefill_eligible``, and
        the same in every bucket's executable; 0 on the CPU, under
        ``exact`` / ``kv_quant``, and for folded pools under a short
        table, where the bounded scan runs).  ``chunks`` since the session
        was built; ``rows_visited`` the sum, over them and over the layers
        that read a page table (full attention, latent), of the key rows
        the reader visits.  The scan
        (:func:`~mxnet_tpu.ops.attention.decode_attention`): up to the
        chunk's furthest horizon ``offset + bucket``, rounded up to the
        block's scan block (its ``prefill_block``) and clipped to the
        table, an ``int``.  The kernel: the mean over the chunk's tiles of
        query rows of what a tile walks, whole key blocks up to its own
        furthest horizon, a ``float``: a chunk at offset 0 counts about
        half its bucket, where the scan counts the bucket.
        ``rows_capacity`` = chunks x layers x the table's rows, what a
        scan of the whole table visits, and ``visited_share`` their
        ratio.  (Under the scan the whole table is still *gathered* in
        front of it; a window layer's ring and a recurrent layer's state
        are not tables and count nothing.)"""
        rep = dict(self._prefill_stats)
        rep["visited_share"] = (
            rep["rows_visited"] / float(rep["rows_capacity"])
            if rep["rows_capacity"] else 0.0)
        rep["prefill_kernel_layers"] = self._exes[
            "prefill_%d" % max(self.config.buckets)].traced.get(
                "prefill_kernel_layers", 0)
        return rep

    def block_report(self):
        """What the block's executables counted on the device since the
        session was built (its ``report(counters, cfg)``), copied to the
        host only here (one small array; no step pays for it).  ``None``
        for a block that counts nothing (GPT-2).

        The latent block: ``assignments_asked`` = real tokens x experts
        per token over every expert layer of every prefill chunk and
        decode step (a decode step routes every slot's row, idle slots
        too); ``assignments_computed`` = those whose tile the expert loop
        reached: equal, or tokens were dropped.  ``distinct_experts`` is
        the sum over decode steps and expert layers of the experts at
        least one row reached (what a step had to read), ``expert_load``
        the (expert layers, experts) cumulative assignments.
        ``expert_kernel_layers`` (this block's and the KDA block's) is not
        a device count: the expert layers of the decode executable that
        were traced with the grouped-matmul kernel
        (``ops/grouped_matmul.py``), 0 where the ``fori_loop`` runs.
        ``latent_lanes`` (both blocks with a latent pool) is not one
        either: the width of the pool's rows at rest, whole lane tiles
        (:attr:`PagedKVCache.latent_lanes`).

        The Mamba-2 / grouped-query block: ``decode_steps``,
        ``prefill_chunks``, ``rows_valid`` and ``rows_padded`` (the rows
        its prefill chunks scanned, real and bucket padding),
        ``prefills_from_zero`` and ``prefills_carried`` (chunks that began
        a request on the zero state ``alloc`` left, and chunks that took
        up the state and the convolution context an earlier chunk wrote),
        and ``state_bytes_per_slot``.

        The window / full grouped-query block (``serve/laguna.py``): the
        share's router counts as the KDA block names them
        (``assignments_asked`` / ``_held`` / ``_computed``,
        ``distinct_held_experts``, ``rows_without_held_expert``);
        ``decode_steps``, ``prefill_chunks`` and
        ``prefill_chunks_continued`` (chunks at an offset past 0: a prompt
        longer than the largest bucket, or a resumed transcript); of the
        decode steps, summed over the window layers,
        ``window_rows_visited`` (every slot's whole ring is read) and
        ``window_rows_in_band`` (those of live slots inside the band), and
        summed over the full layers ``full_rows_live`` (the rows of live
        slots' contexts; what the paged reader visits is
        ``decode_report()``'s ``blocks_visited``); ``ring_rows``, the rows
        a slot's ring holds in a window layer, and ``kv_lanes``.

        The short-convolution / QK-normed grouped-query block
        (``serve/lfm2_moe.py``): the share's router counts, the six
        attention counts above (the two window ones stay 0: it has no
        window layer), and the Mamba-2 block's ``prefills_from_zero``,
        ``prefills_carried``, ``rows_valid`` and ``rows_padded`` for its
        convolutions; ``conv_layers``, ``full_layers``, ``window_layers``
        (0), ``expert_layers``, ``experts_held``, ``state_bytes_per_slot``
        and ``kv_lanes``.

        The block-diffusion / QK-normed grouped-query block
        (``serve/sdar_moe.py``): the share's router counts, the six
        attention counts (a block pass counts as a decode step; the two
        window ones stay 0) and ``diffusion_stats``: ``slot_passes`` (a
        live slot's share of one block-pass call) = ``denoise_slot_passes``
        + ``commit_slot_passes``, ``rows_unmasked_by_threshold`` and
        ``rows_unmasked_by_quota``, ``blocks_committed`` and
        ``tokens_committed`` (the generated tokens the requests asked for:
        a first block's prompt rows and a last block's tail are not);
        ``full_layers``, ``expert_layers``, ``experts_held``,
        ``block_length``, ``denoising_steps`` and ``kv_lanes``.

        Every block that holds a share of its experts (the KDA block and
        the four after it) also counts ``dispatch_rows``, the padded rows
        its expert layers laid out for their tiles (a share's prefill
        chunk lays out rows for what it holds, a round at a time:
        ``latent_moe._held_in_rounds``; a call that took a second round
        counts its rows twice), and ``dispatch_held``, the held
        assignments those rows served (``assignments_held`` under a name
        of its own, so that a reader who differences one count over a
        window differences both or neither).

        Every note of the decode executable's trace is copied in, so
        where the paged-attention kernel was traced its
        ``paged_kernel_layers`` shows here as in ``decode_report()``."""
        rep = self.block.report(self.counters, self.model)
        if rep is not None:
            rep.update(self._exes[self._step_exe].traced)
            if self.cache.latent_lanes is not None:
                rep["latent_lanes"] = self.cache.latent_lanes
            if self.cache.n_window:
                rep["ring_rows"] = self.cache.ring_tokens
        return rep

    moe_report = block_report   # the name it had while only routers counted

    def _pre_dispatch(self, rows, lengths=None):
        """Per-boundary page upkeep before a decode/verify/draft
        dispatch writes ``rows`` KV rows per active slot: grow
        oversubscribed slots to cover their next rows (a no-op under
        reservation admission — the pages are already mapped) and cross
        the copy-on-write guard so no write can land in a shared or
        published page.  The scheduler preempts on the watermark BEFORE
        stepping, so growth here never finds an empty pool.  ``lengths``:
        where the rows begin, a slot; the cache's own unless a launch
        ahead of a read says otherwise (:meth:`_launch_pass`)."""
        cfg = self.config
        if lengths is None:
            lengths = self.cache.lengths
        for slot in sorted(self._slot_tokens):
            n = int(lengths[slot])
            if cfg.oversub:
                self.cache.append_pages(slot, n + rows)
            self.cache.ensure_writable(slot, n, rows)
            if self.draft_cache is not None:
                dn = int(self.draft_cache.lengths[slot])
                if cfg.oversub:
                    self.draft_cache.append_pages(slot, dn + rows)
                self.draft_cache.ensure_writable(slot, dn, rows)

    def pages_short(self, rows=None):
        """Fresh pages the next decode boundary must obtain across all
        active slots — the scheduler compares this (plus its watermark)
        against :attr:`PagedKVCache.reclaimable_pages` to decide whether
        to preempt.  ``rows`` defaults to the step width (1, or the
        speculative window)."""
        if rows is None:
            rows = self.config.spec_window if self.config.spec_k else 1
        short = 0
        for slot in self._slot_tokens:
            short += self.cache.pages_short(
                slot, int(self.cache.lengths[slot]) + rows)
        return short

    def release(self, slot):
        """Give the slot and its pages back.  A step in flight that
        carried the slot still writes its one row (a block pass its
        block's), inside the request's own reservation; what it returns
        for the slot is dropped at the read, and whatever takes the pages
        or the slot next is ordered behind that step by the donated pools
        it takes."""
        self._slot_tokens.pop(slot, None)
        self._slot_history.pop(slot, None)
        self._slot_budget.pop(slot, None)
        self.cache.release(slot)
        if self.draft_cache is not None:
            self.draft_cache.release(slot)

    def active_slots(self):
        return sorted(self._slot_tokens)

    def reset_cold(self):
        """Return the session to a just-built state (replica rejoin
        after a supervisor eject): every slot released and the prefix
        index dropped, so the replica re-enters rotation COLD and warms
        its cache from live traffic — exactly what a restarted process
        would do, minus the recompile (the executables are immutable
        and carry no request state, so reusing them in-process models
        only the state a real restart loses)."""
        # a step in flight carried slots that are all released here:
        # nobody reads it
        self._flight = None
        # allocated-but-never-prefilled slots too (their holder died
        # between ``try_alloc`` and ``prefill``): the cache knows them
        for slot in sorted(set(self._slot_tokens)
                           | set(self.cache.active_slots())):
            try:
                self.release(slot)
            except MXNetError:
                pass
        self.cache.drop_prefix_index()
        if self.draft_cache is not None:
            self.draft_cache.drop_prefix_index()

    def state_report(self):
        """Occupancy snapshot for leak assertions: the gateway's
        cancellation tests take one before traffic and assert the
        post-traffic report is identical — freed slots, freed pages
        (refcount-aware: retained published-prefix pages are reported
        separately, since they deliberately survive release), and the
        draft cache in lockstep.  ``pool_bytes`` rides along to make
        "pool bytes return to baseline" observable (the pools are fixed
        buffers, so it must never move at all)."""
        out = {
            "active_slots": self.active_slots(),
            "free_slots": self.cache.free_slots,
            "free_pages": self.cache.free_pages,
            "retained_pages": self.cache.retained_pages,
            "pool_bytes": self.cache.pool_bytes(),
        }
        if self.draft_cache is not None:
            out["draft_free_slots"] = self.draft_cache.free_slots
            out["draft_free_pages"] = self.draft_cache.free_pages
        return out

    # -- accounting -------------------------------------------------------
    @property
    def executables(self):
        """name -> compiled executable.  Fixed set for the session's
        lifetime: prefill per bucket + decode (a diffusion block:
        ``block_pass`` in its place), plus verify (and draft, for a
        parameterized proposer) when ``spec_k > 0``."""
        return {name: rec.compiled for name, rec in self._exes.items()}

    def memory_analysis(self, name="decode"):
        """Compile-time ``memory_analysis()`` numbers for one
        executable — the decode entry is the flat per-step watermark."""
        return dict(self._exes[name].memory)

    def params_bytes_at_rest(self):
        """Bytes the serving params occupy as held — quantized codes +
        scales under ``config.quant``, full precision otherwise (the
        bench shrink ratios compare the two)."""
        from ..quantize import at_rest_bytes

        return at_rest_bytes(self.params)

    def dequantized_params(self):
        """Plain float32 view of the serving params — for a quantized
        session, exactly the weight values the executables' in-graph
        dequantization computes (elementwise convert + multiply is
        bit-identical on host and in-graph).  Full-precision sessions
        get the params as-is."""
        from ..quantize import dequantize_params

        return dequantize_params(self.params)

    def guard_report(self):
        """name -> the executable's recompile guard (calls, traces,
        signatures) and ``leaves_described``: the parameters' leaves
        once, then a dozen at most for every call, whatever the depth."""
        return {name: dict(rec.guard.snapshot(),
                           leaves_described=rec.leaves_described)
                for name, rec in self._exes.items()}

    def fallback_count(self):
        return sum(rec.fallbacks for rec in self._exes.values())


def _layer_truncated(params, n):
    """Derive a draft from the target's own weights: its first ``n``
    decoder blocks plus the shared embedding / final-LN / head — the
    self-speculative "layer skip" draft.  ``n`` equal to the full depth
    yields an (expensive, always-accepting) identity draft, useful for
    exactness tests."""
    total = 0
    while "blk%d_attn_in_weight" % total in params:
        total += 1
    n = int(n)
    if not 1 <= n <= total:
        raise MXNetError(
            "draft layers:%d out of range (target has %d blocks)"
            % (n, total))
    keep = {"tok_embed_weight", "pos_embed", "final_ln_gamma",
            "final_ln_beta", "lm_head_weight", "lm_head_bias"}
    out = {}
    for key, val in params.items():
        if key in keep or (key.startswith("blk")
                           and int(key[3:].split("_", 1)[0]) < n):
            out[key] = val
    return out


def _num_heads_from_symbol(symbol):
    """Pull ``num_heads`` out of a saved symbol's MultiHeadAttention
    node, if the checkpoint recorded one."""
    try:
        graph = json.loads(symbol.tojson())
    except Exception:
        return None
    for node in graph.get("nodes", []):
        op = (node.get("op") or "").lower()
        if "multiheadattention" in op.replace("_", ""):
            attrs = node.get("attrs") or node.get("param") or {}
            if "num_heads" in attrs:
                try:
                    return int(attrs["num_heads"])
                except (TypeError, ValueError):
                    pass
    return None
