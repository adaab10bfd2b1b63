"""The two seams of the serving path.

* A block is a module with a fixed surface (``serve/model.py``'s
  ``BLOCKS`` table; docs/serving.md, "Adding a block"), and
  ``InferenceSession`` reaches the architecture through nothing else: a
  further block registered here, out of the GPT-2 functions under another
  name and holding ONLY the surface's names, is served end to end by an
  unedited session.
* ``PagedKVCache.pools`` is the one owner of the cache's device state:
  for every session variant that exists, what the executables return is
  what the cache holds, ``pool_bytes()`` is its sum, and a copy-on-write
  copies the page in every pool that pages index and in no other.
"""
import dataclasses
import types

import numpy as np
import pytest

from mxnet_tpu import serve
from mxnet_tpu.base import MXNetError
from mxnet_tpu.serve import kv_cache, latent_moe
from mxnet_tpu.serve import model as serve_model
from mxnet_tpu.serve.scheduler import Request, Scheduler

# what the session may ask of a block, and no more (docs/serving.md)
SURFACE = ("validate", "check_params", "init_params", "full_forward",
           "latent_dim", "state_shapes", "init_counters", "prefill_forward",
           "decode_step",
           "REFUSES", "REFUSES_WHY", "compiler_options", "report",
           "decode_report", "prefill_block", "guard_tag")
SPECULATIVE = ("verify_step", "draft_propose")
# a block that generates by diffusion has these where the others have
# their decode_step
DIFFUSION = ("block_pass", "pass_quota")

GPT2 = serve.ModelConfig(vocab_size=61, num_layers=3, d_model=32,
                         num_heads=2, max_len=64)
# the same block with its layers stated: one full layer, two windowed
GPT2_WINDOWED = dataclasses.replace(
    GPT2, sliding_window=8,
    layer_types=("full_attention", "sliding_attention", "sliding_attention"))
LATENT = serve.ModelConfig(
    block="deepseek_v3", vocab_size=61, num_layers=2, d_model=32,
    num_heads=2, max_len=64, qk_nope_head_dim=8, qk_rope_head_dim=4,
    v_head_dim=8, kv_lora_rank=12, d_ff=48, first_k_dense=1, moe_d_ff=16,
    n_routed_experts=4, num_experts_per_tok=2, n_shared_experts=1)
GRANITE = serve.ModelConfig(
    block="granitemoehybrid", vocab_size=61, num_layers=3, d_model=32,
    num_heads=4, num_key_value_heads=2, max_len=64, d_ff=48,
    rms_norm_eps=1e-5, layer_types=("mamba", "attention", "mamba"),
    mamba_n_heads=4, mamba_d_head=16, mamba_d_state=8, mamba_chunk_size=8,
    embedding_multiplier=12.0, attention_multiplier=0.125,
    residual_multiplier=0.22, logits_scaling=8.0, tie_word_embeddings=True)
BAILING = serve.ModelConfig(
    block="bailing_hybrid", vocab_size=61, num_layers=4, d_model=32,
    num_heads=2, max_len=64, qk_nope_head_dim=8, qk_rope_head_dim=4,
    v_head_dim=8, kv_lora_rank=12, d_ff=48, first_k_dense=1, moe_d_ff=16,
    n_routed_experts=16, num_experts_per_tok=4, n_shared_experts=1,
    routed_scaling_factor=2.5, n_group=4, topk_group=2, experts_held=(4, 4),
    layer_types=("kda", "kda", "mla", "kda"), kda_head_dim=8,
    kda_chunk_size=8, rope_theta=6e6)
LAGUNA = serve.ModelConfig(
    block="laguna", vocab_size=61, num_layers=3, d_model=32, num_heads=2,
    num_key_value_heads=2, max_len=64, attn_head_dim=8,
    num_attention_heads_per_layer=(2, 4, 4), sliding_window=8,
    layer_types=("full_attention", "sliding_attention", "sliding_attention"),
    rope_parameters={
        "full_attention": dict(
            rope_theta=5e5, rope_type="yarn", factor=128,
            original_max_position_embeddings=8192, beta_slow=1, beta_fast=32,
            attention_factor=1.485, partial_rotary_factor=0.5),
        "sliding_attention": dict(rope_theta=1e4)},
    mlp_only_layers=(0,), d_ff=48, moe_d_ff=16, n_routed_experts=16,
    num_experts_per_tok=4, shared_expert_intermediate_size=16,
    routed_scaling_factor=2.5, scoring_func="softmax", experts_held=(4, 4))
LFM2 = serve.ModelConfig(
    block="lfm2_moe", vocab_size=61, num_layers=4, d_model=32, num_heads=4,
    num_key_value_heads=2, max_len=64, attn_head_dim=8, rope_theta=1e6,
    rms_norm_eps=1e-5, layer_types=("conv", "full_attention", "conv", "conv"),
    conv_L_cache=3, d_ff=48, first_k_dense=1, moe_d_ff=16,
    n_routed_experts=16, num_experts_per_tok=4, experts_held=(4, 4),
    tie_word_embeddings=True)
SDAR = serve.ModelConfig(
    block="sdar_moe", vocab_size=61, num_layers=2, d_model=32, num_heads=4,
    num_key_value_heads=2, max_len=64, attn_head_dim=8, rope_theta=1e6,
    moe_d_ff=16, n_routed_experts=16, num_experts_per_tok=4,
    scoring_func="softmax", experts_held=(4, 4), block_length=4,
    mask_token_id=60, denoising_steps=4, confidence_threshold=0.9)
PHI4 = serve.ModelConfig(
    block="phi4flash", vocab_size=61, num_layers=8, d_model=32, num_heads=2,
    num_key_value_heads=1, max_len=64, d_ff=48, sliding_window=8,
    layer_types=("mamba", "sliding_attention") * 2 + (
        "mamba", "full_attention", "gmu", "cross_attention"),
    mamba_d_state=4, mamba_dt_rank=4, rms_norm_eps=1e-5,
    tie_word_embeddings=True)
Q3N = serve.ModelConfig(
    block="qwen3_next", vocab_size=61, num_layers=4, d_model=32, num_heads=4,
    num_key_value_heads=2, max_len=64, attn_head_dim=8,
    partial_rotary_factor=0.5, layer_types=("linear_attention",) * 3 + (
        "full_attention",), linear_num_key_heads=2, linear_num_value_heads=4,
    linear_key_head_dim=8, linear_value_head_dim=8, gdn_chunk_size=4,
    moe_d_ff=16, n_routed_experts=16, num_experts_per_tok=4,
    n_shared_experts=1, shared_expert_gate=True, scoring_func="softmax",
    experts_held=(4, 4))
CONF = dict(slots=3, page_size=8, buckets=(8, 16), max_new=8)


@pytest.mark.parametrize("name", sorted(serve_model.BLOCKS))
def test_every_block_provides_the_surface(name):
    block = serve_model.block_of(dataclasses.replace(GPT2, block=name))
    step = DIFFUSION if hasattr(block, "block_pass") else ("decode_step",)
    assert (step == DIFFUSION) == (name == "sdar_moe")
    assert [n for n in set(SURFACE) - {"decode_step"} | set(step)
            if not hasattr(block, n)] == []
    # the speculative steps, unless the block says it refuses spec_k
    if "spec_k" not in block.REFUSES:
        assert [n for n in SPECULATIVE if not hasattr(block, n)] == []


def served(sess):
    reqs = [Request(rid=i, prompt=np.random.default_rng(i).integers(
        0, GPT2.vocab_size, 5 + 3 * i).tolist(), max_new=6, arrival_s=0.0)
        for i in range(4)]
    done, _ = Scheduler(sess, policy="continuous").run(reqs)
    assert not any(r.failed for r in done), [r.error for r in done]
    assert sess.fallback_count() == 0
    return {r.rid: list(r.tokens) for r in done}


def test_a_third_block_is_served_by_an_unedited_session(monkeypatch):
    twin = types.SimpleNamespace(**{
        n: getattr(serve_model, n) for n in SURFACE + SPECULATIVE})
    monkeypatch.setitem(serve_model.BLOCKS, "gpt2_twin", twin)
    cfg = dataclasses.replace(GPT2, block="gpt2_twin").validate()
    params = serve.init_params(cfg, seed=3)
    conf = serve.ServeConfig(spec_k=2, draft="layers:1", **CONF)
    want = served(serve.InferenceSession(params, num_heads=2, config=conf))
    sess = serve.InferenceSession(params, model=cfg, config=conf)
    assert sess.block is twin and sess.model.block == "gpt2_twin"
    assert sorted(sess.executables) == [
        "decode", "draft", "prefill_16", "prefill_8", "verify"]
    assert served(sess) == want
    assert all(len(toks) == 6 for toks in want.values())
    assert sess.moe_report() is None
    assert sess.decode_report() is not None
    with pytest.raises(MXNetError, match="the architecture"):
        serve.InferenceSession(
            params, model=dataclasses.replace(cfg, max_len=32), config=conf)


# block -> (model, ServeConfig settings, layers that read a page table,
# the scan's key block); the table is (16 + 8) / 8 = 3 pages = 24 rows
PREFILL_SCANS = {
    "dense": (GPT2, dict(), 3, 8),
    "dense_one_full_layer": (GPT2_WINDOWED, dict(), 1, 8),
    "latent": (LATENT, dict(), 2, 8),
    # not exact, a table within 512 keys is one block: nothing to skip
    "latent_one_block": (LATENT, dict(exact=False), 2, 24),
    "granite": (GRANITE, dict(), 1, 8),
    "bailing": (BAILING, dict(), 1, 8),
    "lfm2": (LFM2, dict(), 1, 8),
    "sdar": (SDAR, dict(), 2, 8),
}


@pytest.mark.parametrize("name", sorted(PREFILL_SCANS))
def test_prefill_report_counts_rows_to_the_chunks_horizon(name):
    """``prefill_report()`` is host arithmetic where a chunk is launched:
    a prompt of 5 goes in bucket 8 and its scan ends at row 8, one of 13
    in bucket 16 and ends at row 16 (bucket padding sees that far), in
    whole key blocks, of a table of 24 rows, once for each layer that
    reads a page table."""
    model, settings, layers, block = PREFILL_SCANS[name]
    sess = serve.InferenceSession(
        serve.init_params(model, seed=5), model=model,
        config=serve.ServeConfig(**dict(CONF, **settings)))
    assert sess.block.prefill_block(3, 8, sess.config.exact) == block
    assert sess.prefill_report() == {
        "chunks": 0, "rows_visited": 0, "rows_capacity": 0,
        "visited_share": 0.0, "prefill_kernel_layers": 0}
    for seed, n in enumerate((5, 13)):
        slot = sess.try_alloc(n, 4)
        sess.prefill(slot, np.random.default_rng(seed).integers(
            0, model.vocab_size, n).tolist())
    visited = layers * sum(-(-bucket // block) * block for bucket in (8, 16))
    assert sess.prefill_report() == {
        "chunks": 2, "rows_visited": visited,
        "rows_capacity": layers * 2 * 24,
        "visited_share": visited / (layers * 2 * 24.0),
        "prefill_kernel_layers": 0}     # the CPU runs the scan
    assert visited == (layers * 24 if block == 8 else layers * 48)
    assert type(sess.prefill_report()["rows_visited"]) is int
    sess.step()         # a decode step is no chunk
    assert sess.prefill_report()["chunks"] == 2


@pytest.mark.parametrize("name, by_kernel, tile, heads", [
    ("dense", 3, 8, 1), ("dense_one_full_layer", 1, 4, 1),
    ("lfm2", 1, 8, 2), ("sdar", 2, 24, 4), ("sdar", 1, 8, 4)],
    ids=["dense", "one_full_layer", "lfm2", "sdar", "sdar_one_of_two"])
def test_prefill_report_counts_for_the_reader_that_was_traced(
        monkeypatch, name, by_kernel, tile, heads):
    """``prefill_kernel_layers`` and the kernel's tiling are what a
    bucket's executable noted while it was traced
    (``ops/attention.py:paged_prefill_attention``; nothing on the CPU).
    With them the session counts, for each of those layers, the mean over
    the chunk's tiles of query rows of the key blocks a tile walks to its
    own last row's horizon, a float; the other layers count the scan's.
    The counts are host arithmetic: the notes alone switch them, the
    executables are the CPU's scan.  Here a key block of 8 rows under a
    table of 24, a prompt of 5 in bucket 8 and one of 13 in bucket 16."""
    model, settings, layers, block = PREFILL_SCANS[name]
    sess = serve.InferenceSession(
        serve.init_params(model, seed=5), model=model,
        config=serve.ServeConfig(**dict(CONF, **settings)))
    notes = {"prefill_kernel_layers": by_kernel,
             "prefill_kernel_tile_rows": by_kernel * tile,
             "prefill_kernel_query_heads": by_kernel * heads,
             "prefill_kernel_block_keys": by_kernel * 8}
    for bucket in (8, 16):
        exe = sess._exes["prefill_%d" % bucket]
        monkeypatch.setattr(exe, "traced", dict(exe.traced, **notes))
    for seed, n in enumerate((5, 13)):
        slot = sess.try_alloc(n, 4)
        sess.prefill(slot, np.random.default_rng(seed).integers(
            0, model.vocab_size, n).tolist())
    rep = sess.prefill_report()
    assert rep["prefill_kernel_layers"] == by_kernel and rep["chunks"] == 2

    def walked(bucket):
        """The mean over a chunk's tiles, from offset 0, of the rows up to
        the tile's last token's horizon in whole blocks of 8."""
        ends = [min(end, bucket * heads) for end in
                range(tile, bucket * heads + tile, tile)]
        return sum(-(-((end - 1) // heads + 1) // 8) * 8
                   for end in ends) / len(ends)

    # bucket 8 is one block whatever the tile; bucket 16 in tiles of 8
    # rows of one head walks 8 then 16, of 4 tokens x 2 heads 8, 8, 16, 16
    assert walked(8) == 8 and walked(16) == {
        (8, 1): 12, (4, 1): 12, (8, 2): 12, (24, 4): 40 / 3,
        (8, 4): 12}[tile, heads]
    assert rep["rows_visited"] == pytest.approx(
        by_kernel * (walked(8) + walked(16))
        + (layers - by_kernel) * (8 + 16), rel=1e-12)
    assert type(rep["rows_visited"]) is float
    assert rep["rows_visited"] < layers * (8 + 16)     # the scan's count
    assert rep["visited_share"] == rep["rows_visited"] / rep["rows_capacity"]


VARIANTS = {
    "classic": (GPT2, dict()),
    "kv_int8": (GPT2, dict(kv_quant="int8")),
    "hybrid": (GPT2_WINDOWED, dict()),
    "hybrid_kv_int8": (GPT2_WINDOWED, dict(kv_quant="int8")),
    "spec": (GPT2, dict(spec_k=2, draft="layers:1")),
    "latent": (LATENT, dict()),
    "granite": (GRANITE, dict()),
    "bailing": (BAILING, dict()),
    "laguna": (LAGUNA, dict()),
    "laguna_long_prompts": (LAGUNA, dict(max_prompt=40)),
    "lfm2": (LFM2, dict()),
    "lfm2_long_prompts": (LFM2, dict(max_prompt=40)),
    "sdar": (SDAR, dict()),
    "sdar_long_prompts": (SDAR, dict(max_prompt=40)),
}


def avals(pools):
    return {name: (tuple(p.shape), str(p.dtype))
            for name, p in pools.items()}


@pytest.mark.parametrize("variant", sorted(VARIANTS))
def test_the_cache_owns_its_device_state(variant):
    cfg, over = VARIANTS[variant]
    params = serve.init_params(cfg, seed=5)
    sess = serve.InferenceSession(
        params, model=cfg, config=serve.ServeConfig(**dict(CONF, **over)))
    caches = [c for c in (sess.cache, sess.draft_cache) if c is not None]
    built = [avals(c.pools) for c in caches]
    assert all(built)

    # what every executable returns is what the cache was built with
    prompt = list(range(1, 12))
    slot = sess.try_alloc(len(prompt), 4, tokens=prompt)
    sess.prefill(slot, prompt)
    assert [avals(c.pools) for c in caches] == built
    sess.spec_step() if sess.config.spec_k else sess.step()
    assert [avals(c.pools) for c in caches] == built
    assert sess.fallback_count() == 0
    assert set(sess.counters) == set(sess.block.init_counters(sess.model))

    for cache in caches:
        pools = cache.pools
        assert cache.pool_bytes() == sum(p.nbytes for p in pools.values())
        assert sess.state_report()["pool_bytes"] == sess.cache.pool_bytes()
        # the paged pools are those whose second axis is the page; the
        # slot-private state pools are what the block says a slot holds
        assert set(cache.paged) == {
            n for n, p in pools.items()
            if p.shape[1] == cache.num_pages + 1}
        assert cache.paged
        assert {n: (pools[n].shape[0], tuple(pools[n].shape[2:]),
                    str(pools[n].dtype)) for n in cache.state} \
            == {n: (layers, tuple(shape), dtype) for n, (layers, shape, dtype)
                in sess.block.state_shapes(sess.model).items()}
        assert cache.hybrid == bool(cache.state or cache.n_window)

        # copy-on-write: a second holder of the slot's first page, then a
        # write into it; every paged pool gets the page copied, bit for
        # bit, and every other pool is left as it was
        page = cache._pages_of[slot][0]
        before = {n: np.array(p) for n, p in pools.items()}
        assert all(np.any(before[n][:, page] != 0) for n in cache.paged)
        cache._refcount[page] += 1
        assert cache.ensure_writable(slot, 0, 1) == 1
        new = cache._pages_of[slot][0]
        assert new != page and cache._tables[slot, 0] == new
        for name, pool in cache.pools.items():
            after = np.array(pool)
            if name in cache.paged:
                np.testing.assert_array_equal(after[:, new],
                                              before[name][:, page])
                after[:, new] = before[name][:, new]
            np.testing.assert_array_equal(after, before[name])
        cache._drop_ref(page)           # the second holder lets go
    sess.release(slot)
    assert sess.cache.free_pages == sess.cache.num_pages


def test_pool_names_are_the_caches_alone():
    """A latent cache holds one pool under its own name and no ``k_pool``;
    the latent block's counters are not among the pools."""
    sess = serve.InferenceSession(
        serve.init_params(LATENT, seed=5), model=LATENT,
        config=serve.ServeConfig(**CONF))
    assert list(sess.cache.pools) == ["latent_pool"]
    # 12 + 4 values a row, in one lane tile (kv_cache.latent_pool_shape)
    assert sess.cache.pools["latent_pool"].shape \
        == kv_cache.latent_pool_shape(2, 3 * 3 + 1, 8, 16) \
        == (2, 10, 8, 128)
    assert sess.block_report()["latent_lanes"] == sess.cache.latent_lanes \
        == 128
    assert list(sess.counters) == ["moe_stats"]
    assert sess.counters["moe_stats"].shape == (
        2, latent_moe.stats_size(LATENT))
    assert sess.decode_report() is None
    assert sess.moe_report()["decode_steps"] == 0


def test_a_latent_pool_and_state_pools_in_one_session():
    """The fourth block's cache: one latent pool for its one ``"mla"``
    layer, two state pools for its three ``"kda"`` layers, no ``k_pool``;
    its counters are not among the pools."""
    sess = serve.InferenceSession(
        serve.init_params(BAILING, seed=5), model=BAILING,
        config=serve.ServeConfig(**CONF))
    assert sorted(sess.cache.pools) == ["conv_state", "kda_state",
                                        "latent_pool"]
    assert sess.cache.pools["latent_pool"].shape \
        == kv_cache.latent_pool_shape(1, 3 * 3 + 1, 8, 16)
    assert sess.block_report()["latent_lanes"] == sess.cache.latent_lanes \
        == 128
    assert sess.cache.pools["kda_state"].shape[:2] == (3, CONF["slots"])
    assert sess.cache.paged == ("latent_pool",) and sess.cache.hybrid
    assert list(sess.counters) == ["moe_stats"]
    assert sess.decode_report() is None
    assert sess.block_report()["experts_held"] == 4


def test_rings_by_the_models_window_beside_pages():
    """The fifth block's cache: K/V pages for its one full layer, a ring
    of the model's window (8 rows: one page here) a slot for each of its
    two window layers, whatever the buckets; two counters, neither among
    the pools; the paged reader's report, since its full layers run it."""
    sess = serve.InferenceSession(
        serve.init_params(LAGUNA, seed=5), model=LAGUNA,
        config=serve.ServeConfig(**dict(CONF, max_prompt=40)))
    assert sorted(sess.cache.pools) == ["k_pool", "kw_pool", "v_pool",
                                        "vw_pool"]
    assert sess.cache.pools["k_pool"].shape \
        == kv_cache.kv_pool_shape(1, 3 * 6 + 1, 8, 2, 8) == (1, 19, 8, 16)
    assert sess.cache.pools["kw_pool"].shape == (2, 3, 8, 2 * 8)
    assert sess.cache.paged == ("k_pool", "v_pool") and sess.cache.hybrid
    assert sess.cache.ring_tokens == sess.block_report()["ring_rows"] == 8
    # the GPT-2 block's rule, which follows the buckets, is not asked
    assert serve_model.ring_pages(LAGUNA, sess.config) == 4
    assert sorted(sess.counters) == ["attn_stats", "moe_stats"]
    assert sess.decode_report()["kv_lanes"] \
        == sess.block_report()["kv_lanes"] == 16
    assert sess.block_report()["experts_held"] == 4
    assert sorted(sess.executables) == ["decode", "prefill_16", "prefill_8"]


def test_a_state_pool_and_nothing_else_in_three_layers_of_four():
    """The sixth block's cache: K/V pages for its one attention layer, and
    for its three convolution layers one state pool of two rows a slot and
    nothing else; three counters, none among the pools; the paged reader's
    report, since its attention layer runs it; fresh prompts in chunks
    with buckets + 1 executables."""
    sess = serve.InferenceSession(
        serve.init_params(LFM2, seed=5), model=LFM2,
        config=serve.ServeConfig(**dict(CONF, max_prompt=40)))
    assert sorted(sess.cache.pools) == ["conv_state", "k_pool", "v_pool"]
    assert sess.cache.pools["k_pool"].shape \
        == kv_cache.kv_pool_shape(1, 3 * 6 + 1, 8, 2, 8) == (1, 19, 8, 16)
    assert sess.cache.pools["conv_state"].shape == (3, 3, 2, 32)
    assert sess.cache.state == ("conv_state",)
    assert sess.cache.paged == ("k_pool", "v_pool") and sess.cache.hybrid
    assert (sess.cache.n_full, sess.cache.n_ssm, sess.cache.n_window) \
        == (1, 3, 0)
    assert sorted(sess.counters) == ["attn_stats", "conv_stats", "moe_stats"]
    assert sess.decode_report()["kv_lanes"] \
        == sess.block_report()["kv_lanes"] == 16
    rep = sess.block_report()
    assert (rep["experts_held"], rep["state_bytes_per_slot"]) \
        == (4, 3 * 2 * 32 * 4)
    assert sorted(sess.executables) == ["decode", "prefill_16", "prefill_8"]
    prompt = list(range(1, 38))
    slot = sess.try_alloc(len(prompt), 4, tokens=prompt)
    sess.prefill(slot, prompt)
    assert sess.block_report()["prefills_carried"] == 2
    assert sess.prefill_report()["chunks"] == 3


def test_pages_alone_and_a_block_pass_in_the_place_of_decode():
    """The seventh block's cache: K/V pages in every layer and nothing
    else (a slot's open block is the session's, a few integers); three
    counters, none among the pools; the paged reader's report, since its
    passes run it; fresh prompts in chunks, and a block-pass executable
    where the others have decode: buckets + 1."""
    sess = serve.InferenceSession(
        serve.init_params(SDAR, seed=5), model=SDAR,
        config=serve.ServeConfig(**dict(CONF, max_prompt=40)))
    assert sorted(sess.cache.pools) == ["k_pool", "v_pool"]
    assert sess.cache.pools["k_pool"].shape \
        == kv_cache.kv_pool_shape(2, 3 * 6 + 1, 8, 2, 8) == (2, 19, 8, 16)
    assert sess.cache.paged == ("k_pool", "v_pool")
    assert not sess.cache.hybrid and not sess.cache.state
    assert sorted(sess.counters) == ["attn_stats", "diffusion_stats",
                                     "moe_stats"]
    assert sess.diffusion
    assert sorted(sess.executables) == ["block_pass", "prefill_16",
                                        "prefill_8"]
    prompt = list(range(1, 38))
    slot = sess.try_alloc(len(prompt), 4, tokens=prompt)
    assert sess.prefill(slot, prompt) == (-1, None)
    assert sess.prefill_report()["chunks"] == 3      # 16 + 16 + 4 of 37
    assert int(sess.cache.lengths[slot]) == 36
    out, logits = sess.step()
    assert out == {slot: []} and logits.shape == (3, 4, 61)
    rep = sess.block_report()
    assert (rep["experts_held"], rep["block_length"], rep["slot_passes"],
            rep["prefill_chunks_continued"]) == (4, 4, 1, 2)
    assert sess.decode_report()["kv_lanes"] == rep["kv_lanes"] == 16
    assert sess.decode_report()["steps"] == 1


def test_a_block_whose_layers_share_a_cache_is_served_by_an_unedited_session():
    """The eighth block, whose second half owns no cache: buckets + 1
    executables, four streams through three slots, a step ahead, pools for
    one full layer, two rings and three states of eight layers."""
    sess = serve.InferenceSession(
        serve.init_params(PHI4, seed=5, scale=0.3), model=PHI4,
        config=serve.ServeConfig(**CONF))
    assert sess.block is serve_model.block_of(PHI4)
    assert sorted(sess.executables) == ["decode", "prefill_16", "prefill_8"]
    assert PHI4.kinds.count("shared") == 2
    assert (sess.cache.n_full, sess.cache.n_window, sess.cache.n_ssm,
            sess.cache.n_shared) == (1, 2, 3, 2)
    assert sess.cache.pools["k_pool"].shape[0] == 1
    tokens = served(sess)
    assert all(len(toks) == 6 for toks in tokens.values())
    rep = sess.block_report()
    assert (rep["prefill_chunks"], rep["cross_rows"], rep["shared_readers"]) \
        == (4, 4, 2)
    assert sess.decode_report()["steps_ahead"] > 0
    with pytest.raises(MXNetError, match="does not support.*spec_k"):
        serve.InferenceSession(
            serve.init_params(PHI4, seed=5), model=PHI4,
            config=serve.ServeConfig(spec_k=2, **CONF))


def test_a_block_with_a_matrix_state_a_head_is_served_by_an_unedited_session():
    """The ninth block: buckets + 1 executables, four streams through three
    slots, a step ahead, pages for the one attention layer beside two
    state pools for the three DeltaNet layers, a prompt of three chunks
    that carries its state, and the shared expert's gate as an option of
    the latent block's expert layer."""
    params = serve.init_params(Q3N, seed=5, scale=0.3)
    sess = serve.InferenceSession(
        params, model=Q3N, config=serve.ServeConfig(max_prompt=40, **CONF))
    assert sess.block is serve_model.block_of(Q3N)
    assert sorted(sess.executables) == ["decode", "prefill_16", "prefill_8"]
    assert (sess.cache.n_full, sess.cache.n_ssm) == (1, 3)
    assert sorted(sess.cache.state) == ["conv_state", "gdn_state"]
    assert sess.cache.pools["gdn_state"].shape == (3, 3, 4, 8, 8)
    assert sess.cache.pools["conv_state"].shape == (3, 3, 3, 64)
    assert "blk0_shared_expert_gate_weight" in params
    tokens = served(sess)
    assert all(len(toks) == 6 for toks in tokens.values())
    long = Request(rid=9, prompt=np.random.default_rng(9).integers(
        0, 61, 37).tolist(), max_new=3, arrival_s=0.0)
    done, _ = Scheduler(sess, policy="continuous").run([long])
    assert not done[0].failed and len(done[0].tokens) == 3
    rep = sess.block_report()
    assert (rep["prefill_chunks"], rep["prefills_from_zero"],
            rep["prefills_carried"]) == (7, 5, 2)
    assert sess.decode_report()["steps_ahead"] > 0
    with pytest.raises(MXNetError, match="does not support.*kv_quant"):
        serve.InferenceSession(params, model=Q3N, config=serve.ServeConfig(
            kv_quant="int8", **CONF))


# block -> a model of it: with windowed layers where the block has any
RINGS = {"gpt2": GPT2_WINDOWED, "deepseek_v3": LATENT,
         "granitemoehybrid": GRANITE, "bailing_hybrid": BAILING,
         "laguna": LAGUNA, "lfm2_moe": LFM2, "sdar_moe": SDAR,
         "phi4flash": PHI4, "qwen3_next": Q3N}


@pytest.mark.parametrize("name", sorted(serve_model.BLOCKS))
def test_a_ring_is_sized_by_the_block_and_by_nothing_else(name):
    """One rule for a ring's size, the block's ``ring_pages(model,
    config)``: a session over a model with windowed layers builds rings of
    exactly that many pages (the GPT-2 block's follow the largest bucket,
    laguna's the window alone), and a model with none builds no
    ``kw_pool``.  No ``ServeConfig`` field takes part."""
    model = RINGS[name]
    assert model.block == name
    conf = serve.ServeConfig(**dict(CONF, buckets=(8, 24)))
    sess = serve.InferenceSession(serve.init_params(model, seed=5),
                                  model=model, config=conf)
    windowed = model.kinds.count("window")
    if not windowed:
        assert "kw_pool" not in sess.cache.pools
        assert sess.cache.ring_pages == sess.cache.n_window == 0
        return
    pages = sess.block.ring_pages(model, conf)
    assert pages == {"gpt2": (8 + 24 - 1 + 7) // 8 + 1, "laguna": 1,
                     "phi4flash": 1}[name]
    assert sess.cache.ring_pages == pages
    assert sess.cache.window == model.sliding_window == 8
    assert sess.cache.pools["kw_pool"].shape[:3] == (windowed, 3, pages * 8)
