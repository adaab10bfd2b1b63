"""A serving cell of a block that generates by diffusion over blocks of a
few tokens: ``jobs/serve_closed_long.py``'s closed loop (the same clients,
window and generic checks, ``serve_config`` handed to ``ServeConfig``
WHOLE), for a session whose prefill yields no token and whose step commits
0 to ``block_length`` tokens a slot.

No accepted kind can drive it: each stamps ``out[0][slot]`` as ONE token a
live slot a step and opens a request's stream with the token its prefill
returned, and their comparison holds a served token to the row before it
of a causal forward.  Every helper they export is taken from them through
``manifest.load_module`` (``Handover``; ``length_pool``, ``percentile``,
``steady_host_allocator``); what is written out here is the run's own loop,
the stamps and the comparison:

* a prompt draws its ids below the mask token's (the held slice's last
  row), and a stream opens with no token at the prefill's return;
* a step's wrapper stamps, for every slot whose block was committed, the
  block's tokens at the step's return, each with the denoise pass of its
  block in which it was unmasked and the confidence it was unmasked with,
  as the program hands them out (the rows
  past the request's asked length among them: they were rows of the
  block's passes; the request's own tokens are the first ``max_new``);
  time to first token runs from due to the first block's commit, and a
  gap is between consecutive tokens of a request as delivered, so three in
  four are 0;
* a step's stamp holds the live slots, the K/V rows inside their horizons
  (committed rows and the open block's own, which is what a pass attends)
  and how many of them were in a denoise pass;
* ``correct``: after the window, for each of ``check_requests`` finished
  requests (the longest among them), ONE block-causal forward of the
  request's final tokens gives every layer's keys and values
  (``reference.context``); then for the first and the last generated block
  and a seeded sample of ``check_blocks - 2`` more (all of them where there
  are no more), and for every denoise pass of such a block, the
  reference's forward of that pass (``reference.denoise_logits``) with
  exactly the rows visible that were visible to it: (a) the gap by which
  each row unmasked in that pass lies under the reference's best logit of
  its row (the mask token's left out), as a share of the row's spread,
  widest and mean (``unmasked_token_gap``, ``unmasked_token_mean_gap``);
  (b) the gap by which the least confident row unmasked in that pass lies
  under the reference's n-th most confident still-masked row, n the rows
  unmasked, as a share of the most confident (the choice of WHICH row; 0
  where every row unmasked clears the threshold by the reference's own
  confidence), widest and mean (``unmask_choice_gap``,
  ``unmask_choice_mean_gap``); (c) the confidence the program says each
  row was unmasked with (it hands it out with the token) over the
  reference's of the same row in the same pass, less 1: the root of the
  mean square over every unmasked row (``unmask_confidence_error``): a
  number every row adds to, where (a) and (b) are zero until an order
  flips.  A commit that left a denoise pass's K/V in the pages shows in
  every later block of the request;
* the run's log says where the window's wall went (the step, prefill and
  tick calls: their sum, median, p99 and five longest: a run that draws a
  stalled call or two reads as many tokens/s less at the same median pass)
  and what Python's cyclic collector took of it (milliseconds);
* the generic checks of every serving run (no compile in the window, no
  lazy-jit fallback, buckets + 1 executables, every request at its asked
  length, streams that match their requests token for token and pass for
  pass, no assignment dropped).

It fails at once, before any weight is made and before it builds a
``ServeConfig``, on a program whose ``serve.model.BLOCKS`` cannot serve the
family's block.

For the next ``benchmark`` issue: the ``serve_closed*`` kinds are now six
and should become one (PERF.md, Open questions).
"""
import gc
import statistics
import time

import jax
import jax.numpy as jnp
import numpy as np

import manifest
import weights
from manifest import sized
from probes import compile_count, peak_bytes


class Stream(object):
    """What the benchmark saw leave the session for one request: the rows
    its committed blocks generated, each with the pass it was unmasked in
    and the time of its block's commit."""

    __slots__ = ("prompt", "tokens", "passes", "confidences", "times")

    def __init__(self, prompt):
        self.prompt, self.tokens, self.passes = prompt, [], []
        self.confidences, self.times = [], []


def run(cell, args, recorder, tracer, t_process, log):
    from mxnet_tpu import serve

    base = manifest.load_module("jobs", "serve_closed", cell.root)
    handover = manifest.load_module("jobs", "serve_closed_block",
                                    cell.root).Handover
    family = cell.family()
    cfg = sized(cell.config, args.rehearse)
    job = sized(cell.traffic, args.rehearse)
    blocks = getattr(getattr(serve, "model", None), "BLOCKS", {})
    if family.BLOCK not in blocks:
        # fail now, before 4.9 GB of weights are made and before a
        # ModelConfig is asked for a field it may not have
        raise manifest.ManifestError(
            "this program cannot serve family %s: its serve.model.BLOCKS "
            "has no %r (it has %s)" % (cell.family_name, family.BLOCK,
                                       sorted(blocks) or "no such table"))
    model = serve.ModelConfig(**family.model_config(cfg))
    base.steady_host_allocator(job.get("host_allocator", {}))
    rng = np.random.default_rng(args.seed)
    words = weights.seed_words(args.seed)
    ref_lm = family.reference
    made = weights.maker(ref_lm.spec(cfg), cfg.get("init_std"))

    def make(seed_words):
        return family.published_init(made(seed_words), cfg)

    # the whole group, and over it the control of the correctness check:
    # the program's own next lower precision, which has to come out not
    # correct
    sc = dict(job["serve_config"], **(job["control"] if args.control else {}))
    config = serve.ServeConfig(**dict(sc, buckets=tuple(sc["buckets"])))
    session = serve.InferenceSession(handover(make(words)), model=model,
                                     config=config)
    b = cfg["block_length"]
    n_exec = len(session.executables)
    log("serve: %s, %d slots, buckets %s, max_prompt %d, max_new %d, blocks "
        "of %d in %d denoising steps at threshold %g, %d executables, cache "
        "pools %.3f GB", cell.config_name, config.slots, list(config.buckets),
        config.max_prompt, config.max_new, b, cfg["denoising_steps"],
        cfg["confidence_threshold"], n_exec, session.cache.pool_bytes() / 1e9)

    # -- traffic: the same set of sizes for every seed; in the order the
    # traffic file's ``order_seed`` gives, or in the seed's own without it
    pool = base.length_pool(job)
    order = (np.random.default_rng(job["order_seed"])
             if "order_seed" in job else rng)
    left = []

    def next_sizes():
        if not left:
            left.extend(pool[i] for i in order.permutation(len(pool)))
        return left.pop()

    def reports():
        return session.block_report(), session.decode_report()

    due = {}                      # rid -> perf_counter when it was due
    state = {"rid": 0, "done": 0, "window": None, "end": None,
             "compiles": None, "open": None, "close": None}

    def new_request(now_s):
        p_len, o_len = next_sizes()
        rid = state["rid"]
        state["rid"] += 1
        due[rid] = time.perf_counter()
        # below the mask token's id: a prompt holds no mask
        prompt = rng.integers(0, cfg["mask_token_id"], p_len).tolist()
        return serve.Request(rid=rid, prompt=prompt, max_new=o_len,
                             arrival_s=now_s)

    def followup(req, now_s):
        state["done"] += 1
        if state["window"] is None and state["done"] >= job["warmup_requests"]:
            state["compiles"] = compile_count()
            state["open"] = reports()
            if args.trace:
                tracer.start()
            log("serve: window opens after %d requests", state["done"])
            state["window"] = time.perf_counter()
            state["end"] = state["window"] + args.seconds
        if state["end"] is not None and time.perf_counter() >= state["end"]:
            return None
        return new_request(now_s)

    # -- the benchmark's own wrappers: spans, token stamps, live rows
    open_streams, streams, step_live, prefills = {}, [], [], []
    pauses = []     # (start, seconds, generation) of the collector's runs

    def on_gc(phase, info, started=[0.0]):
        if phase == "start":
            started[0] = time.perf_counter()
        else:
            pauses.append((started[0], time.perf_counter() - started[0],
                           info["generation"]))

    gc.callbacks.append(on_gc)

    def after_prefill(out, t0, t1, call_args):
        slot, prompt = call_args[0], call_args[1]
        stream = Stream(prompt)
        prefills.append((t0, len(prompt)))
        open_streams[slot] = stream
        streams.append(stream)

    def after_step(out, t0, t1, call_args):
        live = denoise = 0
        for slot, pairs in out[0].items():
            stream = open_streams[slot]
            # what the pass attended: the committed rows and its own block
            live += (len(stream.prompt) + len(stream.tokens)) // b * b + b
            denoise += not pairs
            for token, unmasked_at, confidence in pairs:
                stream.tokens.append(token)
                stream.passes.append(unmasked_at)
                stream.confidences.append(confidence)
                stream.times.append(t1)
        step_live.append((t0, t1, len(out[0]), live, denoise))
        if state["end"] is not None and t1 >= state["end"]:
            if tracer.running:
                tracer.stop()
            if state["close"] is None:
                state["close"] = reports()

    def after_release(out, t0, t1, call_args):
        open_streams.pop(call_args[0], None)

    recorder.wrap(session, "prefill", after=after_prefill)
    recorder.wrap(session, "step", after=after_step)
    recorder.wrap(session, "release", after=after_release)
    sched = serve.Scheduler(session)
    recorder.wrap(sched, "tick")
    first = [new_request(0.0) for _ in range(job["clients"])]
    t_run = time.perf_counter()
    for rid in range(len(first)):
        due[rid] = t_run
    done, _ = sched.run(first, followup=followup)
    gc.callbacks.remove(on_gc)
    if tracer.running:
        tracer.stop()
    w0, w1 = state["window"], state["end"]
    if w0 is None:
        raise RuntimeError("the run ended before %d warm-up requests had "
                           "finished" % job["warmup_requests"])
    new_compiles = compile_count() - state["compiles"]
    fallbacks = session.fallback_count()
    peak = peak_bytes()
    # the window's own counts
    (b0, d0), (b1, d1) = state["open"], state["close"] or reports()
    block = {k: (b1[k] - b0[k] if k in family.COUNTED else b1[k])
             for k in b1}
    decode = {k: d1[k] - d0[k] for k in ("steps", "blocks_visited")}
    log("serve: the block in the window: %s; the paged reader (%d layers by "
        "the kernel) visited %d page blocks in %d passes",
        " ".join("%s %d" % kv for kv in sorted(block.items())),
        d1["paged_kernel_layers"], decode["blocks_visited"], decode["steps"])
    nan = float("nan")
    dropped = block.get("assignments_held", nan) \
        - block.get("assignments_computed", nan)
    log("serve: %s slot passes (%s denoise, %s commit) made %s tokens: %.4f "
        "passes a token; %s rows cleared the threshold and %s were the "
        "quota's; of %s assignments %s fell on the experts held here and %s "
        "were computed; %.1f distinct held experts a pass a layer",
        block.get("slot_passes"), block.get("denoise_slot_passes"),
        block.get("commit_slot_passes"), block.get("tokens_committed"),
        block.get("slot_passes", nan) / max(
            block.get("tokens_committed", 0), 1),
        block.get("rows_unmasked_by_threshold"),
        block.get("rows_unmasked_by_quota"), block.get("assignments_asked"),
        block.get("assignments_held"), block.get("assignments_computed"),
        block.get("distinct_held_experts", nan) / max(
            block.get("decode_steps", 0) * block.get("expert_layers", 0), 1))

    # -- requests and their streams, matched by prompt; a request's own
    # tokens are the first max_new of its stream's
    by_prompt = {}
    for stream in streams:
        by_prompt.setdefault(tuple(stream.prompt), []).append(stream)
    failed, mismatched, tokens_in_window = 0, 0, 0
    in_window = []                # (request, stream) due inside the window
    for req in done:
        found = by_prompt.get(tuple(req.prompt), [])
        stream = found.pop(0) if found else None
        n = req.max_new
        if stream is not None:
            tokens_in_window += sum(w0 <= t < w1 for t in stream.times[:n])
        if req.failed or len(req.tokens) != n:
            failed += 1
            continue
        if stream is None or stream.tokens[:n] != list(req.tokens) \
                or stream.passes[:n] != list(req.passes):
            mismatched += 1
            continue
        if w0 <= due[req.rid] < w1:
            in_window.append((req, stream))
    ttft = [s.times[0] - due[r.rid] for r, s in in_window]
    gaps = [t1 - t0 for r, s in in_window
            for t0, t1 in zip(s.times[:r.max_new], s.times[1:r.max_new])
            if t1 < w1]
    log("serve: %d requests finished, %d due inside the window; %d tokens "
        "in %.3f s; TTFT median %.2f ms over %d; gap median %.2f ms over %d, "
        "between blocks %.2f ms", len(done), len(in_window), tokens_in_window,
        w1 - w0, 1e3 * statistics.median(ttft), len(ttft),
        1e3 * statistics.median(gaps), len(gaps),
        1e3 * statistics.median([g for g in gaps if g > 0] or [0.0]))
    log("serve: TTFT p95 %.2f ms, gap p95 %.2f ms",
        1e3 * base.percentile(ttft, 95), 1e3 * base.percentile(gaps, 95))

    # where the window's wall went: a run that loses a second to a few
    # long calls reads as many tokens/s less at the same median pass
    for name in ("step", "prefill", "tick"):
        walls = sorted(t1 - t0 for n, t0, t1 in recorder.spans
                       if n == name and w0 <= t0 < w1)
        log("serve: the window's %d %s calls: %.3f s in all, median %.2f ms, "
            "p99 %.2f, the five longest %s", len(walls), name, sum(walls),
            1e3 * statistics.median(walls or [0.0]),
            1e3 * base.percentile(walls or [0.0], 99),
            " ".join("%.1f" % (1e3 * w) for w in walls[-5:]))
    inside = [(s, g) for t, s, g in pauses if w0 <= t < w1]
    log("serve: the collector ran %d times in the window (%d of the oldest "
        "generation), %.1f ms in all, the longest %.1f ms", len(inside),
        sum(g == 2 for _, g in inside), 1e3 * sum(s for s, _ in inside),
        1e3 * max([s for s, _ in inside] or [0.0]))
    sixth = (w1 - w0) / 6
    log("serve: median pass wall before the window %.2f ms, by sixth of "
        "the window: %s", 1e3 * statistics.median(
            [s[1] - s[0] for s in step_live if s[1] < w0] or [0.0]),
        " ".join("%.2f" % (1e3 * statistics.median(
            [s[1] - s[0] for s in step_live
             if w0 + i * sixth <= s[0] < w0 + (i + 1) * sixth] or [0.0]))
            for i in range(6)))

    # the session goes before the reference comes
    sample = pick_sample(in_window, rng, job["check_requests"])
    del session, sched, open_streams
    gc.collect()
    checks = compare(ref_lm, cfg, words, make, sample,
                     config.max_prompt + config.max_new, job["check_blocks"],
                     rng, sized(cell.limits, args.rehearse), log)
    checks += [("streams_not_matching_requests", mismatched, 0),
               ("compiles_in_window", new_compiles, 0),
               ("lazy_jit_fallbacks", fallbacks, 0),
               ("executables_beyond_buckets_plus_one",
                abs(n_exec - len(config.buckets) - 1), 0),
               ("failed_or_short_requests", failed, 0),
               ("moe_assignments_dropped", dropped, 0)]
    in_steps = [s[2:] for s in step_live if w0 <= s[0] < w1]
    return {
        "attempted": len(done), "failed": failed, "checks": checks,
        "window": (w0, w1), "peak_bytes": peak, "setup_s": w0 - t_process,
        "facts": {
            "window_s": w1 - w0, "steps": len(in_steps),
            # (live slots, rows inside their horizons, slots in a denoise
            # pass) a pass
            "step_live": in_steps, "config": cfg,
            "serve_config": {"slots": config.slots,
                             "page_size": config.page_size},
            "decode_module": "block_pass", "prefill_module": "prefill",
            "family": cell.family_name, "bench_root": cell.root,
            "block": block, "decode": decode,
            "prefill_tokens": [n for t0, n in prefills if w0 <= t0 < w1],
        },
        "end_to_end": {
            "serve_tokens_per_s": tokens_in_window / (w1 - w0),
            "serve_ttft_p95_ms": 1e3 * base.percentile(ttft, 95),
            "serve_gap_p95_ms": 1e3 * base.percentile(gaps, 95),
        },
    }


def pick_sample(in_window, rng, n):
    """The longest finished request and ``n - 1`` more, drawn from the
    seed -> [(prompt, every row its blocks generated, the pass each was
    unmasked in, the confidence it was unmasked with)]."""
    if not in_window:
        return []
    ranked = sorted(range(len(in_window)), key=lambda i: -(
        len(in_window[i][0].prompt) + len(in_window[i][0].tokens)))
    chosen = [ranked[0]] + [int(i) for i in rng.permutation(ranked[1:])[:n - 1]]
    return [(list(in_window[i][0].prompt), list(in_window[i][1].tokens),
             list(in_window[i][1].passes),
             list(in_window[i][1].confidences)) for i in chosen]


def pick_blocks(first, last, n, rng):
    """The generated blocks to check: all of ``first .. last``, or the
    first, the last and ``n - 2`` more drawn from the seed."""
    if last - first + 1 <= n:
        return list(range(first, last + 1))
    between = rng.permutation(np.arange(first + 1, last))[:n - 2]
    return sorted([first, last] + [int(i) for i in between])


def compare(ref_lm, cfg, words, make, sample, pad_to, n_blocks, rng, limits,
            log):
    """The plain reference over each sampled request: one block-causal
    forward of its final tokens for every layer's keys and values, then
    the forward of every denoise pass of the blocks checked, with the rows
    visible that were visible to that pass (the module's docstring has the
    four gaps)."""
    t0 = time.perf_counter()
    params = make(words)
    b, mask = cfg["block_length"], cfg["mask_token_id"]
    threshold = cfg["confidence_threshold"]
    context = jax.jit(lambda p, t: ref_lm.context(p, t, cfg))

    @jax.jit
    def block_passes(p, t, blk, visible, ctx):
        """Every denoise pass of one block in one call (the weights are
        read once): ``visible`` (passes, B), a row a pass."""
        def one_pass(seen):
            rows = ref_lm.denoise_logits(p, t, cfg, blk, seen, ctx)
            return ref_lm.confidence(rows, cfg) + (rows,)

        return jax.vmap(one_pass)(visible)

    token_gaps, choice_gaps, errors, n_passes, flips = [], [], [], 0, 0
    for prompt, tokens, passes, confidences in sample:
        seq = prompt + tokens
        unmasked_at = [-1] * len(prompt) + passes   # a prompt's row: given
        with_c = np.asarray([0.0] * len(prompt) + confidences)
        assert len(seq) % b == 0 and len(tokens) == len(passes)
        fed = jnp.asarray(seq + [0] * (pad_to - len(seq)), jnp.int32)
        ctx = context(params, fed)
        for blk in pick_blocks(len(prompt) // b, len(seq) // b - 1, n_blocks,
                               rng):
            at = np.asarray(unmasked_at[blk * b:(blk + 1) * b])
            served = np.asarray(seq[blk * b:(blk + 1) * b])
            stated = with_c[blk * b:(blk + 1) * b]
            # a pass past the block's last sees every row: not read
            visible = at[None, :] < np.arange(cfg["denoising_steps"])[:, None]
            per_pass = [np.asarray(a) for a in block_passes(
                params, fed, jnp.int32(blk), jnp.asarray(visible), ctx)]
            for i in range(int(at.max()) + 1):
                x0, conf, rows = (a[i] for a in per_pass)
                rows = rows.astype(np.float64)
                taken = np.flatnonzero(at == i)
                still = np.flatnonzero(at >= i)
                open_ = rows.copy()
                open_[:, mask] = -np.inf
                best = open_.max(-1)
                spread = best - rows.min(-1)
                gap = (best - rows[np.arange(b), served]) / spread
                token_gaps.extend(gap[taken].tolist())
                flips += int((x0[taken] != served[taken]).sum())
                errors.extend((stated[taken] / conf[taken] - 1.0).tolist())
                if (conf[taken] > threshold).all():
                    choice_gaps.append(0.0)
                else:
                    ranked = np.sort(conf[still])[::-1]
                    choice_gaps.append(max(
                        0.0, float(ranked[len(taken) - 1] - conf[taken].min())
                    ) / float(ranked[0]))
                n_passes += 1
    log("reference: %d requests, %d denoise passes rebuilt, %d unmasked "
        "rows, in %.2f s (not in setup_s)", len(sample), n_passes,
        len(token_gaps), time.perf_counter() - t0)
    log("reference: %d of the %d unmasked rows are off the reference's "
        "argmax and %d of the %d passes took another row than the "
        "reference's most confident (reported; the gaps are compared)",
        flips, len(token_gaps), sum(g > 0 for g in choice_gaps), n_passes)
    nan = float("nan")

    def widest(values):
        values = np.asarray(values)
        return float(values.max()) if values.size and np.isfinite(
            values).all() else nan

    def mean(values):
        return float(np.mean(values)) if len(values) else nan

    rms = float(np.sqrt(np.mean(np.square(errors)))) if errors else nan
    log("reference: the confidences the rows were unmasked with, over the "
        "reference's of the same row in the same pass: rms error %.3e, "
        "widest %.3e, mean %+.3e", rms, widest(np.abs(errors)), mean(errors))
    return [("unmask_confidence_error", rms,
             limits["unmask_confidence_error"]),
            ("unmasked_token_gap", widest(token_gaps),
             limits["unmasked_token_gap"]),
            ("unmasked_token_mean_gap", mean(token_gaps),
             limits["unmasked_token_mean_gap"]),
            ("unmask_choice_gap", widest(choice_gaps),
             limits["unmask_choice_gap"]),
            ("unmask_choice_mean_gap", mean(choice_gaps),
             limits["unmask_choice_mean_gap"])]
