"""A serving cell whose prompts are longer than the largest prefill bucket:
``jobs/serve_closed_share.py``'s run (the same clients, wrappers, stamps,
window, checks and comparison), with the traffic file's ``serve_config``
group handed to ``ServeConfig`` WHOLE.

``serve_closed_block.py`` hands ``ServeConfig`` five named fields of that
group (``slots``, ``page_size``, ``buckets``, ``max_new``, ``exact``) and
nothing else, ``serve_closed_share.py`` wraps it, and this PR may edit
neither: no accepted kind can pass ``max_prompt`` (the longest admissible
fresh prompt, which the session feeds in chunks of the largest bucket).
Every helper the accepted kinds export is taken from them through
``manifest.load_module`` (``Handover``; ``length_pool``, ``Stream``,
``percentile``, ``pick_sample``, ``compare``, ``steady_host_allocator``);
what is written out again is the run's own loop.  On top of
``serve_closed_share``'s run:

* the comparison's padded length is ``max_prompt + max_new``, the longest
  context a request can reach, and the log says how long the longest
  checked context was and in how many chunks its prompt went;
* each decode step's stamp also holds the rows inside the band, min(a
  slot's context, the window) summed over the live slots, which the
  window layers' share of ``decode_least_bytes`` needs;
* ``session.decode_report()`` (host counts: the page blocks the paged
  reader had to visit) is read where ``block_report()`` is, and the
  window's difference goes to ``facts["decode"]``.

Like ``serve_closed_block.py`` it fails at once, before any weight is made
and before it builds a ``ServeConfig``, on a program whose
``serve.model.BLOCKS`` cannot serve the family's block.

For the next ``benchmark`` issue: the ``serve_closed*`` kinds are now five
and should become one (PERF.md, Open questions).
"""
import gc
import statistics
import time

import jax.numpy as jnp
import numpy as np

import manifest
import weights
from manifest import sized
from probes import compile_count, peak_bytes


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
        # fail now, before 6.9 GB of weights are made and before a
        # ServeConfig is asked for a field it may not have
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
    window = cfg["sliding_window"]
    n_exec = len(session.executables)
    log("serve: %s, %d slots, buckets %s, max_prompt %d, max_new %d, %d "
        "executables, cache pools %.3f GB", cell.config_name, config.slots,
        list(config.buckets), config.max_prompt, config.max_new, n_exec,
        session.cache.pool_bytes() / 1e9)

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
        prompt = rng.integers(0, cfg["vocab_size"], p_len).tolist()
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

    # -- the benchmark's own wrappers: spans, token stamps, live lengths
    open_streams, streams, step_live, prefills = {}, [], [], []

    def after_prefill(out, t0, t1, call_args):
        slot, prompt = call_args[0], call_args[1]
        stream = base.Stream(prompt, out[0], t1)
        prefills.append((t0, len(prompt)))
        open_streams[slot] = stream
        streams.append(stream)

    def after_step(out, t0, t1, call_args):
        live = band = 0
        for slot, token in out[0].items():
            stream = open_streams[slot]
            context = len(stream.prompt) + len(stream.tokens)
            live += context
            band += min(context, window)
            stream.tokens.append(token)
            stream.times.append(t1)
        step_live.append((t0, t1, len(out[0]), live, band))
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
    if tracer.running:
        tracer.stop()
    w0, w1 = state["window"], state["end"]
    if w0 is None:
        raise RuntimeError("the run ended before %d warm-up requests had "
                           "finished" % job["warmup_requests"])
    new_compiles = compile_count() - state["compiles"]
    fallbacks = session.fallback_count()
    peak = peak_bytes()
    # the window's own counts; the state check is over the whole run
    (b0, d0), (b1, d1) = state["open"], state["close"] or reports()
    block = {k: (b1[k] - b0[k] if k in family.COUNTED else b1[k])
             for k in b1}
    decode = {k: d1[k] - d0[k] for k in ("steps", "blocks_visited")}
    not_finite = sum(
        int(jnp.sum(~jnp.isfinite(session.cache.pools[name])))
        for name in session.cache.state)
    log("serve: the block in the window: %s; the paged reader visited %d "
        "page blocks in %d steps; over the run %d values of its state pools "
        "(%s) are not finite",
        " ".join("%s %d" % kv for kv in sorted(block.items())),
        decode["blocks_visited"], decode["steps"], not_finite,
        ", ".join(session.cache.state) or "none")
    nan = float("nan")
    dropped = block.get("assignments_held", nan) \
        - block.get("assignments_computed", nan)
    log("serve: of %s assignments in the window %s fell on the experts held "
        "here and %s were computed; %.1f distinct held experts a decode step "
        "a layer", block.get("assignments_asked"),
        block.get("assignments_held"), block.get("assignments_computed"),
        block.get("distinct_held_experts", nan) / max(
            block.get("decode_steps", 0) * block.get("expert_layers", 0), 1))

    # -- requests and their streams, matched by prompt
    by_prompt = {}
    for stream in streams:
        by_prompt.setdefault(tuple(stream.prompt), []).append(stream)
    failed, mismatched = 0, 0
    in_window = []                # (request, stream) due inside the window
    for req in done:
        found = by_prompt.get(tuple(req.prompt), [])
        stream = found.pop(0) if found else None
        if req.failed or len(req.tokens) != req.max_new:
            failed += 1
            continue
        if stream is None or stream.tokens != list(req.tokens):
            mismatched += 1
            continue
        if w0 <= due[req.rid] < w1:
            in_window.append((req, stream))
    tokens_in_window = sum(1 for s in streams for t in s.times if w0 <= t < w1)
    ttft = [s.times[0] - due[r.rid] for r, s in in_window]
    gaps = [b - a for _, s in in_window
            for a, b in zip(s.times, s.times[1:]) if b < w1]
    log("serve: %d requests finished, %d due inside the window; %d tokens "
        "in %.3f s; TTFT median %.2f ms over %d; gap median %.2f ms over %d",
        len(done), len(in_window), tokens_in_window, w1 - w0,
        1e3 * statistics.median(ttft), len(ttft),
        1e3 * statistics.median(gaps), len(gaps))
    log("serve: TTFT p95 %.2f ms, gap p95 %.2f ms",
        1e3 * base.percentile(ttft, 95), 1e3 * base.percentile(gaps, 95))

    sixth = (w1 - w0) / 6
    log("serve: median step wall before the window %.2f ms, by sixth of "
        "the window: %s", 1e3 * statistics.median(
            [s[1] - s[0] for s in step_live if s[1] < w0] or [0.0]),
        " ".join("%.2f" % (1e3 * statistics.median(
            [s[1] - s[0] for s in step_live
             if w0 + i * sixth <= s[0] < w0 + (i + 1) * sixth] or [0.0]))
            for i in range(6)))

    # the session goes before the reference comes
    sample = base.pick_sample(in_window, rng, job["check_requests"])
    longest = max([(len(p) + len(t), len(p)) for p, t in sample] or [(0, 0)])
    log("serve: %d requests checked; the longest context among them is %d "
        "tokens (a prompt of %d, fed in %d chunk(s)), the window %d",
        len(sample), longest[0], longest[1],
        -(-longest[1] // max(config.buckets)), window)
    del session, sched, open_streams
    gc.collect()
    checks = base.compare(ref_lm, cfg, words, make, sample,
                          config.max_prompt + config.max_new,
                          sized(cell.limits, args.rehearse), log)
    checks += [("streams_not_matching_requests", mismatched, 0),
               ("compiles_in_window", new_compiles, 0),
               ("lazy_jit_fallbacks", fallbacks, 0),
               ("executables_beyond_buckets_plus_one",
                abs(n_exec - len(config.buckets) - 1), 0),
               ("failed_or_short_requests", failed, 0),
               ("state_values_not_finite", not_finite, 0),
               ("moe_assignments_dropped", dropped, 0)]
    in_steps = [s[2:] for s in step_live if w0 <= s[0] < w1]
    return {
        "attempted": len(done), "failed": failed, "checks": checks,
        "window": (w0, w1), "peak_bytes": peak, "setup_s": w0 - t_process,
        "facts": {
            "window_s": w1 - w0, "steps": len(in_steps),
            # (live slots, live rows, rows inside the band) a step
            "step_live": in_steps, "config": cfg,
            "serve_config": {"slots": config.slots,
                             "page_size": config.page_size},
            "decode_module": "decode", "prefill_module": "prefill",
            "family": cell.family_name, "bench_root": cell.root,
            "block": block, "decode": decode,
            "prefill_tokens": [n for t0, n in prefills if w0 <= t0 < w1],
            "longest_checked_context": longest[0],
        },
        "end_to_end": {
            "serve_tokens_per_s": tokens_in_window / (w1 - w0),
            "serve_ttft_p95_ms": 1e3 * base.percentile(ttft, 95),
            "serve_gap_p95_ms": 1e3 * base.percentile(gaps, 95),
        },
    }
