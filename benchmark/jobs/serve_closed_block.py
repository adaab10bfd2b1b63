"""A serving cell of a block that counts its own work and has no routers:
``jobs/serve_closed.py``'s closed loop, with the session built from the
family's architecture and ``session.block_report()`` read into ``facts``.

``serve_closed_model.py`` builds the session the same way, but reads
``session.moe_report()`` as router arithmetic (assignments, experts
reached) that a block without routers cannot answer.  This kind is
otherwise the same run: the same clients, wrappers, stamps, window, checks
and comparison, whose helpers (``length_pool``, ``Stream``, ``percentile``,
``pick_sample``, ``compare``, ``steady_host_allocator``) it takes from
``serve_closed.py``.  On top of them:

* it fails at once, before any weight is made, on a program whose
  ``serve.model.BLOCKS`` cannot serve the family's block;
* the family's ``published_init`` is applied to what ``weights.maker``
  made, for the program and for the reference alike;
* the weights are handed over: the session's copy is the only one, so
  the int8 control of a model that fills the chip never holds float32
  and int8 copies of the whole model side by side;
* ``block_report()`` is read when the window opens, at the first step
  that ends past its close and once the run has ended (small host copies,
  none inside the window); the window's counts go to ``facts["block"]``;
* one more check, ``state_values_not_finite``: values of the cache's
  slot-private state pools that are not finite when the run has ended,
  limit 0.

For the next ``benchmark`` issue: the three ``serve_closed*`` kinds differ
in how the session is built and in what they read after the window, and
should become one.  This PR may edit no file the benchmark has.
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


class Handover(dict):
    """A parameter dict that is emptied as it is read: what takes its
    items holds the only reference to each array."""

    def items(self):
        while self:
            yield self.popitem()


def run(cell, args, recorder, tracer, t_process, log):
    from mxnet_tpu import serve

    base = manifest.load_module("jobs", "serve_closed", cell.root)
    family = cell.family()
    cfg = sized(cell.config, args.rehearse)
    job = sized(cell.traffic, args.rehearse)
    blocks = getattr(getattr(serve, "model", None), "BLOCKS", {})
    if family.BLOCK not in blocks:
        # fail now, before 12.8 GB of weights are made
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

    sc = job["serve_config"]
    session = serve.InferenceSession(
        Handover(make(words)), model=model,
        config=serve.ServeConfig(
            slots=sc["slots"], page_size=sc["page_size"],
            buckets=tuple(sc["buckets"]), max_new=sc["max_new"],
            exact=sc["exact"],
            # the control of the correctness check: the program's own
            # next lower precision, which has to come out not correct
            **(job["control"] if args.control else {})))
    n_exec = len(session.executables)
    log("serve: %s, %d slots, buckets %s, max_new %d, %d executables, "
        "cache pools %.3f GB", cell.config_name, sc["slots"], sc["buckets"],
        sc["max_new"], n_exec, session.cache.pool_bytes() / 1e9)

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

    due = {}                      # rid -> perf_counter when it was due
    state = {"rid": 0, "done": 0, "window": None, "end": None,
             "compiles": None, "block": None, "block_end": None}

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
            state["block"] = session.block_report()
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
        live = 0
        for slot, token in out[0].items():
            stream = open_streams[slot]
            live += len(stream.prompt) + len(stream.tokens)
            stream.tokens.append(token)
            stream.times.append(t1)
        step_live.append((t0, t1, len(out[0]), live))
        if state["end"] is not None and t1 >= state["end"]:
            if tracer.running:
                tracer.stop()
            if state["block_end"] is None:
                state["block_end"] = session.block_report()

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
    whole = session.block_report()
    b0, b1 = state["block"], state["block_end"] or whole
    block = {k: (b1[k] - b0[k] if k in family.COUNTED else b1[k])
             for k in b1}
    not_finite = sum(
        int(jnp.sum(~jnp.isfinite(session.cache.pools[name])))
        for name in session.cache.state)
    log("serve: the block in the window: %s; over the run %d values of "
        "its state pools (%s) are not finite",
        " ".join("%s %d" % kv for kv in sorted(block.items())), not_finite,
        ", ".join(session.cache.state))

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

    sixth = (w1 - w0) / 6
    log("serve: median step wall before the window %.2f ms, by sixth of "
        "the window: %s", 1e3 * statistics.median(
            [t1 - t0 for t0, t1, _, _ in step_live if t1 < w0] or [0.0]),
        " ".join("%.2f" % (1e3 * statistics.median(
            [t1 - t0 for t0, t1, _, _ in step_live
             if w0 + i * sixth <= t0 < w0 + (i + 1) * sixth] or [0.0]))
            for i in range(6)))

    # the session goes before the reference comes
    sample = base.pick_sample(in_window, rng, job["check_requests"])
    del session, sched, open_streams
    gc.collect()
    pad_to = max(sc["buckets"]) + sc["max_new"]
    checks = base.compare(ref_lm, cfg, words, make, sample, pad_to,
                          sized(cell.limits, args.rehearse), log)
    checks += [("streams_not_matching_requests", mismatched, 0),
               ("compiles_in_window", new_compiles, 0),
               ("lazy_jit_fallbacks", fallbacks, 0),
               ("executables_beyond_buckets_plus_one",
                abs(n_exec - len(sc["buckets"]) - 1), 0),
               ("failed_or_short_requests", failed, 0),
               ("state_values_not_finite", not_finite, 0)]
    in_steps = [(n, live) for t0, t1, n, live in step_live if w0 <= t0 < w1]
    return {
        "attempted": len(done), "failed": failed, "checks": checks,
        "window": (w0, w1), "peak_bytes": peak, "setup_s": w0 - t_process,
        "facts": {
            "window_s": w1 - w0, "steps": len(in_steps),
            "step_live": in_steps, "config": cfg,
            "decode_module": "decode", "prefill_module": "prefill",
            "family": cell.family_name, "bench_root": cell.root,
            "block": block,
            "prefill_tokens": [n for t0, n in prefills if w0 <= t0 < w1],
        },
        "end_to_end": {
            "serve_tokens_per_s": tokens_in_window / (w1 - w0),
            "serve_ttft_p95_ms": 1e3 * base.percentile(ttft, 95),
            "serve_gap_p95_ms": 1e3 * base.percentile(gaps, 95),
        },
    }
