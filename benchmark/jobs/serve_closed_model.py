"""A serving cell of a model whose architecture shapes cannot tell:
``jobs/serve_closed.py``'s closed loop, with the session built from the
family's architecture.

``serve_closed.py`` builds ``InferenceSession(params, num_heads=...)``,
which infers a GPT-2 shaped block from the parameter dict.  This kind
builds ``InferenceSession(params, model=serve.ModelConfig(**family
.model_config(cfg)), config=...)`` and is otherwise the same run: the
same clients, wrappers, stamps, window, checks and comparison, whose
helpers (``length_pool``, ``Stream``, ``percentile``, ``pick_sample``,
``compare``, ``steady_host_allocator``) it takes from that file.  On top
of them it reads ``session.moe_report()`` when the window opens, at the
first step that ends past its close (what drains afterwards runs with
emptying slots and is not the window's), and once the run has ended (three
small host copies, none inside the window), into ``facts`` and one more
check: ``moe_assignments_dropped``, assignments
asked minus assignments computed, limit 0.

For the next ``benchmark`` issue: the two kinds differ in how the session
is built and in what they read after the window, and should become one
(a family that says how its session is built; ``moe_report`` read where
the session has one).  This PR may edit no file the benchmark has.
"""
import gc
import statistics
import time

import numpy as np

import manifest
import weights
from manifest import sized
from probes import compile_count, peak_bytes


def run(cell, args, recorder, tracer, t_process, log):
    from mxnet_tpu import serve

    base = manifest.load_module("jobs", "serve_closed", cell.root)
    family = cell.family()
    cfg = sized(cell.config, args.rehearse)
    job = sized(cell.traffic, args.rehearse)
    if "block" not in getattr(serve.ModelConfig, "__dataclass_fields__", {}):
        # a program from before the architecture could be stated: fail
        # now, before 12 GB of weights are made
        raise manifest.ManifestError(
            "this program's serve.ModelConfig cannot state an architecture "
            "(no `block`): it cannot serve family %s" % cell.family_name)
    model = serve.ModelConfig(**family.model_config(cfg))
    base.steady_host_allocator(job.get("host_allocator", {}))
    rng = np.random.default_rng(args.seed)
    words = weights.seed_words(args.seed)
    ref_lm = family.reference
    spec = ref_lm.spec(cfg)
    make = weights.maker(spec, cfg.get("init_std"))
    sc = job["serve_config"]
    session = serve.InferenceSession(
        make(words), model=model,
        config=serve.ServeConfig(
            slots=sc["slots"], page_size=sc["page_size"],
            buckets=tuple(sc["buckets"]), max_new=sc["max_new"],
            exact=sc["exact"],
            # the control of the correctness check: the program's own
            # next lower precision, which has to come out not correct
            **(job["control"] if args.control else {})))
    n_exec = len(session.executables)
    log("serve: %s, %d slots, buckets %s, max_new %d, %d executables",
        cell.config_name, sc["slots"], sc["buckets"], sc["max_new"], n_exec)

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
             "compiles": None, "moe": None, "moe_end": None}

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
            state["moe"] = session.moe_report()
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
            if state["moe_end"] is None:
                state["moe_end"] = session.moe_report()

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
    # the window's own routers; the dropless check is over the whole run
    whole = session.moe_report()
    moe0, moe1 = state["moe"], state["moe_end"] or whole
    moe = {k: moe1[k] - moe0[k] for k in (
        "decode_steps", "prefill_chunks", "assignments_asked",
        "assignments_computed", "distinct_experts")}
    load = (moe1["expert_load"] - moe0["expert_load"]).astype(float)
    log("serve: routers in the window: %d decode steps, %d prefill chunks, "
        "%d assignments asked, %d computed; %.1f distinct experts a decode "
        "step a layer of %d; load max/mean by layer %s",
        moe["decode_steps"], moe["prefill_chunks"], moe["assignments_asked"],
        moe["assignments_computed"], moe["distinct_experts"] / max(
            moe["decode_steps"] * moe1["expert_layers"], 1),
        cfg["n_routed_experts"], " ".join(
            "%.2f" % (row.max() / max(row.mean(), 1e-9)) for row in load))

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
               ("moe_assignments_dropped", whole["assignments_asked"]
                - whole["assignments_computed"], 0)]
    in_steps = [(n, live) for t0, t1, n, live in step_live if w0 <= t0 < w1]
    return {
        "attempted": len(done), "failed": failed, "checks": checks,
        "window": (w0, w1), "peak_bytes": peak, "setup_s": w0 - t_process,
        "facts": {
            "window_s": w1 - w0, "steps": len(in_steps),
            "step_live": in_steps, "config": cfg,
            "decode_module": "decode", "prefill_module": "prefill",
            "family": cell.family_name, "bench_root": cell.root,
            "moe": dict(moe, expert_layers=moe1["expert_layers"]),
            "prefill_tokens": [n for t0, n in prefills if w0 <= t0 < w1],
        },
        "end_to_end": {
            "serve_tokens_per_s": tokens_in_window / (w1 - w0),
            "serve_ttft_p95_ms": 1e3 * base.percentile(ttft, 95),
            "serve_gap_p95_ms": 1e3 * base.percentile(gaps, 95),
        },
    }
