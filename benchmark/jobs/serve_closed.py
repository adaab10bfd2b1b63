"""A serving cell: clients in a closed loop on ``Scheduler.run``.

Copied from ``chip_smoke.py``'s ``serve_phase`` (PR 21): the same public
calls (``InferenceSession``, ``Scheduler.run(requests, followup=)``) and
the same wrappers around ``session.prefill`` / ``session.step``.  The
scheduler keeps only a request's first-token and completion times, so the
benchmark stamps every token itself: a token's time is the host clock at
the return of the call that produced it (both end in a host read).

One ``run`` call serves set-up and window alike: the clients start, the
first completions warm every shape (set-up), the window opens at a later
completion and closes ``--seconds`` after; no new request is sent then,
and what is in flight drains.
"""
import ctypes
import gc
import math
import statistics
import time

import jax
import numpy as np

import weights
from manifest import sized
from probes import compile_count, peak_bytes


def quantiles(spec, n):
    """``n`` lengths at the mid-quantiles of a clipped log-normal."""
    z = np.array([statistics.NormalDist().inv_cdf((i + 0.5) / n)
                  for i in range(n)])
    raw = spec["median"] * np.exp(spec["sigma"] * z)
    return np.clip(np.rint(raw), spec["min"], spec["max"]).astype(int)


def length_pool(job):
    """The fixed set of (prompt, output) lengths every seed serves: the
    distributions' mid-quantiles, paired by a fixed permutation."""
    n = job["pool"]
    prompts, outputs = quantiles(job["prompt"], n), quantiles(job["output"], n)
    pairing = np.random.default_rng(job["pairing_seed"]).permutation(n)
    return list(zip(prompts.tolist(), outputs[pairing].tolist()))


def steady_host_allocator(settings):
    """Pin glibc malloc's two moving thresholds (``mallopt``), as the
    traffic file's ``host_allocator`` group says.  The session hands a
    (slots, vocab) float32 array to the host at every step; by default
    the thresholds drift with a process's history of frees, and whether
    that array lands in a reused heap block or in freshly mapped pages
    set a run's level for its whole life, 9 % apart (``PERF.md``)."""
    libc = ctypes.CDLL(None)
    libc.mallopt.argtypes, libc.mallopt.restype = [ctypes.c_int] * 2, ctypes.c_int
    for key, param in (("mmap_threshold", -3), ("trim_threshold", -1)):
        if key in settings and libc.mallopt(param, settings[key]) != 1:
            raise RuntimeError("mallopt(%s, %d) was refused"
                               % (key, settings[key]))


class Stream(object):
    """Tokens of one request as the benchmark saw them leave the session."""

    __slots__ = ("prompt", "tokens", "times")

    def __init__(self, prompt, token, when):
        self.prompt, self.tokens, self.times = prompt, [token], [when]


def percentile(values, pct):
    """Nearest-rank percentile of all the values."""
    ordered = sorted(values)
    rank = max(int(math.ceil(pct / 100.0 * len(ordered))), 1)
    return ordered[rank - 1]


def run(cell, args, recorder, tracer, t_process, log):
    from mxnet_tpu import serve

    cfg = sized(cell.config, args.rehearse)
    job = sized(cell.traffic, args.rehearse)
    steady_host_allocator(job.get("host_allocator", {}))
    rng = np.random.default_rng(args.seed)
    words = weights.seed_words(args.seed)
    ref_lm = cell.family().reference
    spec = ref_lm.spec(cfg)
    make = weights.maker(spec, cfg.get("init_std"))
    sc = job["serve_config"]
    session = serve.InferenceSession(
        make(words), num_heads=cfg["num_heads"],
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
    pool = length_pool(job)
    order = (np.random.default_rng(job["order_seed"])
             if "order_seed" in job else rng)
    left = []

    def next_sizes():
        if not left:
            left.extend(pool[i] for i in order.permutation(len(pool)))
        return left.pop()

    due = {}                      # rid -> perf_counter when it was due
    state = {"rid": 0, "done": 0, "window": None, "end": None,
             "compiles": None}

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
            if args.trace:
                tracer.start()
            log("serve: window opens after %d requests", state["done"])
            state["window"] = time.perf_counter()
            state["end"] = state["window"] + args.seconds
        if state["end"] is not None and time.perf_counter() >= state["end"]:
            return None
        return new_request(now_s)

    # -- the benchmark's own wrappers: spans, token stamps, live lengths
    open_streams, streams, step_live = {}, [], []

    def after_prefill(out, t0, t1, call_args):
        slot, prompt = call_args[0], call_args[1]
        stream = Stream(prompt, out[0], t1)
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
        if tracer.running and t1 >= state["end"]:
            tracer.stop()

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
    sample = pick_sample(in_window, rng, job["check_requests"])
    del session, sched, open_streams
    gc.collect()
    pad_to = max(sc["buckets"]) + sc["max_new"]
    checks = compare(ref_lm, cfg, words, make, sample, pad_to,
                     sized(cell.limits, args.rehearse), log)
    checks += [("streams_not_matching_requests", mismatched, 0),
               ("compiles_in_window", new_compiles, 0),
               ("lazy_jit_fallbacks", fallbacks, 0),
               ("executables_beyond_buckets_plus_one",
                abs(n_exec - len(sc["buckets"]) - 1), 0),
               ("failed_or_short_requests", failed, 0)]
    in_steps = [(n, live) for t0, t1, n, live in step_live if w0 <= t0 < w1]
    return {
        "attempted": len(done), "failed": failed, "checks": checks,
        "window": (w0, w1), "peak_bytes": peak, "setup_s": w0 - t_process,
        "facts": {
            "window_s": w1 - w0, "steps": len(in_steps),
            "step_live": in_steps, "config": cfg,
            "decode_module": "decode",
        },
        "end_to_end": {
            "serve_tokens_per_s": tokens_in_window / (w1 - w0),
            "serve_ttft_p95_ms": 1e3 * percentile(ttft, 95),
            "serve_gap_p95_ms": 1e3 * percentile(gaps, 95),
        },
    }


def pick_sample(in_window, rng, n):
    """The longest finished request and ``n - 1`` more, drawn from the
    seed."""
    if not in_window:
        return []
    ranked = sorted(range(len(in_window)), key=lambda i: -(
        len(in_window[i][0].prompt) + len(in_window[i][0].tokens)))
    chosen = [ranked[0]] + [int(i) for i in rng.permutation(ranked[1:])[:n - 1]]
    return [(list(in_window[i][0].prompt), list(in_window[i][0].tokens))
            for i in chosen]


def compare(ref_lm, cfg, words, make, sample, pad_to, limits, log):
    """The plain reference once over each sampled prompt with its served
    tokens: the gap by which a served token's logit lies below the
    reference's best, as a share of the row's spread; the widest over the
    sample, and the mean (steadier from seed to seed)."""
    import jax.numpy as jnp

    t0 = time.perf_counter()
    params = make(words)
    forward = jax.jit(lambda p, t: ref_lm.logits(p, t, cfg))
    worst, n_tokens, flips, total = 0.0, 0, 0, 0.0
    for prompt, tokens in sample:
        # one padded length, so one compilation; the mask is causal, so
        # what follows a position cannot reach it
        fed = prompt + tokens[:-1]
        seq = jnp.asarray(fed + [0] * (pad_to - len(fed)), jnp.int32)
        rows = np.asarray(forward(params, seq))[len(prompt) - 1:len(fed)]
        served = np.asarray(tokens)
        spread = rows.max(-1) - rows.min(-1)
        gap = (rows.max(-1) - rows[np.arange(len(served)), served]) / spread
        if not np.isfinite(gap).all():
            worst = float("nan")
            break
        worst = max(worst, float(gap.max()))
        total += float(gap.sum())
        flips += int((rows.argmax(-1) != served).sum())
        n_tokens += len(served)
    log("reference: %d requests, %d served tokens, in %.2f s (not in "
        "setup_s)", len(sample), n_tokens, time.perf_counter() - t0)
    log("reference: %d of the %d served tokens are off the reference's "
        "argmax (reported, not compared: the control moves it under 3x)",
        flips, n_tokens)
    nan = float("nan")
    return [("served_token_gap", worst if sample else nan,
             limits["served_token_gap"]),
            ("served_token_mean_gap", total / n_tokens if n_tokens else nan,
             limits["served_token_mean_gap"])]
