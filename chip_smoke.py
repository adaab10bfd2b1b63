#!/usr/bin/env python3
"""Chip smoke: the train and serve main paths, once, on the TPU.

    python chip_smoke.py             # one chip: train phase, serve phase
    python chip_smoke.py --chips 4   # four chips: ONLY the multi-chip
                                     # training paths and their one-chip
                                     # comparison
    python chip_smoke.py --tiny      # CPU rehearsal at toy sizes: does
                                     # every step, then exits non-zero

One process drives every chip; nothing here starts a child.  The last
line of stdout on success is exactly

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

and no run without the chip can print it: off-TPU the script fails at
once, or under ``--tiny`` after the last phase, naming the devices it
found.  Everything else printed on the way (step times, compile
seconds, peak memory, cache hits) is a fact about this run, not a
benchmark: times here include host work the smoke adds on purpose
(a metric read and a full wait after every step).
"""
import argparse
import gc
import json
import math
import os
import sys
import time
from importlib import metadata

import jax
import jax.numpy as jnp
import numpy as np

import mxnet_tpu as mx
from mxnet_tpu import compile_cache, profiler, serve
from mxnet_tpu.context import describe_devices
from mxnet_tpu.models import transformer
from mxnet_tpu.ops import attention
from mxnet_tpu.parallel import overlap

# the transformer LM of models/transformer.py at the bench_transformer.py
# headline width (539M parameters); --tiny keeps the code path, not the size
FULL = dict(vocab_size=32768, num_layers=8, d_model=2048, num_heads=16,
            seq_len=1024)
TINY = dict(vocab_size=128, num_layers=1, d_model=32, num_heads=2,
            seq_len=64)
BATCH = 8
N_BATCHES = 3      # two epochs over three batches: every batch repeats
N_EPOCHS = 2
# SGD(momentum 0.9).  Module.fit supplies rescale_grad = 1/batch on top
# of SoftmaxOutput(normalization="batch"), and Uniform(0.01) weights start
# the loss at ln(vocab); a CPU probe at d512-T256 put the rate at which
# six steps diverge a good ten times above this one, and the fall per
# revisit it predicts (~0.005) far above bf16 noise in a mean over 8192
# tokens (~4e-5).  The toy width needs a far larger rate to move at all.
LEARNING_RATE = {"full": 5e-3, "tiny": 0.5}


def log(msg, *args):
    print(msg % args if args else msg, flush=True)


def fail(msg, *args):
    log("FAILED: " + msg, *args)
    sys.exit(1)


def check(cond, msg, *args):
    if not cond:
        fail(msg, *args)


def memory_line(tag, devices):
    for d in devices:
        stats = d.memory_stats() or {}
        log("  memory[%s] %s: in_use=%s peak=%s", tag, d,
            stats.get("bytes_in_use", "n/a"),
            stats.get("peak_bytes_in_use", "n/a"))


def cache_line(tag):
    cs = compile_cache.cache_stats()
    log("  compile cache[%s]: dir=%s hits=%d misses=%d entries=%d",
        tag, cs["dir"], cs["hits"], cs["misses"], cs["entries"])


def token_batches(cfg, seed):
    """Seeded next-token data over the full id range but a 512-id
    working set, so a few SGD steps can move the loss: labels are the
    inputs shifted by one."""
    rs = np.random.RandomState(seed)
    ids = rs.permutation(cfg["vocab_size"])[:min(512, cfg["vocab_size"])]
    n = BATCH * N_BATCHES
    seq = ids[rs.randint(0, len(ids), (n, cfg["seq_len"] + 1))]
    return seq[:, :-1].astype("float32"), seq[:, 1:].astype("float32")


def device_bytes(tree):
    """{device: bytes} over every addressable shard of a pytree."""
    out = {}
    for leaf in jax.tree.leaves(tree):
        for sh in leaf.addressable_shards:
            out[sh.device] = out.get(sh.device, 0) + sh.data.nbytes
    return out


def fit_transformer(cfg, ctx, seed, tag, **fit_kwargs):
    """One ``Module.fit`` through the public path; returns the module
    and the per-step record a ``batch_end_callback`` took."""
    mx.random.seed(seed)
    data, labels = token_batches(cfg, seed)
    train = mx.io.NDArrayIter(data, labels, batch_size=BATCH,
                              shuffle=False, label_name="softmax_label")
    mod = mx.mod.Module(transformer.get_symbol(**cfg), context=ctx)
    steps = []
    clock = [time.perf_counter()]

    def on_batch(param):
        # the metric read pulls this step's outputs; waitall then waits
        # for everything the step produced (params, optimizer state)
        _, loss = param.eval_metric.get()
        mx.nd.waitall()
        now = time.perf_counter()
        param.eval_metric.reset()
        batch = param.locals["data_batch"].data[0]._data
        # where the step keeps its state, read while the loop is live
        # (fit's epoch end syncs parameters back to the first context)
        live = getattr(mod, "_zero3_params", None) or {
            n: mod._exec.arg_dict[n]._data for n in mod._param_names}
        steps.append({"epoch": param.epoch, "nbatch": param.nbatch,
                      "loss": float(loss), "wall_s": now - clock[0],
                      "batch_devices": len(batch.sharding.device_set),
                      "param_bytes": device_bytes(live),
                      "state_bytes": device_bytes(mod._fused_states),
                      "memory_report": mod._fused.memory_report(
                          live, mod._fused_states)})
        log("  %s step %d (epoch %d batch %d): loss=%.4f wall=%.3fs",
            tag, len(steps), param.epoch, param.nbatch, loss,
            now - clock[0])
        clock[0] = time.perf_counter()

    mod.fit(train, num_epoch=N_EPOCHS,
            eval_metric=mx.metric.CrossEntropy(),
            optimizer="sgd",
            optimizer_params={
                "learning_rate": LEARNING_RATE[
                    "full" if cfg is FULL else "tiny"],
                "momentum": 0.9},
            compute_dtype="bfloat16", batch_end_callback=on_batch,
            **fit_kwargs)
    return mod, steps


def check_training(mod, steps, cfg, tag):
    """What every trained module must show, whatever its layout."""
    step = mod._fused
    check(step is not None, "%s: the fused step was not built", tag)
    check(step._aot is not None,
          "%s: the AOT-compiled step was dropped for the lazy jit", tag)
    check(len(steps) == N_BATCHES * N_EPOCHS, "%s: %d steps ran, not %d",
          tag, len(steps), N_BATCHES * N_EPOCHS)
    losses = [s["loss"] for s in steps]
    check(all(math.isfinite(v) for v in losses), "%s: loss not finite: %s",
          tag, losses)
    ln_v = math.log(cfg["vocab_size"])
    check(abs(losses[0] - ln_v) < 0.5,
          "%s: first loss %.4f is not within 0.5 of ln(vocab)=%.4f",
          tag, losses[0], ln_v)
    for b in range(N_BATCHES):
        first, again = losses[b], losses[b + N_BATCHES]
        check(again < first, "%s: loss on repeated batch %d did not fall: "
              "%.4f -> %.4f", tag, b, first, again)
    stats = step.compile_stats or {}
    log("  %s compile: %.2fs cache_hit=%s", tag,
        stats.get("duration_s", float("nan")), stats.get("cache_hit"))
    return step


def train_phase(cfg, ctx, seed):
    log("== train: Module.fit, %dL-d%d-H%d-T%d-V%d, batch %d, bf16 ==",
        cfg["num_layers"], cfg["d_model"], cfg["num_heads"],
        cfg["seq_len"], cfg["vocab_size"], BATCH)
    mod, steps = fit_transformer(cfg, ctx, seed, "train")
    step = check_training(mod, steps, cfg, "train")
    dev = ctx.jax_device
    check(all(s["batch_devices"] == 1 for s in steps),
          "train: the batch was not on one device")
    for what, key in (("parameters", "param_bytes"),
                      ("optimizer state", "state_bytes")):
        held = steps[-1][key]
        check(set(held) == {dev}, "train: %s live on %s, not on %s",
              what, sorted(map(str, held)), dev)
        log("  %s: %d bytes on %s", what, held[dev], dev)
    # the compiled program must hold the attention kernel exactly when
    # the dispatch says so (shape, dtype and backend decide; see
    # ops/attention.py pallas_eligible)
    head = cfg["d_model"] // cfg["num_heads"]
    q = jax.ShapeDtypeStruct((BATCH, cfg["num_heads"], cfg["seq_len"],
                              head), "bfloat16")
    want_kernel = (attention.attention_impl() == "auto"
                   and jax.default_backend() == "tpu"
                   and attention.pallas_eligible(q, q, q))
    has_kernel = "tpu_custom_call" in step._aot.as_text()
    log("  attention dispatch says pallas=%s; compiled step holds "
        "tpu_custom_call=%s", want_kernel, has_kernel)
    check(want_kernel == has_kernel,
          "train: compiled step and attention dispatch disagree")
    return mod


def reference_logits(params, model_cfg, seq):
    """fp32-weight full-context forward over a finished sequence — the
    same gemm path (exact=False), independent of pages and buckets."""
    fwd = jax.jit(lambda p, t: serve.full_forward(p, t, model_cfg,
                                                  exact=False))
    return jax.device_get(fwd(params, jnp.asarray([seq], jnp.int32))[0])


def serve_phase(cfg, seed, tiny):
    buckets = (16, 32) if tiny else (128, 512)
    max_new = 4 if tiny else 32
    lo, hi = (4, 28) if tiny else (16, 400)
    log("== serve: InferenceSession + Scheduler, same width, fp32 "
        "weights, exact=False, buckets %s, max_new %d ==", buckets, max_new)
    model_cfg = serve.ModelConfig(
        vocab_size=cfg["vocab_size"], num_layers=cfg["num_layers"],
        d_model=cfg["d_model"], num_heads=cfg["num_heads"],
        max_len=cfg["seq_len"])
    params = serve.init_params(model_cfg, seed=seed)
    n_events = len(profiler.compile_events())
    t0 = time.perf_counter()
    session = serve.InferenceSession(
        params, num_heads=cfg["num_heads"],
        config=serve.ServeConfig(slots=8, page_size=16, buckets=buckets,
                                 max_new=max_new, exact=False))
    log("  session built in %.2fs", time.perf_counter() - t0)
    for ev in profiler.compile_events()[n_events:]:
        log("  compile %s: %.2fs cache_hit=%s", ev["name"],
            ev["duration_s"], ev.get("cache_hit"))
    check(len(session.executables) == len(buckets) + 1,
          "serve: %d executables, expected %d", len(session.executables),
          len(buckets) + 1)

    # wall time per call, taken around the session's own blocking calls
    # (each ends in a host read of the new tokens)
    timings = {"prefill": [], "step": []}
    for name in timings:
        inner = getattr(session, name)

        def timed(*args, _inner=inner, _name=name, **kwargs):
            t = time.perf_counter()
            out = _inner(*args, **kwargs)
            timings[_name].append(time.perf_counter() - t)
            return out

        setattr(session, name, timed)

    rs = np.random.RandomState(seed)
    lengths = np.linspace(lo, hi, 12).astype(int)
    rs.shuffle(lengths)
    requests = [
        serve.Request(rid=i, max_new=max_new, arrival_s=0.0,
                      prompt=[int(t) for t in
                              rs.randint(0, cfg["vocab_size"], int(n))])
        for i, n in enumerate(lengths)]
    done, makespan = serve.Scheduler(session).run(requests)
    log("  %d requests, prompts %s, makespan %.2fs", len(done),
        sorted(int(n) for n in lengths), makespan)
    for name, vals in timings.items():
        log("  %s calls: %d, wall s: %s", name, len(vals),
            " ".join("%.4f" % v for v in vals))
    for req in done:
        check(not req.failed, "serve: request %d failed: %s", req.rid,
              req.error)
        check(len(req.tokens) == max_new,
              "serve: request %d produced %d tokens, not %d", req.rid,
              len(req.tokens), max_new)
    check(session.fallback_count() == 0,
          "serve: %d dispatches fell back to the lazy jit",
          session.fallback_count())

    # greedy tokens against the reference's argmax, shortest and longest
    # prompt; where they differ the reference must call it a near-tie
    by_len = sorted(done, key=lambda r: len(r.prompt))
    for req in (by_len[0], by_len[-1]):
        seq = list(req.prompt) + list(req.tokens)
        logits = reference_logits(params, model_cfg, seq[:-1])
        rows = logits[len(req.prompt) - 1:]
        check(np.isfinite(rows).all(), "serve: reference logits not finite")
        best = rows.argmax(-1)
        got = np.asarray(req.tokens)
        gap = rows.max(-1) - rows[np.arange(len(got)), got]
        # bf16-pass matmuls over fp32 weights: a logit is good to about
        # 2^-7 of the row's spread
        tol = 2.0 ** -7 * (rows.max(-1) - rows.min(-1))
        flips = int((best != got).sum())
        log("  request %d (prompt %d): %d/%d tokens equal the reference "
            "argmax, %d near-tie flips, worst gap %.3g (tolerance %.3g)",
            req.rid, len(req.prompt), len(got) - flips, len(got), flips,
            float(gap.max()), float(tol.min()))
        check((gap <= tol).all(),
              "serve: request %d token(s) beyond tolerance of the "
              "reference: gaps %s", req.rid, gap[gap > tol])
        check(flips <= len(got) // 4,
              "serve: request %d: %d of %d tokens off the reference "
              "argmax", req.rid, flips, len(got))
    return session


def multichip_phase(cfg, ctxs, seed):
    """One chip, then data-parallel over all of them, then ZeRO-3: same
    model, same seeded data, losses compared step by step."""
    n = len(ctxs)
    log("== multichip: 1 chip vs dist_tpu_sync x%d vs plan data=%d,zero=3 "
        "==", n, n)
    devices = [c.jax_device for c in ctxs]
    check(len(set(devices)) == n, "contexts name %d distinct devices, "
          "not %d", len(set(devices)), n)

    mod, base = fit_transformer(cfg, ctxs[0], seed, "one-chip")
    check_training(mod, base, cfg, "one-chip")
    del mod
    gc.collect()

    def compare(steps, tag):
        for ref, got in zip(base, steps):
            # same data and init; bf16 compute reduced in another order
            check(abs(ref["loss"] - got["loss"]) < 0.05,
                  "%s: loss %.4f vs one-chip %.4f at epoch %d batch %d",
                  tag, got["loss"], ref["loss"], got["epoch"],
                  got["nbatch"])
            check(got["batch_devices"] == n,
                  "%s: batch on %d device(s), not %d", tag,
                  got["batch_devices"], n)

    def per_device(held, what, tag):
        log("  %s %s bytes per device: %s", tag, what,
            {str(d): b for d, b in sorted(held.items(),
                                          key=lambda kv: kv[0].id)})
        check(set(held) == set(devices),
              "%s: %s on %d device(s), not the %d asked for", tag, what,
              len(held), n)
        return held

    # (b) the data-parallel path of the reference's users
    mod, steps = fit_transformer(cfg, ctxs, seed, "dist_tpu_sync",
                                 kvstore="dist_tpu_sync")
    step = check_training(mod, steps, cfg, "dist_tpu_sync")
    log("  dist_tpu_sync: explicit bucketed reduction over axis %r, "
        "sharded optimizer update (MXNET_ZERO=auto) over axis %r",
        step.grad_overlap_axis, step.zero_axis)
    full = sum(base[-1]["param_bytes"].values())
    held = per_device(steps[-1]["param_bytes"], "parameter",
                      "dist_tpu_sync")
    check(all(b == full for b in held.values()),
          "dist_tpu_sync: replicated parameters should hold %d bytes on "
          "every device", full)
    per_device(steps[-1]["state_bytes"], "optimizer-state",
               "dist_tpu_sync")
    text = step._aot.as_text()
    check("all-reduce" in text,
          "dist_tpu_sync: no all-reduce in the compiled step")
    log("  dist_tpu_sync: compiled step holds all-reduce")
    compare(steps, "dist_tpu_sync")
    del mod, step, text
    gc.collect()

    # (c) ZeRO-3 under one plan declaration
    plan = "data=%d,zero=3" % n
    mod, steps = fit_transformer(cfg, ctxs, seed, plan, plan=plan)
    step = check_training(mod, steps, cfg, plan)
    check(step.zero3, "%s: the step is not ZeRO-3", plan)
    report = steps[-1]["memory_report"]
    log("  %s memory_report: %s", plan, report)
    for what, held, key in (
            ("parameter", steps[-1]["param_bytes"],
             "params_bytes_per_replica"),
            ("optimizer-state", steps[-1]["state_bytes"],
             "opt_state_bytes")):
        held = per_device(held, what, plan)
        check(all(b == report[key] for b in held.values()),
              "%s: %s bytes per device %s differ from the layout's %d",
              plan, what, sorted(held.values()), report[key])
        # tiled 1/n (padding and the few small replicated leaves aside)
        check(report[key] < 1.1 * full / n + (1 << 20),
              "%s: %d %s bytes per device is not ~1/%d of %d", plan,
              report[key], what, n, full)
    text = step._aot.as_text()
    check("all-gather" in text, "%s: no all-gather in the compiled step",
          plan)
    # the step asks for psum_scatter; the TPU compiler may keep it as
    # reduce-scatter or rewrite it to all-reduce + slice (compiling for
    # a described v5e:2x2 showed the latter) — say which it chose
    form = "reduce-scatter" if "reduce-scatter" in text else "all-reduce"
    check(form in text, "%s: no gradient reduction in the compiled step",
          plan)
    log("  %s: compiled step holds all-gather; the gradient "
        "reduce-scatter compiled as %s", plan, form)
    # last, so that a run whose losses part still shows where the
    # state sat
    compare(steps, plan)
    return mod


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: only the multi-chip training paths and "
                         "their one-chip comparison")
    ap.add_argument("--tiny", action="store_true",
                    help="CPU rehearsal at toy sizes; runs every step, "
                         "then exits non-zero unless on the chip")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    # libtpu reads LIBTPU_INIT_ARGS once, at backend start-up: only what
    # is in the environment NOW, before the first device lookup, can arm
    # the scheduler flags
    lhs_in_env = overlap.lhs_flags_present()
    devices = jax.devices()
    on_chip = devices[0].platform == "tpu" and len(devices) == args.chips
    found = "jax.devices() holds " + describe_devices()
    if not on_chip and not args.tiny:
        fail("chip_smoke.py needs %d TPU chip(s); %s", args.chips, found)
    if on_chip:
        ctxs = [mx.tpu(i) for i in range(args.chips)]
    else:
        check(len(devices) >= args.chips, "--tiny --chips %d needs %d "
              "devices (XLA_FLAGS=--xla_force_host_platform_device_count="
              "%d); %s", args.chips, args.chips, args.chips, found)
        ctxs = [mx.cpu(i) for i in range(args.chips)]
    log("chip_smoke: contexts %s; %s; jax %s, jaxlib %s, libtpu %s%s",
        ctxs, found, jax.__version__, metadata.version("jaxlib"),
        metadata.version("libtpu"),
        "" if on_chip else "  [REHEARSAL: mx.cpu contexts, no TPU — "
        "this run cannot pass]")
    log("  scheduler flags in LIBTPU_INIT_ARGS before the backend "
        "initialized: %s", lhs_in_env)
    log("  JAX_COMPILATION_CACHE_DIR=%r",
        os.environ.get("JAX_COMPILATION_CACHE_DIR"))
    cfg = TINY if args.tiny else FULL
    used = [c.jax_device for c in ctxs]
    t_start = time.perf_counter()

    if args.chips == 1:
        trainer = train_phase(cfg, ctxs[0], args.seed)
        memory_line("after train", used)
        cache_line("after train")
        # the trainer's ~8.5 GB must be gone before the session is built
        del trainer
        gc.collect()
        memory_line("trainer dropped", used)
        session = serve_phase(cfg, args.seed, args.tiny)
        memory_line("after serve", used)
        del session
    else:
        trainer = multichip_phase(cfg, ctxs, args.seed)
        memory_line("after multichip", used)
        del trainer
    cache_line("end")
    log("chip_smoke: all phases passed in %.1fs",
        time.perf_counter() - t_start)

    if not on_chip:
        fail("every phase ran, but not on the chip: %s", found)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
