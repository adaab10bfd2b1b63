#!/usr/bin/env python
"""Serving benchmark: serial vs static-batch vs continuous batching.

Drives one :class:`mxnet_tpu.serve.InferenceSession` (compiled ONCE —
the same bucketed prefill + fixed-shape decode executables serve every
policy) through an identical Poisson open-loop arrival trace under the
three scheduler policies, and reports per-policy p50/p99 TTFT,
per-token latency, and tokens/s.  The headline metric is the
continuous-batching speedup over serial one-request-at-a-time serving.

Also certifies the serving acceptance criteria directly in the JSON:

* ``bitexact``           — paged decode logits == jitted full-context
                           reference forward (``assert_array_equal``).
* ``kv_pool_bytes_*``    — decode KV memory at step 1 vs step N
                           (identical: the pools are fixed buffers).
* ``executables`` / ``recompiles`` — compiled-executable count stays at
                           ``len(buckets) + 1`` with one trace each
                           (``len(buckets) + 3`` for the speculative
                           session).
* ``bitexact_spec``      — speculative decoding emits token streams
                           identical to non-speculative greedy decode
                           (exact acceptance), measured over a full
                           continuous-batching A/B whose
                           ``spec_speedup`` / ``acceptance_rate`` /
                           ``tokens_per_verify_step`` ride along.
* ``quant_speedup`` / ``quant_bytes_shrink`` / ``max_logit_drift``
                         — weight-only quantization A/B
                           (``ServeConfig.quant``): decode tokens/s
                           fp32 vs int8, at-rest param shrink, and the
                           teacher-forced logit drift, with the
                           speedup-or-shrink acceptance bar asserted
                           and per-precision bit-exactness
                           (``bitexact_quant``) re-proved on the
                           quantized tree.
* ``kv_capacity_multiplier`` / ``kv_max_logit_drift`` /
  ``bitexact_kv_quant``  — quantized KV-cache A/B
                           (``ServeConfig.kv_quant``): pages held at a
                           fixed pool-byte budget f32 vs int8/e4m3
                           codes, the teacher-forced logit drift
                           (bound asserted), the per-precision paged
                           oracle re-proved bit-exactly, and the
                           executable count held frozen.
* ``prefix_*`` / ``bitexact_prefix`` — prefix-cache A/B over a
                           shared-preamble trace (same executables, only
                           ``prefix_pages`` flips): hit rate, prefill
                           tokens saved, TTFT p50/p99 per side, with the
                           measured TTFT reduction on hits and
                           stream-level bit-exactness asserted.
* ``oversub_*`` / ``bitexact_oversub`` — admission A/B at an equal
                           undersized page pool: reservation vs
                           oversubscription peak concurrency (oversub
                           must sustain more requests in flight),
                           preemption/resume counts, and bit-identical
                           token streams across the two policies.
* ``closed_loop_*``      — closed-loop load generator (the scheduler's
                           ``followup`` hook holds concurrency constant)
                           under a TTFT budget: goodput-under-SLO and
                           SLO attainment.
* ``window_*``           — hybrid long-context A/B
                           (``ServeConfig.layers``/``window``): peak
                           concurrency of a window+SSM stack vs full
                           attention at a fixed pool-byte budget (>= 2x
                           asserted — the hybrid stack reserves no
                           pages), per-side goodput, and per-token
                           decode latency at pinned 4k vs 32k contexts
                           with the O(1) flatness bound asserted.
* ``soak_*``             — replicated-serving chaos soak
                           (``serve.ReplicaSet``, 3 replicas): one
                           replica chaos-killed mid-traffic, asserting
                           zero lost requests, bit-exact survivor
                           streams vs the fault-free baseline, typed
                           shed accounting, goodput >= 60% of baseline,
                           and the per-replica executable count frozen
                           across death + failover.
* ``gw_*``               — network-edge soak: the streaming asyncio
                           ``serve.Gateway`` over real sockets, same
                           trace + replica kill, with every 5th client
                           RST-crashing mid-stream — zero lost
                           requests, byte-identical completed streams,
                           state back at the cold snapshot, and a clean
                           graceful drain, all asserted.
* ``compile_report``     — ``compile_cache.write_artifact`` path for
                           the serving executable set
                           (pretty-print: ``tools/compile_report.py``).

Prints ONE JSON line.  Honors ``MXNET_BENCH_BUDGET_S`` (valid partial
JSON + exit 0) and always arms the ``bench_util`` watchdog.

Usage: bench_serve.py [--requests=N] [--max-new=N] [--quant=MODE]
                      [--kv-quant=MODE] [--watchdog SEC]
"""
import json
import sys
import time

sys.path.insert(0, ".")

import bench_util

_RESULT = {"metric": "serve_continuous_speedup_vs_serial"}


def _poisson_trace(n_requests, mean_gap_s, prompt_lens, max_new, seed):
    """Seeded open-loop arrival trace, replayed for every policy."""
    import numpy as np

    from mxnet_tpu.serve import Request

    rs = np.random.RandomState(seed)
    gaps = rs.exponential(mean_gap_s, size=n_requests)
    arrivals = np.cumsum(gaps) - gaps[0]  # first request at t=0
    reqs = []
    for i in range(n_requests):
        plen = int(prompt_lens[i % len(prompt_lens)])
        prompt = rs.randint(1, 127, size=plen).tolist()
        reqs.append(dict(rid=i, prompt=prompt, max_new=int(max_new),
                         arrival_s=float(arrivals[i])))
    return reqs


def _gw_client(port, spec, disconnect, out):
    """One socket client for the gateway soak: sleeps to its Poisson
    arrival offset, POSTs ``/v1/generate``, parses the chunked SSE
    stream, and records a TYPED terminal outcome.  ``disconnect``
    clients RST-close after the first token event (a crashed client —
    the gateway must cancel the decode and free its state)."""
    import socket
    import struct

    time.sleep(spec["arrival_s"])
    rec = {"outcome": "error", "ttft_s": None, "tokens": None}
    out[spec["rid"]] = rec
    t0 = time.perf_counter()
    try:
        sk = socket.create_connection(("127.0.0.1", port), timeout=300)
    except OSError:
        return
    try:
        body = json.dumps({"rid": spec["rid"], "prompt": spec["prompt"],
                           "max_new": spec["max_new"]}).encode()
        sk.sendall(b"POST /v1/generate HTTP/1.1\r\nHost: bench\r\n"
                   b"Content-Length: " + str(len(body)).encode()
                   + b"\r\n\r\n" + body)
        buf = b""
        while b"\r\n\r\n" not in buf:
            chunk = sk.recv(65536)
            if not chunk:
                return
            buf += chunk
        head, _, buf = buf.partition(b"\r\n\r\n")
        status = int(head.split(None, 2)[1])
        if status == 429:
            rec["outcome"] = "shed"
            return
        if status != 200:
            rec["outcome"] = "http_%d" % status
            return
        while b"data: " not in buf:
            chunk = sk.recv(65536)
            if not chunk:
                return
            buf += chunk
        rec["ttft_s"] = time.perf_counter() - t0
        if disconnect:
            sk.setsockopt(socket.SOL_SOCKET, socket.SO_LINGER,
                          struct.pack("ii", 1, 0))
            rec["outcome"] = "disconnected"
            return
        while True:
            chunk = sk.recv(65536)
            if not chunk:
                break
            buf += chunk
        payload, events = b"", []
        while buf:  # de-chunk the HTTP body, then parse the SSE events
            size, _, buf = buf.partition(b"\r\n")
            n = int(size, 16)
            if n == 0:
                break
            payload += buf[:n]
            buf = buf[n + 2:]
        for line in payload.split(b"\n"):
            if line.startswith(b"data: "):
                events.append(json.loads(line[6:]))
        last = events[-1] if events else {}
        if last.get("done") and last.get("tokens") is not None:
            rec["outcome"] = "completed"
            rec["tokens"] = last["tokens"]
        elif last.get("done"):
            rec["outcome"] = "failed:%s" % last.get("error")
    except (OSError, ValueError):
        pass  # rec stays "error": the zero-lost assert surfaces it
    finally:
        sk.close()


def measure(argv=None):
    import numpy as np

    from mxnet_tpu import compile_cache, serve
    from mxnet_tpu.serve import model as serve_model

    argv = sys.argv if argv is None else argv
    n_requests = int(next((a.split("=")[1] for a in argv
                           if a.startswith("--requests=")), 16))
    max_new = int(next((a.split("=")[1] for a in argv
                        if a.startswith("--max-new=")), 16))

    cfg = serve.ModelConfig(vocab_size=128, num_layers=2, d_model=64,
                            num_heads=2, max_len=128)
    params = serve_model.init_params(cfg, seed=0)
    sconf = serve.ServeConfig(slots=8, page_size=16, buckets=(16, 32),
                              max_new=max_new, exact=True)
    t0 = time.perf_counter()
    sess = serve.InferenceSession(params, num_heads=cfg.num_heads,
                                  config=sconf)
    _RESULT["compile_s"] = round(time.perf_counter() - t0, 3)
    _RESULT["model"] = "%dL-d%d-V%d" % (cfg.num_layers, cfg.d_model,
                                        cfg.vocab_size)
    _RESULT["slots"] = sconf.slots
    _RESULT["buckets"] = list(sconf.buckets)
    _RESULT["executables"] = sorted(sess.executables)

    # -- acceptance probe 1: paged decode bit-exact vs reference ---------
    def ref_row(seq):
        return np.asarray(serve_model.reference_last_logits(
            sess.params, seq, cfg, sconf.page_size, exact=True))

    probe = list(np.random.RandomState(1).randint(1, 127, size=9))
    slot = sess.try_alloc(len(probe), 8)
    first, last_logits = sess.prefill(slot, probe)
    last_logits = np.asarray(last_logits)
    np.testing.assert_array_equal(last_logits, ref_row(probe))
    seq = list(probe) + [first]
    for _ in range(7):
        toks, logits = sess.step()
        logits = np.asarray(logits)
        np.testing.assert_array_equal(logits[slot], ref_row(seq))
        seq.append(toks[slot])
    sess.release(slot)
    _RESULT["bitexact"] = True

    # -- acceptance probe 2: KV memory flat in generated length ----------
    # the pools are fixed-shape buffers and the ONE decode executable
    # serves every step, so the watermark cannot move; record it from
    # both ends of a max-length generation to make that observable.
    mem = sess.memory_analysis("decode")
    _RESULT["decode_memory_analysis"] = mem
    slot = sess.try_alloc(16, max_new)
    sess.prefill(slot, list(range(1, 17)))
    step1_bytes = sess.cache.pool_bytes()
    sess.step()
    for _ in range(max_new - 2):
        sess.step()
    stepN_bytes = sess.cache.pool_bytes()
    sess.release(slot)
    _RESULT["kv_pool_bytes_step1"] = step1_bytes
    _RESULT["kv_pool_bytes_stepN"] = stepN_bytes
    assert step1_bytes == stepN_bytes, "KV pool bytes moved during decode"

    # -- the policy comparison -------------------------------------------
    trace = _poisson_trace(n_requests, mean_gap_s=0.002,
                           prompt_lens=(9, 14, 23, 30), max_new=max_new,
                           seed=2)
    policies = ("serial", "static", "continuous")
    for policy in policies:
        reqs = [serve.Request(**spec) for spec in trace]
        sched = serve.Scheduler(sess, policy=policy)
        done, makespan = sched.run(reqs)
        summary = serve.summarize(done, makespan)
        assert summary["failed"] == 0, "%s: %d requests failed" \
            % (policy, summary["failed"])
        assert summary["completed"] == n_requests
        for key, val in summary.items():
            _RESULT["%s_%s" % (policy, key)] = (
                round(val, 5) if isinstance(val, float) else val)

    speedup = (_RESULT["continuous_tokens_per_sec"]
               / max(_RESULT["serial_tokens_per_sec"], 1e-9))
    _RESULT["value"] = round(speedup, 2)
    _RESULT["unit"] = "x serial tokens/s"
    _RESULT["tokens_per_sec"] = _RESULT["continuous_tokens_per_sec"]

    # -- speculative decoding A/B ----------------------------------------
    # Self-speculative rig sharing the target family: damp the target's
    # upper-block out-projections so the first block carries most of the
    # prediction, then draft with the target truncated to that block
    # (layer-skip).  Acceptance is high for honest, reported reasons —
    # the damping is part of the rig, acceptance_rate is the measurement.
    import dataclasses as _dc

    # Speculation pays where decode is dispatch-bound, i.e. low slot
    # occupancy and long generations (a batch-8 decode step already
    # amortizes dispatch 8 ways, and draft prompt ingest must amortize
    # over the tokens it unlocks) — so the A/B runs its own
    # low-concurrency rig: 2 slots, short prompts, 64-token decodes.
    spec_k = int(next((a.split("=")[1] for a in argv
                       if a.startswith("--spec-k=")), 7))
    spec_max_new = 80
    damped = dict(params)
    for name in list(damped):
        blk = name.split("_", 1)[0]
        if (blk.startswith("blk") and int(blk[3:]) >= 1
                and name.endswith(("attn_out_weight", "ffn2_weight"))):
            damped[name] = damped[name] * 0.03
    spec_base = _dc.replace(sconf, slots=2, max_new=spec_max_new)
    spec_off = serve.InferenceSession(damped, num_heads=cfg.num_heads,
                                      config=spec_base)
    spec_conf = _dc.replace(spec_base, spec_k=spec_k, draft="layers:1")
    spec_on = serve.InferenceSession(damped, num_heads=cfg.num_heads,
                                     config=spec_conf)
    assert len(spec_on.executables) == len(spec_conf.buckets) + 3
    spec_trace = _poisson_trace(max(n_requests // 2, 8),
                                mean_gap_s=0.002,
                                prompt_lens=(9, 14),
                                max_new=spec_max_new, seed=4)
    spec_outs = {}
    for tag, spec_sess in (("spec_off", spec_off), ("spec_on", spec_on)):
        # one unmeasured warmup pass per rig irons out first-dispatch
        # jitter so the A/B compares steady-state serving
        serve.Scheduler(spec_sess, policy="continuous").run(
            [serve.Request(**spec) for spec in spec_trace[:2]])
        reqs = [serve.Request(**spec) for spec in spec_trace]
        done, makespan = serve.Scheduler(spec_sess,
                                         policy="continuous").run(reqs)
        summary = serve.summarize(done, makespan)
        assert summary["failed"] == 0, "%s: %d requests failed" \
            % (tag, summary["failed"])
        spec_outs[tag] = {r.rid: list(r.tokens) for r in done}
        for key in ("tokens_per_sec", "ttft_p50_s", "ttft_p99_s",
                    "total_tokens", "makespan_s"):
            val = summary[key]
            _RESULT["%s_%s" % (tag, key)] = (
                round(val, 5) if isinstance(val, float) else val)
    # the acceptance criterion: speculation may change only the cost of
    # a token stream, never its content
    _RESULT["bitexact_spec"] = spec_outs["spec_on"] == spec_outs["spec_off"]
    assert _RESULT["bitexact_spec"], "speculative decode drifted"
    rep = spec_on.spec_report()
    _RESULT["spec_k"] = spec_k
    _RESULT["acceptance_rate"] = round(rep["acceptance_rate"], 4)
    _RESULT["tokens_per_verify_step"] = round(
        rep["tokens_per_verify_step"], 3)
    _RESULT["spec_speedup"] = round(
        _RESULT["spec_on_tokens_per_sec"]
        / max(_RESULT["spec_off_tokens_per_sec"], 1e-9), 2)
    _RESULT["spec_executables"] = sorted(spec_on.executables)
    assert spec_on.fallback_count() == 0

    # -- weight-only quantization A/B ------------------------------------
    # Same model, same executable count, 1-byte weight codes: the A/B
    # measures steady-state decode tokens/s fp32 vs int8 and certifies
    # the two acceptance bars — either decode gets >= 1.15x faster or
    # the at-rest + gather bytes shrink >= 3.5x with throughput held —
    # plus an explicit logit-drift bound under teacher forcing.
    from mxnet_tpu import quantize as _quantize

    def _decode_tps(s, steps, cycles=4):
        # several alloc->decode cycles per measurement, timing only the
        # steady-state step loops: one cycle's window is ~steps decode
        # dispatches, too short to survive scheduler jitter
        rs = np.random.RandomState(7)
        total_dt, total_tok = 0.0, 0
        for _ in range(cycles):
            slots = []
            for _ in range(s.config.slots):
                sl = s.try_alloc(9, s.config.max_new)
                s.prefill(sl, rs.randint(1, 127, size=9).tolist())
                slots.append(sl)
            for _ in range(2):  # warmup: steady-state dispatch only
                s.step()
            t0 = time.perf_counter()
            for _ in range(steps):
                s.step()
            total_dt += time.perf_counter() - t0
            total_tok += s.config.slots * steps
            for sl in slots:
                s.release(sl)
        return total_tok / total_dt

    qmode = next((a.split("=")[1] for a in argv
                  if a.startswith("--quant=")), "int8")
    qsess = serve.InferenceSession(
        params, num_heads=cfg.num_heads,
        config=_dc.replace(sconf, quant=qmode))
    assert len(qsess.executables) == len(sconf.buckets) + 1
    _RESULT["quant"] = qmode
    _RESULT["weight_dtype"] = "float32"
    _RESULT["quant_weight_dtype"] = str(
        np.dtype(_quantize.quant_dtype(qmode)))

    # bit-exactness holds PER PRECISION: the quantized session must
    # match the jitted reference forward over its own quantized tree
    qslot = qsess.try_alloc(len(probe), 8)
    qfirst, qlogits = qsess.prefill(qslot, probe)
    qlogits = np.asarray(qlogits)
    np.testing.assert_array_equal(
        qlogits, np.asarray(serve_model.reference_last_logits(
            qsess.params, probe, cfg, sconf.page_size, exact=True)))
    qsess.release(qslot)
    _RESULT["bitexact_quant"] = True

    # logit drift vs fp32, teacher-forced so both sessions score the
    # SAME token sequence (greedy streams may diverge after one flip)
    drift = 0.0
    bslot = sess.try_alloc(len(probe), 8)
    qslot = qsess.try_alloc(len(probe), 8)
    bfirst, blog = sess.prefill(bslot, probe)
    blog = np.asarray(blog)
    _, qlog = qsess.prefill(qslot, probe)
    qlog = np.asarray(qlog)
    drift = max(drift, float(np.max(np.abs(qlog - blog))))
    for _ in range(6):
        qsess._slot_tokens[qslot] = sess._slot_tokens[bslot]
        btoks, blogs = sess.step()
        blogs = np.asarray(blogs)
        qtoks, qlogs = qsess.step()
        qlogs = np.asarray(qlogs)
        drift = max(drift, float(np.max(np.abs(qlogs[qslot]
                                               - blogs[bslot]))))
    sess.release(bslot)
    qsess.release(qslot)
    drift_bound = 0.25 if qmode == "int8" else 1.0
    _RESULT["max_logit_drift"] = round(drift, 5)
    _RESULT["logit_drift_bound"] = drift_bound
    assert drift <= drift_bound, \
        "%s logit drift %.4f exceeds %.2f" % (qmode, drift, drift_bound)

    # bytes: at-rest params and the decode executable's argument volume
    base_bytes = sess.params_bytes_at_rest()
    quant_bytes = qsess.params_bytes_at_rest()
    _RESULT["params_bytes_fp32"] = base_bytes
    _RESULT["params_bytes_quant"] = quant_bytes
    _RESULT["quant_bytes_shrink"] = round(base_bytes
                                          / max(quant_bytes, 1), 2)
    qmem = qsess.memory_analysis("decode")
    _RESULT["quant_decode_argument_bytes"] = qmem.get(
        "argument_size_in_bytes")
    _RESULT["decode_argument_bytes"] = mem.get("argument_size_in_bytes")

    # steady-state decode throughput A/B (same slot count, same step
    # count; the baseline reuses the already-warm main session).
    # Interleaved best-of-3: single passes swing ~20% under scheduler
    # noise at these tiny step times; alternating the sides and taking
    # each side's best damps both the noise and any slow load drift.
    ab_steps = max(4, min(12, max_new - 3))
    base_tps, quant_tps = 0.0, 0.0
    for _ in range(3):
        base_tps = max(base_tps, _decode_tps(sess, ab_steps))
        quant_tps = max(quant_tps, _decode_tps(qsess, ab_steps))
    _RESULT["decode_tokens_per_sec_fp32"] = round(base_tps, 1)
    _RESULT["decode_tokens_per_sec_quant"] = round(quant_tps, 1)
    _RESULT["quant_speedup"] = round(quant_tps / max(base_tps, 1e-9), 3)
    # Acceptance: EITHER decode gets >=1.15x faster (bandwidth-bound
    # accelerator rigs, where 4x-smaller weights shrink the HBM reads
    # each step) OR the at-rest/gather footprint shrinks >=3.5x with
    # throughput held.  "Held" is 0.82 here: on CPU the per-step
    # dequant is exposed arithmetic next to these tiny matmuls
    # (measured 0.86-0.94 across runs), a real but bounded cost — the
    # bar sits just under that band's floor so it catches a regression
    # (e.g. dequant falling out of the fused executable) without
    # flaking on scheduler noise.
    assert (_RESULT["quant_speedup"] >= 1.15
            or (_RESULT["quant_bytes_shrink"] >= 3.5
                and _RESULT["quant_speedup"] >= 0.82)), \
        "quant A/B: speedup %.3f, shrink %.2fx — neither bar met" \
        % (_RESULT["quant_speedup"], _RESULT["quant_bytes_shrink"])

    # -- quantized KV-cache A/B (int8/e4m3 pages) ------------------------
    # Same model, same executable set, 1-byte KV codes with one f32
    # scale per (layer, page, offset) row: the A/B certifies the
    # capacity multiplier at a fixed pool-byte budget, bounds the logit
    # drift vs the f32 cache under teacher forcing, and re-proves the
    # paged oracle bit-exactly at the cache's own precision.
    from mxnet_tpu.serve.kv_cache import PagedKVCache

    kvq = next((a.split("=")[1] for a in argv
                if a.startswith("--kv-quant=")), "int8")
    kvsess = serve.InferenceSession(
        params, num_heads=cfg.num_heads,
        config=_dc.replace(sconf, kv_quant=kvq))
    assert len(kvsess.executables) == len(sconf.buckets) + 1
    _RESULT["kv_quant"] = kvq
    _RESULT["kv_code_dtype"] = str(np.dtype(
        kvsess.cache.pools["k_pool"].dtype))

    # the M-invariant oracle holds PER PRECISION: quantized paged decode
    # must match the jitted reference forward at the SAME kv precision
    kslot = kvsess.try_alloc(len(probe), 8)
    kfirst, klogits = kvsess.prefill(kslot, probe)
    klogits = np.asarray(klogits)
    np.testing.assert_array_equal(
        klogits, np.asarray(serve_model.reference_last_logits(
            kvsess.params, probe, cfg, sconf.page_size, exact=True,
            kv_quant=kvq)))
    kseq = list(probe) + [kfirst]
    for _ in range(4):
        ktoks, klogs = kvsess.step()
        klogs = np.asarray(klogs)
        np.testing.assert_array_equal(
            klogs[kslot], np.asarray(serve_model.reference_last_logits(
                kvsess.params, kseq, cfg, sconf.page_size, exact=True,
                kv_quant=kvq)))
        kseq.append(ktoks[kslot])
    kvsess.release(kslot)
    _RESULT["bitexact_kv_quant"] = True

    # logit drift vs the f32 cache, teacher-forced (same bound shape as
    # the weight A/B: int8 rows carry more mantissa than e4m3)
    kv_drift = 0.0
    bslot = sess.try_alloc(len(probe), 8)
    kslot = kvsess.try_alloc(len(probe), 8)
    _, blog = sess.prefill(bslot, probe)
    blog = np.asarray(blog)
    _, klog = kvsess.prefill(kslot, probe)
    klog = np.asarray(klog)
    kv_drift = max(kv_drift, float(np.max(np.abs(klog - blog))))
    for _ in range(6):
        kvsess._slot_tokens[kslot] = sess._slot_tokens[bslot]
        btoks, blogs = sess.step()
        blogs = np.asarray(blogs)
        ktoks, klogs = kvsess.step()
        klogs = np.asarray(klogs)
        kv_drift = max(kv_drift, float(np.max(np.abs(klogs[kslot]
                                                     - blogs[bslot]))))
    sess.release(bslot)
    kvsess.release(kslot)
    kv_bound = 0.25 if kvq == "int8" else 1.0
    _RESULT["kv_max_logit_drift"] = round(kv_drift, 5)
    _RESULT["kv_logit_drift_bound"] = kv_bound
    assert kv_drift <= kv_bound, \
        "kv %s logit drift %.4f exceeds %.2f" % (kvq, kv_drift, kv_bound)

    # slot capacity at a FIXED pool-byte budget: a page's rows shrink
    # from 4-byte floats to 1-byte codes plus one f32 scale per row, so
    # the same byte budget holds ~(4·H·D)/(H·D+4) times the pages —
    # multiplicative atop oversubscription's admission-by-need
    head_dim = cfg.d_model // cfg.num_heads
    f32_page = PagedKVCache.page_bytes(cfg.num_layers, cfg.num_heads,
                                       head_dim, sconf.page_size)
    q_page = PagedKVCache.page_bytes(cfg.num_layers, cfg.num_heads,
                                     head_dim, sconf.page_size,
                                     kv_quant=kvq)
    _RESULT["kv_page_bytes_f32"] = f32_page
    _RESULT["kv_page_bytes_quant"] = q_page
    _RESULT["kv_capacity_multiplier"] = round(f32_page / q_page, 2)
    budget_pages = 64
    _RESULT["kv_pages_at_budget_f32"] = budget_pages
    _RESULT["kv_pages_at_budget_quant"] = (budget_pages * f32_page) // q_page
    assert _RESULT["kv_capacity_multiplier"] >= 3.0, \
        "kv capacity multiplier %.2f below 3x" \
        % _RESULT["kv_capacity_multiplier"]

    # throughput: quantize-on-append and in-kernel dequant must stay
    # inside the one decode executable.  Recorded, not barred — on CPU
    # the per-block dequant is exposed arithmetic next to tiny matmuls;
    # on bandwidth-bound accelerators the 4x-smaller KV reads win.
    kv_tps = 0.0
    for _ in range(3):
        base_tps = max(base_tps, _decode_tps(sess, ab_steps))
        kv_tps = max(kv_tps, _decode_tps(kvsess, ab_steps))
    _RESULT["decode_tokens_per_sec_kv_quant"] = round(kv_tps, 1)
    _RESULT["kv_quant_speedup"] = round(kv_tps / max(base_tps, 1e-9), 3)
    kv_guards = {
        name: snap for name, snap in kvsess.guard_report().items()
        if snap.get("traces", 0) > 1 or snap.get("signatures", 0) > 1}
    assert not kv_guards, "kv-quant executables retraced: %r" % (kv_guards,)

    # -- prefix caching A/B ----------------------------------------------
    # Prefix-heavy trace: every prompt opens with the same 96-token
    # system preamble (6 full pages at page_size 16) and a 16-token
    # per-request suffix.  The two sessions compile the SAME executable
    # set; only prefix_pages flips.  On a hit the preamble's pages are
    # mapped read-only and prefill runs just the suffix through the
    # 32-bucket instead of the whole prompt through the 112-bucket —
    # the TTFT delta is that compute, measured.
    pfx_conf = _dc.replace(sconf, slots=4, buckets=(32, 112), max_new=4)
    pfx_off = serve.InferenceSession(params, num_heads=cfg.num_heads,
                                     config=pfx_conf)
    pfx_on = serve.InferenceSession(
        params, num_heads=cfg.num_heads,
        config=_dc.replace(pfx_conf, prefix_pages=-1))
    assert len(pfx_on.executables) == len(pfx_conf.buckets) + 1
    assert len(pfx_off.executables) == len(pfx_conf.buckets) + 1
    rs = np.random.RandomState(9)
    preamble = rs.randint(1, 127, size=96).tolist()
    pfx_trace = _poisson_trace(8, mean_gap_s=0.002, prompt_lens=(16,),
                               max_new=4, seed=5)
    for spec in pfx_trace:
        spec["prompt"] = preamble + spec["prompt"]
    # interleaved best-of-3 (as in the quant A/B): each pass replays the
    # identical trace; the on-session's published preamble pages persist
    # across passes, so from the first pass's second request onward
    # every admission is a hit
    pfx_p50 = {"off": float("inf"), "on": float("inf")}
    pfx_p99 = {"off": float("inf"), "on": float("inf")}
    pfx_streams = {}
    for _ in range(3):
        for tag, psess in (("off", pfx_off), ("on", pfx_on)):
            reqs = [serve.Request(**spec) for spec in pfx_trace]
            done, makespan = serve.Scheduler(
                psess, policy="continuous").run(reqs)
            summary = serve.summarize(done, makespan)
            assert summary["failed"] == 0
            pfx_p50[tag] = min(pfx_p50[tag], summary["ttft_p50_s"])
            pfx_p99[tag] = min(pfx_p99[tag], summary["ttft_p99_s"])
            pfx_streams[tag] = {r.rid: list(r.tokens) for r in done}
    stats = pfx_on.cache.prefix_stats
    _RESULT["prefix_hit_rate"] = round(
        stats["hits"] / max(stats["lookups"], 1), 3)
    _RESULT["prefix_prefill_tokens_saved"] = stats["hit_tokens"]
    _RESULT["prefix_ttft_p50_off_s"] = round(pfx_p50["off"], 5)
    _RESULT["prefix_ttft_p50_on_s"] = round(pfx_p50["on"], 5)
    _RESULT["prefix_ttft_p99_off_s"] = round(pfx_p99["off"], 5)
    _RESULT["prefix_ttft_p99_on_s"] = round(pfx_p99["on"], 5)
    _RESULT["prefix_ttft_reduction"] = round(
        1.0 - pfx_p50["on"] / max(pfx_p50["off"], 1e-9), 3)
    # acceptance: hits must MEASURABLY cut TTFT, and the cache may
    # change only the cost of a stream, never its content
    assert _RESULT["prefix_hit_rate"] > 0.5
    assert _RESULT["prefix_prefill_tokens_saved"] > 0
    assert _RESULT["prefix_ttft_reduction"] > 0, \
        "prefix hits did not reduce TTFT (p50 on %.5fs vs off %.5fs)" \
        % (pfx_p50["on"], pfx_p50["off"])
    _RESULT["bitexact_prefix"] = pfx_streams["on"] == pfx_streams["off"]
    assert _RESULT["bitexact_prefix"], "prefix-cache hits drifted"
    assert pfx_on.fallback_count() == 0

    # -- oversubscription A/B at an equal undersized pool ----------------
    # 7-page pool, 16-token prompts decoding 16 tokens (2 pages at
    # rest).  Reservation admission can hold at most 3 requests in
    # flight; oversubscription admits by current need (1 page), fills
    # all 6 slots, and pays with watermark preemption + deterministic
    # re-prefill when growth drains the pool.
    ovs_conf = _dc.replace(sconf, slots=6, buckets=(16, 32), max_new=16,
                           num_pages=7)
    ovs_burst = [dict(rid=i,
                      prompt=np.random.RandomState(20 + i).randint(
                          1, 127, size=16).tolist(),
                      max_new=16, arrival_s=0.0) for i in range(12)]
    ovs_streams, ovs_peak = {}, {}
    for tag, oconf in (("reserved", ovs_conf),
                       ("oversub", _dc.replace(ovs_conf, oversub=True,
                                               watermark=1))):
        osess = serve.InferenceSession(params, num_heads=cfg.num_heads,
                                       config=oconf)
        assert len(osess.executables) == len(oconf.buckets) + 1
        sched = serve.Scheduler(osess, policy="continuous")
        done, makespan = sched.run(
            [serve.Request(**spec) for spec in ovs_burst])
        summary = serve.summarize(done, makespan)
        assert summary["failed"] == 0, "%s: %d requests failed" \
            % (tag, summary["failed"])
        ovs_streams[tag] = {r.rid: list(r.tokens) for r in done}
        ovs_peak[tag] = sched.stats["peak_active"]
        _RESULT["oversub_%s_peak_active" % tag] = sched.stats["peak_active"]
        _RESULT["oversub_%s_tokens_per_sec" % tag] = round(
            summary["tokens_per_sec"], 1)
        if tag == "oversub":
            _RESULT["oversub_preemptions"] = sched.stats["preemptions"]
            _RESULT["oversub_resumes"] = sched.stats["resumes"]
            assert sched.stats["preemptions"] > 0
            assert osess.fallback_count() == 0
    # acceptance: more requests in flight at the same pool size, with
    # bit-identical streams — oversubscription changes capacity only
    assert ovs_peak["oversub"] > ovs_peak["reserved"], \
        "oversub peak %d not above reservation peak %d" \
        % (ovs_peak["oversub"], ovs_peak["reserved"])
    _RESULT["bitexact_oversub"] = (ovs_streams["oversub"]
                                   == ovs_streams["reserved"])
    assert _RESULT["bitexact_oversub"], "preempt-and-recompute drifted"

    # -- closed-loop goodput under a TTFT SLO ----------------------------
    # The scheduler's followup hook spawns one replacement request per
    # completion, holding concurrency at the slot count instead of
    # replaying an open-loop trace; the session's TTFT budget drives
    # can-still-meet-first admission and summarize() reports goodput.
    slo_ms = 250.0
    slo_sess = serve.InferenceSession(
        params, num_heads=cfg.num_heads,
        config=_dc.replace(sconf, slots=4, max_new=8, ttft_slo_ms=slo_ms))
    cl_total = max(n_requests, 12)
    cl_rs = np.random.RandomState(13)
    cl_issued = {"n": 0}

    def _cl_request(now_s):
        cl_issued["n"] += 1
        plen = int(cl_rs.choice((9, 14, 23)))
        return serve.Request(rid=2000 + cl_issued["n"],
                             prompt=cl_rs.randint(1, 127,
                                                  size=plen).tolist(),
                             max_new=8, arrival_s=now_s)

    def _cl_followup(req, now_s):
        return _cl_request(now_s) if cl_issued["n"] < cl_total else None

    seeds = [_cl_request(0.0) for _ in range(4)]
    done, makespan = serve.Scheduler(slo_sess, policy="continuous").run(
        seeds, followup=_cl_followup)
    summary = serve.summarize(done, makespan, ttft_slo_ms=slo_ms)
    assert summary["failed"] == 0
    assert summary["completed"] == cl_total
    assert summary["goodput_rps"] > 0
    _RESULT["closed_loop_requests"] = summary["completed"]
    _RESULT["closed_loop_ttft_slo_ms"] = slo_ms
    _RESULT["closed_loop_goodput_rps"] = round(summary["goodput_rps"], 2)
    _RESULT["closed_loop_slo_attainment"] = round(
        summary["slo_attainment"], 3)
    _RESULT["closed_loop_ttft_p50_s"] = round(summary["ttft_p50_s"], 5)
    _RESULT["closed_loop_ttft_p99_s"] = round(summary["ttft_p99_s"], 5)
    _RESULT["closed_loop_tokens_per_sec"] = round(
        summary["tokens_per_sec"], 1)

    # -- replicated-serving chaos soak -----------------------------------
    # Three identical replicas (replica 0 IS the main session) behind
    # the ReplicaSet dispatcher; one replica is chaos-killed mid-traffic
    # and stays dead (huge rejoin backoff), so the survivors absorb its
    # in-flight work through the park/resume failover path.  Acceptance,
    # asserted here and recorded in the JSON: zero lost requests,
    # completed streams bit-identical to the fault-free baseline run,
    # shed requests typed and accounted, goodput >= 60% of the baseline
    # (proportional to the capacity that survived), and the per-replica
    # executable count frozen across death + failover.
    from mxnet_tpu.testing import faults as _faults

    soak_sessions = [sess] + [
        serve.InferenceSession(params, num_heads=cfg.num_heads,
                               config=sconf) for _ in range(2)]
    soak_n = max(3 * n_requests // 2, 24)
    soak_trace = _poisson_trace(soak_n, mean_gap_s=0.002,
                                prompt_lens=(9, 14), max_new=8, seed=11)

    def _soak_run():
        rs_set = serve.ReplicaSet(sessions=soak_sessions,
                                  rejoin_backoff_s=1e9)
        done, makespan = rs_set.run(
            [serve.Request(**spec) for spec in soak_trace])
        return rs_set, done, makespan, serve.summarize(done, makespan)

    # fault-free baseline: the goodput bar's denominator and the
    # bit-exactness oracle
    _, base_done, base_makespan, base_sum = _soak_run()
    assert base_sum["failed"] == 0 and base_sum["completed"] == soak_n
    soak_oracle = {r.rid: list(r.tokens) for r in base_done}
    base_rps = base_sum["completed"] / max(base_makespan, 1e-9)

    import os as _os
    _os.environ["MXNET_FAULT_INJECT"] = "serve_replica_kill:kill:after=16"
    _faults.reset()
    try:
        rs_set, done, makespan, soak_sum = _soak_run()
    finally:
        del _os.environ["MXNET_FAULT_INJECT"]
        _faults.reset()
    _RESULT["soak_replicas"] = 3
    _RESULT["soak_requests"] = soak_n
    _RESULT["soak_deaths"] = rs_set.counters["deaths"]
    _RESULT["soak_failover_requests"] = rs_set.counters["failover_requests"]
    _RESULT["soak_resumes"] = soak_sum["resumes"]
    _RESULT["soak_shed"] = soak_sum["shed"]
    _RESULT["soak_completed"] = soak_sum["completed"]
    assert rs_set.counters["deaths"] == 1
    # zero lost: every request either completed or was shed TYPED —
    # nothing vanished with the dead replica
    _RESULT["soak_zero_lost"] = (
        soak_sum["completed"] + soak_sum["shed"] == soak_n
        and soak_sum["faulted"] == 0)
    assert _RESULT["soak_zero_lost"], \
        "soak lost requests: %r" % {k: soak_sum[k] for k in
                                    ("completed", "shed", "faulted")}
    assert all(("ServeOverloaded" in r.error) for r in done if r.failed)
    # completed streams bit-identical to the never-failed baseline
    _RESULT["soak_bitexact"] = all(
        soak_oracle[r.rid] == r.tokens for r in done if not r.failed)
    assert _RESULT["soak_bitexact"], "failover streams drifted"
    # goodput degrades no worse than the capacity lost: one of three
    # replicas died mid-run, so >= 60% of baseline must survive
    soak_rps = soak_sum["completed"] / max(makespan, 1e-9)
    _RESULT["soak_baseline_rps"] = round(base_rps, 2)
    _RESULT["soak_chaos_rps"] = round(soak_rps, 2)
    _RESULT["soak_goodput_ratio"] = round(soak_rps / max(base_rps, 1e-9), 3)
    assert _RESULT["soak_goodput_ratio"] >= 0.6, \
        "soak goodput %.2f below 60%% of baseline" \
        % _RESULT["soak_goodput_ratio"]
    # executables stay frozen per replica across death + failover
    _RESULT["soak_executables_per_replica"] = rs_set.executables_per_replica()
    assert rs_set.executables_per_replica() \
        == [len(sconf.buckets) + 1] * 3, "soak minted executables"
    assert all(s.fallback_count() == 0 for s in soak_sessions)
    _RESULT["soak_incident"] = rs_set.incident_path

    # deterministic overload probe: a 2-deep admission queue under the
    # same burst must shed typed, with the accounting closed
    rs_over = serve.ReplicaSet(sessions=soak_sessions[1:], queue_cap=2)
    odone, omakespan = rs_over.run(
        [serve.Request(**spec) for spec in soak_trace])
    over_sum = serve.summarize(odone, omakespan)
    _RESULT["soak_overload_shed"] = over_sum["shed"]
    assert over_sum["shed"] > 0 and over_sum["faulted"] == 0
    assert over_sum["completed"] + over_sum["shed"] == soak_n
    assert all(r.shed and "ServeOverloaded" in r.error
               for r in odone if r.failed)
    assert over_sum["shed"] == rs_over.counters["shed"]

    # -- network-edge soak: the same chaos, now over real sockets --------
    # A streaming asyncio Gateway fronts three fresh replicas; threaded
    # socket clients replay the Poisson trace closed-loop (every 5th
    # client crashes mid-stream with an RST) while one replica is
    # chaos-killed mid-traffic.  Acceptance, asserted: zero lost
    # requests (every client reached a typed terminal outcome),
    # completed streams byte-identical to the in-process oracle,
    # cancellation returned every replica to its cold-state snapshot,
    # the per-replica executable count frozen, and the closing
    # SIGTERM-style drain completed clean.
    import threading as _threading

    for s in soak_sessions:
        s.reset_cold()
    gw_snap = [s.state_report() for s in soak_sessions]
    rs_gw = serve.ReplicaSet(sessions=soak_sessions, rejoin_backoff_s=1e9)
    gw = serve.Gateway(rs_gw, port=0).start()
    gw_out = {}
    gw_drops = set(range(2, soak_n, 5))
    gw_threads = [
        _threading.Thread(target=_gw_client,
                          args=(gw.port, spec, spec["rid"] in gw_drops,
                                gw_out))
        for spec in soak_trace]
    _os.environ["MXNET_FAULT_INJECT"] = "serve_replica_kill:kill:after=16"
    _faults.reset()
    gw_t0 = time.perf_counter()
    try:
        for t in gw_threads:
            t.start()
        for t in gw_threads:
            t.join(timeout=300)
    finally:
        del _os.environ["MXNET_FAULT_INJECT"]
        _faults.reset()
    gw_wall = time.perf_counter() - gw_t0
    _RESULT["gw_drain_clean"] = bool(gw.drain(wait=True))
    gw.stop()
    assert not any(t.is_alive() for t in gw_threads), "socket client hung"
    outcomes = [rec["outcome"] for rec in gw_out.values()]
    _RESULT["gw_requests"] = soak_n
    _RESULT["gw_completed"] = outcomes.count("completed")
    _RESULT["gw_disconnects"] = outcomes.count("disconnected")
    _RESULT["gw_shed_429"] = outcomes.count("shed")
    _RESULT["gw_deaths"] = rs_gw.counters["deaths"]
    assert rs_gw.counters["deaths"] == 1
    # zero lost: nothing timed out, errored untyped, or vanished with
    # the dead replica or the crashed clients
    _RESULT["gw_zero_lost"] = (
        len(gw_out) == soak_n
        and all(o in ("completed", "disconnected", "shed")
                for o in outcomes))
    assert _RESULT["gw_zero_lost"], \
        "gateway soak lost requests: %r" % sorted(set(outcomes))
    assert _RESULT["gw_completed"] \
        >= soak_n - len(gw_drops) - _RESULT["gw_shed_429"]
    # every completed stream byte-identical to the in-process oracle
    _RESULT["gw_bitexact"] = all(
        rec["tokens"] == soak_oracle[rid]
        for rid, rec in gw_out.items() if rec["outcome"] == "completed")
    assert _RESULT["gw_bitexact"], "gateway streams drifted from oracle"
    # the drain was clean: no stream needed a force-cancel
    assert _RESULT["gw_drain_clean"], "gateway drain force-cancelled"
    assert gw.counters["force_cancelled"] == 0
    # crashed clients + chaos kill freed everything: each replica is
    # byte-for-byte back at its cold snapshot
    assert [s.state_report() for s in soak_sessions] == gw_snap, \
        "gateway soak leaked serving state"
    assert rs_gw.executables_per_replica() \
        == [len(sconf.buckets) + 1] * 3, "gateway soak minted executables"
    gw_rps = _RESULT["gw_completed"] / max(gw_wall, 1e-9)
    gw_ttfts = sorted(rec["ttft_s"] for rec in gw_out.values()
                      if rec["ttft_s"] is not None)
    gw_ttft_p50 = gw_ttfts[len(gw_ttfts) // 2]
    _RESULT["gw_goodput_rps"] = round(gw_rps, 2)
    _RESULT["gw_goodput_ratio"] = round(gw_rps / max(base_rps, 1e-9), 3)
    _RESULT["gw_ttft_p50_s"] = round(gw_ttft_p50, 5)
    # the wire tax: socket TTFT p50 minus the in-process baseline's
    _RESULT["gw_ttft_p50_delta_s"] = round(
        gw_ttft_p50 - base_sum["ttft_p50_s"], 5)
    assert _RESULT["gw_goodput_ratio"] >= 0.2, \
        "gateway goodput %.2f below 20%% of in-process baseline" \
        % _RESULT["gw_goodput_ratio"]
    _RESULT["gw_counters"] = dict(gw.counters)

    # -- hybrid long-context A/B: O(1) per-slot serving memory -----------
    # An all-window stack (the model states its layers) against full
    # attention at a FIXED pool-byte budget.  Two acceptance bars: it
    # reserves no pages (admission is slot-bounded), so peak concurrency
    # at the same pool bytes must be >= 2x; and its per-slot state is
    # constant in context length, so per-token decode latency must stay
    # flat as the context jumps 4k -> 32k (the full-attention pool could
    # not even HOLD those contexts).
    hyb_window = 16
    ab_max_new = 112  # 144-token requests: context >> window
    long_cfg = serve.ModelConfig(vocab_size=128, num_layers=2,
                                 d_model=64, num_heads=2, max_len=33024)
    long_params = serve_model.init_params(long_cfg, seed=0)
    ab_base = _dc.replace(sconf, slots=8, buckets=(32,),
                          max_new=ab_max_new)
    hyb_conf = _dc.replace(ab_base, num_pages=1)
    hyb_ab = serve.InferenceSession(
        long_params, config=hyb_conf, model=_dc.replace(
            long_cfg, layer_types=("sliding_attention",) * 2,
            sliding_window=hyb_window))
    # executable count frozen: windowed layers change executable
    # ARGUMENTS (ring pools), never the executable set
    assert len(hyb_ab.executables) == len(hyb_conf.buckets) + 1
    # the full-attention side gets the hybrid footprint as its page
    # budget — the fixed-pool-bytes framing of the capacity claim
    hyb_bytes = hyb_ab.cache.pool_bytes()
    ab_page = PagedKVCache.page_bytes(
        long_cfg.num_layers, long_cfg.num_heads,
        long_cfg.d_model // long_cfg.num_heads, sconf.page_size)
    full_conf = _dc.replace(ab_base, num_pages=max(hyb_bytes // ab_page,
                                                   1))
    full_ab = serve.InferenceSession(long_params, num_heads=2,
                                     config=full_conf)
    _RESULT["window_pool_bytes_full"] = full_ab.cache.pool_bytes()
    _RESULT["window_pool_bytes_hybrid"] = hyb_bytes
    assert hyb_bytes <= _RESULT["window_pool_bytes_full"] + ab_page, \
        "hybrid exceeded the fixed byte budget"

    ab_rs = np.random.RandomState(17)
    ab_peak, ab_tps = {}, {}
    for tag, ab_sess in (("full", full_ab), ("hybrid", hyb_ab)):
        reqs = [serve.Request(rid=i,
                              prompt=ab_rs.randint(1, 127,
                                                   size=32).tolist(),
                              max_new=ab_max_new, arrival_s=0.0)
                for i in range(8)]
        sched = serve.Scheduler(ab_sess, policy="continuous")
        done, makespan = sched.run(reqs)
        summary = serve.summarize(done, makespan)
        assert summary["failed"] == 0, "%s A/B failed requests" % tag
        ab_peak[tag] = sched.stats["peak_active"]
        ab_tps[tag] = round(summary["tokens_per_sec"], 1)
    _RESULT["window_peak_active_full"] = ab_peak["full"]
    _RESULT["window_peak_active_hybrid"] = ab_peak["hybrid"]
    _RESULT["window_goodput_full_tps"] = ab_tps["full"]
    _RESULT["window_goodput_hybrid_tps"] = ab_tps["hybrid"]
    _RESULT["window_capacity_ratio"] = round(
        ab_peak["hybrid"] / max(ab_peak["full"], 1), 2)
    assert _RESULT["window_capacity_ratio"] >= 2.0, \
        "hybrid capacity %.2fx below the 2x acceptance bar" \
        % _RESULT["window_capacity_ratio"]

    # flat-latency probe: pin the slot's context length artificially
    # (the executables read lengths as data; a no-full-layer stack has
    # no page tables to outgrow) and time steady-state decode steps at
    # 4k and 32k.  O(context) attention would be ~8x slower at 32k;
    # the O(1) hybrid step must stay within noise.
    probe_slot = hyb_ab.try_alloc(16, 16)
    hyb_ab.prefill(probe_slot, list(ab_rs.randint(1, 127, size=16)))

    def _pinned_step_ms(ctx_len, steps=24):
        best = float("inf")
        for _ in range(3):
            hyb_ab.cache.lengths[probe_slot] = ctx_len
            hyb_ab.step()  # warm this context length
            t0 = time.perf_counter()
            for _ in range(steps):
                hyb_ab.cache.lengths[probe_slot] = ctx_len
                hyb_ab.step()
            best = min(best, (time.perf_counter() - t0) / steps)
        return best * 1e3

    ms_4k = _pinned_step_ms(4096)
    ms_32k = _pinned_step_ms(32640)
    hyb_ab.release(probe_slot)
    _RESULT["window_decode_ms_4k"] = round(ms_4k, 4)
    _RESULT["window_decode_ms_32k"] = round(ms_32k, 4)
    _RESULT["window_latency_ratio_32k_over_4k"] = round(
        ms_32k / max(ms_4k, 1e-9), 3)
    assert _RESULT["window_latency_ratio_32k_over_4k"] <= 1.5, \
        "hybrid decode latency grew %.2fx from 4k to 32k context" \
        % _RESULT["window_latency_ratio_32k_over_4k"]

    # -- acceptance probe 3: no per-request recompiles -------------------
    guards = sess.guard_report()
    _RESULT["recompiles"] = {
        name: snap for name, snap in guards.items()
        if snap.get("traces", 0) > 1 or snap.get("signatures", 0) > 1}
    assert not _RESULT["recompiles"], \
        "serving executables retraced: %r" % (_RESULT["recompiles"],)
    assert len(sess.executables) == len(sconf.buckets) + 1
    _RESULT["dispatch_fallbacks"] = sess.fallback_count()

    # -- satellite: compile-report artifact for the serving set ----------
    try:
        _RESULT["compile_report"] = compile_cache.write_artifact()
    except Exception as exc:
        _RESULT["compile_report_error"] = str(exc)[:200]
    return dict(_RESULT)


def main():
    # watchdog + budget armed before measure()'s jax imports: a hung
    # backend init still yields valid partial JSON + exit 0
    seconds = None
    for i, a in enumerate(sys.argv):
        if a == "--watchdog" and i + 1 < len(sys.argv):
            seconds = float(sys.argv[i + 1])
        elif a.startswith("--watchdog="):
            seconds = float(a.split("=", 1)[1])
    bench_util.arm_watchdog(_RESULT, seconds=seconds)
    bench_util.arm_budget(_RESULT)
    result = measure()
    result.update(bench_util.compile_summary())
    print(json.dumps(result))


if __name__ == "__main__":
    main()
