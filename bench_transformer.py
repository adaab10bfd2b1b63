#!/usr/bin/env python
"""Secondary benchmark: decoder-only transformer LM training MFU on one
TPU chip.

`bench.py` (the driver metric) measures ResNet-50 — which at 224px is
HBM-bandwidth-bound on this hardware generation (see README).  This
benchmark exists to show the framework's compute ceiling on an MXU-bound
workload: a GPT-style model whose FLOPs sit in large matmuls.

Prints ONE JSON line with tokens/sec and %MFU.

Usage: bench_transformer.py [--small|--deep|--moe] [--batch=N]
"""
import json
import sys
import time

sys.path.insert(0, ".")

import bench_util

# phase-by-phase partial result for the MXNET_BENCH_BUDGET_S emitter
_RESULT = {"metric": "transformer_lm_tokens_per_sec_per_chip"}


def measure(argv=None):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mxnet_tpu.fused import TrainStep
    from mxnet_tpu.models import transformer

    argv = sys.argv if argv is None else argv
    small = "--small" in argv
    # no chip, no number: only the --small rehearsal runs elsewhere, and
    # it reports under a rehearsal name with no utilization
    device = bench_util.require_tpu(rehearsal=small)
    on_tpu = device.platform == "tpu"
    if small:
        cfg = dict(vocab_size=8192, num_layers=4, d_model=256,
                   num_heads=4, seq_len=256)
    elif "--deep" in argv:
        cfg = dict(vocab_size=32768, num_layers=16, d_model=1024,
                   num_heads=16, seq_len=1024)
    elif "--moe" in argv:
        # routed top-2 MoE: 8 experts of d_ff=1024 per block = 2x the
        # total FFN parameters of the dense d1024 config (d_ff=4096)
        # with only a 2048-wide active path per token (top-2) — the
        # capacity/compute decoupling MoE buys.  Single-chip routed
        # dispatch, no expert mesh.
        cfg = dict(vocab_size=32000, num_layers=8, d_model=1024,
                   num_heads=8, seq_len=1024, d_ff=1024,
                   moe_experts=8, moe_top_k=2)
    else:
        # the MFU-headline config: d2048 keeps every matmul MXU-shaped
        # (measured 65% MFU at batch 8 vs 42% for the 16L-d1024 config)
        cfg = dict(vocab_size=32768, num_layers=8, d_model=2048,
                   num_heads=16, seq_len=1024)
    batch = 2 if small else int(next((a.split("=")[1] for a in argv
        if a.startswith("--batch=")), 8))
    remat = next((a.split("=")[1] for a in argv
                  if a.startswith("--remat=")), None)

    sym = transformer.get_symbol(**cfg)
    step = TrainStep(sym, optimizer="sgd",
                     optimizer_params={"learning_rate": 1e-3,
                                       "momentum": 0.9,
                                       "rescale_grad": 1.0 / batch},
                     compute_dtype="bfloat16", remat=remat)
    shapes = {"data": (batch, cfg["seq_len"]),
              "softmax_label": (batch, cfg["seq_len"])}
    # compile_s measured separately from step_s (and reused from the
    # persistent cache on a repeat run)
    compile_s = bench_util.timed_compile(step, shapes, _RESULT)
    _RESULT["compile_s"] = round(compile_s, 3)
    # attention peak-memory visibility: the compiled step's temp-buffer
    # peak (memory_analysis, the examples/memcost harness) is dominated
    # by attention intermediates at these shapes, so this one number
    # makes the O(T^2) -> O(T*block) flash drop visible per-PR
    try:
        mem = step._aot.memory_analysis()
        _RESULT["attn_peak_bytes"] = int(mem.temp_size_in_bytes)
    except Exception:
        _RESULT["attn_peak_bytes"] = None
    params, aux, states = step.init_state(shapes)
    # optimizer-state residency beside the attention peak: per-replica
    # state bytes plus the per-step fresh-param all-gather volume (0
    # unless the ZeRO sharded update is active — needs a >=2-way mesh)
    mem_rep = step.memory_report(params, states)
    _RESULT["opt_state_bytes"] = int(mem_rep.get("opt_state_bytes") or 0)
    _RESULT["update_gather_bytes"] = int(
        mem_rep.get("update_gather_bytes") or 0)
    # ZeRO-3 residency columns: at-rest per-replica param bytes (1/N
    # when params are sharded at rest) and the total per-step gather
    # traffic (2x the sharded footprint under zero=3: forward bucket
    # gathers + backward re-gathers; the stage-1 trailing gather
    # otherwise)
    _RESULT["params_bytes_at_rest"] = int(
        mem_rep.get("params_bytes_per_replica") or 0)
    _RESULT["gather_bytes_per_step"] = int(
        mem_rep.get("gather_bytes_per_step") or 0)
    rng = jax.random.PRNGKey(0)
    toks = jnp.asarray(
        np.random.RandomState(0).randint(
            0, cfg["vocab_size"], shapes["data"]).astype("float32"))
    batch_dict = {"data": toks, "softmax_label": toks}

    moe = "moe_experts" in cfg
    if moe:
        # analytic count ignores MoE; count the real params.  6*P*tokens
        # is NOT the executed-FLOP count under top-k routing (only k/E
        # of expert FLOPs run), so the MoE row reports tokens/s only.
        p_count = sum(int(np.prod(v.shape)) for v in params.values())
    else:
        p_count = transformer.count_params(**cfg)
    tokens = batch * cfg["seq_len"]
    # analytic train FLOPs (MAC=2): 6*P*tokens for the matmul stack plus
    # the attention score/value terms; skipped for MoE (6*P overcounts
    # top-k-routed expert FLOPs)
    flops_per_step = None if moe else (
        6.0 * p_count * tokens +
        12.0 * cfg["num_layers"] * batch *
        cfg["seq_len"] ** 2 * cfg["d_model"])

    params, aux, states, out = step(params, aux, states, batch_dict, rng)
    float(np.asarray(out[0][0, 0]))  # force compile + completion
    iters = 3 if small else 10
    t0 = time.perf_counter()
    for _ in range(iters):
        params, aux, states, out = step(params, aux, states, batch_dict,
                                        rng)
    float(np.asarray(out[0][0, 0]))
    dt = (time.perf_counter() - t0) / iters

    achieved = None if flops_per_step is None \
        else flops_per_step / dt
    kind = device.device_kind
    peak = bench_util.peak_flops(device) if on_tpu else None
    _RESULT.update({
        "metric": "transformer_lm_tokens_per_sec_per_chip" if on_tpu
                  else "transformer_lm_rehearsal_tokens_per_sec",
        "value": round(tokens / dt, 1),
        "unit": "tokens/s",
        "model": "%dL-d%d-T%d%s (%.0fM params)" % (
            cfg["num_layers"], cfg["d_model"], cfg["seq_len"],
            "-MoE-E%d-top%d" % (cfg["moe_experts"], cfg["moe_top_k"])
            if moe else "",
            p_count / 1e6),
        "step_ms": round(dt * 1e3, 2),
        "step_s": round(dt, 4),
        "compile_s": round(compile_s, 3),
        "achieved_tflops": round(achieved / 1e12, 2)
                           if achieved is not None else None,
        "mfu_pct": round(100 * achieved / peak, 2)
                   if peak and achieved is not None else None,
        # 6*P*tokens (matmul stack) + 12*L*B*T^2*d_model (attention
        # score/value contractions, MAC=2) — the honest numerator at
        # long T, where the quadratic term is a double-digit share
        "flops_accounting": None if moe else "6P_tokens+attn_12LBT2D",
        "precision": "bf16+fp32-master",
        # the dtype the 6*P numerator counts over: training weights
        # stay fp32 master (serving may quantize at rest — that shows
        # up in bench_serve.py's quant_* fields, never here)
        "weight_dtype": str(next(iter(params.values())).dtype),
        "device": kind,
    })
    # autotune provenance: which cached knobs (if any) this step was
    # built under — MXNET_AUTOTUNE=1 + a tools/autotune.py record
    try:
        from mxnet_tpu import autotune
        _RESULT["autotune"] = autotune.provenance()
    except ImportError:
        _RESULT["autotune"] = []
    return dict(_RESULT)


def main():
    # watchdog + budget arm before measure()'s jax imports: a hung
    # backend init still yields valid partial JSON (no
    # module-level jax import exists in this file, so arming here is
    # already first-touch)
    bench_util.arm_watchdog(_RESULT)
    bench_util.arm_budget(_RESULT)
    result = measure()
    result.update(bench_util.compile_summary())
    print(json.dumps(result))


if __name__ == "__main__":
    main()
