#!/usr/bin/env python
"""Benchmark: ResNet-50 training throughput (fwd+bwd+SGD update) on one
TPU chip, the headline metric of BASELINE.md (reference: 109 img/s train
on a K80 at bs32, ``example/image-classification/README.md:154``).

Runs the fused single-program train step in mixed precision (bf16
activations over fp32 master weights) and reports achieved model FLOP/s
and %MFU against the chip's bf16 peak alongside the reference-comparable
img/s metric.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...}.
Partial snapshots stream to stderr after each phase, and the shared
``bench_util`` watchdog (``--watchdog SEC`` / env
``MXNET_BENCH_WATCHDOG``, default 420, 0 to disable) prints the partial
line to stdout and exits 0 if the run wedges — so a hung backend init
still yields a parseable artifact instead of rc=124 with nothing.

The default sweep is sized to finish inside the watchdog: ResNet-50 at
one batch size plus the transformer MFU row.  The AlexNet/Inception-v3
flagship rows are opt-in via ``--all-models`` (they add two full
compile+measure cycles), ``--sweep`` adds the ResNet batch sweep, and
``--piped`` the record-fed epoch run.

Usage: bench.py [batch] [--fp32] [--sweep] [--all-models]
                [--piped (opt-in long run)] [--watchdog SEC]
"""
import json
import sys
import time

sys.path.insert(0, ".")

import bench_util

# the run's (partial) result — filled in phase by phase so a watchdog
# fire, a budget expiry (MXNET_BENCH_BUDGET_S), or an operator reading
# stderr mid-run still gets a usable line
_RESULT = {}


def _emit_partial():
    """Progress snapshot to stderr after each phase (stdout stays ONE
    final JSON line)."""
    print(json.dumps({"partial": True, **_RESULT}), file=sys.stderr,
          flush=True)

# fwd+bwd model FLOPs per 224x224 image for ResNet-50 under the standard
# MFU convention (multiply-add = 2 FLOPs, the same convention as the
# chip's peak spec): fwd ≈ 4.1 GMACs → 8.2 GFLOPs, train ≈ 3x fwd.
# Cross-checked against XLA's cost analysis of the compiled step, which
# reports ~24.0e9/img for fwd+bwd+SGD.  (Rounds 1-2 used 12.3e9 — the
# MAC=1 count — understating MFU 2x vs the peak's MAC=2 convention.)
TRAIN_FLOPS_PER_IMG = 24.6e9

def _measure(step, shapes, batch, iters=20):
    import jax
    import jax.numpy as jnp
    import numpy as np

    params, aux, states = step.init_state(shapes)
    rng = jax.random.PRNGKey(0)
    batch_dict = {
        "data": jax.random.normal(rng, shapes["data"], "float32"),
        "softmax_label": jnp.zeros(shapes["softmax_label"], "float32"),
    }
    # AOT compile FIRST, measured separately: compile_s stops being
    # silently folded into the warmup step, and the persistent cache
    # (MXNET_COMPILE_CACHE_DIR) makes it near-zero on a repeat run
    compile_s = bench_util.timed_compile(step, shapes, _RESULT)
    # XLA's own FLOP count of the step (MAC=2 convention, includes
    # fwd+bwd+optimizer) — the honest numerator for MFU.  The AOT path
    # recorded it already; otherwise take it from a host-side lower()
    # (no second backend compile — lower() is tracing only).
    xla_flops = (step.compile_stats or {}).get("flops")
    if xla_flops is None:
        try:
            lowered = step._jit_step.lower(
                params, aux, states, batch_dict, rng, step.lr,
                jnp.asarray(1, "int32"))
            ca = lowered.cost_analysis()
            ca = ca[0] if isinstance(ca, list) else ca
            xla_flops = float(ca.get("flops", 0.0)) or None
        except Exception:
            pass
    # warmup (compiles lazily when the AOT form was unavailable);
    # a host fetch of one element waits for the whole step
    params, aux, states, out = step(params, aux, states, batch_dict, rng)
    float(np.asarray(out[0][0, 0]))
    t0 = time.perf_counter()
    for _ in range(iters):
        params, aux, states, out = step(params, aux, states, batch_dict, rng)
    float(np.asarray(out[0][0, 0]))  # forces the whole dependency chain
    return batch * iters / (time.perf_counter() - t0), xla_flops


def _bench_model(sym, batch, compute_dtype, image_shape=(3, 224, 224),
                 iters=20):
    """img/s for one model config on the current chip."""
    from mxnet_tpu.fused import TrainStep

    step = TrainStep(
        sym, optimizer="sgd",
        optimizer_params={"learning_rate": 0.1, "momentum": 0.9,
                          "rescale_grad": 1.0 / batch},
        compute_dtype=compute_dtype)
    shapes = {"data": (batch,) + tuple(image_shape),
              "softmax_label": (batch,)}
    return _measure(step, shapes, batch, iters=iters)


def _measure_piped(step, shapes, batch, iters=20, threads=8):
    """img/s for the same step fed by ImageRecordIter from a generated
    .rec — the end-to-end number all reference baselines are
    (docs/how_to/perf.md: every published img/s is pipeline-fed).
    Returns (img_s, pipeline_mb_s): the second is the raw JPEG MB/s the
    feeder sustained."""
    import os
    import tempfile

    import numpy as np

    import mxnet_tpu as mx

    cache = os.path.join(tempfile.gettempdir(), "mxtpu_bench_rec")
    rec = os.path.join(cache, "bench224.rec")
    n_imgs = 2048
    if not os.path.exists(rec):
        from PIL import Image

        os.makedirs(cache, exist_ok=True)
        rs = np.random.RandomState(0)
        w = mx.recordio.MXRecordIO(rec, "w")
        import io as _io

        for i in range(n_imgs):
            arr = (rs.rand(224, 224, 3) * 255).astype("uint8")
            buf = _io.BytesIO()
            Image.fromarray(arr).save(buf, format="JPEG", quality=90)
            hdr = mx.recordio.IRHeader(0, float(i % 1000), i, 0)
            w.write(mx.recordio.pack(hdr, buf.getvalue()))
        w.close()
    rec_bytes = os.path.getsize(rec)

    params, aux, states = step.init_state(shapes)
    import jax
    import jax.numpy as jnp
    import time as _t

    rng = jax.random.PRNGKey(0)

    # host->device bandwidth for a FRESH buffer (the piped path ships
    # one decoded uint8 batch per step)
    probe = (np.random.rand(batch, 224, 224, 3) * 255).astype("uint8")
    t0 = _t.perf_counter()
    float(np.asarray(jnp.sum(jax.device_put(probe)[0, 0, 0])))
    put_mb_s = probe.nbytes / 1e6 / (_t.perf_counter() - t0)

    it = mx.io.ImageRecordIter(
        path_imgrec=rec, data_shape=(3, 224, 224), batch_size=batch,
        preprocess_threads=threads, prefetch_buffer=4, shuffle=False)

    # feeder-only rate: decode + host augs, no device consumption (drop
    # the device work by reading only shapes) — measured over one epoch
    inner = it
    t0 = _t.perf_counter()
    n_dec = 0
    for b in inner:
        n_dec += batch
    decode_img_s = n_dec / (_t.perf_counter() - t0)
    inner.reset()
    # warmup: one epoch primes decode threads + compiles the step
    # (batches arrive fp32 NCHW already ON DEVICE — the augmenter tail
    # runs jitted per batch, so no host cast happens here)
    n_batches = 0
    for b in it:
        bd = {"data": b.data[0]._data,
              "softmax_label": b.label[0]._data}
        params, aux, states, out = step(params, aux, states, bd, rng)
        n_batches += 1
    float(np.asarray(out[0][0, 0]))
    it.reset()
    t0 = _t.perf_counter()
    seen = 0
    epochs = max(1, iters // n_batches)
    for _ in range(epochs):
        for b in it:
            bd = {"data": b.data[0]._data,
                  "softmax_label": b.label[0]._data}
            params, aux, states, out = step(params, aux, states, bd, rng)
            seen += batch
        it.reset()
    float(np.asarray(out[0][0, 0]))
    dt = _t.perf_counter() - t0
    mb_s = epochs * rec_bytes / 1e6 / dt
    return seen / dt, mb_s, decode_img_s, put_mb_s


def main():
    # watchdog + budget timer arm BEFORE the first jax import: backend
    # init can hang (driver handshake, stale TPU lockfile) and a bench
    # that dies with rc=124 and no JSON is useless to the driver — armed
    # here, a hung init still emits valid partial JSON and exits non-zero
    argv = sys.argv[1:]
    watchdog_s = None
    if "--watchdog" in argv:
        i = argv.index("--watchdog")
        watchdog_s = float(argv[i + 1])
        del argv[i:i + 2]
    bench_util.arm_watchdog(_RESULT, watchdog_s)
    bench_util.arm_budget(_RESULT)

    import jax

    from mxnet_tpu.models import resnet
    from mxnet_tpu.fused import TrainStep

    device = bench_util.require_tpu()
    args = [a for a in argv if not a.startswith("--")]
    fp32 = "--fp32" in sys.argv
    compute_dtype = None if fp32 else "bfloat16"
    batches = [int(args[0])] if args else [512]
    if "--sweep" in sys.argv:
        batches = sorted(set(batches) | {64, 128, 256, 512})

    layout = "NHWC" if "--nhwc" in sys.argv else "NCHW"
    result = _RESULT
    result["metric"] = "resnet50_train_images_per_sec_per_chip"
    result["precision"] = "float32" if fp32 else "bf16+fp32-master"
    result["layout"] = layout
    sym = resnet.get_symbol(num_classes=1000, num_layers=50,
                            image_shape=(3, 224, 224), layout=layout)
    best = (0.0, None, None)
    for batch in batches:
        step = TrainStep(
            sym, optimizer="sgd",
            optimizer_params={"learning_rate": 0.1, "momentum": 0.9,
                              "rescale_grad": 1.0 / batch},
            compute_dtype=compute_dtype)
        dshape = (batch, 3, 224, 224) if layout == "NCHW" \
            else (batch, 224, 224, 3)
        shapes = {"data": dshape, "softmax_label": (batch,)}
        img_s, xla_flops = _measure(step, shapes, batch)
        result.setdefault("sweep", {})[str(batch)] = round(img_s, 2)
        _emit_partial()
        if img_s > best[0]:
            best = (img_s, batch, xla_flops)

    img_s, batch, xla_flops = best
    flops_per_img = (xla_flops / batch) if xla_flops else TRAIN_FLOPS_PER_IMG
    achieved = img_s * flops_per_img
    # peak table is bf16; fp32 peak differs per generation, so report
    # MFU only for the bf16 path
    peak = None if fp32 else bench_util.peak_flops(device)
    baseline = 109.0  # K80 bs32 train img/s, BASELINE.md
    result.update({
        "value": round(img_s, 2),
        "unit": "img/s",
        "vs_baseline": round(img_s / baseline, 2),
        "batch_size": batch,
        "achieved_tflops": round(achieved / 1e12, 2),
        "flops_accounting": "xla_cost_analysis" if xla_flops
                            else "analytic_mac2",
        "mfu_pct": round(100 * achieved / peak, 2) if peak else None,
        "device": device.device_kind,
    })
    _emit_partial()
    # secondary metric: the MXU-bound transformer workload, where the
    # framework's compute ceiling shows (ResNet-50@224 is HBM-bound on
    # this hardware generation — see README).  Runs EARLY — right after
    # the headline metric — so the MFU row the roadmap tracks survives
    # a watchdog/budget cut that strands the longer optional phases.
    # Skipped under --fp32.
    if not fp32 and "--resnet-only" not in sys.argv:
        try:
            import bench_transformer

            tf = bench_transformer.measure(argv=[])
            result["transformer_tokens_per_sec"] = tf["value"]
            result["transformer_mfu_pct"] = tf["mfu_pct"]
            result["transformer_model"] = tf["model"]
            result["transformer_attn_peak_bytes"] = \
                tf.get("attn_peak_bytes")
        except Exception as exc:  # keep the primary metric robust
            result["transformer_error"] = str(exc)[:200]
        _emit_partial()
    # ZeRO A/B row: the sharded update's state shrink (~1/N per
    # replica), the ZeRO-3 at-rest param shrink + step-rate ratios vs
    # the replicated update, over the local device mesh
    # (bench_fit.measure_zero_ab; skipped when the host exposes a
    # single device).  Cheap MLP config — the claim under test is the
    # collective swap, not model FLOPs.
    if not fp32 and "--resnet-only" not in sys.argv:
        try:
            import bench_fit

            zsym = bench_fit.build_sym(512, 1024, 10)
            zrow = bench_fit.measure_zero_ab(zsym, 64, 512)
            for k, v in zrow.items():
                result[k] = v
        except Exception as exc:  # keep the primary metric robust
            result["zero_ab_error"] = str(exc)[:200]
        _emit_partial()
    # composed-plan A/B row: pure DP vs tp(2) x zero3 vs pipe(2) —
    # per-replica params/opt-state bytes, step ratios and gather
    # traffic under ONE ParallelPlan declaration
    # (bench_fit.measure_plan_ab; skipped below 4 devices)
    if not fp32 and "--resnet-only" not in sys.argv:
        try:
            import bench_fit

            psym = bench_fit.build_sym(512, 1024, 10)
            prow = bench_fit.measure_plan_ab(psym, 64, 512)
            for k, v in prow.items():
                result[k] = v
        except Exception as exc:  # mxlint: disable=MX008 — the one-JSON-line contract survives a failed A/B row
            result["plan_ab_error"] = str(exc)[:200]
        _emit_partial()
    # data-plane summary row: multiprocess decode pool vs the GIL-bound
    # thread pool over real JPEGs (bench_fit.measure_decode_ab has the
    # full A/B; small config here — the claim under test is decode
    # scaling, not record volume)
    if not fp32 and "--resnet-only" not in sys.argv:
        try:
            import bench_fit

            drow = bench_fit.measure_decode_ab(n_images=128, epochs=1)
            result["decode_pool_speedup"] = drow["decode_pool_speedup"]
            result["decode_pool_images_per_sec"] = \
                drow["decode_pool_images_per_sec"]
            result["data_workers"] = drow["data_workers"]
        except Exception as exc:  # keep the primary metric robust
            result["decode_ab_error"] = str(exc)[:200]
        _emit_partial()
    # serving summary row: continuous-batching speedup over serial plus
    # the continuous tokens/s and tail TTFT (bench_serve.py has the
    # full per-policy breakdown and the bit-exactness/KV-flat probes)
    if not fp32 and "--resnet-only" not in sys.argv:
        try:
            import bench_serve

            sv = bench_serve.measure(argv=[])
            result["serving_speedup_vs_serial"] = sv["value"]
            result["serving_tokens_per_sec"] = sv["tokens_per_sec"]
            result["serving_ttft_p99_s"] = sv["continuous_ttft_p99_s"]
            result["serving_bitexact"] = sv["bitexact"]
            # speculative-decoding A/B row (spec-on vs spec-off on the
            # low-concurrency rig; bench_serve.py has the full record)
            result["serving_spec_speedup"] = sv["spec_speedup"]
            result["serving_spec_bitexact"] = sv["bitexact_spec"]
            result["serving_spec_acceptance_rate"] = sv["acceptance_rate"]
            result["serving_spec_tokens_per_verify_step"] = \
                sv["tokens_per_verify_step"]
            # hybrid long-context row (window+SSM stack vs full
            # attention at fixed pool bytes; bench_serve.py asserts the
            # 2x capacity bar and the O(1) latency flatness)
            result["serving_window_capacity_ratio"] = \
                sv["window_capacity_ratio"]
            result["serving_window_latency_ratio_32k_over_4k"] = \
                sv["window_latency_ratio_32k_over_4k"]
            # network-edge row (real-socket gateway soak under chaos;
            # bench_serve.py asserts zero-lost, bit-exactness, and the
            # clean drain)
            result["serving_socket_goodput_rps"] = sv["gw_goodput_rps"]
            result["serving_socket_ttft_p50_delta_s"] = \
                sv["gw_ttft_p50_delta_s"]
            result["serving_socket_drain_clean"] = sv["gw_drain_clean"]
        except Exception as exc:  # keep the primary metric robust
            result["serving_error"] = str(exc)[:200]
        _emit_partial()
    # the BASELINE distributed-scaling flagships (docs/how_to/
    # perf.md:157-167: alexnet bs256 483.37 img/s, inception-v3 bs32
    # 29.62 img/s on K80) — single-chip rows so BENCH anchors more than
    # one model family.  OPT-IN via --all-models: two extra
    # compile+measure cycles do not fit the default watchdog budget
    # alongside the headline rows (the round-5 lesson).
    if not fp32 and "--all-models" in sys.argv:
        try:
            from mxnet_tpu.models import alexnet, inception_v3

            alex_s, _ = _bench_model(alexnet.get_symbol(1000), 512,
                                     compute_dtype)
            result["alexnet_train_images_per_sec_per_chip"] = \
                round(alex_s, 2)
            result["alexnet_vs_baseline"] = round(alex_s / 483.37, 2)
            inc_s, _ = _bench_model(inception_v3.get_symbol(1000), 128,
                                    compute_dtype,
                                    image_shape=(3, 299, 299), iters=10)
            result["inception_v3_train_images_per_sec_per_chip"] = \
                round(inc_s, 2)
            result["inception_v3_vs_baseline"] = round(inc_s / 29.62, 2)
        except Exception as exc:  # keep the primary metric robust
            result["secondary_model_error"] = str(exc)[:200]
        _emit_partial()

    # end-to-end fed benchmark: the same step consuming ImageRecordIter
    # batches decoded from a real .rec (reference numbers are all
    # pipeline-fed).  OPT-IN via --piped: it generates a 2048-image .rec
    # on first use and runs whole epochs, which is the long pole of the
    # run (the watchdog bounds it either way).  The feeder emits NCHW fp32,
    # so the piped row is NCHW-only; fp32 mode has no piped row (the
    # piped step is the bf16 headline config) — skips are marked in the
    # JSON.
    want_piped = "--piped" in sys.argv and "--no-piped" not in sys.argv
    if want_piped and (fp32 or layout != "NCHW"):
        result["piped_skipped"] = "fp32 run" if fp32 else \
            "piped feeder is NCHW-only"
        want_piped = False
    if want_piped:
        try:
            step = TrainStep(
                sym, optimizer="sgd",
                optimizer_params={"learning_rate": 0.1, "momentum": 0.9,
                                  "rescale_grad": 1.0 / batch},
                compute_dtype=compute_dtype)
            piped_iters = 20
            piped_s, mb_s, dec_s, put_mb_s = _measure_piped(
                step, {"data": (batch, 3, 224, 224),
                       "softmax_label": (batch,)}, batch,
                iters=piped_iters)
            import os as _os

            result["piped_images_per_sec"] = round(piped_s, 2)
            result["piped_vs_synthetic"] = round(piped_s / img_s, 4)
            result["input_pipeline_mb_per_sec"] = round(mb_s, 1)
            result["piped_decode_images_per_sec"] = round(dec_s, 1)
            result["piped_h2d_mb_per_sec"] = round(put_mb_s, 1)
            result["piped_host_cores"] = _os.cpu_count()
            # the binding constraint: min(decode rate, transfer rate)
            xfer_img_s = put_mb_s * 1e6 / (3 * 224 * 224)
            result["piped_bound"] = (
                "h2d-transfer" if xfer_img_s < dec_s else "host-decode")
        except Exception as exc:
            result["piped_error"] = str(exc)[:200]
        _emit_partial()

    result["step_s"] = round(batch / img_s, 4) if img_s else None
    result.update(bench_util.compile_summary())
    print(json.dumps(result))
    # the JSON line keeps each failed phase's *_error field; the exit
    # code says that a phase failed
    failed = sorted(k for k in result if k.endswith("_error"))
    if failed:
        sys.exit("bench.py: phases failed: %s" % ", ".join(failed))


if __name__ == "__main__":
    main()
