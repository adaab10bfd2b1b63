"""A training cell: one ``Module.fit`` call, clocked from its callbacks.

Copied from ``chip_smoke.py``'s ``fit_transformer`` (PR 21): the same
public call, without the ``waitall`` and the metric reset the smoke adds.
Set-up builds ONE module, drives it from the seed through the checked
steps and the warm-up steps, and the window goes on with that same
object: ``fit`` is one call, and the window is a stretch of it.

The traffic file's ``contexts`` (1 where it has none) says over how many
devices the module is bound and ``kvstore`` (``fit``'s own default where
it has none) how they keep in step; ``contexts`` has to be the cell's
``chips``.
"""
import gc
import math
import time

import numpy as np

import weights
from probes import compile_count, peak_bytes
from manifest import ManifestError, sized
from references import sgd


class WindowClosed(Exception):
    """Raised from the batch-end callback to leave ``fit``."""


def run(cell, args, recorder, tracer, t_process, log):
    import mxnet_tpu as mx

    cfg = sized(cell.config, args.rehearse)
    job = sized(cell.traffic, args.rehearse)
    fam = cell.family()
    batch = job["batch_size"]
    n_ctx = job.get("contexts", 1)
    if n_ctx != cell.chips:
        raise ManifestError(
            "traffic %s binds %d context(s); cell %s asks for %d chip(s)"
            % (cell.traffic_name, n_ctx, cell.name, cell.chips))
    check_steps, warm_steps = job["check_steps"], job["warmup_steps"]
    if warm_steps < check_steps:
        raise ValueError("warmup_steps %d < check_steps %d"
                         % (warm_steps, check_steps))
    rng = np.random.default_rng(args.seed)
    data, labels = fam.batches(cfg, job, rng)
    items_per_step = batch * fam.items_per_row(cfg)

    device = mx.cpu if args.rehearse else mx.tpu
    ctxs = [device(i) for i in range(n_ctx)]
    ctx = ctxs[0]
    mx.random.seed(args.seed % (2 ** 31))
    train = mx.io.NDArrayIter(data, labels, batch_size=batch, shuffle=False,
                              label_name="softmax_label")
    symbol = fam.symbol(cfg)
    mod = mx.mod.Module(symbol, context=ctx if n_ctx == 1 else ctxs)

    # seeded weights, on the device, in one call; the shapes the program
    # infers for its own symbol must be the reference's
    arg_shapes, _, aux_shapes = symbol.infer_shape(
        data=(batch,) + data.shape[1:],
        softmax_label=(batch,) + labels.shape[1:])
    prog = {n: tuple(s) for n, s in zip(symbol.list_arguments(), arg_shapes)
            if n not in ("data", "softmax_label")}
    prog_aux = {n: tuple(s) for n, s in
                zip(symbol.list_auxiliary_states(), aux_shapes)}
    spec, aux_spec = fam.reference.spec(cfg), fam.reference.aux_spec(cfg)
    if prog != spec or prog_aux != aux_spec:
        diff = sorted(set(prog.items()) ^ set(spec.items())
                      | set(prog_aux.items()) ^ set(aux_spec.items()))
        raise RuntimeError("program and reference disagree on parameter "
                           "shapes: %s" % diff[:8])
    words = weights.seed_words(args.seed)
    make = weights.maker({**spec, **aux_spec}, cfg.get("init_std"))
    made = make(words)
    wrap = lambda names: {n: mx.nd.NDArray(made[n], ctx) for n in names}
    arg_params, aux_params = wrap(spec), wrap(aux_spec)
    del made
    change_norms = weights.change_norms(spec, cfg.get("init_std"))
    opt = dict(job["optimizer_params"])
    lr = opt["learning_rate"]

    state = {"t": [], "losses": [], "seen": (0.0, 0), "failed": 0,
             "window": None, "compiles": None, "grad_norms": None,
             "change": None}

    def on_batch(param):
        now = time.perf_counter()
        # the loss of this step alone, from the metric's running sums
        # (no reset: that would be the benchmark's work, not fit's)
        loss = step_loss(param.eval_metric, state)
        state["t"].append(now)
        n = len(state["t"])
        if not math.isfinite(loss):
            state["failed"] += 1
        if n <= check_steps:
            state["losses"].append(loss)
            log("fit: step %d done", n)
        if n == 1:
            # SGD's momentum after one step is -lr * (the gradient as the
            # optimizer got it)
            mom = {k: mod._fused_states[k] for k in spec}
            state["grad_norms"] = {k: v / lr
                                   for k, v in sgd.leaf_norms(mom).items()}
        if n == check_steps:
            live = {k: mod._exec.arg_dict[k]._data for k in spec}
            state["change"] = {k: float(v) for k, v in
                               change_norms(words, live).items()}
        if n == warm_steps:
            state["compiles"] = compile_count()
            if args.trace:
                tracer.start()
            log("fit: window opens after %d steps", n)
            state["window"] = time.perf_counter()
        elif n > warm_steps and now - state["window"] >= args.seconds:
            if tracer.running:
                tracer.stop()
            raise WindowClosed()

    recorder.wrap(mod, "forward_backward")
    recorder.wrap(mod, "update_metric")
    log("fit: %s on %s, batch %d, %d items a step", cell.config_name,
        ctxs if n_ctx > 1 else ctx, batch, items_per_step)
    # only what the traffic file gives is passed: a one-chip job says
    # nothing of a kvstore, as its users do not
    fit_kwargs = {k: job[k] for k in ("kvstore",) if k in job}
    try:
        mod.fit(train, num_epoch=10 ** 9, **fit_kwargs,
                eval_metric=mx.metric.create(job["eval_metric"]),
                optimizer=job["optimizer"], optimizer_params=opt,
                compute_dtype=job["compute_dtype"], arg_params=arg_params,
                aux_params=aux_params, batch_end_callback=on_batch)
    except WindowClosed:
        pass
    t_end = state["t"][-1]
    steps = len(state["t"]) - warm_steps
    window_s = t_end - state["window"]
    new_compiles = compile_count() - state["compiles"]
    fused = getattr(mod, "_fused", None)
    aot = fused is not None and fused._aot is not None
    step_times = np.diff([state["window"]] + state["t"][warm_steps:])
    log("fit: %d steps in %.3f s; step wall median %.4f s, min %.4f, "
        "max %.4f", steps, window_s, float(np.median(step_times)),
        float(step_times.min()), float(step_times.max()))
    peak = peak_bytes()

    # the module goes before the reference comes: both do not fit
    del mod, fused, arg_params, aux_params, train
    gc.collect()

    checks = follow_reference(fam, cfg, job, sized(cell.limits, args.rehearse),
                              words, make, spec, aux_spec, data, labels,
                              state, log)
    checks.append(("compiles_in_window", new_compiles, 0))
    checks.append(("fused_aot_step_missing", 0 if aot else 1, 0))
    checks.append(("nonfinite_steps", state["failed"], 0))
    out_dtype = 2 if job["compute_dtype"] == "bfloat16" else 4
    return {
        "attempted": steps, "failed": state["failed"], "checks": checks,
        "window": (state["window"], t_end), "peak_bytes": peak,
        "setup_s": state["window"] - t_process,
        "facts": {
            "steps": steps, "window_s": window_s,
            "items_per_step": items_per_step,
            "step_times_s": [float(v) for v in step_times],
            "train_flops_per_item": fam.train_flops_per_item(cfg),
            "n_params": fam.n_params(cfg),
            "batch_bytes": int(data[:batch].nbytes + labels[:batch].nbytes),
            "output_bytes": batch * fam.output_bytes_per_row(cfg, out_dtype),
        },
        "end_to_end": {"train_items_per_s": steps * items_per_step / window_s},
    }


def step_loss(metric, state):
    """Mean loss of the newest batch from the running sums of the metric
    named ``cross-entropy`` (alone or inside a composite)."""
    ce = metric
    for m in getattr(metric, "metrics", []):
        if m.name == "cross-entropy":
            ce = m
    total, count = float(ce.sum_metric), int(ce.num_inst)
    before_total, before_count = state["seen"]
    if count <= before_count:            # fit reset the metric: new epoch
        before_total, before_count = 0.0, 0
    state["seen"] = (total, count)
    return (total - before_total) / max(count - before_count, 1)


def worst_leaf(got, ref):
    """Largest gap between the program's and the reference's norm of a
    leaf, against the reference's norm of that leaf or of the median
    leaf, whichever is larger (some gradients are all but zero)."""
    median = float(np.median(list(ref.values())))
    worst, where = 0.0, None
    for k, r in ref.items():
        gap = abs(got[k] - r) / max(r, median, 1e-30)
        if not gap <= worst:             # also catches nan
            worst, where = gap, k
    return worst, where


def follow_reference(fam, cfg, job, limits, words, make, spec, aux_spec,
                     data, labels, state, log):
    """The plain reference's own first steps from the same seeded start
    on the same batches, and each number compared with its limit."""
    import jax.numpy as jnp

    t0 = time.perf_counter()
    batch, n = job["batch_size"], job["check_steps"]
    made = make(words)
    params = {k: made[k] for k in spec}
    aux = {k: made[k] for k in aux_spec}
    del made
    batches = [(jnp.asarray(data[i * batch:(i + 1) * batch]),
                jnp.asarray(labels[i * batch:(i + 1) * batch]))
               for i in range(n)]
    opt = job["optimizer_params"]
    losses, grad_norms, change = sgd.follow(
        fam.reference.make_loss_and_grads(cfg), params, aux, batches,
        lr=opt["learning_rate"], momentum=opt.get("momentum", 0.0),
        wd=opt.get("wd", 0.0), grad_scale=fam.grad_scale(batch))
    log("reference: %d steps in %.2f s (not in setup_s)", n,
        time.perf_counter() - t0)
    checks = []
    for i, (got, ref) in enumerate(zip(state["losses"], losses)):
        log("  step %d loss: program %.6f reference %.6f", i + 1, got, ref)
        checks.append(("loss_gap_step%d" % (i + 1), abs(got - ref),
                       limits["loss_gap"]))
    gap, where = worst_leaf(state["grad_norms"], grad_norms)
    log("  first-gradient norm: worst leaf %s", where)
    checks.append(("first_grad_norm_gap", gap, limits["first_grad_norm_gap"]))
    gap, where = worst_leaf(state["change"], change)
    log("  parameter-change norm after %d steps: worst leaf %s", n, where)
    checks.append(("param_change_norm_gap", gap,
                   limits["param_change_norm_gap"]))
    return checks
