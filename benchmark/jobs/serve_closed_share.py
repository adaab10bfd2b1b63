"""A serving cell of a block that holds a share of its routed experts:
``jobs/serve_closed_block.py``'s run, and one more check.

An expert layer that is told which experts it holds routes every token
over all of them and computes the assignments that fall on its own
(``mxnet_tpu/serve/latent_moe.py``).  The block counts both on the device:
``assignments_held``, those asked of the experts held here, and
``assignments_computed``, those of them whose tile the expert loop
reached.  ``moe_assignments_dropped`` is their difference over the window,
limit 0: a token whose expert is held elsewhere is not dropped, one whose
held expert the loop never reached is.  A block whose report has no such
counts reads not-a-number and fails the check.

``serve_closed_block.py`` cannot report it (it knows no router) and
``serve_closed_model.py`` reads ``moe_report()`` as if every expert were
held; this PR may edit neither.  For the next ``benchmark`` issue: a
fourth ``serve_closed*`` kind to fold into one (PERF.md, Open questions).
"""
import manifest


def run(cell, args, recorder, tracer, t_process, log):
    base = manifest.load_module("jobs", "serve_closed_block", cell.root)
    out = base.run(cell, args, recorder, tracer, t_process, log)
    block = out["facts"]["block"]
    nan = float("nan")
    dropped = block.get("assignments_held", nan) \
        - block.get("assignments_computed", nan)
    log("serve: of %s assignments in the window %s fell on the experts held "
        "here and %s were computed; %.1f distinct held experts a decode step "
        "a layer", block.get("assignments_asked"),
        block.get("assignments_held"), block.get("assignments_computed"),
        block.get("distinct_held_experts", nan) / max(
            block.get("decode_steps", 0) * block.get("expert_layers", 0), 1))
    out["checks"].append(("moe_assignments_dropped", dropped, 0))
    return out
