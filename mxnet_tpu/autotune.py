"""Persistent measured autotuner over the project's knob surface.

The codebase has grown a handful of performance knobs that are still
hand-set per rig: the flash-attention block (``MXNET_ATTN_BLOCK``), the
gradient and ZeRO-3 gather bucket sizes (``MXNET_GRAD_BUCKET_MB``,
``MXNET_ZERO_GATHER_BUCKET_MB``), the serve prefill-bucket ladder, and
now the weight-only quant mode (``MXNET_SERVE_QUANT``).  In the spirit
of TVM's learned schedule search (arXiv 1802.04799) scaled down to a
knob surface XLA already compiles well (arXiv 2301.13062), this module
closes the loop:

* :func:`search` runs a measured greedy coordinate-descent over a knob
  space — the measure callback reports a throughput metric (steps/s or
  tokens/s, from the ``bench_fit.py`` / ``bench_serve.py`` style timing
  loops) plus optional aux metrics (``temp_bytes`` etc. from
  ``memory_analysis`` / the fusion-audit counters) used to break ties
  between knob settings within noise of each other;
* results persist as one JSON record per (kind, model-fingerprint,
  mesh, backend) under ``MXNET_AUTOTUNE_DIR`` (default: an ``autotune``
  directory next to the PR 4 compile cache's home), so the SECOND run
  on the same key is a pure cache hit — stored knobs apply with zero
  measurement passes;
* with ``MXNET_AUTOTUNE`` on, cached knobs auto-apply at build time:
  :func:`apply_serve` folds serve knobs into an env-derived
  ``ServeConfig`` and :func:`apply_train_env` arms the env knobs a
  ``TrainStep`` reads at trace time (never overriding a value the user
  set explicitly);
* every application is recorded in :func:`provenance`, which
  ``compile_cache.report()`` embeds — the compile-report artifact says
  exactly which tuned knobs a process ran under.

``tools/autotune.py`` is the operator CLI: ``--search`` runs measured
searches on this rig, ``--report`` pretty-prints the store.
"""
from __future__ import annotations

import hashlib
import json
import os
import time

from .base import MXNetError, get_env

__all__ = ["autotune_enabled", "store_dir", "budget_s", "fingerprint",
           "fingerprint_symbol", "mesh_desc", "backend_name", "Key",
           "Knob", "AutotuneStore", "search", "apply_serve",
           "apply_train_env", "provenance", "note_applied",
           "clear_applied", "TRAIN_KNOB_ENV"]

DEFAULT_REL_TIE = 0.02

# train-side knobs are applied through the environment because the ops
# read them at trace time (attention.attention_block_size & co.)
TRAIN_KNOB_ENV = {
    "attn_block": "MXNET_ATTN_BLOCK",
    "grad_bucket_mb": "MXNET_GRAD_BUCKET_MB",
    "gather_bucket_mb": "MXNET_ZERO_GATHER_BUCKET_MB",
    # per-layer fp8 allow-list: a tuned comma list of layer names keeps
    # drift-sensitive layers on bf16 while the rest take the fp8 route
    "fp8_layers": "MXNET_FP8_LAYERS",
}

_APPLIED = []  # provenance of knob applications in this process
_ENV_SET = []  # env keys apply_train_env set (so tests can undo)


def autotune_enabled():
    """``MXNET_AUTOTUNE``: apply cached tuned knobs at session /
    TrainStep build (default off — searches themselves are always
    explicit, via tools/autotune.py)."""
    return get_env("MXNET_AUTOTUNE", False, bool)


def store_dir():
    """``MXNET_AUTOTUNE_DIR``: where tuning records persist (default
    ``~/.cache/mxnet_tpu/autotune``, alongside the compile cache)."""
    path = get_env("MXNET_AUTOTUNE_DIR", "", str)
    if not path:
        path = os.path.join(os.path.expanduser("~"), ".cache",
                            "mxnet_tpu", "autotune")
    return path


def budget_s():
    """``MXNET_AUTOTUNE_BUDGET_S``: wall-clock cap for one search's
    measurement passes (0 = unbounded)."""
    return max(0.0, get_env("MXNET_AUTOTUNE_BUDGET_S", 0.0, float))


# -- keys ------------------------------------------------------------------

def fingerprint(params):
    """Stable model fingerprint from parameter names/shapes/dtypes —
    12 hex chars.  Works on arrays, NDArray, ShapeDtypeStructs, and
    quantized ``{"q", "s"}`` records alike."""
    items = []
    for name in sorted(params):
        v = params[name]
        dtype = None
        if isinstance(v, dict) and "q" in v:
            # quantized {"q","s"} record: shape from the codes, dtype
            # the float32 they dequantize to — so a tree quantized
            # after apply_serve still fingerprints like the raw one
            v, dtype = v["q"], "float32"
        v = getattr(v, "_data", v)
        shape = tuple(int(s) for s in getattr(v, "shape", ()))
        if dtype is None:
            dtype = str(getattr(v, "dtype", "?"))
        items.append("%s:%r:%s" % (name, shape, dtype))
    blob = ";".join(items).encode()
    return hashlib.sha256(blob).hexdigest()[:12]


def fingerprint_symbol(symbol):
    """Model fingerprint for a symbolic training graph."""
    try:
        blob = symbol.tojson().encode()
    except Exception:  # mxlint: disable=MX008 — repr fallback is the point
        blob = repr(symbol).encode()
    return hashlib.sha256(blob).hexdigest()[:12]


def mesh_desc(mesh):
    """Canonical mesh description (``"-"`` for no mesh)."""
    shape = getattr(mesh, "shape", None)
    if not shape:
        return "-"
    return ",".join("%s:%d" % (ax, int(n))
                    for ax, n in sorted(dict(shape).items()))


def backend_name():
    """The jax backend this process measures on (``"cpu"`` when jax is
    not importable — record keys must not require a backend init)."""
    try:
        import jax

        return str(jax.default_backend())
    except Exception:  # mxlint: disable=MX008 — keys must not need a backend
        return "cpu"


class Key(object):
    """Identity of one tuning record: what was tuned (``kind``), for
    which model (``fingerprint``), on which topology (``mesh``,
    ``backend``)."""

    __slots__ = ("kind", "fingerprint", "mesh", "backend")

    def __init__(self, kind, fingerprint, mesh="-", backend=None):
        self.kind = str(kind)
        self.fingerprint = str(fingerprint)
        self.mesh = str(mesh or "-")
        self.backend = str(backend if backend is not None
                           else backend_name())

    @property
    def slug(self):
        mesh = hashlib.sha256(self.mesh.encode()).hexdigest()[:8] \
            if self.mesh != "-" else "none"
        return "%s-%s-%s-%s" % (self.kind, self.fingerprint, mesh,
                                self.backend)

    def __repr__(self):
        return ("Key(kind=%r, fingerprint=%r, mesh=%r, backend=%r)"
                % (self.kind, self.fingerprint, self.mesh, self.backend))


class Knob(object):
    """One searchable dimension: ``values[0]`` is the default the
    coordinate descent starts from."""

    __slots__ = ("name", "values")

    def __init__(self, name, values):
        self.name = str(name)
        self.values = tuple(values)
        if not self.values:
            raise MXNetError("Knob %r has no values" % (name,))


def _space_desc(space):
    # normalize through JSON so equality with a stored record's
    # knob_space is round-trip stable (tuples come back as lists)
    return json.loads(json.dumps({k.name: list(k.values)
                                  for k in space}))


# -- the persistent store --------------------------------------------------

class AutotuneStore(object):
    """One JSON file per record under ``directory`` — the same
    file-per-entry, atomic-replace stance as the compile cache."""

    def __init__(self, directory=None):
        self.directory = directory or store_dir()

    def _path(self, key):
        return os.path.join(self.directory, "autotune-%s.json" % key.slug)

    def get(self, key):
        try:
            with open(self._path(key)) as f:
                return json.load(f)
        except (OSError, ValueError):
            return None

    def put(self, key, record):
        os.makedirs(self.directory, exist_ok=True)
        path = self._path(key)
        tmp = "%s.tmp.%d" % (path, os.getpid())
        with open(tmp, "w") as f:
            json.dump(record, f, indent=2, sort_keys=True)
        os.replace(tmp, path)
        return path

    def records(self):
        """Every record in the store (for ``--report``)."""
        out = []
        try:
            names = sorted(os.listdir(self.directory))
        except OSError:
            return out
        for name in names:
            if not (name.startswith("autotune-")
                    and name.endswith(".json")):
                continue
            try:
                with open(os.path.join(self.directory, name)) as f:
                    out.append(json.load(f))
            except (OSError, ValueError):
                continue
        return out


# -- the search ------------------------------------------------------------

def _measurement(raw):
    if isinstance(raw, dict):
        return {"metric": float(raw["metric"]),
                "aux": dict(raw.get("aux") or {})}
    return {"metric": float(raw), "aux": {}}


def _better(cand, best, rel_tie):
    """Higher metric wins outright; within ``rel_tie`` relative noise,
    lower aux ``temp_bytes`` (the fusion-audit memory signal) breaks
    the tie."""
    m, b = cand["metric"], best["metric"]
    if m > b * (1.0 + rel_tie):
        return True
    if m < b * (1.0 - rel_tie):
        return False
    ca = cand["aux"].get("temp_bytes")
    bb = best["aux"].get("temp_bytes")
    return ca is not None and bb is not None and ca < bb


def search(measure, space, key, store=None, budget=None,
           rel_tie=DEFAULT_REL_TIE, force=False):
    """Greedy coordinate descent over ``space`` (a list of
    :class:`Knob`), measuring each candidate with ``measure(knobs) ->
    metric | {"metric": ..., "aux": {...}}`` (higher is better).

    The record persists under ``key``; a repeat call with the same key
    and knob space returns the stored record WITHOUT calling
    ``measure`` at all (``cache_hit: True``) — the acceptance contract
    for warm builds.  ``budget`` seconds (default
    ``MXNET_AUTOTUNE_BUDGET_S``) bounds measurement time; the baseline
    is always measured, later candidates are skipped once the budget is
    spent (recorded as ``budget_exhausted``).
    """
    space = list(space)
    if not space:
        raise MXNetError("search: empty knob space")
    store = store or AutotuneStore()
    desc = _space_desc(space)
    if not force:
        rec = store.get(key)
        if rec is not None and rec.get("knob_space") == desc:
            rec = dict(rec)
            rec["cache_hit"] = True
            return rec
    if budget is None:
        budget = budget_s()
    t0 = time.perf_counter()
    current = {k.name: k.values[0] for k in space}
    best = _measurement(measure(dict(current)))
    baseline = best["metric"]
    trials = [{"knobs": dict(current), **best}]
    exhausted = False
    for knob in space:
        for val in knob.values[1:]:
            if budget and time.perf_counter() - t0 > budget:
                exhausted = True
                break
            cand = dict(current)
            cand[knob.name] = val
            m = _measurement(measure(dict(cand)))
            trials.append({"knobs": dict(cand), **m})
            if _better(m, best, rel_tie):
                best, current = m, cand
        if exhausted:
            break
    record = {
        "kind": key.kind,
        "fingerprint": key.fingerprint,
        "mesh": key.mesh,
        "backend": key.backend,
        "knob_space": desc,
        "knobs": dict(current),
        "metric": best["metric"],
        "aux": best["aux"],
        "baseline_metric": baseline,
        "speedup_vs_default": (best["metric"] / baseline
                               if baseline else 0.0),
        "measurements": len(trials),
        "trials": trials,
        "elapsed_s": round(time.perf_counter() - t0, 3),
        "budget_exhausted": exhausted,
        "created": time.time(),
    }
    store.put(key, record)
    rec = dict(record)
    rec["cache_hit"] = False
    return rec


# -- application + provenance ----------------------------------------------

def note_applied(record, where, applied):
    """Record one knob application for the compile report."""
    _APPLIED.append({
        "kind": record.get("kind"),
        "fingerprint": record.get("fingerprint"),
        "mesh": record.get("mesh"),
        "backend": record.get("backend"),
        "knobs": dict(record.get("knobs") or {}),
        "applied": list(applied),
        "where": str(where),
        "metric": record.get("metric"),
    })


def provenance():
    """Knob applications this process performed (embedded in
    ``compile_cache.report()`` under ``"autotune"``)."""
    return [dict(rec) for rec in _APPLIED]


def clear_applied():
    """Undo this process's applications: drop the provenance log and
    remove the env vars :func:`apply_train_env` set (test hook)."""
    del _APPLIED[:]
    while _ENV_SET:
        os.environ.pop(_ENV_SET.pop(), None)


def _user_set(env_name):
    """Whether the user set this knob explicitly (either accepted
    prefix counts — see ``base.get_env``)."""
    alt = "MXTPU_" + env_name[len("MXNET_"):]
    return env_name in os.environ or alt in os.environ


def apply_serve(config, params, store=None):
    """Fold a cached serve tuning record into an env-derived
    ``ServeConfig`` (called by ``InferenceSession`` only when the
    caller did NOT pass an explicit config).  Applies ``quant``,
    ``kv_quant`` (int8/fp8 KV-cache pages), ``buckets``,
    ``prefix_pages`` (prefix-cache retention size) and ``watermark``
    (preemption free-pool floor; inert until the caller turns
    ``oversub`` on): capacity and precision, never what the model
    computes layer by layer.  Anything the record doesn't carry keeps
    the env/default value.  No-op unless ``MXNET_AUTOTUNE`` is on
    and a record exists for this (model-fingerprint, backend)."""
    if not autotune_enabled():
        return config
    import dataclasses

    from .quantize import quant_mode

    store = store or AutotuneStore()
    rec = store.get(Key("serve", fingerprint(params)))
    if not rec:
        return config
    knobs = rec.get("knobs") or {}
    updates = {}
    if "quant" in knobs:
        updates["quant"] = quant_mode(knobs["quant"])
    if "kv_quant" in knobs:
        updates["kv_quant"] = quant_mode(knobs["kv_quant"])
    if "buckets" in knobs:
        updates["buckets"] = tuple(int(b) for b in knobs["buckets"])
    if "prefix_pages" in knobs:
        updates["prefix_pages"] = int(knobs["prefix_pages"])
    if "watermark" in knobs:
        updates["watermark"] = int(knobs["watermark"])
    if not updates:
        return config
    note_applied(rec, where="InferenceSession",
                 applied=sorted(updates))
    return dataclasses.replace(config, **updates)


def train_key_topology(mesh, plan=None):
    """The Key ``mesh`` field for a train record: the plan fingerprint
    (its own namespace) when a composed plan drives the step — tuned
    knobs for a tp x zero3 plan must not leak onto pure-DP runs of the
    same symbol on the same mesh — else the plain mesh description."""
    if plan is not None:
        return "plan:%s" % plan.fingerprint(mesh)
    return mesh_desc(mesh)


def apply_train_env(symbol, mesh, store=None, plan=None):
    """Arm cached train knobs (:data:`TRAIN_KNOB_ENV`) in the
    environment before a ``TrainStep`` traces — the ops read them at
    trace time.  A knob the user already set (either env prefix) is
    never overridden.  Records are keyed by topology —
    :func:`train_key_topology` — so a composed plan's knobs stay scoped
    to that plan.  Returns the record applied, or None."""
    if not autotune_enabled():
        return None
    store = store or AutotuneStore()
    rec = store.get(Key("train", fingerprint_symbol(symbol),
                        train_key_topology(mesh, plan)))
    if not rec:
        return None
    knobs = rec.get("knobs") or {}
    applied = []
    for kname, env_name in TRAIN_KNOB_ENV.items():
        if kname not in knobs or _user_set(env_name):
            continue
        os.environ[env_name] = str(knobs[kname])
        _ENV_SET.append(env_name)
        applied.append(env_name)
    if applied:
        note_applied(rec, where="TrainStep", applied=applied)
        return rec
    return None
