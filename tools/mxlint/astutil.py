"""Shared AST machinery for the mxlint checkers.

Everything here is *approximate on purpose*: mxlint is a linter, not a
verifier.  Name resolution follows import aliases within one module,
"traced" functions are found by local evidence (decorator, or the name
being handed to jit/shard_map/scan/...), and value taint is a single
forward pass over parameter-derived names.  Findings the heuristics get
wrong are suppressed inline (``# mxlint: disable=CODE``) — precision
beats recall for a gate that runs in tier-1.
"""
import ast

# ---------------------------------------------------------------------------
# import-alias resolution


def import_aliases(tree):
    """Map local name -> canonical dotted prefix for a module.

    ``import jax.numpy as jnp`` -> {'jnp': 'jax.numpy'};
    ``from jax import lax`` -> {'lax': 'jax.lax'};
    ``from .testing import faults`` -> {'faults': 'testing.faults'}
    (relative dots are dropped — suffix matching absorbs them).
    """
    aliases = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                aliases[a.asname or a.name.split(".")[0]] = (
                    a.name if a.asname else a.name.split(".")[0])
        elif isinstance(node, ast.ImportFrom):
            mod = node.module or ""
            for a in node.names:
                if a.name == "*":
                    continue
                full = ("%s.%s" % (mod, a.name)) if mod else a.name
                aliases[a.asname or a.name] = full
    return aliases


def dotted(node, aliases):
    """Canonical dotted name of a Name/Attribute chain, or None."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if not isinstance(node, ast.Name):
        return None
    root = aliases.get(node.id, node.id)
    parts.append(root)
    return ".".join(reversed(parts))


def call_name(call, aliases):
    """Canonical dotted name of a call's callee, or None."""
    return dotted(call.func, aliases)


def matches(name, suffixes):
    """True when canonical ``name`` ends with any of ``suffixes``
    (component-aligned: 'jax.jit' matches 'jit' and 'jax.jit', not
    'myjit')."""
    if name is None:
        return False
    for suf in suffixes:
        if name == suf or name.endswith("." + suf):
            return True
    return False


# ---------------------------------------------------------------------------
# parent links / enclosing scopes


def parent_map(tree):
    parents = {}
    for node in ast.walk(tree):
        for child in ast.iter_child_nodes(node):
            parents[child] = node
    return parents


def enclosing(node, parents, kinds):
    """Nearest ancestor of one of ``kinds`` (a tuple of AST classes)."""
    cur = parents.get(node)
    while cur is not None:
        if isinstance(cur, kinds):
            return cur
        cur = parents.get(cur)
    return None


_FUNCS = (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)


def qualname(node, parents):
    """Dotted human name of the def/class chain enclosing ``node``."""
    names = []
    cur = node
    while cur is not None:
        if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef,
                            ast.ClassDef)):
            names.append(cur.name)
        elif isinstance(cur, ast.Lambda):
            names.append("<lambda>")
        cur = parents.get(cur)
    return ".".join(reversed(names)) or "<module>"


# ---------------------------------------------------------------------------
# traced-function discovery (MX001/MX002)

# callables whose function argument is traced by jax
TRACING_CALLS = (
    "jax.jit", "jit", "pjit", "jax.pmap", "pmap",
    "shard_map", "jax.shard_map",
    "jax.checkpoint", "jax.remat", "remat", "checkpoint",
    "lax.scan", "scan", "lax.cond", "cond", "lax.while_loop",
    "while_loop", "lax.fori_loop", "fori_loop", "lax.switch",
    "lax.map", "lax.associative_scan",
    "jax.vmap", "vmap", "jax.grad", "grad", "jax.value_and_grad",
    "value_and_grad", "jax.custom_vjp", "custom_vjp", "jax.custom_jvp",
    "custom_jvp", "jax.linearize", "jax.vjp", "jax.jvp",
    "jax.eval_shape", "eval_shape",
)

def _decorator_traces(dec, aliases):
    """True when a decorator node is a tracing transform (possibly via
    functools.partial(jax.jit, ...))."""
    if isinstance(dec, ast.Call):
        name = call_name(dec, aliases)
        if matches(name, TRACING_CALLS):
            return True
        if matches(name, ("functools.partial", "partial")) and dec.args:
            return matches(dotted(dec.args[0], aliases), TRACING_CALLS)
        return False
    return matches(dotted(dec, aliases), TRACING_CALLS)


def traced_functions(tree, aliases, parents):
    """The set of FunctionDef/Lambda nodes whose bodies run under a jax
    trace, by local evidence:

    * decorated with jit/checkpoint/custom_vjp/... (or a
      functools.partial of one);
    * their name (bare or ``self.name``) appears as an argument to a
      tracing call anywhere in the module;
    * defined lexically inside a traced function (nested helpers run
      at trace time);
    * a lambda passed directly to a tracing call.
    """
    defs_by_name = {}
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            defs_by_name.setdefault(node.name, []).append(node)

    traced = set()
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            if any(_decorator_traces(d, aliases)
                   for d in node.decorator_list):
                traced.add(node)
        elif isinstance(node, ast.Call):
            if not matches(call_name(node, aliases), TRACING_CALLS):
                continue
            for arg in list(node.args) + [k.value for k in node.keywords]:
                if isinstance(arg, ast.Lambda):
                    traced.add(arg)
                elif isinstance(arg, ast.Name):
                    traced.update(defs_by_name.get(arg.id, ()))
                elif isinstance(arg, ast.Attribute):
                    traced.update(defs_by_name.get(arg.attr, ()))

    # nested defs of traced functions are traced too (fixpoint over the
    # lexical tree — one sweep per nesting level)
    changed = True
    while changed:
        changed = False
        for node in ast.walk(tree):
            if not isinstance(node, _FUNCS) or node in traced:
                continue
            anc = enclosing(node, parents, _FUNCS)
            while anc is not None and anc not in traced:
                anc = enclosing(anc, parents, _FUNCS)
            if anc is not None:
                traced.add(node)
                changed = True
    return traced


# ---------------------------------------------------------------------------
# taint: parameter-derived values within one function

# attribute/call results that are static at trace time even on a traced
# array (shapes and dtypes are compile-time constants)
_STATIC_ATTRS = {"shape", "ndim", "dtype", "size", "aval", "sharding"}
_STATIC_CALLS = ("len", "range", "enumerate", "isinstance", "type",
                 "getattr", "hasattr", "zip")


def _param_names(fn):
    a = fn.args
    names = [p.arg for p in
             list(a.posonlyargs) + list(a.args) + list(a.kwonlyargs)]
    if a.vararg:
        names.append(a.vararg.arg)
    if a.kwarg:
        names.append(a.kwarg.arg)
    return set(names)


def contains_taint(node, tainted, aliases):
    """True when ``node`` references a tainted name *as a value* —
    descending, but treating shape/dtype accesses and len()/range()
    results as static."""
    if isinstance(node, ast.Attribute) and node.attr in _STATIC_ATTRS:
        return False
    if isinstance(node, ast.Call):
        if matches(call_name(node, aliases), _STATIC_CALLS):
            return False
        kids = list(node.args) + [k.value for k in node.keywords]
        if isinstance(node.func, ast.Attribute):
            kids.append(node.func.value)  # method on a tainted receiver
        return any(contains_taint(k, tainted, aliases) for k in kids)
    if isinstance(node, ast.Name):
        return node.id in tainted
    return any(contains_taint(c, tainted, aliases)
               for c in ast.iter_child_nodes(node))


def tainted_names(fn, aliases):
    """Forward may-taint pass: parameters are tainted; an assignment
    whose RHS contains a tainted value taints its targets.  Two sweeps
    approximate loop back-edges."""
    tainted = _param_names(fn)
    body = fn.body if isinstance(fn.body, list) else [fn.body]
    for _ in range(2):
        before = len(tainted)
        for stmt in body:
            for node in ast.walk(stmt):
                if isinstance(node, _FUNCS):
                    continue
                value = None
                targets = []
                if isinstance(node, ast.Assign):
                    value, targets = node.value, node.targets
                elif isinstance(node, (ast.AugAssign, ast.AnnAssign)):
                    value = node.value
                    targets = [node.target]
                elif isinstance(node, ast.For):
                    value, targets = node.iter, [node.target]
                if value is None:
                    continue
                if contains_taint(value, tainted, aliases):
                    for t in targets:
                        for leaf in ast.walk(t):
                            if isinstance(leaf, ast.Name):
                                tainted.add(leaf.id)
        if len(tainted) == before:
            break
    return tainted
