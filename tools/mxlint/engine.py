"""mxlint engine: checker registry, suppressions, baseline, runner.

A checker is a class with ``code``/``name``/``hint`` and either a
per-file ``check(file_ctx) -> [Finding]`` (subclass :class:`Checker`)
or a whole-project ``check_project(project_ctx) -> [Finding]``
(subclass :class:`ProjectChecker` — for cross-file registries like the
env-var catalog).  Register with ``@register``.

Suppressions: ``# mxlint: disable=MX001`` (or ``=MX001,MX003`` /
``=all``) on the finding's line, or ``# mxlint: disable-file=CODE``
within the first ten lines of the file.

Baseline: grandfathered findings live in ``tools/mxlint/baseline.json``
keyed by ``path::code::symbol`` (no line numbers, so unrelated edits
don't churn it) with an occurrence count.  ``--write-baseline``
regenerates it; ``--prune-baseline`` fails when an entry no longer
matches anything, so the debt can only shrink.
"""
import ast
import fnmatch
import json
import os
import re
import sys

JSON_SCHEMA_VERSION = 1
DEFAULT_BASELINE = os.path.join(os.path.dirname(__file__), "baseline.json")

_SUPPRESS_RE = re.compile(
    r"#\s*mxlint:\s*disable=([A-Za-z0-9_,\s]+)")
_SUPPRESS_FILE_RE = re.compile(
    r"#\s*mxlint:\s*disable-file=([A-Za-z0-9_,\s]+)")

# directories never worth parsing
_SKIP_DIRS = {"__pycache__", ".git", "_build", ".ipynb_checkpoints",
              "node_modules"}


class Finding(object):
    """One diagnostic.

    ``symbol`` is the checker-chosen *stable identity* of the finding
    (an env-var name, a class name, a ``function:callee`` pair...) —
    the baseline keys on ``path::code::symbol`` so reformatting a file
    does not invalidate grandfathered entries.
    """

    __slots__ = ("path", "line", "col", "code", "message", "hint",
                 "symbol", "baselined")

    def __init__(self, path, line, col, code, message, hint="",
                 symbol=""):
        self.path = path
        self.line = int(line)
        self.col = int(col)
        self.code = code
        self.message = message
        self.hint = hint
        self.symbol = symbol or "%s:%s" % (line, col)
        self.baselined = False

    @property
    def key(self):
        return "%s::%s::%s" % (self.path, self.code, self.symbol)

    def render(self):
        txt = "%s:%d:%d: %s %s" % (
            self.path, self.line, self.col, self.code, self.message)
        if self.hint:
            txt += "\n    fix: %s" % self.hint
        return txt

    def as_json(self):
        return {"path": self.path, "line": self.line, "col": self.col,
                "code": self.code, "message": self.message,
                "hint": self.hint, "symbol": self.symbol,
                "baselined": self.baselined}


class FileContext(object):
    """Parsed view of one source file handed to per-file checkers."""

    def __init__(self, path, relpath, source, tree):
        self.path = path          # absolute
        self.relpath = relpath    # repo-root-relative, '/'-separated
        self.source = source
        self.lines = source.splitlines()
        self.tree = tree
        self._aliases = None
        self._parents = None

    @property
    def aliases(self):
        if self._aliases is None:
            from . import astutil
            self._aliases = astutil.import_aliases(self.tree)
        return self._aliases

    @property
    def parents(self):
        if self._parents is None:
            from . import astutil
            self._parents = astutil.parent_map(self.tree)
        return self._parents

    def finding(self, node, code, message, hint="", symbol=""):
        return Finding(self.relpath, getattr(node, "lineno", 1),
                       getattr(node, "col_offset", 0) + 1, code,
                       message, hint, symbol)


class ProjectContext(object):
    """Whole-repo view for cross-file checkers (MX004/MX005).

    ``files`` is the list of scanned FileContexts; ``root`` the repo
    root.  ``library_files()`` parses the *canonical* code set
    (mxnet_tpu/, tools/, bench*.py, __graft_entry__.py, chip_smoke.py)
    even when the CLI was pointed at a subset, so registry comparisons
    are stable.
    """

    def __init__(self, root, files):
        self.root = root
        self.files = files
        self._canon = None

    def read(self, relpath):
        p = os.path.join(self.root, relpath)
        if not os.path.exists(p):
            return None
        with open(p, "r", encoding="utf-8", errors="replace") as f:
            return f.read()

    def library_files(self):
        if self._canon is not None:
            return self._canon
        canon_rel = set()
        for sub in ("mxnet_tpu", "tools"):
            base = os.path.join(self.root, sub)
            if os.path.isdir(base):
                for p in _iter_py(base):
                    canon_rel.add(os.path.relpath(p, self.root))
        for name in sorted(os.listdir(self.root)):
            if fnmatch.fnmatch(name, "bench*.py") or \
                    name in ("__graft_entry__.py", "chip_smoke.py"):
                canon_rel.add(name)
        by_rel = {f.relpath: f for f in self.files}
        out = []
        for rel in sorted(r.replace(os.sep, "/") for r in canon_rel):
            if rel in by_rel:
                out.append(by_rel[rel])
                continue
            parsed = _parse_file(os.path.join(self.root, rel), rel)
            if isinstance(parsed, FileContext):
                out.append(parsed)
        self._canon = out
        return out


class Checker(object):
    code = "MX000"
    name = "unnamed"
    hint = ""

    def check(self, ctx):  # pragma: no cover - interface
        raise NotImplementedError


class ProjectChecker(Checker):
    def check_project(self, project):  # pragma: no cover - interface
        raise NotImplementedError


_REGISTRY = {}


def register(cls):
    """Class decorator: add a checker to the global registry."""
    if cls.code in _REGISTRY and _REGISTRY[cls.code] is not cls:
        raise ValueError("duplicate checker code %s" % cls.code)
    _REGISTRY[cls.code] = cls
    return cls


def all_checkers():
    from . import checkers  # noqa: F401 — populates the registry
    return dict(_REGISTRY)


# ---------------------------------------------------------------------------
# file discovery / parsing


def _iter_py(path):
    if os.path.isfile(path):
        yield path
        return
    for dirpath, dirnames, filenames in os.walk(path):
        dirnames[:] = sorted(d for d in dirnames if d not in _SKIP_DIRS)
        for fn in sorted(filenames):
            if fn.endswith(".py"):
                yield os.path.join(dirpath, fn)


def _parse_file(path, relpath):
    """FileContext, or a Finding (MX000) on unreadable/unparsable."""
    try:
        with open(path, "r", encoding="utf-8", errors="replace") as f:
            source = f.read()
        tree = ast.parse(source, filename=path)
    except (OSError, SyntaxError, ValueError) as exc:
        line = getattr(exc, "lineno", 1) or 1
        return Finding(relpath, line, 1, "MX000",
                       "cannot parse: %s" % exc,
                       symbol="parse-error")
    return FileContext(path, relpath, source, tree)


def find_root(start):
    """Ascend from ``start`` to the repo root (the dir holding
    docs/env_vars.md or .git); fall back to ``start``."""
    cur = os.path.abspath(start)
    if os.path.isfile(cur):
        cur = os.path.dirname(cur)
    while True:
        if os.path.exists(os.path.join(cur, "docs", "env_vars.md")) or \
                os.path.isdir(os.path.join(cur, ".git")):
            return cur
        parent = os.path.dirname(cur)
        if parent == cur:
            return os.path.abspath(start if os.path.isdir(start)
                                   else os.path.dirname(start))
        cur = parent


# ---------------------------------------------------------------------------
# suppressions


def suppressed_codes(ctx):
    """{lineno: set(codes)} plus a '*'-keyed file-wide set.

    A suppression on a comment-only line also covers the next code
    line (so long hints fit above the statement they wave through).
    """
    per_line = {}
    for i, text in enumerate(ctx.lines, 1):
        m = _SUPPRESS_RE.search(text)
        if m:
            codes = {c.strip().upper() for c in m.group(1).split(",")
                     if c.strip()}
            per_line.setdefault(i, set()).update(codes)
            if text.lstrip().startswith("#"):
                j = i + 1
                while j <= len(ctx.lines) and \
                        (not ctx.lines[j - 1].strip() or
                         ctx.lines[j - 1].lstrip().startswith("#")):
                    j += 1
                per_line.setdefault(j, set()).update(codes)
        if i <= 10:
            mf = _SUPPRESS_FILE_RE.search(text)
            if mf:
                codes = {c.strip().upper() for c in mf.group(1).split(",")
                         if c.strip()}
                per_line.setdefault("*", set()).update(codes)
    return per_line


def _is_suppressed(finding, supp_by_file):
    supp = supp_by_file.get(finding.path)
    if not supp:
        return False
    filewide = supp.get("*", set())
    if "ALL" in filewide or finding.code in filewide:
        return True
    codes = supp.get(finding.line, set())
    return "ALL" in codes or finding.code in codes


# ---------------------------------------------------------------------------
# baseline


def load_baseline(path):
    if not path or not os.path.exists(path):
        return {}
    with open(path, "r", encoding="utf-8") as f:
        data = json.load(f)
    entries = data.get("entries", {})
    return {str(k): int(v) for k, v in entries.items()}


def write_baseline(path, findings):
    entries = {}
    for f in findings:
        entries[f.key] = entries.get(f.key, 0) + 1
    payload = {
        "comment": "mxlint grandfathered findings — see "
                   "docs/static_analysis.md. Keys are path::code::symbol "
                   "with an occurrence count; --prune-baseline enforces "
                   "that this file only ever shrinks.",
        "version": 1,
        "entries": {k: entries[k] for k in sorted(entries)},
    }
    with open(path, "w", encoding="utf-8") as f:
        json.dump(payload, f, indent=1, sort_keys=False)
        f.write("\n")
    return payload


def apply_baseline(findings, baseline):
    """Mark findings covered by the baseline; return the stale entries
    (key -> unmatched count) whose grandfathered debt no longer
    exists."""
    budget = dict(baseline)
    for f in findings:
        if budget.get(f.key, 0) > 0:
            budget[f.key] -= 1
            f.baselined = True
    return {k: v for k, v in budget.items() if v > 0}


# ---------------------------------------------------------------------------
# runner


def run_paths(paths, root=None, select=None, ignore=None):
    """Run every registered checker over ``paths``.

    Returns ``(findings, parse_errors)`` — suppression comments already
    applied (suppressed findings dropped), baseline NOT applied (the
    CLI layer owns that policy).
    """
    checkers = all_checkers()
    if select:
        checkers = {c: v for c, v in checkers.items() if c in select}
    if ignore:
        checkers = {c: v for c, v in checkers.items() if c not in ignore}

    root = os.path.abspath(root or find_root(paths[0] if paths else "."))
    files, parse_errors = [], []
    seen = set()
    for p in paths:
        for fp in _iter_py(os.path.abspath(p)):
            if fp in seen:
                continue
            seen.add(fp)
            rel = os.path.relpath(fp, root).replace(os.sep, "/")
            parsed = _parse_file(fp, rel)
            if isinstance(parsed, Finding):
                parse_errors.append(parsed)
            else:
                files.append(parsed)

    project = ProjectContext(root, files)
    findings = []
    instances = [cls() for _, cls in sorted(checkers.items())]
    for ctx in files:
        for chk in instances:
            if isinstance(chk, ProjectChecker):
                continue
            findings.extend(chk.check(ctx))
    for chk in instances:
        if isinstance(chk, ProjectChecker):
            findings.extend(chk.check_project(project))

    supp_by_file = {ctx.relpath: suppressed_codes(ctx) for ctx in files}
    findings = [f for f in findings
                if not _is_suppressed(f, supp_by_file)]
    findings.sort(key=lambda f: (f.path, f.line, f.col, f.code))
    return findings, parse_errors


def emit_json(findings, parse_errors, stale, stream=None):
    """The stable ``--json`` artifact (schema version pinned by
    tests/test_mxlint.py)."""
    active = [f for f in findings if not f.baselined]
    payload = {
        "kind": "mxnet_tpu-mxlint",
        "schema_version": JSON_SCHEMA_VERSION,
        "counts": {
            "findings": len(active),
            "baselined": sum(1 for f in findings if f.baselined),
            "parse_errors": len(parse_errors),
            "stale_baseline": len(stale),
        },
        "findings": [f.as_json() for f in findings],
        "parse_errors": [f.as_json() for f in parse_errors],
        "stale_baseline": sorted(stale),
    }
    json.dump(payload, stream or sys.stdout, indent=1)
    (stream or sys.stdout).write("\n")
    return payload
