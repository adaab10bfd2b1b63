"""CLI: ``python -m tools.mxlint [paths] [options]``.

Exit codes (bench_util-style — machine-parseable, never a traceback for
a finding): 0 = clean (baselined debt allowed), 1 = at least one
non-baselined finding or a parse error, 2 = stale baseline under
``--prune-baseline``, 3 = usage error.
"""
import argparse
import os
import sys

from . import engine


def _codes(text):
    return {c.strip().upper() for c in text.split(",") if c.strip()}


def main(argv=None):
    ap = argparse.ArgumentParser(
        prog="python -m tools.mxlint",
        description="Project-invariant static analysis for tpu-mx "
                    "(docs/static_analysis.md).")
    ap.add_argument("paths", nargs="*",
                    help="files/dirs to scan (default: mxnet_tpu tools "
                         "bench*.py __graft_entry__.py chip_smoke.py "
                         "tests/test_tpu_compile.py under the repo "
                         "root)")
    ap.add_argument("--select", default="",
                    help="comma-separated codes to run (default: all)")
    ap.add_argument("--ignore", default="",
                    help="comma-separated codes to skip")
    ap.add_argument("--baseline", default=None, metavar="FILE",
                    help="baseline file (default: tools/mxlint/"
                         "baseline.json)")
    ap.add_argument("--no-baseline", action="store_true",
                    help="report grandfathered findings too")
    ap.add_argument("--write-baseline", action="store_true",
                    help="rewrite the baseline from the current "
                         "findings and exit 0")
    ap.add_argument("--prune-baseline", action="store_true",
                    help="fail (exit 2) when a baseline entry no longer "
                         "matches any finding — grandfathered debt may "
                         "only shrink")
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="emit one JSON object (stable schema) instead "
                         "of text")
    ap.add_argument("--root", default=None,
                    help="repo root (default: auto-detected)")
    ap.add_argument("--list-checkers", action="store_true",
                    help="print the checker catalog and exit")
    args = ap.parse_args(argv)

    checkers = engine.all_checkers()
    if args.list_checkers:
        for code in sorted(checkers):
            cls = checkers[code]
            print("%s  %-24s %s" % (code, cls.name,
                                    (cls.__doc__ or "").strip()
                                    .split("\n")[0]))
        return 0

    select = _codes(args.select)
    ignore = _codes(args.ignore)
    unknown = (select | ignore) - set(checkers) - {"MX000"}
    if unknown:
        print("mxlint: unknown code(s): %s (known: %s)"
              % (",".join(sorted(unknown)), ",".join(sorted(checkers))),
              file=sys.stderr)
        return 3

    root = os.path.abspath(args.root or engine.find_root(
        args.paths[0] if args.paths else os.getcwd()))
    paths = args.paths
    if not paths:
        paths = [os.path.join(root, "mxnet_tpu"),
                 os.path.join(root, "tools"),
                 os.path.join(root, "__graft_entry__.py"),
                 os.path.join(root, "chip_smoke.py"),
                 # the one test file that compiles for the chip
                 os.path.join(root, "tests", "test_tpu_compile.py")]
        import glob as _glob
        paths += sorted(_glob.glob(os.path.join(root, "bench*.py")))
        paths = [p for p in paths if os.path.exists(p)]
    missing = [p for p in paths if not os.path.exists(p)]
    if missing:
        print("mxlint: no such path: %s" % ", ".join(missing),
              file=sys.stderr)
        return 3

    findings, parse_errors = engine.run_paths(
        paths, root=root, select=select or None, ignore=ignore or None)

    baseline_path = args.baseline or engine.DEFAULT_BASELINE
    if args.write_baseline:
        payload = engine.write_baseline(baseline_path, findings)
        print("mxlint: wrote %d baseline entries (%d findings) to %s"
              % (len(payload["entries"]), len(findings),
                 os.path.relpath(baseline_path, root)))
        return 0

    baseline = {} if args.no_baseline \
        else engine.load_baseline(baseline_path)
    stale = engine.apply_baseline(findings, baseline)
    # a subset scan can't tell whether debt outside its paths was paid
    # — only report stale entries the scan actually covered
    scanned = [os.path.relpath(os.path.abspath(p), root)
               .replace(os.sep, "/") for p in paths]
    stale = {k: v for k, v in stale.items()
             if any(s in (".", k.split("::", 1)[0]) or
                    k.startswith(s + "/") for s in scanned)}

    if args.as_json:
        engine.emit_json(findings, parse_errors, stale)
    else:
        shown = [f for f in findings if not f.baselined] + parse_errors
        for f in shown:
            print(f.render())
        n_base = sum(1 for f in findings if f.baselined)
        tail = "mxlint: %d finding(s)" % len(shown)
        if n_base:
            tail += ", %d baselined" % n_base
        if stale:
            tail += ", %d STALE baseline entr%s" % (
                len(stale), "y" if len(stale) == 1 else "ies")
        print(tail)
        for key in sorted(stale):
            print("  stale baseline: %s (debt paid — remove the entry "
                  "or run --write-baseline)" % key)

    if args.prune_baseline and stale:
        return 2
    if any(not f.baselined for f in findings) or parse_errors:
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
