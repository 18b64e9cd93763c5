#!/usr/bin/env python
"""dslint — the unified static-analysis pass (r11 tentpole).

Runs every registered checker (determinism, crash-transparency,
fault-sites, event-registry, atomic-write, kv-lifetime, state-machine) in
one AST walk per file and exits non-zero on any unsuppressed finding.
Deterministic: two identical runs produce byte-identical output (``--json``
asserted in tier-1, tests/unit/test_dslint.py).

    python scripts/dslint.py deepspeed_tpu scripts            # the tier-1 run
    python scripts/dslint.py --json deepspeed_tpu scripts
    python scripts/dslint.py --list-checkers
    python scripts/dslint.py --checkers determinism path/to/file.py

Suppression: ``# dslint-ok(<checker>): <reason>`` on the flagged line —
the reason is mandatory (checker catalog + syntax: docs/ANALYSIS.md).

The ``analysis`` package is imported standalone (the ``deepspeed_tpu/``
directory itself goes on ``sys.path``) so dslint never imports jax and the
full-repo run stays well inside its 5-second budget.
"""

import argparse
import os
import sys

REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def load_analysis(root: str = REPO_ROOT):
    """Import ``deepspeed_tpu/analysis`` as the top-level package
    ``analysis`` — skipping deepspeed_tpu/__init__ (jax, ~seconds)."""
    pkg_dir = os.path.join(root, "deepspeed_tpu")
    if pkg_dir not in sys.path:
        sys.path.insert(0, pkg_dir)
    import analysis
    return analysis


def run_dslint(paths, root=REPO_ROOT, checkers=None, use_cache=False):
    """Programmatic entry (the tier-1 test and the atomic-write shim use
    this): returns the populated ``analysis.core.Runner`` — or, on a warm
    ``use_cache=True`` hit, an ``analysis.cache.CachedResult`` with the
    identical output surface (same ``--json`` bytes; see
    analysis/cache.py for the conservative invalidation stance)."""
    analysis = load_analysis()
    everything = analysis.all_checkers()
    selected = everything
    if checkers is not None:
        wanted = set(checkers)
        unknown = sorted(wanted - {c.name for c in everything})
        if unknown:
            # a typo'd --checkers must not silently lint nothing and pass
            raise ValueError(
                f"unknown checker(s): {', '.join(unknown)} "
                f"(known: {', '.join(sorted(c.name for c in everything))})")
        selected = [c for c in everything if c.name in wanted]
    cache = key = hashes = None
    if use_cache:
        from analysis.cache import DslintCache
        names = [c.name for c in selected]
        cache = DslintCache(root)
        files = analysis.core.collect_files(
            [p if os.path.isabs(p) else os.path.join(root, p)
             for p in paths], root)
        hashes = cache.file_hashes(files)
        key = cache.scan_key(names, hashes)
        rec = cache.lookup(key, hashes)
        if rec is not None:
            return cache.result_of(rec)
    runner = analysis.Runner(root, selected,
                             known_checker_names=[c.name for c in everything])
    runner.run(paths)
    if cache is not None:
        cache.store(key, [c.name for c in selected], hashes, runner.files,
                    runner.findings, runner.suppressed_count)
    return runner


def main() -> int:
    ap = argparse.ArgumentParser(
        prog="dslint", description="unified static-analysis pass")
    ap.add_argument("paths", nargs="*", default=None,
                    help="files/directories to scan (default: deepspeed_tpu scripts)")
    ap.add_argument("--root", default=REPO_ROOT,
                    help="repo root findings are reported relative to")
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="machine-readable deterministic output")
    ap.add_argument("--checkers", default=None,
                    help="comma-separated subset of checkers to run")
    ap.add_argument("--list-checkers", action="store_true")
    ap.add_argument("--sync-state-machines", action="store_true",
                    help="regenerate docs/STATE_MACHINES.md from the "
                         "declared transition tables, then exit")
    ap.add_argument("--no-cache", action="store_true",
                    help="bypass the .dslint_cache/ incremental cache "
                         "(reads and writes)")
    args = ap.parse_args()

    analysis = load_analysis()
    if args.list_checkers:
        for c in analysis.all_checkers():
            print(f"{c.name:30s} {c.description}")
        return 0
    if args.sync_state_machines:
        root = os.path.abspath(args.root)
        runner = run_dslint(args.paths or ["deepspeed_tpu", "scripts"],
                            root=root, checkers=["state-machine"])
        sm = next(c for c in runner.checkers if c.name == "state-machine")
        print(f"wrote {sm.sync_doc(root)}")
        return 0

    paths = args.paths or ["deepspeed_tpu", "scripts"]
    checkers = args.checkers.split(",") if args.checkers else None
    try:
        runner = run_dslint(paths, root=os.path.abspath(args.root),
                            checkers=checkers,
                            use_cache=not args.no_cache)
    except ValueError as e:
        print(f"dslint: error: {e}", file=sys.stderr)
        return 2
    if args.as_json:
        sys.stdout.write(runner.to_json())
    else:
        for f in runner.findings:
            print(f.human())
        print(runner.summary())
    return 1 if runner.findings else 0


if __name__ == "__main__":
    sys.exit(main())
