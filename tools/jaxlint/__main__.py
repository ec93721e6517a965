"""jaxlint orchestrator: `python -m tools.jaxlint [roots...] [flags]`.

Run order per invocation:
1. read every file once, hash it, consult the incremental cache;
2. per-file passes (J001-J017, J999) on cache misses only;
3. whole-program passes (J018-J020) over the shared ProgramIndex —
   skipped entirely when the tree digest matches the cached one;
4. suppression filtering LAST, so the hygiene pass (J021/J000) sees
   which suppressions actually cover a live finding.

Flags: --json (machine-readable findings), --changed (report only
files differing from git HEAD), --no-cache, --budget SECONDS (fail if
the run exceeds the wall-clock budget), --check-index (print the check
inventory and exit — docs/static-analysis.md embeds this table).

Exit code: min(number of findings, 125); 99 on budget breach.
"""

from __future__ import annotations

import argparse
import json
import subprocess
import sys
import time
from pathlib import Path

from tools.jaxlint import concurrency, hygiene, registry
from tools.jaxlint.base import Finding, Suppressions
from tools.jaxlint.cache import LintCache, file_digest, tree_digest
from tools.jaxlint.perfile import parse_file, run_perfile
from tools.jaxlint.program import ProgramIndex, module_name
from tools.lint import iter_py_files

DEFAULT_ROOTS = [
    # tests/ are deliberately out of the default roots: test corpora seed
    # the very defects this gate rejects (tests/test_jaxlint.py)
    "horaedb_tpu", "benchmarks", "tools",
    "bench.py", "chip_smoke.py", "__graft_entry__.py",
]
HYGIENE_CODES = {"J000", "J021", "J999"}  # never suppressible


def _changed_paths() -> set[str] | None:
    """Absolute posix paths of files differing from HEAD (tracked diff
    + untracked); None when git is unavailable."""
    out: set[str] = set()
    for cmd in (
        ["git", "diff", "--name-only", "HEAD"],
        ["git", "ls-files", "--others", "--exclude-standard"],
    ):
        try:
            r = subprocess.run(cmd, capture_output=True, text=True,
                               timeout=30)
        except (OSError, subprocess.TimeoutExpired):
            return None
        if r.returncode != 0:
            return None
        for line in r.stdout.splitlines():
            if line.strip():
                out.add(Path(line.strip()).resolve().as_posix())
    return out


def run(argv: list[str]) -> int:
    ap = argparse.ArgumentParser(
        prog="jaxlint", description="domain-aware lint gate")
    ap.add_argument("roots", nargs="*", default=None)
    ap.add_argument("--json", action="store_true", dest="as_json")
    ap.add_argument("--changed", action="store_true",
                    help="report findings only for files differing "
                         "from git HEAD (analysis still sees the whole "
                         "tree so graph passes stay sound)")
    ap.add_argument("--no-cache", action="store_true")
    ap.add_argument("--budget", type=float, default=None,
                    metavar="SECONDS",
                    help="fail (exit 99) if the run takes longer")
    ap.add_argument("--check-index", action="store_true",
                    help="print the check inventory and exit")
    args = ap.parse_args(argv)

    if args.check_index:
        if args.as_json:
            print(json.dumps(registry.check_index_json(), indent=2))
        else:
            print(registry.check_index_markdown())
        return 0

    t0 = time.monotonic()
    explicit_roots = bool(args.roots)
    files = iter_py_files(args.roots or DEFAULT_ROOTS)

    cache = None
    if not args.no_cache:
        cache = LintCache(registry.inventory_digest())
        cache.load()

    # ---- pass 1: read + hash + per-file passes (cached) --------------
    digests: dict[str, str] = {}
    texts: dict[str, str] = {}
    trees: dict[str, object] = {}       # parsed ASTs (cache misses only)
    perfile_raw: dict[str, list[Finding]] = {}
    sups: dict[str, Suppressions] = {}
    for f in files:
        posix = f.as_posix()
        data = f.read_bytes()
        digests[posix] = file_digest(data)
        cached = cache.get_file(posix, digests[posix]) if cache else None
        if cached is not None:
            perfile_raw[posix], sups[posix] = cached
            continue
        text, tree, syntax = parse_file(f)
        texts[posix] = text
        if syntax is not None:
            perfile_raw[posix] = [syntax]
            sups[posix] = Suppressions(text.split("\n"))
        else:
            trees[posix] = tree
            perfile_raw[posix], sups[posix] = run_perfile(f, text, tree)
        if cache:
            cache.put_file(posix, digests[posix], perfile_raw[posix],
                           sups[posix])

    # ---- pass 2: whole-program passes (tree-digest cached) -----------
    tdigest = tree_digest(digests)
    graph = cache.get_tree(tdigest) if cache else None
    if graph is None:
        index = ProgramIndex()
        for f in files:
            posix = f.as_posix()
            if module_name(f) is None:
                continue
            tree = trees.get(posix)
            if tree is None:
                _, tree, syntax = parse_file(f)
                if syntax is not None:
                    continue
            index.add_file(f, tree)
        index.finish()
        graph = {}
        for pass_fn in (concurrency.check_event_loop_blocking,
                        concurrency.check_lock_order,
                        concurrency.check_deadline_propagation):
            for posix, fs in pass_fn(index).items():
                graph.setdefault(posix, []).extend(fs)
        if cache:
            cache.put_tree(tdigest, graph)

    # ---- pass 3: suppression filter + hygiene ------------------------
    changed = _changed_paths() if args.changed else None
    report: list[tuple[str, Finding]] = []
    for f in files:
        posix = f.as_posix()
        raw = perfile_raw[posix] + graph.get(posix, [])
        sup = sups[posix]
        final = [x for x in raw
                 if x.code in HYGIENE_CODES
                 or not sup.covers(x.lineno, x.code)]
        final += hygiene.check_suppression_hygiene(sup, raw)
        if changed is not None and \
                f.resolve().as_posix() not in changed:
            continue
        for x in sorted(final, key=lambda x: (x.lineno, x.code)):
            report.append((str(f), x))

    if cache:
        if not explicit_roots:
            cache.prune(set(digests))
        cache.save()

    elapsed = time.monotonic() - t0
    n = len(report)
    if args.as_json:
        print(json.dumps({
            "findings": [
                {"path": p, "line": x.lineno, "code": x.code,
                 "msg": x.msg} for p, x in report
            ],
            "files": len(files),
            "count": n,
            "elapsed_s": round(elapsed, 3),
        }, indent=2))
    else:
        for p, x in report:
            print(f"{p}:{x.lineno}: {x.code} {x.msg}")
        print(f"jaxlint: {n} finding(s) in {len(files)} files")
    if args.budget is not None and elapsed > args.budget:
        print(f"jaxlint: budget exceeded: {elapsed:.2f}s > "
              f"{args.budget:.2f}s", file=sys.stderr)
        return 99
    return min(n, 125)


if __name__ == "__main__":
    raise SystemExit(run(sys.argv[1:]))
