"""edl-lint CLI: run the static-analysis plane over the repo.

    python -m tools.edl_lint                         # human output
    python -m tools.edl_lint --json                  # machine output
    python -m tools.edl_lint --baseline .edl_lint_baseline.json
    python -m tools.edl_lint --only lock-discipline --only atomic-write
    python -m tools.edl_lint --changed               # git-diff-scoped (<1s)
    python -m tools.edl_lint --write-baseline        # (re)accept findings
    python -m tools.edl_lint --write-knob-catalogue  # regen DESIGN.md table
    python -m tools.edl_lint --write-protocol-catalogue  # regen wire table

Exit codes: 0 = clean against the baseline (stale baseline entries are
reported but don't fail), 1 = new findings, 2 = usage/runtime error.
The tier-1 suite runs this with the committed baseline, so a new
finding fails CI until it is fixed or deliberately baselined with a
tracking note.
"""

from __future__ import annotations

import argparse
import json
import subprocess
import sys
import time
from pathlib import Path

from edl_tpu.analysis import (
    PASS_REGISTRY,
    build_context,
    diff_baseline,
    generate_knob_catalogue,
    load_baseline,
    run_analysis,
    write_baseline,
)
from edl_tpu.analysis.catalogue import KNOB_BEGIN, KNOB_END, extract_knob_block
from edl_tpu.analysis.protocol import (
    WIRE_BEGIN, WIRE_END, extract_wire_block, generate_wire_catalogue,
)

_DEFAULT_PATHS = ("edl_tpu", "tools")


def _repo_root() -> Path:
    return Path(__file__).resolve().parent.parent


def _rewrite_block(root: Path, generate, extract, begin, end) -> bool:
    """Regenerate one marker-delimited generated table in DESIGN.md in
    place; returns True when the file changed."""
    design = Path(root, "DESIGN.md")
    text = design.read_text()
    block = extract(text)
    if block is None:
        raise SystemExit(
            "DESIGN.md has no %s … %s markers; add them where the "
            "generated catalogue should live" % (begin, end)
        )
    generated = generate()
    if block == generated:
        return False
    design.write_text(text.replace(block, generated, 1))
    return True


def rewrite_knob_catalogue(root: Path, ctx) -> bool:
    return _rewrite_block(
        root, lambda: generate_knob_catalogue(ctx), extract_knob_block,
        KNOB_BEGIN, KNOB_END,
    )


def rewrite_wire_catalogue(root: Path, ctx) -> bool:
    return _rewrite_block(
        root, lambda: generate_wire_catalogue(ctx), extract_wire_block,
        WIRE_BEGIN, WIRE_END,
    )


def changed_paths(root: Path, subpaths) -> list:
    """Git-changed .py files (worktree+index vs HEAD, plus untracked)
    under the analyzed subtrees — the pre-commit fast path. Raises
    ``RuntimeError`` when git is unavailable (the CLI maps it to exit
    2: silently analyzing nothing must not read as "clean")."""
    try:
        diff = subprocess.run(
            ["git", "-C", str(root), "diff", "--name-only", "HEAD", "--"],
            capture_output=True, text=True, timeout=30,
        )
        untracked = subprocess.run(
            ["git", "-C", str(root), "ls-files", "--others",
             "--exclude-standard"],
            capture_output=True, text=True, timeout=30,
        )
    except (OSError, subprocess.SubprocessError) as exc:
        raise RuntimeError("git unavailable for --changed: %s" % exc)
    if diff.returncode != 0:
        raise RuntimeError(
            "git diff failed for --changed: %s" % diff.stderr.strip()
        )
    if untracked.returncode != 0:
        # brand-new files are the likeliest carriers of new findings;
        # silently dropping them must not read as "clean"
        raise RuntimeError(
            "git ls-files failed for --changed: %s"
            % untracked.stderr.strip()
        )
    names = set(diff.stdout.splitlines()) | set(untracked.stdout.splitlines())
    out = []
    for name in sorted(names):
        if not name.endswith(".py"):
            continue
        if not any(
            name == sub or name.startswith(sub.rstrip("/") + "/")
            for sub in subpaths
        ):
            continue
        if (root / name).exists():  # deleted files have nothing to parse
            out.append(name)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="edl-lint",
        description="AST static analysis for concurrency, durability, "
        "jit-purity and catalogue invariants",
    )
    ap.add_argument(
        "paths", nargs="*", default=None,
        help="subpaths to analyze (default: edl_tpu tools)",
    )
    ap.add_argument("--root", default=None, help="repo root (default: auto)")
    ap.add_argument("--json", action="store_true", dest="as_json")
    ap.add_argument(
        "--baseline", default=None,
        help="baseline file; findings present in it don't fail the run",
    )
    ap.add_argument(
        "--only", action="append", default=None, metavar="PASS",
        help="run only the named pass (repeatable)",
    )
    ap.add_argument(
        "--write-baseline", action="store_true",
        help="write all current findings to --baseline (keeps notes)",
    )
    ap.add_argument(
        "--write-knob-catalogue", action="store_true",
        help="regenerate the EDL_* knob table in DESIGN.md",
    )
    ap.add_argument(
        "--write-protocol-catalogue", action="store_true",
        help="regenerate the wire-protocol op table in DESIGN.md",
    )
    ap.add_argument(
        "--changed", action="store_true",
        help="narrow analysis to git-changed .py files (vs HEAD, plus "
        "untracked) under the analyzed paths — the pre-commit fast path",
    )
    ap.add_argument(
        "--compact", action="store_true",
        help="with --json: single-line output (for suite archiving)",
    )
    ap.add_argument("--list-passes", action="store_true")
    args = ap.parse_args(argv)

    root = Path(args.root) if args.root else _repo_root()
    if args.only:
        unknown = [n for n in args.only if n not in PASS_REGISTRY]
        # registry fills lazily; import the pass modules for validation
        if unknown:
            from edl_tpu.analysis import (  # noqa: F401
                blocking, blockunder, catalogue, donation, durability,
                locks, lockorder, protocol, purity,
            )
            unknown = [n for n in args.only if n not in PASS_REGISTRY]
        if unknown:
            ap.error("unknown pass(es): %s (see --list-passes)"
                     % ", ".join(unknown))

    if args.list_passes:
        from edl_tpu.analysis import (  # noqa: F401
            blocking, blockunder, catalogue, donation, durability,
            locks, lockorder, protocol, purity,
        )
        for name, p in sorted(PASS_REGISTRY.items()):
            print("%-18s %s" % (name, p.description))
        return 0

    t0, cpu0 = time.time(), time.process_time()
    subpaths = tuple(args.paths) if args.paths else _DEFAULT_PATHS
    if args.changed:
        if args.paths:
            ap.error("--changed and explicit paths are mutually exclusive")
        if args.write_knob_catalogue or args.write_protocol_catalogue:
            # a narrowed context would silently truncate the committed
            # DESIGN.md table to the changed-file subset
            ap.error("--changed cannot regenerate DESIGN.md catalogues; "
                     "run the --write-* flags without --changed")
        try:
            narrowed = changed_paths(root, _DEFAULT_PATHS)
        except RuntimeError as exc:
            print("edl-lint: %s" % exc, file=sys.stderr)
            return 2
        if not narrowed:
            print("edl-lint: no changed python files under %s — nothing "
                  "to analyze" % "/".join(_DEFAULT_PATHS))
            return 0
        subpaths = tuple(narrowed)
    try:
        ctx = build_context(root, subpaths)
    except FileNotFoundError as exc:
        print("edl-lint: %s" % exc, file=sys.stderr)
        return 2

    if args.write_knob_catalogue or args.write_protocol_catalogue:
        # a --changed / path-narrowed context has not seen every read
        # or op site; regenerating from it would silently truncate the
        # committed catalogue to the narrowed subset
        from edl_tpu.analysis.catalogue import _covers_default_scope

        if not _covers_default_scope(ctx):
            ap.error(
                "--write-knob-catalogue/--write-protocol-catalogue need "
                "the full default scope; drop --changed/path arguments"
            )

    if args.write_knob_catalogue:
        changed = rewrite_knob_catalogue(root, ctx)
        print("knob catalogue %s" % ("updated" if changed else "up to date"))
        ctx = build_context(root, subpaths)  # re-read DESIGN.md
    if args.write_protocol_catalogue:
        changed = rewrite_wire_catalogue(root, ctx)
        print("wire-protocol catalogue %s"
              % ("updated" if changed else "up to date"))
        ctx = build_context(root, subpaths)  # re-read DESIGN.md

    findings, counts = run_analysis(ctx, only=args.only)
    baseline = load_baseline(args.baseline) if args.baseline else {}
    new, old, stale = diff_baseline(findings, baseline)
    # entries of passes that did not run (--only) or in files outside
    # the analyzed paths were neither confirmed nor refuted: they are
    # not stale and must not expire. (DESIGN.md-anchored findings count
    # as checked whenever their pass ran — it is always read.)
    ran = set(counts) | {"parse"}

    # cross-file conclusions are scope-gated inside their passes: a
    # narrowed run never re-evaluated them, so their baseline entries
    # must be kept, not expired (a --changed --write-baseline would
    # otherwise silently drop an accepted wire-protocol drift/unsent
    # entry and the next full run would fail it as NEW)
    from edl_tpu.analysis.catalogue import _covers_default_scope

    full_scope = _covers_default_scope(ctx)
    _SCOPE_GATED = {
        "wire-protocol": ("unhandled:", "unsent:", "frame-undecoded:",
                          "uncatalogued:", "stale-row:", "drift", "markers"),
        "env-registry": ("stale:", "drift", "markers"),
    }

    def _unchecked_key(k: str) -> bool:
        parts = k.split(":", 2)
        if parts[0] not in ran:
            return True
        if (
            not full_scope
            and parts[0] in _SCOPE_GATED
            and len(parts) > 2
            and parts[2].startswith(_SCOPE_GATED[parts[0]])
        ):
            return True
        return len(parts) > 1 and parts[1] != "DESIGN.md" and (
            parts[1] not in ctx.by_path
        )

    unchecked = {k: v for k, v in baseline.items() if _unchecked_key(k)}
    stale = [k for k in stale if k not in unchecked]

    if args.write_baseline:
        if not args.baseline:
            ap.error("--write-baseline requires --baseline")
        entries = write_baseline(
            args.baseline, findings, notes=baseline, keep=unchecked,
        )
        print("baseline written: %d entries (%d were new, %d expired, "
              "%d unchecked kept)"
              % (len(entries), len(new), len(stale), len(unchecked)))
        return 0

    # wall, and this process's own CPU: what a budget can hold whoever
    # shares the host
    elapsed, cpu = time.time() - t0, time.process_time() - cpu0
    if args.as_json:
        new_by_pass = {}
        for f in new:
            new_by_pass[f.pass_name] = new_by_pass.get(f.pass_name, 0) + 1
        doc = {
            "version": 1,
            "root": str(root),
            "paths": list(subpaths),
            "seconds": round(elapsed, 3),
            "cpu_seconds": round(cpu, 3),
            "passes": [
                {
                    "name": name,
                    "description": PASS_REGISTRY[name].description,
                    "findings": counts.get(name, 0),
                    "new": new_by_pass.get(name, 0),
                    "status": (
                        "fail" if new_by_pass.get(name, 0) else "pass"
                    ),
                    # one-line per-pass summary
                    "line": "%s: %s — %d finding(s), %d new" % (
                        name,
                        "FAIL" if new_by_pass.get(name, 0) else "PASS",
                        counts.get(name, 0), new_by_pass.get(name, 0),
                    ),
                }
                for name in sorted(counts)
            ],
            "findings": [
                dict(f.to_dict(), new=(f.key not in baseline))
                for f in findings
            ],
            "summary": {
                "total": len(findings),
                "new": len(new),
                "baselined": len(old),
                "stale_baseline_keys": stale,
            },
        }
        if args.compact:
            doc.pop("findings")
            doc["findings_new"] = [f.key for f in new]
            print(json.dumps(doc, sort_keys=True))
        else:
            print(json.dumps(doc, indent=1))
    else:
        for f in findings:
            tag = "NEW " if f.key not in baseline else "    "
            print("%s%s" % (tag, f))
        for key in stale:
            print("STALE baseline entry (no longer found): %s" % key)
        print(
            "edl-lint: %d finding(s) — %d new, %d baselined, %d stale "
            "baseline entr%s — %d pass(es) in %.1fs" % (
                len(findings), len(new), len(old), len(stale),
                "y" if len(stale) == 1 else "ies", len(counts), elapsed,
            )
        )
    return 1 if new else 0


if __name__ == "__main__":
    sys.exit(main())
