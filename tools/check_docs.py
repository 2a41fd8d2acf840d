"""Doc-sync check: code references in the docs must resolve at HEAD.

Docs rot silently: a refactor renames `ColumnScheduler.rebalance` or moves
`serve/stream.py` and every prose reference to it keeps reading fine while
pointing at nothing. This checker makes the references load-bearing — the
lint job (and `tests/test_docs.py`) fails when any of them breaks.

What counts as a reference (extracted from backticked spans and markdown
link targets in `docs/*.md` and `README.md`):

* repo file paths with a checked suffix (`.py`, `.md`, `.yml`, `.toml`) —
  resolved against the repo root and, for source paths written without
  the `src/` prefix (e.g. `serve/stream.py`), against `src/repro/`;
  directory references ending in `/` are checked as directories;
* `path.py:symbol` anchors (e.g. `serve/stream.py:BiosignalStream` or
  `serve/stream.py:StreamTelemetry.record_retire`) — the file must exist
  AND define the symbol: a `class`/`def` of that name at any nesting, or
  a `name = ...` / `name: ...` binding; dotted `Cls.member` requires the
  class and the member definition.

Artifact names (`BENCH_*.json`), URLs, and glob patterns are ignored.

Usage: ``python tools/check_docs.py [files-or-dirs...]`` (default:
``docs`` and ``README.md``). Exits 1 with one line per broken reference.

On a default (argument-less) run the docs in `REQUIRED` must be among
the checked set — the authoring guide `docs/STAGE_GRAPHS.md` in
particular is load-bearing for the stage-graph layer, so deleting or
renaming it fails the check instead of silently shrinking coverage.
"""
from __future__ import annotations

import re
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
CHECKED_SUFFIXES = (".py", ".md", ".yml", ".toml")

# docs that MUST exist and be checked on a default run (see module
# docstring) — extend this when a new doc becomes load-bearing
REQUIRED = ("README.md", "docs/ARCHITECTURE.md", "docs/BENCHMARKS.md",
            "docs/STAGE_GRAPHS.md")

# a repo-looking path, optionally with a :symbol anchor (only for .py)
_PATH_RE = re.compile(
    r"(?P<path>[A-Za-z0-9_][A-Za-z0-9_./-]*"
    r"(?:\.(?:py|md|yml|toml)|/))"
    r"(?::(?P<sym>[A-Za-z_][A-Za-z0-9_.]*))?$")
_BACKTICK_RE = re.compile(r"`([^`\n]+)`")
_LINK_RE = re.compile(r"\]\(([^)#\s]+)\)")


def resolve(path: str, doc: Path) -> Path | None:
    """Resolve a doc reference to a real file/dir, trying (in order) the
    repo root, the `src/repro/` source prefix, and the doc's own
    directory (relative markdown links)."""
    for base in (ROOT, ROOT / "src", ROOT / "src" / "repro", doc.parent):
        p = base / path
        if p.exists():
            return p
    return None


def symbol_defined(src: str, sym: str) -> bool:
    """True when `sym` is defined in the module text: a class/def at any
    nesting, or a `name = ...` / `name: ...` binding (module constants,
    dataclass fields). Dotted `Cls.member` needs the class AND a member
    definition."""
    def has(name: str) -> bool:
        n = re.escape(name)
        return re.search(
            rf"(?m)^\s*(?:(?:class|def)\s+{n}\b|{n}\s*[:=])",
            src) is not None

    parts = sym.split(".")
    return all(has(p) for p in parts)


def check_file(doc: Path) -> list[str]:
    text = doc.read_text()
    refs: set[tuple[str, str | None]] = set()
    for span in _BACKTICK_RE.findall(text):
        span = span.strip()
        if "*" in span or "://" in span or " " in span:
            continue
        m = _PATH_RE.match(span)
        if m:
            refs.add((m.group("path"), m.group("sym")))
    for target in _LINK_RE.findall(text):
        if "://" in target or "*" in target:
            continue
        if target.endswith(CHECKED_SUFFIXES) or target.endswith("/"):
            refs.add((target, None))
    errors = []
    rel = doc.relative_to(ROOT) if doc.is_relative_to(ROOT) else doc
    for path, sym in sorted(refs, key=lambda r: (r[0], r[1] or "")):
        resolved = resolve(path, doc)
        if resolved is None:
            errors.append(f"{rel}: broken file reference `{path}`")
            continue
        if sym is not None:
            if not resolved.suffix == ".py":
                errors.append(f"{rel}: symbol anchor on non-Python file "
                              f"`{path}:{sym}`")
            elif not symbol_defined(resolved.read_text(), sym):
                errors.append(f"{rel}: `{path}` does not define `{sym}`")
    return errors


def main(argv: list[str]) -> int:
    targets = argv or ["docs", "README.md"]
    docs: list[Path] = []
    for t in targets:
        p = (ROOT / t) if not Path(t).is_absolute() else Path(t)
        if p.is_dir():
            docs += sorted(p.glob("*.md"))
        elif p.exists():
            docs.append(p)
        else:
            print(f"check_docs: no such file or directory: {t}",
                  file=sys.stderr)
            return 2
    errors = []
    if not argv:
        rels = {str(d.relative_to(ROOT)) for d in docs
                if d.is_relative_to(ROOT)}
        errors += [f"required doc missing from tree: {r}"
                   for r in REQUIRED if r not in rels]
    for doc in docs:
        errors += check_file(doc)
    for e in errors:
        print(f"check_docs: {e}", file=sys.stderr)
    if errors:
        print(f"check_docs: {len(errors)} broken reference(s) across "
              f"{len(docs)} file(s)", file=sys.stderr)
        return 1
    print(f"check_docs: ok ({len(docs)} doc file(s), all code references "
          f"resolve)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
