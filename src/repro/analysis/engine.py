"""Invariant-lint engine: the framework half of ``repro.analysis``.

The repo's concurrency and durability contracts (never block while holding a
lock, fsync-before-rename, declared fault sites, injected clocks, locked
stats mutation — see ``rules.py`` and the ROADMAP "Invariants as lint rules"
table) are enforced by an AST pass over ``src/``, gated by ``scripts/ci.sh``:

    python -m repro.analysis --check src/

Pieces:

* :class:`Rule` — one invariant, implemented as a visitor over a parsed
  module (:class:`ModuleContext` carries the tree, source lines, and path).
* suppression pragma — a finding on a line carrying
  ``# lint: ok(<rule>) — <reason>`` (same line or the line directly above)
  is a *deliberate exception*; the reason is mandatory, so every suppression
  documents itself. A pragma that matches no finding is itself reported
  (``unused-pragma``) so stale exceptions can't accumulate.
* baseline — grandfathered findings live in a committed JSON file
  (``analysis-baseline.json``). ``--check`` fails on any NEW finding *and*
  on any STALE baseline entry: the baseline can only drift by being
  regenerated (``--write-baseline``) in a reviewed commit.
* config — ``[tool.repro-analysis]`` in ``pyproject.toml`` (paths to scan,
  excluded seed-era dirs, baseline location, fault-registry module), read
  with ``tomllib``.
"""
from __future__ import annotations

import ast
import json
import re
import tomllib
from dataclasses import dataclass, field
from pathlib import Path
from typing import Iterable, Iterator, Sequence

__all__ = [
    "AnalysisConfig", "Engine", "Finding", "ModuleContext", "Rule",
    "load_config",
]

#: ``# lint: ok(<rules>) — <reason>`` (em-dash, double or single hyphen all
#: accepted as the reason separator; the reason itself is REQUIRED).
_PRAGMA_RE = re.compile(
    r"#\s*lint:\s*ok\(\s*(?P<rules>[a-z0-9_,\s-]+?)\s*\)"
    r"\s*(?:—|--|-)\s*(?P<reason>\S.*)")


@dataclass(frozen=True)
class Finding:
    """One rule violation at one source location."""

    rule: str
    path: str          # repo-relative, '/'-separated (stable across hosts)
    line: int          # 1-indexed
    message: str

    def key(self) -> str:
        """Identity used for baseline matching and dedup. Includes the
        message so two distinct findings on one line stay distinct."""
        return f"{self.path}:{self.line}:{self.rule}:{self.message}"

    def to_dict(self) -> dict:
        return {"rule": self.rule, "path": self.path, "line": self.line,
                "message": self.message}

    @classmethod
    def from_dict(cls, d: dict) -> "Finding":
        return cls(rule=str(d["rule"]), path=str(d["path"]),
                   line=int(d["line"]), message=str(d["message"]))

    def render(self) -> str:
        return f"{self.path}:{self.line}: [{self.rule}] {self.message}"


@dataclass
class ModuleContext:
    """Everything a rule gets to look at for one file."""

    path: str                    # repo-relative
    tree: ast.Module
    lines: list[str]             # raw source lines (0-indexed)
    config: "AnalysisConfig"

    def line_text(self, lineno: int) -> str:
        """1-indexed source line (empty string past EOF)."""
        if 1 <= lineno <= len(self.lines):
            return self.lines[lineno - 1]
        return ""


class Rule:
    """Base class for one invariant. Subclasses set ``id``/``doc`` and
    implement :meth:`check` yielding findings. ``doc`` is one line — it is
    what ``--list-rules`` prints and what the ROADMAP table cites."""

    id: str = ""
    doc: str = ""

    def check(self, ctx: ModuleContext) -> Iterator[Finding]:
        raise NotImplementedError

    def finding(self, ctx: ModuleContext, node: ast.AST, message: str
                ) -> Finding:
        return Finding(rule=self.id, path=ctx.path,
                       line=getattr(node, "lineno", 0), message=message)


@dataclass
class AnalysisConfig:
    """Scan configuration (see ``[tool.repro-analysis]`` in pyproject.toml)."""

    root: Path                       # repo root (pyproject.toml's directory)
    paths: list[str] = field(default_factory=lambda: ["src"])
    exclude: list[str] = field(default_factory=list)
    baseline: str = "analysis-baseline.json"
    #: module whose ``SITES`` mapping declares every legal fault site
    fault_registry: str = "src/repro/core/faults.py"
    #: module whose ``ComponentStats`` dataclass declares the stats fields
    stats_module: str = "src/repro/core/metrics.py"

    def baseline_path(self) -> Path:
        return self.root / self.baseline

    def is_excluded(self, rel_path: str) -> bool:
        rel = rel_path.replace("\\", "/")
        return any(rel == ex or rel.startswith(ex.rstrip("/") + "/")
                   for ex in self.exclude)


def load_config(root: str | Path | None = None) -> AnalysisConfig:
    """Read ``[tool.repro-analysis]`` from ``<root>/pyproject.toml``. With
    no ``root``, walk up from this file to the directory holding one (the
    repo checkout)."""
    if root is None:
        here = Path(__file__).resolve()
        for cand in here.parents:
            if (cand / "pyproject.toml").is_file():
                root = cand
                break
        else:                                    # pragma: no cover
            root = Path.cwd()
    root = Path(root)
    cfg = AnalysisConfig(root=root)
    pyproject = root / "pyproject.toml"
    if pyproject.is_file():
        data = (tomllib.loads(pyproject.read_text())
                .get("tool", {}).get("repro-analysis", {}))
        for key in ("paths", "exclude"):
            if key in data:
                setattr(cfg, key, list(data[key]))
        for key in ("baseline", "fault_registry", "stats_module"):
            if key in data:
                setattr(cfg, key, str(data[key]))
    return cfg


@dataclass
class PragmaIndex:
    """Per-file map of suppression pragmas: line -> (rules, reason).
    ``"*"`` in rules suppresses any rule on that line (discouraged; spell
    the rule out so the suppression survives rule renames loudly)."""

    by_line: dict[int, tuple[frozenset[str], str]]

    @classmethod
    def scan(cls, lines: Sequence[str]) -> "PragmaIndex":
        by_line: dict[int, tuple[frozenset[str], str]] = {}
        for i, text in enumerate(lines, start=1):
            m = _PRAGMA_RE.search(text)
            if not m:
                continue
            rules = frozenset(r.strip() for r in m.group("rules").split(",")
                              if r.strip())
            by_line[i] = (rules, m.group("reason").strip())
        return by_line and cls(by_line) or cls({})

    def suppresses(self, finding: Finding) -> bool:
        """A pragma applies to findings on its own line or the line directly
        below it (pragma-above style for lines with no trailing room)."""
        for line in (finding.line, finding.line - 1):
            entry = self.by_line.get(line)
            if entry and (finding.rule in entry[0] or "*" in entry[0]):
                return True
        return False

    def unused(self, findings: Iterable[Finding],
               all_raw: Iterable[Finding]) -> list[int]:
        """Pragma lines that matched no raw finding at all — stale
        suppressions that should be deleted."""
        hit: set[int] = set()
        for f in all_raw:
            for line in (f.line, f.line - 1):
                entry = self.by_line.get(line)
                if entry and (f.rule in entry[0] or "*" in entry[0]):
                    hit.add(line)
        return sorted(set(self.by_line) - hit)


@dataclass
class ScanResult:
    findings: list[Finding]          # post-suppression, pre-baseline
    suppressed: list[Finding]        # pragma'd deliberate exceptions
    unused_pragmas: list[tuple[str, int]]   # (path, line)
    files_scanned: int = 0
    scanned_paths: set[str] = field(default_factory=set)

    def partition_against(self, baseline: list[Finding]
                          ) -> tuple[list[Finding], list[Finding]]:
        """Split into (new findings, stale baseline entries). A baseline
        entry for a file OUTSIDE this scan (e.g. ``--check src/repro/core``
        with a baselined finding under ``checkpoint/``) is neither new nor
        stale — staleness is only judged for files actually rescanned."""
        current = {f.key() for f in self.findings}
        base = {f.key() for f in baseline}
        new = [f for f in self.findings if f.key() not in base]
        stale = [f for f in baseline
                 if f.path in self.scanned_paths and f.key() not in current]
        return new, stale


class Engine:
    """Runs every registered rule over every configured file."""

    def __init__(self, config: AnalysisConfig,
                 rules: Sequence[Rule] | None = None) -> None:
        self.config = config
        if rules is None:
            from .rules import default_rules
            rules = default_rules(config)
        self.rules = list(rules)

    # -- file discovery -------------------------------------------------------
    def iter_files(self, paths: Sequence[str] | None = None) -> Iterator[Path]:
        root = self.config.root
        for p in (paths or self.config.paths):
            target = (root / p) if not Path(p).is_absolute() else Path(p)
            if target.is_file() and target.suffix == ".py":
                yield target
                continue
            for f in sorted(target.rglob("*.py")):
                rel = f.relative_to(root).as_posix() if f.is_relative_to(root) \
                    else f.as_posix()
                if not self.config.is_excluded(rel):
                    yield f

    # -- scanning -------------------------------------------------------------
    def scan_file(self, path: Path) -> tuple[list[Finding], list[Finding],
                                             list[tuple[str, int]]]:
        src = path.read_text()
        rel = (path.relative_to(self.config.root).as_posix()
               if path.is_relative_to(self.config.root) else path.as_posix())
        try:
            tree = ast.parse(src, filename=rel)
        except SyntaxError as e:
            return ([Finding("syntax-error", rel, e.lineno or 0,
                             f"unparseable: {e.msg}")], [], [])
        lines = src.splitlines()
        ctx = ModuleContext(path=rel, tree=tree, lines=lines,
                            config=self.config)
        raw: list[Finding] = []
        for rule in self.rules:
            raw.extend(rule.check(ctx))
        pragmas = PragmaIndex.scan(lines)
        kept = [f for f in raw if not pragmas.suppresses(f)]
        suppressed = [f for f in raw if pragmas.suppresses(f)]
        unused = [(rel, line) for line in pragmas.unused(kept, raw)]
        return kept, suppressed, unused

    def scan(self, paths: Sequence[str] | None = None) -> ScanResult:
        findings: list[Finding] = []
        suppressed: list[Finding] = []
        unused: list[tuple[str, int]] = []
        n = 0
        seen: set[Path] = set()
        scanned: set[str] = set()
        for f in self.iter_files(paths):
            if f in seen:
                continue
            seen.add(f)
            n += 1
            scanned.add(f.relative_to(self.config.root).as_posix()
                        if f.is_relative_to(self.config.root) else f.as_posix())
            kept, supp, un = self.scan_file(f)
            findings.extend(kept)
            suppressed.extend(supp)
            unused.extend(un)
        findings.sort(key=lambda f: (f.path, f.line, f.rule))
        return ScanResult(findings=findings, suppressed=suppressed,
                          unused_pragmas=sorted(unused), files_scanned=n,
                          scanned_paths=scanned)

    # -- baseline -------------------------------------------------------------
    def load_baseline(self) -> list[Finding]:
        path = self.config.baseline_path()
        if not path.is_file():
            return []
        data = json.loads(path.read_text())
        return [Finding.from_dict(d) for d in data.get("findings", [])]

    def write_baseline(self, result: ScanResult) -> Path:
        path = self.config.baseline_path()
        payload = {
            "comment": ("Grandfathered findings. Regenerate ONLY via "
                        "`python -m repro.analysis --write-baseline` in a "
                        "reviewed commit; ci.sh fails on any drift."),
            "findings": [f.to_dict() for f in result.findings],
        }
        path.write_text(json.dumps(payload, indent=2, sort_keys=True) + "\n")
        return path
