"""tmlint core: file model, rule registry, pragmas, baseline, driver.

Everything here is deliberately boring: parse each file once with `ast`,
hand the whole-project view to every registered rule, subtract pragma'd
and baselined findings, emit `path:line RULE message` sorted. Rules are
pure functions of the Project, so two runs over the same tree produce
byte-identical output (tests/test_lint.py pins that).
"""

from __future__ import annotations

import ast
import io
import os
import re
import subprocess
import tokenize
from dataclasses import dataclass

# Directories never scanned (caches, VCS innards).
_SKIP_DIRS = {"__pycache__", ".git", ".claude", ".pytest_cache"}

# The ONE default scan set (CLI, __graft_entry__.lint_gate, the tier-1
# gate in tests/test_lint.py all import this — hand-copied lists drift).
DEFAULT_PATHS = ["tendermint_tpu", "tools", "tests",
                 "bench.py", "chip_smoke.py", "__graft_entry__.py"]

# Paths (relative, '/'-separated) treated as *production* code: the
# concurrency/device rules apply here. Tests may spawn bare threads and
# poke device arrays on purpose; the registry/parity rules still scan them.
_PROD_PREFIX = "tendermint_tpu/"

_PRAGMA_RE = re.compile(
    r"#\s*tmlint:\s*(?P<kind>disable-file|disable)\s*=\s*"
    r"(?P<rules>[A-Za-z0-9_,\- ]+)")


@dataclass(frozen=True, order=True)
class Finding:
    path: str      # repo-relative, forward slashes
    line: int
    rule: str
    message: str

    def render(self) -> str:
        return f"{self.path}:{self.line} {self.rule} {self.message}"

    def key(self) -> tuple[str, str, str]:
        """Baseline identity: line numbers drift, (rule, path, message)
        pins the finding."""
        return (self.rule, self.path, self.message)


class SourceFile:
    """One parsed file: AST + raw lines + its tmlint pragmas."""

    def __init__(self, root: str, relpath: str):
        self.path = relpath.replace(os.sep, "/")
        with open(os.path.join(root, relpath), "r", encoding="utf-8",
                  errors="replace") as fh:
            self.text = fh.read()
        self.lines = self.text.splitlines()
        self.tree: ast.Module | None = None
        self.parse_error: SyntaxError | None = None
        try:
            self.tree = ast.parse(self.text, filename=self.path)
        except SyntaxError as e:  # surfaced as its own finding by run_rules
            self.parse_error = e
        # pragma maps: line -> set of rule names (or {"*"}), plus file-wide.
        # Only real COMMENT tokens count — a pragma-shaped string literal
        # (a lint test fixture, a doc snippet) must never register a live
        # suppression.
        self._line_pragmas: dict[int, set[str]] = {}
        self._file_pragmas: set[str] = set()
        if "tmlint:" not in self.text:
            return  # cheap pre-filter: tokenizing ~200 pragma-free files
            # would double the scan time for nothing
        try:
            tokens = list(tokenize.generate_tokens(
                io.StringIO(self.text).readline))
        except (tokenize.TokenError, SyntaxError, ValueError,
                IndentationError):
            tokens = []
        for tok in tokens:
            if tok.type != tokenize.COMMENT:
                continue
            m = _PRAGMA_RE.search(tok.string)
            if not m:
                continue
            rules = {r.strip() for r in m.group("rules").split(",") if r.strip()}
            if m.group("kind") == "disable-file":
                self._file_pragmas |= rules
            else:
                self._line_pragmas[tok.start[0]] = rules

    def suppressed(self, line: int, rule: str) -> bool:
        """A pragma suppresses findings on its own line or the line below
        (so it can sit above a long statement)."""
        if rule in self._file_pragmas or "*" in self._file_pragmas:
            return True
        for at in (line, line - 1):
            rules = self._line_pragmas.get(at)
            if rules and (rule in rules or "*" in rules):
                return True
        return False


class Project:
    """The whole scanned tree, plus the repo root for side files
    (docs/CONFIG.md, docs/FAULTS.md) rules cross-check against."""

    def __init__(self, root: str, files: list[SourceFile]):
        self.root = root
        self.files = sorted(files, key=lambda f: f.path)
        self._by_path = {f.path: f for f in self.files}

    def file(self, path: str) -> SourceFile | None:
        return self._by_path.get(path)

    def prod_files(self) -> list[SourceFile]:
        return [f for f in self.files
                if f.path.startswith(_PROD_PREFIX) and f.tree is not None]

    def read_side_file(self, relpath: str) -> str | None:
        try:
            with open(os.path.join(self.root, relpath), "r",
                      encoding="utf-8", errors="replace") as fh:
                return fh.read()
        except OSError:
            return None


def collect_files(root: str, paths: list[str]) -> list[SourceFile]:
    out: list[SourceFile] = []
    seen: set[str] = set()
    for p in paths:
        abspath = os.path.join(root, p)
        if os.path.isfile(abspath):
            if p.endswith(".py") and p not in seen:
                seen.add(p)
                out.append(SourceFile(root, p))
            continue
        for dirpath, dirnames, filenames in os.walk(abspath):
            dirnames[:] = sorted(d for d in dirnames if d not in _SKIP_DIRS)
            for fn in sorted(filenames):
                if not fn.endswith(".py"):
                    continue
                rel = os.path.relpath(os.path.join(dirpath, fn), root)
                rel = rel.replace(os.sep, "/")
                if rel not in seen:
                    seen.add(rel)
                    out.append(SourceFile(root, rel))
    return out


# --- rule registry ----------------------------------------------------------

# name -> (fn(project) -> list[Finding], one-line doc)
RULES: dict[str, tuple] = {}


def rule(name: str, doc: str):
    def deco(fn):
        RULES[name] = (fn, doc)
        return fn
    return deco


def run_rules(project: Project, rules: list[str] | None = None) -> list[Finding]:
    """All findings, pragma-filtered, deduped, sorted. Parse failures are
    findings too (rule ``parse-error``): a file the analyzer cannot see is
    a hole in every invariant."""
    selected = sorted(rules) if rules else sorted(RULES)
    unknown = [r for r in selected if r not in RULES]
    if unknown:
        raise ValueError(f"unknown rule(s): {', '.join(unknown)} "
                         f"(have: {', '.join(sorted(RULES))})")
    findings: list[Finding] = []
    for f in project.files:
        if f.parse_error is not None:
            findings.append(Finding(
                f.path, f.parse_error.lineno or 1, "parse-error",
                f"file does not parse: {f.parse_error.msg}"))
    for name in selected:
        findings.extend(RULES[name][0](project))
    out = []
    for fd in findings:
        sf = project.file(fd.path)
        if sf is not None and sf.suppressed(fd.line, fd.rule):
            continue
        out.append(fd)
    return sorted(set(out))


# --- baseline ---------------------------------------------------------------

BASELINE_PATH = os.path.join(os.path.dirname(__file__), "baseline.txt")


def load_baseline(path: str | None = None) -> set[tuple[str, str, str]]:
    """Baseline grammar: one finding per line, TAB-separated
    ``rule<TAB>path<TAB>message`` (no line numbers — they drift). Blank
    lines and ``#`` comments ignored."""
    entries: set[tuple[str, str, str]] = set()
    path = path or BASELINE_PATH
    try:
        with open(path, "r", encoding="utf-8") as fh:
            for line in fh:
                line = line.rstrip("\n")
                if not line.strip() or line.lstrip().startswith("#"):
                    continue
                parts = line.split("\t", 2)
                if len(parts) == 3:
                    entries.add((parts[0], parts[1], parts[2]))
    except OSError:
        pass
    return entries


def write_baseline(findings: list[Finding], path: str | None = None) -> None:
    path = path or BASELINE_PATH
    with open(path, "w", encoding="utf-8") as fh:
        fh.write("# tmlint baseline: grandfathered findings "
                 "(rule<TAB>path<TAB>message). Keep ~empty.\n")
        for fd in sorted(set(findings)):
            fh.write(f"{fd.rule}\t{fd.path}\t{fd.message}\n")


def split_baselined(findings: list[Finding],
                    baseline: set[tuple[str, str, str]]):
    new, old = [], []
    for fd in findings:
        (old if fd.key() in baseline else new).append(fd)
    return new, old


# --- git scoping (--changed) ------------------------------------------------

def changed_paths(root: str) -> set[str]:
    """Repo-relative paths touched in the working tree (staged, unstaged,
    untracked) — the fast pre-commit scope."""
    try:
        proc = subprocess.run(
            ["git", "-C", root, "status", "--porcelain", "-uall"],
            capture_output=True, text=True, timeout=30, check=True)
    except (OSError, subprocess.SubprocessError):
        return set()
    out: set[str] = set()
    for line in proc.stdout.splitlines():
        if len(line) < 4:
            continue
        path = line[3:]
        if " -> " in path:  # rename: take the new side
            path = path.split(" -> ", 1)[1]
        out.add(path.strip().strip('"'))
    return out
