"""Project loading: discover source files, parse, collect suppressions.

The default scan set is the *checked repo surface*: ``slate_tpu/`` and
``tools/`` under the project root.  ``tests/`` and
``examples/`` are deliberately excluded — rule fixtures live there and
must be allowed to violate rules on purpose.

Everything is pure stdlib (``ast`` + ``tokenize``): the analyzer never
imports the code it checks, so it runs on machines without jax.
"""

from __future__ import annotations

import ast
import io
import tokenize
from pathlib import Path

from .model import parse_suppressions

DEFAULT_TARGETS = ("slate_tpu", "tools")


class SourceModule:
    """One parsed file: AST, dotted module name, per-line suppressions."""

    def __init__(self, root: Path, path: Path, text: str):
        self.path = path
        self.rel = path.relative_to(root).as_posix()
        self.text = text
        self.tree = ast.parse(text, filename=str(path))
        self.dotted = self.rel[:-3].replace("/", ".")  # a/b/c.py -> a.b.c
        if self.dotted.endswith(".__init__"):
            self.dotted = self.dotted[: -len(".__init__")]
        self.suppressions = parse_suppressions(_comments(text))

    def suppressed(self, line: int, rule: str) -> bool:
        rules = self.suppressions.get(line, ())
        return rule in rules or "all" in rules


def _comments(text: str) -> list[tuple[int, str, bool]]:
    """(lineno, comment, standalone?) for every comment token.  tokenize
    (not a regex) so ``#`` inside string literals is never misread."""
    out = []
    lines = text.splitlines()
    try:
        for tok in tokenize.generate_tokens(io.StringIO(text).readline):
            if tok.type == tokenize.COMMENT:
                lineno, col = tok.start
                src_line = lines[lineno - 1] if lineno <= len(lines) else ""
                standalone = not src_line[:col].strip()
                out.append((lineno, tok.string, standalone))
    except tokenize.TokenError:  # unterminated strings etc: best effort
        pass
    return out


class Project:
    """The loaded repo: modules by repo-relative path, plus a scratch cache
    rules share (reachability results, seam scans)."""

    def __init__(self, root: Path, modules: dict[str, SourceModule]):
        self.root = root
        self.modules = modules
        self.by_dotted = {m.dotted: m for m in modules.values()}
        self.cache: dict[str, object] = {}

    def module(self, rel: str) -> SourceModule | None:
        return self.modules.get(rel)


def iter_source_files(root: Path, targets=DEFAULT_TARGETS):
    for target in targets:
        p = root / target
        if p.is_file() and p.suffix == ".py":
            yield p
        elif p.is_dir():
            yield from sorted(p.rglob("*.py"))


def load_project(root: Path | str, targets=DEFAULT_TARGETS) -> Project:
    root = Path(root).resolve()
    modules: dict[str, SourceModule] = {}
    for path in iter_source_files(root, targets):
        try:
            mod = SourceModule(root, path, path.read_text())
        except (SyntaxError, UnicodeDecodeError):
            # unparseable files are invisible to the analyzer; the test
            # suite will catch them long before lint does
            continue
        modules[mod.rel] = mod
    return Project(root, modules)
