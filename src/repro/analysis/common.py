"""Shared infrastructure of the static-analysis passes: findings and
source helpers."""
from __future__ import annotations

import ast
import dataclasses
import re
from pathlib import Path
from typing import Iterable, List, Optional, Sequence, Tuple

REPO_ROOT = Path(__file__).resolve().parents[3]


@dataclasses.dataclass(frozen=True)
class Finding:
    """One structured lint finding: location, rule id, message, fix hint."""

    file: str        # repo-relative path
    line: int        # 1-based line number
    rule: str        # kebab-case rule id (stable; tests assert on it)
    message: str
    suggestion: str = ""

    def render(self) -> str:
        s = f" [{self.suggestion}]" if self.suggestion else ""
        return f"{self.file}:{self.line}: {self.rule}: {self.message}{s}"


def rel(path: "Path | str") -> str:
    """Repo-relative display path (absolute paths outside the repo are
    kept as-is — fixture corpora under a tmpdir stay addressable)."""
    p = Path(path).resolve()
    try:
        return str(p.relative_to(REPO_ROOT))
    except ValueError:
        return str(p)


def read_source(path: "Path | str") -> Tuple[str, List[str]]:
    text = Path(path).read_text()
    return text, text.splitlines()


def find_line(lines: Sequence[str], pattern: str,
              start: int = 0) -> Optional[int]:
    """1-based line number of the first line matching ``pattern``."""
    rx = re.compile(pattern)
    for i in range(start, len(lines)):
        if rx.search(lines[i]):
            return i + 1
    return None


def attribute_names(trees: Iterable[ast.AST]) -> set:
    """Every attribute name accessed anywhere in the given ASTs."""
    out = set()
    for tree in trees:
        for n in ast.walk(tree):
            if isinstance(n, ast.Attribute):
                out.add(n.attr)
    return out
