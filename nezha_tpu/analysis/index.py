"""The shared AST/source index every lint rule runs over.

Before this package existed, each of the three ``tools/check_*.py``
validators walked the source tree on its own — three ``os.walk`` loops,
three regex dialects, zero shared parsing. The index is the one walk:
every ``.py`` file under the configured roots is read ONCE and parsed
ONCE (``ast.parse``), with parent back-links attached so rules can ask
"what function/class encloses this node" without re-deriving it. Rules
receive the index and never touch the filesystem themselves (non-Python
artifacts — RUNBOOK tables, committed BENCH records — go through
:meth:`SourceIndex.read_text`, which caches too).

Stdlib-only by design: the analysis subpackage itself never imports
jax (the ``tools/check_*.py`` shims exploit this with a namespace stub
to stay runnable on jaxless boxes — the ``nezha-lint`` console script
lives in ``nezha_tpu.cli`` and does import the package), and the whole
tree (~140 files) indexes in well under a second.
"""

from __future__ import annotations

import ast
import dataclasses
import os
from typing import Any, Dict, Iterator, List, Optional, Tuple

# What `nezha-lint` covers by default: the package, the checker shims,
# and the benchmark drivers. tests/ is deliberately NOT indexed (rules
# lint product source; the fault-points rule reads tests as text via
# read_text to verify coverage).
DEFAULT_ROOTS: Tuple[str, ...] = ("nezha_tpu", "tools", "benchmarks")
DEFAULT_EXTRA_FILES: Tuple[str, ...] = ("bench.py",)


@dataclasses.dataclass
class Module:
    """One parsed source file."""

    rel: str                  # repo-relative posix path (stable in keys)
    path: str                 # absolute path
    text: str
    tree: ast.Module
    parents: Dict[ast.AST, ast.AST]   # child node -> parent node
    nodes: List[ast.AST]              # every node, in ast.walk order
    # What rules derive from ``tree`` and share, computed once a module
    # by its first reader (``traced.traced_functions``: three rules).
    memo: Dict[str, Any] = dataclasses.field(default_factory=dict)


def _walk_with_parents(tree: ast.Module
                       ) -> Tuple[List[ast.AST], Dict[ast.AST, ast.AST]]:
    """Every node in ``ast.walk`` order, and each child's parent: one
    walk."""
    nodes: List[ast.AST] = [tree]
    parents: Dict[ast.AST, ast.AST] = {}
    for node in nodes:          # grows as it goes: breadth first
        for child in ast.iter_child_nodes(node):
            parents[child] = node
            nodes.append(child)
    return nodes, parents


def dotted_name(node: ast.AST) -> Optional[str]:
    """``a.b.c`` for a Name/Attribute chain, None for anything dynamic
    (calls, subscripts) — rules match call targets by this string."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def call_name(call: ast.Call) -> Optional[str]:
    """Dotted name of a call's target (``obs.counter``, ``self.executor.
    run``), None when the callee is itself computed."""
    return dotted_name(call.func)


def str_arg(call: ast.Call, pos: int = 0) -> Optional[str]:
    """The literal string at positional arg ``pos``, None when absent or
    non-literal (f-strings and variables are skipped, never guessed)."""
    if len(call.args) > pos:
        a = call.args[pos]
        if isinstance(a, ast.Constant) and isinstance(a.value, str):
            return a.value
    return None


class SourceIndex:
    """Parsed view of the repo for one lint run.

    ``parse_errors`` holds ``(rel, message)`` for files that failed to
    parse — the runner turns those into findings (a tree that does not
    parse must fail the lint, not silently shrink its coverage).
    """

    def __init__(self, root: str,
                 roots: Tuple[str, ...] = DEFAULT_ROOTS,
                 extra_files: Tuple[str, ...] = DEFAULT_EXTRA_FILES):
        self.root = os.path.abspath(root)
        self.modules: Dict[str, Module] = {}
        self.parse_errors: List[Tuple[str, str]] = []
        self._text_cache: Dict[str, Optional[str]] = {}
        paths: List[str] = []
        for sub in roots:
            base = os.path.join(self.root, sub)
            for dirpath, dirnames, files in os.walk(base):
                dirnames.sort()
                for fn in sorted(files):
                    if fn.endswith(".py"):
                        paths.append(os.path.join(dirpath, fn))
        for extra in extra_files:
            p = os.path.join(self.root, extra)
            if os.path.isfile(p):
                paths.append(p)
        for path in paths:
            rel = os.path.relpath(path, self.root).replace(os.sep, "/")
            try:
                with open(path, encoding="utf-8") as f:
                    text = f.read()
                tree = ast.parse(text, filename=rel)
            except (OSError, SyntaxError, ValueError) as e:
                self.parse_errors.append((rel, f"{type(e).__name__}: {e}"))
                continue
            nodes, parents = _walk_with_parents(tree)
            self.modules[rel] = Module(
                rel=rel, path=path, text=text, tree=tree,
                parents=parents, nodes=nodes)

    def __iter__(self) -> Iterator[Module]:
        for rel in sorted(self.modules):
            yield self.modules[rel]

    def read_text(self, rel: str) -> Optional[str]:
        """Text of any repo file (RUNBOOK, tests, JSON records), cached;
        None when absent."""
        if rel not in self._text_cache:
            try:
                with open(os.path.join(self.root, rel),
                          encoding="utf-8") as f:
                    self._text_cache[rel] = f.read()
            except OSError:
                self._text_cache[rel] = None
        return self._text_cache[rel]

    # ----------------------------------------------------- AST helpers
    def enclosing(self, mod: Module, node: ast.AST,
                  kinds: tuple) -> Optional[ast.AST]:
        cur = mod.parents.get(node)
        while cur is not None:
            if isinstance(cur, kinds):
                return cur
            cur = mod.parents.get(cur)
        return None

    def qualname(self, mod: Module, node: ast.AST) -> str:
        """Dotted path of enclosing defs/classes (``Cls.method.inner``),
        "" at module level — the line-number-free context baseline keys
        anchor on."""
        parts: List[str] = []
        cur: Optional[ast.AST] = node
        while cur is not None:
            if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef,
                                ast.ClassDef)):
                parts.append(cur.name)
            cur = mod.parents.get(cur)
        return ".".join(reversed(parts))

    def functions(self, mod: Module) -> Iterator[ast.AST]:
        for node in ast.walk(mod.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                yield node
