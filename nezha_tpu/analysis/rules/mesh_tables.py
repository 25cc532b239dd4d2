"""Rule ``mesh-host-side-tables``: pool bookkeeping never mutates
inside a ``shard_map``-lowered body.

The sharded serve engine's whole design rests on one split: KV *bytes*
live device-side, head-sharded over the mesh, while every piece of
pool *bookkeeping* — the per-slot block tables, the block free list,
ref counts, per-slot bound counts, and the prefix trie — stays
host-side, single, and layout-identical to the single-device pool
(serve/sharded/pool.py). A block-table or free-list mutation inside a
``shard_map``-lowered body would either trace-crash (host containers
inside a trace), or worse: silently fork the bookkeeping per shard, so
two devices disagree about which block a slot owns — the stale-write /
double-bind corruption the write-at-ref==1 invariant exists to make
impossible.

Scope: the ``shard_map``-lowered subset of the traced-body index the
host-sync rule already builds (:mod:`nezha_tpu.analysis.traced`) —
functions passed to ``shard_map(...)`` plus everything transitively
called from their bodies in the same module. Flagged mutations:

- assignments (plain/aug/ann, including subscript stores like
  ``self.tables_host[slot, i] = b``) whose target touches one of the
  host-state attributes;
- mutating method calls (``append``/``pop``/``insert``/``evict``/...)
  whose receiver chain touches one of them.

Reads stay legal — a shard_map body may consume an UPLOADED copy of
the tables as an operand; it may never write the host mirror.
"""

from __future__ import annotations

import ast
from typing import Dict, List

from nezha_tpu.analysis.core import Finding, rule
from nezha_tpu.analysis.index import SourceIndex
from nezha_tpu.analysis.traced import close_over_calls, traced_functions

# The host-side pool bookkeeping state (PagedSlotPool and its sharded
# subclass). Renaming one of these fields means updating this set — the
# rule's fixture test fails otherwise.
HOST_TABLE_STATE = frozenset({
    "tables_host", "_free_blocks", "_free_slots", "_refs", "_bound",
    "trie",
})

# Method names that mutate their receiver (list/dict/set/trie surface).
_MUTATORS = frozenset({
    "append", "extend", "pop", "remove", "insert", "clear", "add",
    "discard", "update", "setdefault", "evict",
})


def shard_map_bodies(mod) -> Dict[ast.AST, str]:
    """The ``shard_map``-lowered slice of the traced-body index:
    functions the shared :func:`traced_functions` walk attributes to a
    ``shard_map(...)`` call, plus the transitive in-module closure of
    functions their bodies reference — the same closure rule the
    host-sync scope uses, rooted narrower."""
    bodies: Dict[ast.AST, str] = {
        fn: reason for fn, reason in traced_functions(mod).items()
        if "shard_map" in reason}
    return close_over_calls(mod, bodies,
                            "called from shard_map-lowered {}()")


def _touched_state(node: ast.AST):
    """Host-state attributes referenced anywhere under ``node``."""
    return sorted({sub.attr for sub in ast.walk(node)
                   if isinstance(sub, ast.Attribute)
                   and sub.attr in HOST_TABLE_STATE})


@rule("mesh-host-side-tables",
      "block-table / free-list / trie state is host-side only — never "
      "mutated inside a shard_map-lowered body")
def check(index: SourceIndex) -> List[Finding]:
    findings: List[Finding] = []
    for mod in index:
        bodies = shard_map_bodies(mod)
        for fn, reason in bodies.items():
            qual = index.qualname(mod, fn)
            for node in ast.walk(fn):
                hits, what = [], None
                if isinstance(node, (ast.Assign, ast.AugAssign,
                                     ast.AnnAssign)):
                    targets = (node.targets
                               if isinstance(node, ast.Assign)
                               else [node.target])
                    for t in targets:
                        hits.extend(_touched_state(t))
                    what = "assignment to"
                elif (isinstance(node, ast.Call)
                        and isinstance(node.func, ast.Attribute)
                        and node.func.attr in _MUTATORS):
                    hits = _touched_state(node.func.value)
                    what = f".{node.func.attr}() on"
                for name in sorted(set(hits)):
                    findings.append(Finding(
                        file=mod.rel, line=node.lineno,
                        rule="mesh-host-side-tables",
                        symbol=qual, detail=name,
                        message=(f"{what} host-side pool state "
                                 f"{name!r} inside shard_map-lowered "
                                 f"{qual or '<module>'} ({reason}) — "
                                 f"bookkeeping would fork per shard")))
    return findings
