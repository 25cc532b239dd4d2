"""Which functions run under a JAX trace — the scope of the hot-path rules.

``host-sync-in-hot-path`` and ``traced-value-branch`` only make sense
inside function bodies that jit/pallas traces: a ``float()`` on a host
value is fine in the scheduler but a recompile (or a
``TracerBoolConversionError``) inside a step program. Static detection
is necessarily heuristic; this module errs toward the repo's actual
idioms:

1. **decorated** — ``@jax.jit`` / ``@jit`` / ``@partial(jax.jit, ...)``
   (and the other tracing transforms in :data:`TRACING_CALLS`);
2. **handed to a tracer** — any local function whose NAME appears inside
   the arguments of a ``jax.jit(...)`` / ``lax.scan(...)`` /
   ``pl.pallas_call(...)`` / ``lax.while_loop`` / ... call, including
   through ``functools.partial`` nesting (how the Pallas kernels are
   bound);
3. **builder convention** — an inner ``def`` returned by an enclosing
   ``_build_*`` function (the serve engine's program builders: the
   returned closures are dispatched through the donating Executor and
   jitted there — ``serve/engine.py`` step/prefill);
4. **transitive** — a function referenced by name from the body of any
   traced function in the same module (the ``core``/``body`` helpers the
   builders share, the ``_block_step`` math the kernel variants share).

Cross-module tracedness (a model method called from a traced program in
another file) is out of scope: resolving it statically would need whole-
program type inference, and the in-module rules already cover the paths
the contracts name (engine builders, pallas kernels).
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Set

from nezha_tpu.analysis.index import Module, dotted_name

# Call targets (matched on the LAST dotted component) whose function
# arguments get traced.
TRACING_CALLS: Set[str] = {
    "jit", "scan", "while_loop", "fori_loop", "cond", "switch",
    "pallas_call", "vmap", "pmap", "shard_map", "remat", "checkpoint",
    "grad", "value_and_grad", "custom_jvp", "custom_vjp",
    "associative_scan",
}

# Mesh collectives that only ever execute under a shard_map/pmap
# lowering: a function whose body ISSUES one is a traced body even
# when no in-module shard_map call references it by name — the ring-
# attention library helpers (parallel/ring.py, serve/sharded/
# seq_prefill.py) are handed to shard_map cross-module, so the ring
# hop loops they build would otherwise sit outside the hot-path
# rules' scope. The reason string deliberately says "shard_map" so
# the mesh-host-side-tables rule roots at these bodies too.
COLLECTIVE_CALLS: Set[str] = {"ppermute", "all_to_all", "pshuffle"}

_FuncDef = (ast.FunctionDef, ast.AsyncFunctionDef)


def _decorator_traces(dec: ast.AST) -> bool:
    """True for ``@jit``-family decorators, bare or partial-wrapped."""
    if isinstance(dec, ast.Call):
        name = dotted_name(dec.func) or ""
        if name.rsplit(".", 1)[-1] in TRACING_CALLS:
            return True
        if name.rsplit(".", 1)[-1] == "partial":
            return any(_decorator_traces(a) for a in
                       list(dec.args) + [k.value for k in dec.keywords])
        return False
    name = dotted_name(dec) or ""
    return name.rsplit(".", 1)[-1] in TRACING_CALLS


def _function_facts(mod: Module):
    """``(by_name, refs, collective)``: defs by name, the names each
    def's body loads (in its walk order), and the first mesh collective
    each issues (None if none). One pass over the module's nodes; kept
    on it."""
    if "function_facts" not in mod.memo:
        by_name: Dict[str, List[ast.AST]] = {}
        refs: Dict[ast.AST, Dict[str, None]] = {}
        collective: Dict[ast.AST, Optional[str]] = {}

        def enclosing_defs(node):
            cur = mod.parents.get(node)
            while cur is not None:
                if isinstance(cur, _FuncDef):
                    yield cur
                cur = mod.parents.get(cur)

        for node in mod.nodes:
            if isinstance(node, _FuncDef):
                by_name.setdefault(node.name, []).append(node)
                refs[node], collective[node] = {}, None
            elif isinstance(node, ast.Name):
                if isinstance(node.ctx, ast.Load):
                    for fn in enclosing_defs(node):
                        refs[fn].setdefault(node.id)
            elif isinstance(node, ast.Call):
                last = (dotted_name(node.func) or "").rsplit(".", 1)[-1]
                if last in COLLECTIVE_CALLS:
                    for fn in enclosing_defs(node):
                        if collective[fn] is None:
                            collective[fn] = last
        mod.memo["function_facts"] = (by_name, refs, collective)
    return mod.memo["function_facts"]


def close_over_calls(mod: Module, found: Dict[ast.AST, str],
                     reason: str) -> Dict[ast.AST, str]:
    """Add to ``found`` every def of ``mod`` that a def in it references
    by name, transitively (``reason`` formatted with the referrer's
    name), in breadth-first order: the first referrer names the
    reason."""
    by_name, refs, _ = _function_facts(mod)
    queue = list(found)
    for fn in queue:
        for name in refs[fn]:
            for callee in by_name.get(name, ()):
                if callee not in found:
                    found[callee] = reason.format(getattr(fn, "name", "?"))
                    queue.append(callee)
    return found


def traced_functions(mod: Module) -> Dict[ast.AST, str]:
    """-> {FunctionDef node: one-line reason it is considered traced}.
    Computed once a module and kept on it (three rules read it)."""
    if "traced" in mod.memo:
        return mod.memo["traced"]
    by_name, _, collective = _function_facts(mod)
    assigns: Dict[str, List[ast.AST]] = {}
    for node in mod.nodes:
        if isinstance(node, ast.Assign):
            for t in node.targets:
                if isinstance(t, ast.Name):
                    assigns.setdefault(t.id, []).append(node.value)

    traced: Dict[ast.AST, str] = {}

    def mark(fn: ast.AST, reason: str) -> None:
        if fn not in traced:
            traced[fn] = reason

    for node in mod.nodes:
        if isinstance(node, _FuncDef):
            for dec in node.decorator_list:
                if _decorator_traces(dec):
                    mark(node, "decorated with a tracing transform")
            if collective[node] is not None:
                mark(node, f"issues mesh collective {collective[node]}() "
                           f"(shard_map-lowered body)")
        if isinstance(node, ast.Call):
            cn = dotted_name(node.func) or ""
            if cn.rsplit(".", 1)[-1] in TRACING_CALLS:
                for arg in list(node.args) + [k.value for k in
                                              node.keywords]:
                    for sub in ast.walk(arg):
                        if not isinstance(sub, ast.Name):
                            continue
                        if sub.id in by_name:
                            for fn in by_name[sub.id]:
                                mark(fn, f"passed to "
                                         f"{cn.rsplit('.', 1)[-1]}()")
                        elif sub.id in assigns:
                            # `kernel = functools.partial(_decode_
                            # kernel, ...)` then `pallas_call(kernel,
                            # ...)` — resolve one assignment hop. Only
                            # REFERENCES to a def count: in
                            # `mesh = _mesh(devs)` the def is CALLED
                            # and the variable holds its result, not
                            # the function.
                            for rhs in assigns[sub.id]:
                                callees = {id(c.func) for c in
                                           ast.walk(rhs)
                                           if isinstance(c, ast.Call)}
                                for s2 in ast.walk(rhs):
                                    if (isinstance(s2, ast.Name)
                                            and id(s2) not in callees
                                            and s2.id in by_name):
                                        for fn in by_name[s2.id]:
                                            mark(fn, f"bound to "
                                                 f"{sub.id} passed to "
                                                 f"{cn.rsplit('.', 1)[-1]}"
                                                 f"()")
        # Builder convention: `def _build_x(): def f(...): ...;
        # return f` — the returned closure is the compiled program.
        if (isinstance(node, _FuncDef)
                and node.name.startswith("_build")):
            inner = {n.name: n for n in node.body
                     if isinstance(n, _FuncDef)}
            # Inner defs may sit one level down (if/else variants).
            for stmt in ast.walk(node):
                if isinstance(stmt, _FuncDef) and stmt is not node:
                    inner.setdefault(stmt.name, stmt)
            for stmt in ast.walk(node):
                if isinstance(stmt, ast.Return) and stmt.value is not None:
                    for sub in ast.walk(stmt.value):
                        if (isinstance(sub, ast.Name)
                                and sub.id in inner):
                            mark(inner[sub.id],
                                 f"program built by {node.name}()")

    # Transitive closure: helpers called from traced bodies trace too.
    mod.memo["traced"] = close_over_calls(mod, traced,
                                          "called from traced {}()")
    return mod.memo["traced"]


# Attributes that are STATIC on a traced array — reading them yields
# Python values, so branching on them is fine (`if q.shape[0] == 1:`).
_STATIC_ATTRS = {"shape", "dtype", "ndim", "size", "sharding",
                 "is_fully_replicated", "itemsize"}

# Dotted prefixes whose call results are device values.
_DEVICE_BASES = ("jnp.", "jax.numpy.", "lax.", "jax.lax.", "jax.random.",
                 "jax.nn.", "jnn.")


def _is_device_call(call: ast.Call) -> bool:
    name = dotted_name(call.func) or ""
    return name.startswith(_DEVICE_BASES) or name in ("jnp", "lax")


def only_static_use(root: ast.AST, leaf: ast.Name) -> bool:
    """True when ``leaf`` appears under a ``.shape``-style static-
    metadata access within ``root`` (so it contributes no device
    value). Shared by the taint propagation here and the branch-test
    check in rules/traced_branch.py — ONE definition of "static", so
    the two can never disagree on an attribute."""
    for sub in ast.walk(root):
        if isinstance(sub, ast.Attribute) and sub.attr in _STATIC_ATTRS:
            for inner in ast.walk(sub):
                if inner is leaf:
                    return True
    return False


def device_tainted(fn: ast.AST, *,
                   include_params: bool = True) -> Set[str]:
    """Names inside a traced function that (conservatively) hold traced
    array values: positional/vararg parameters (keyword-only params are
    excluded — the repo binds statics like ``scale``/``block_k`` through
    ``functools.partial`` keywords) plus anything assigned from a
    ``jnp.``/``lax.``/``jax.`` call or from arithmetic over already-
    tainted names. Taint does NOT flow through ``.shape``/``.dtype``-
    style static attributes."""
    tainted: Set[str] = set()
    if include_params:
        args = fn.args
        for a in list(args.posonlyargs) + list(args.args):
            tainted.add(a.arg)
        if args.vararg is not None:
            tainted.add(args.vararg.arg)

    def expr_tainted(node: ast.AST) -> bool:
        for sub in ast.walk(node):
            if isinstance(sub, ast.Call) and _is_device_call(sub):
                return True
            if (isinstance(sub, ast.Name) and isinstance(sub.ctx, ast.Load)
                    and sub.id in tainted
                    and not only_static_use(node, sub)):
                return True
        return False

    changed = True
    while changed:
        changed = False
        for node in ast.walk(fn):
            targets: List[ast.AST] = []
            value: Optional[ast.AST] = None
            if isinstance(node, ast.Assign):
                targets, value = node.targets, node.value
            elif isinstance(node, ast.AugAssign):
                targets, value = [node.target], node.value
            elif isinstance(node, ast.AnnAssign) and node.value is not None:
                targets, value = [node.target], node.value
            if value is None or not expr_tainted(value):
                continue
            for t in targets:
                for sub in ast.walk(t):
                    if isinstance(sub, ast.Name) and sub.id not in tainted:
                        tainted.add(sub.id)
                        changed = True
    return tainted
