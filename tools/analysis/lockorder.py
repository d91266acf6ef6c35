"""Pass 1: lock-order cycles and blocking calls under a held lock.

The serving stack is a heavily threaded system whose concurrency bugs so
far (LoadVoice stale-lock double-load, prober-vs-shutdown thread leak,
trace-JSONL appends under the ring lock) were all instances of two
patterns this pass machine-checks:

- **lock-order inversion**: thread 1 holds A and wants B while thread 2
  holds B and wants A.  The pass records an edge A → B whenever code
  acquires B while holding A (directly nested ``with``, or via a call
  whose transitive summary acquires B) and fails on any cycle.
- **blocking while locked**: a call that can block — ``queue.put/get``
  without a timeout, ``Future.result``, ``Thread.join``, ``Event.wait``
  without a timeout, ``time.sleep``, file ``open``, and device work —
  made while a lock is held.

v2 (PR 19): resolution runs on :mod:`tools.analysis.callgraph` — the
class-aware, type-seeded resolver — instead of bare names.  Locks have
class-qualified identities (``module:Class.attr``), method calls
resolve through receiver types, and the bare-name fallback survives
only as a LOW-confidence last resort that this pass *downgrades*:

- LOW resolutions still propagate **can-block** facts (missing a
  blocked hold is worse than an occasional duplicate), but
- lock-acquisition **edges are HIGH-confidence only** — a LOW edge is
  exactly the same-name-implies-same-lock false-cycle class that
  forced the PR 12/17 defensive renames (``mesh_view``, ``debug_doc``)
  this release reverts.

``block_line`` anchors the ``with`` statement of the *innermost* held
lock, so an allowlist ``block = true`` entry on an outer lock never
silently covers findings under a distinct inner one (locks that fail
to resolve still open their own anonymous block).

Intentional holds are suppressed in ``allowlist.toml``; each entry
carries a rationale and a line anchor that breaks loudly when the code
moves.
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Set, Tuple

from . import callgraph
from .callgraph import (
    HIGH,
    CallGraph,
    FuncInfo,
    LockDef,
    Resolution,
    direct_block_reason,
    walk_own,
)
from .core import AnalysisContext, Diagnostic, call_name

PASS_NAME = "lock-order"

#: repo modules this pass covers (everything outside ``sonata_tpu`` —
#: i.e. test fixtures — is always analyzed)
SCOPE_PREFIXES = (
    "sonata_tpu/serving",
    "sonata_tpu/synth",
    "sonata_tpu/frontends",
    "sonata_tpu/models/piper.py",
    "sonata_tpu/models/shape_plan.py",
    "sonata_tpu/utils/profiling.py",
    "sonata_tpu/utils/dispatch_policy.py",
)


def in_scope(rel: str) -> bool:
    return not rel.startswith("sonata_tpu") \
        or any(rel.startswith(p) for p in SCOPE_PREFIXES)


def _analyze_holds(cg: CallGraph, fi: FuncInfo,
                   edges: Dict[str, Dict[str, Tuple[str, int]]],
                   diags: List[Diagnostic]) -> None:
    """Walk one function; report blocking calls made while holding a
    lock and record acquisition-order edges."""

    def add_edge(held: LockDef, acquired_id: str, line: int) -> None:
        if held.lock_id == acquired_id:
            if held.reentrant:
                return
            diags.append(Diagnostic(
                PASS_NAME, "self-deadlock", fi.module, line,
                f"{fi.name}: re-acquires non-reentrant lock "
                f"{held.lock_id} while already holding it"))
            return
        edges.setdefault(held.lock_id, {}).setdefault(
            acquired_id, (fi.module, line))

    def callee_effects(node: ast.Call, held: List[Tuple[LockDef, int]],
                       block_line: int) -> None:
        """Blocking + edge effects of one call's resolved summaries."""
        reported = False
        for res in cg.resolve_call(fi, node):
            callee = res.func
            if callee is fi:
                continue
            # can-block propagates at ANY confidence; a LOW witness is
            # labeled so readers know the resolution was by name only
            if callee.blocks is not None and not reported:
                hedge = "" if res.confidence == HIGH \
                    else " (name-resolved; low confidence)"
                diags.append(Diagnostic(
                    PASS_NAME, "blocking-under-lock", fi.module,
                    node.lineno,
                    f"{fi.name}: call to {callee.name}() can block "
                    f"({callee.blocks}) while holding "
                    f"{held[-1][0].lock_id}{hedge}",
                    block_line=block_line))
                reported = True
            # lock-order edges are HIGH-confidence ONLY: resolution AND
            # every propagation hop of the acquisition must be typed
            if res.confidence != HIGH:
                continue
            for lock_id, conf in callee.acquires.items():
                if conf != HIGH:
                    continue
                for h, _ln in held:
                    add_edge(h, lock_id, node.lineno)

    def visit(node: ast.AST, held: List[Tuple[LockDef, int]]) -> None:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                and node is not fi.node:
            return  # nested defs analyzed separately (no lock inherited)
        if isinstance(node, ast.With):
            new_held = list(held)
            for item in node.items:
                if not isinstance(item.context_expr, ast.Call):
                    d = cg.resolve_lock(fi, item.context_expr)
                    if d is not None:
                        for h, _ln in new_held:
                            add_edge(h, d.lock_id, node.lineno)
                        new_held.append((d, node.lineno))
                        continue
                visit(item.context_expr, held)
            for child in node.body:
                visit(child, new_held)
            return
        if isinstance(node, ast.Call) and held:
            # the innermost held lock anchors the finding: an allowlist
            # block entry on an OUTER lock must not cover it
            block_line = held[-1][1]
            reason = direct_block_reason(cg, fi, node)
            if reason is not None:
                diags.append(Diagnostic(
                    PASS_NAME, "blocking-under-lock", fi.module,
                    node.lineno,
                    f"{fi.name}: {reason} while holding "
                    f"{held[-1][0].lock_id}", block_line=block_line))
            else:
                callee_effects(node, held, block_line)
            # getattr(x, "prop") property load under the lock
            if call_name(node) == "getattr" and len(node.args) >= 2 \
                    and isinstance(node.args[1], ast.Constant) \
                    and isinstance(node.args[1].value, str):
                _property_effects(node.args[1].value, node.args[0],
                                  node.lineno, held, block_line)
        if isinstance(node, ast.Attribute) and held \
                and isinstance(node.ctx, ast.Load):
            _property_effects(node.attr, node.value, node.lineno, held,
                              held[-1][1])
        for child in ast.iter_child_nodes(node):
            visit(child, held)

    def _property_effects(attr: str, base: ast.AST, line: int,
                          held: List[Tuple[LockDef, int]],
                          block_line: int) -> None:
        props = cg.properties.get(attr)
        if not props:
            return
        # typed receiver narrows to the owning class's property (HIGH);
        # otherwise every same-named property is a LOW candidate
        ci = cg.receiver_class(fi, base)
        if ci is not None:
            m = ci.methods.get(attr)
            cands = [Resolution(m, HIGH)] if m is not None \
                and m.is_property else []
        else:
            cands = [Resolution(p, callgraph.LOW) for p in props]
        for res in cands:
            p = res.func
            if p.blocks is not None:
                diags.append(Diagnostic(
                    PASS_NAME, "blocking-under-lock", fi.module, line,
                    f"{fi.name}: property {p.name} can block "
                    f"({p.blocks}) while holding {held[-1][0].lock_id}",
                    block_line=block_line))
                break
        for res in cands:
            if res.confidence != HIGH:
                continue
            for lock_id, conf in res.func.acquires.items():
                if conf != HIGH:
                    continue
                for h, _ln in held:
                    add_edge(h, lock_id, line)

    for stmt in fi.node.body:
        visit(stmt, [])

    # lexical acquire()/release() regions (e.g. try/finally around a
    # non-blocking acquire): treat lines after the acquire as held
    acq_line: Optional[int] = None
    acq_lock: Optional[LockDef] = None
    for node in walk_own(fi.node):
        if isinstance(node, ast.Call) and call_name(node) == "acquire" \
                and isinstance(node.func, ast.Attribute):
            d = cg.resolve_lock(fi, node.func.value)
            if d is not None:
                acq_line, acq_lock = node.lineno, d
                break
    if acq_lock is not None:
        for node in walk_own(fi.node):
            if isinstance(node, ast.Call) and node.lineno > acq_line:
                if call_name(node) in ("release", "acquire"):
                    continue
                reason = direct_block_reason(cg, fi, node)
                if reason is not None:
                    diags.append(Diagnostic(
                        PASS_NAME, "blocking-under-lock", fi.module,
                        node.lineno,
                        f"{fi.name}: {reason} while holding "
                        f"{acq_lock.lock_id} (acquire()d at line "
                        f"{acq_line})", block_line=acq_line))


def _find_cycles(edges: Dict[str, Dict[str, Tuple[str, int]]]
                 ) -> List[List[str]]:
    cycles: List[List[str]] = []
    seen_cycles: Set[Tuple[str, ...]] = set()

    def dfs(start: str, node: str, path: List[str],
            on_path: Set[str]) -> None:
        for nxt in edges.get(node, {}):
            if nxt == start and len(path) > 1:
                canon = tuple(sorted(path))
                if canon not in seen_cycles:
                    seen_cycles.add(canon)
                    cycles.append(path + [start])
            elif nxt not in on_path and nxt in edges:
                dfs(start, nxt, path + [nxt], on_path | {nxt})

    for start in list(edges):
        dfs(start, start, [start], {start})
    return cycles


def run(ctx: AnalysisContext) -> List[Diagnostic]:
    cg = callgraph.graph_with_summaries(ctx)
    diags: List[Diagnostic] = []
    edges: Dict[str, Dict[str, Tuple[str, int]]] = {}
    for fi in cg.funcs:
        if in_scope(fi.module):
            _analyze_holds(cg, fi, edges, diags)
    for cycle in _find_cycles(edges):
        a, b = cycle[0], cycle[1]
        mod, line = edges[a][b]
        diags.append(Diagnostic(
            PASS_NAME, "lock-cycle", mod, line,
            "lock-order cycle: " + " -> ".join(cycle)
            + " (threads taking these locks in different orders can "
              "deadlock)"))
    # de-duplicate identical findings (a call may be reached twice via
    # nested with-blocks)
    unique: Dict[Tuple, Diagnostic] = {}
    for d in diags:
        unique.setdefault((d.code, d.file, d.line, d.message), d)
    return sorted(unique.values(), key=lambda d: (d.file, d.line))
