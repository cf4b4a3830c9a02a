"""ZL711 — use-after-donate over the exception-path CFG.

``jax.jit(f, donate_argnums=...)`` transfers buffer ownership into the
executable: after the call, the arrays passed at donated positions are
INVALID — XLA may already have reused their memory as the output (the
whole point: the DecodeEngine's slot-array step updates its
(capacity, max_len, heads * d_head) slabs in place instead of copying
them per token).  Reading a donated buffer afterwards is at best a
``RuntimeError: Array has been deleted`` and at worst silent garbage
on a backend that aliased eagerly.  The protocol the decode loop pins
is: every call site REBINDS the donated state from the call's result
in the same statement —

    self._caches, self._tok, self._pos = self._step_fn(
        self._caches, self._tok, self._pos)       # OK: rebound

    out = self._step_fn(self._caches, tok, pos)
    x = self._caches[0]                           # ZL711: poisoned

Mechanics (name-based, like the hot-path call graph):

* a *donating callable* is anything bound from a ``jax.jit``/``pmap``
  call with literal ``donate_argnums`` — directly, or through the
  module call graph: a function whose body (transitively) contains
  such a jit call is a *donating producer*, and names/attributes
  assigned from calls to it inherit the donated positions (this is how
  ``self._step_fn = self._build_step_plan()`` and the
  ``self._admit_fns[bucket]`` plan dict are recognized);
* at a call through a donating callable, the argument expressions at
  donated positions (plain names or ``self.attr`` chains) become
  POISONED;
* any later read of a poisoned name — including passing it to another
  call, which is how the hazard escapes into the call graph — is
  flagged; rebinding it (assignment target, including the same
  statement's tuple target) clears the poison.  The dataflow runs over
  the CFG, so a poison that survives a loop back-edge is caught on the
  next iteration's first read.
"""

from __future__ import annotations

import ast
from typing import Dict, Iterator, List, Optional, Set, Tuple

from .cfg import CFG, build_cfg
from .context import (ModuleContext, binding_targets, dotted_name,
                      header_parts, iter_function_defs, last_name,
                      walk_shallow)
from .dataflow import solve_forward
from .findings import Finding

_JIT_NAMES = ("jax.jit", "jax.pmap")


def _donate_ints(node: ast.AST) -> Iterator[int]:
    """Literal ints of a donate_argnums value, descending through
    tuples/lists AND conditional expressions (``(0, 1) if donate else
    ()`` — the Trainer's gated-donation idiom): may-donate is the
    conservative read."""
    if isinstance(node, ast.Constant) and isinstance(node.value, int):
        yield node.value
    elif isinstance(node, (ast.Tuple, ast.List)):
        for e in node.elts:
            yield from _donate_ints(e)
    elif isinstance(node, ast.IfExp):
        yield from _donate_ints(node.body)
        yield from _donate_ints(node.orelse)


def _jit_donate_positions(ctx: ModuleContext,
                          node: ast.AST) -> Optional[Set[int]]:
    if not isinstance(node, ast.Call) \
            or ctx.resolve(node.func) not in _JIT_NAMES:
        return None
    for kw in node.keywords:
        if kw.arg == "donate_argnums":
            pos = set(_donate_ints(kw.value))
            return pos or None
    return None


def _donating_producers(ctx: ModuleContext) -> Dict[str, Set[int]]:
    """final function name -> donated positions, to a fixpoint over
    the name-based call graph (module docstring)."""
    fns: Dict[str, Tuple[ast.AST, Set[str]]] = {}
    producers: Dict[str, Set[int]] = {}
    for node in ast.walk(ctx.tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        callees: Set[str] = set()
        for sub in ast.walk(node):
            pos = _jit_donate_positions(ctx, sub)
            if pos:
                producers.setdefault(node.name, set()).update(pos)
            if isinstance(sub, ast.Call):
                name = last_name(sub.func)
                if name:
                    callees.add(name)
        fns[node.name] = (node, callees)
    changed = True
    while changed:
        changed = False
        for name, (_fd, callees) in fns.items():
            for c in callees & set(producers):
                pos = producers[c]
                if not pos <= producers.get(name, set()):
                    producers.setdefault(name, set()).update(pos)
                    changed = True
    return producers


def _value_donates(ctx: ModuleContext, value: ast.AST,
                   producers: Dict[str, Set[int]]) -> Optional[Set[int]]:
    """Donated positions of the callable a value expression builds: a
    literal jit-donate call, a call to a donating producer, or a call
    that THREADS a donating callable through (the decode engine's
    ``self._plan(name, jax.jit(..., donate_argnums=...), specs)`` /
    ``self._plan(name, self._build_admit_fn(b), specs)`` AOT shape —
    the wrapper returns the compiled form of its donating argument, so
    the binding inherits the donated positions)."""
    pos = _jit_donate_positions(ctx, value)
    if pos:
        return pos
    if isinstance(value, ast.Call):
        name = last_name(value.func)
        if name in producers:
            return set(producers[name]) or None
        inherited: Set[int] = set()
        for arg in value.args:
            p = _jit_donate_positions(ctx, arg)
            if not p and isinstance(arg, ast.Call):
                aname = last_name(arg.func)
                if aname in producers:
                    p = producers[aname]
            if p:
                inherited |= p
        if inherited:
            return inherited
    return None


def _attr_donors(ctx: ModuleContext,
                 producers: Dict[str, Set[int]]) -> Dict[str, Set[int]]:
    """Module-wide attribute donors, keyed by the attribute's FINAL
    name: ``self._step_fn`` / ``self._admit_fns[...]`` assigned from a
    donating value anywhere marks every ``<recv>._step_fn`` call a
    donating call — receivers vary across functions (``self`` at the
    binding, a parameter at the call site) but the attribute is the
    protocol, same over-approximation as the hot-path call graph."""
    donors: Dict[str, Set[int]] = {}
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Assign):
            continue
        pos = _value_donates(ctx, node.value, producers)
        if not pos:
            continue
        for t in node.targets:
            if isinstance(t, ast.Subscript):
                t = t.value
            if isinstance(t, ast.Attribute):
                donors.setdefault(t.attr, set()).update(pos)
    return donors


def _callee_key(func: ast.AST) -> Optional[str]:
    """The donor-lookup key of a call's callee: a bare name, a dotted
    attr chain, or the chain of a subscripted plan table
    (``self._stepk_fns[k](...)``)."""
    if isinstance(func, ast.Subscript):
        func = func.value
    return dotted_name(func)


def rule_use_after_donate(ctx: ModuleContext) -> List[Finding]:
    producers = _donating_producers(ctx)
    attr_donors = _attr_donors(ctx, producers)
    # module-level name donors (``step = jax.jit(f, donate_argnums=…)``
    # at top level) are visible to every function in the module
    module_donors: Dict[str, Set[int]] = {}
    for node in walk_shallow(ctx.tree.body):
        if isinstance(node, ast.Assign):
            pos = _value_donates(ctx, node.value, producers)
            if pos:
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        module_donors.setdefault(t.id,
                                                 set()).update(pos)
    findings: List[Finding] = []
    seen: Set[Tuple[str, int]] = set()

    for qual, fd in iter_function_defs(ctx):
        # function-local name donors: ``fn = self._admit_fn_for(b)``
        # (a producer call) and ``fn = self._admit_fns[b]`` (a read
        # out of a donating plan table), layered over the module-level
        # bindings
        name_donors: Dict[str, Set[int]] = {
            k: set(v) for k, v in module_donors.items()}
        for node in walk_shallow(fd.body):
            if isinstance(node, ast.Assign):
                pos = _value_donates(ctx, node.value, producers)
                if not pos and isinstance(node.value, ast.Subscript):
                    d = dotted_name(node.value.value)
                    if d:
                        pos = attr_donors.get(d.rsplit(".", 1)[-1])
                if pos:
                    for t in node.targets:
                        if isinstance(t, ast.Name):
                            name_donors.setdefault(t.id,
                                                   set()).update(pos)
        if not name_donors and not attr_donors:
            continue
        cfg = build_cfg(fd)

        def _stmt_events(st: ast.stmt):
            """(poison_gens, kills, reads) for one statement."""
            gens: Set[Tuple[str, int]] = set()
            kills: Set[str] = set()
            reads: List[Tuple[str, int]] = []
            for part in header_parts(st):
                for n in walk_shallow([part]):
                    if isinstance(n, ast.Call):
                        # NOTE: calling a *producer* builds a donating
                        # callable — it does not donate its own args;
                        # only calls THROUGH a donor binding poison.
                        # Attr donors match on the attribute tail.
                        key = _callee_key(n.func)
                        pos = None
                        if key is not None:
                            pos = (name_donors.get(key)
                                   if "." not in key else
                                   attr_donors.get(
                                       key.rsplit(".", 1)[-1]))
                        if pos:
                            for p in pos:
                                if p < len(n.args):
                                    d = dotted_name(n.args[p])
                                    if d:
                                        gens.add((d, n.lineno))
                    elif isinstance(n, (ast.Name, ast.Attribute)) \
                            and isinstance(getattr(n, "ctx", None),
                                           ast.Load):
                        d = dotted_name(n)
                        if d:
                            reads.append((d, n.lineno))
            for t in _targets(st):
                d = dotted_name(t)
                if d:
                    kills.add(d)
            return gens, kills, reads

        def transfer(node: int, state, _cfg=cfg):
            st = _cfg.stmts.get(node)
            if st is None:
                return state
            gens, kills, _reads = _stmt_events(st)
            out = {el for el in state if el[0] not in kills}
            out |= {g for g in gens if g[0] not in kills}
            return frozenset(out)

        sol = solve_forward(cfg, transfer)
        for node, st in cfg.stmts.items():
            poisoned = {el[0]: el[1] for el in sol.in_state(node)}
            if not poisoned:
                continue
            _gens, _kills, reads = _stmt_events(st)
            for d, line in reads:
                if d in poisoned and (d, line) not in seen:
                    seen.add((d, line))
                    findings.append(Finding(
                        "ZL711", ctx.path, line, 0, qual,
                        f"read of {d} after it was donated to a "
                        f"donate_argnums executable at line "
                        f"{poisoned[d]}: the buffer now belongs to "
                        "XLA (it may already BE the output) — rebind "
                        "the name from the call's result in the same "
                        "statement, like the DecodeEngine slot-array "
                        "protocol"))
    findings.sort(key=lambda f: (f.line, f.message))
    return findings


def _targets(st: ast.stmt) -> List[ast.AST]:
    out = binding_targets(st)
    if isinstance(st, ast.AugAssign):
        # for poison purposes an augmented write DOES rebind the name
        out.append(st.target)
    return out
