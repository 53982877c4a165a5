"""PT3xx — Pallas kernel grid/block contracts.

The bug class behind round 5's high-severity varlen-attention advisory:
a Pallas grid of ``seq // block`` whole tiles *floor-truncates* — if the
block does not divide the packed length exactly, the trailing
``seq % block`` tokens are silently never computed (640/768/896-token
packs dropped their tails while every 512-aligned test passed).  The
fixed contract (ops/pallas/varlen_attention.py `_vfa_block`) is: a block
must be *selected to divide* (``s % b == 0``) or the call must fall back
to the dense reference.

These rules enforce that contract statically:

- PT301: ``x // y`` inside a ``pallas_call`` ``grid=`` expression whose
  divisor has no reachable divisibility guard (a ``% y`` check in the
  module, a guarded block-selector feeding it, or a guard on the callee
  parameter it binds to).
- PT302: ``pl.BlockSpec`` block shapes built from ``min(...)``/
  ``max(...)`` clamps without a ``%`` guard — "merely fits" is exactly
  the pre-fix varlen bug.
- PT303: ``pltpu.TPUCompilerParams`` — the name jax dropped when it
  renamed the class to ``CompilerParams``; it does not exist on the
  installed jax (pyproject.toml pins the floor), so any use is an
  AttributeError waiting for its first call.
"""
from __future__ import annotations

import ast
from typing import Optional

from .engine import call_name, rule

_PLTPU_REMOVED = {"TPUCompilerParams"}


# ---------------------------------------------------------------------------
# guard resolution
# ---------------------------------------------------------------------------

def _mod_ops_with_divisor(tree, name: str):
    """All `<x> % <name>` BinOps in the subtree."""
    for node in ast.walk(tree):
        if isinstance(node, ast.BinOp) and isinstance(node.op, ast.Mod) \
                and isinstance(node.right, ast.Name) \
                and node.right.id == name:
            yield node


def _has_mod_guard(tree, name: str) -> bool:
    return any(True for _ in _mod_ops_with_divisor(tree, name))


def _has_any_divisibility_compare(fn) -> bool:
    """Does this function body contain a `x % y == 0`-shaped compare
    (the block-selector pattern, e.g. varlen `_vfa_block`)?"""
    for node in ast.walk(fn):
        if isinstance(node, ast.Compare) and \
                isinstance(node.left, ast.BinOp) and \
                isinstance(node.left.op, ast.Mod):
            return True
        if isinstance(node, ast.BinOp) and isinstance(node.op, ast.Mod):
            parent = getattr(node, "_pt_parent", None)
            if isinstance(parent, ast.Compare):
                return True
    return False


def _selector_functions(mod) -> set:
    """Module functions whose body proves divisibility (contain a
    `% ... == 0`-style compare) — calls to these are trusted block
    sources."""
    cached = getattr(mod, "_pt_selectors", None)
    if cached is not None:
        return cached
    out = {name for name, fn in mod.functions.items()
           if _has_any_divisibility_compare(fn)}
    mod._pt_selectors = out
    return out


def _expr_calls_selector(expr, selectors: set) -> bool:
    for node in ast.walk(expr):
        if isinstance(node, ast.Call) and call_name(node) in selectors:
            return True
    return False


def _local_assignment(fn, name: str) -> Optional[ast.expr]:
    """Last simple assignment `name = <expr>` in the function body."""
    found = None
    for node in ast.walk(fn):
        if isinstance(node, ast.Assign):
            for t in node.targets:
                if isinstance(t, ast.Name) and t.id == name:
                    found = node.value
        elif isinstance(node, ast.AnnAssign) and \
                isinstance(node.target, ast.Name) and \
                node.target.id == name and node.value is not None:
            found = node.value
    return found


def _param_index(fn: ast.FunctionDef, name: str) -> Optional[int]:
    params = [a.arg for a in fn.args.posonlyargs + fn.args.args]
    try:
        return params.index(name)
    except ValueError:
        return None


def _call_sites(mod, func_name: str):
    for node in ast.walk(mod.tree):
        if isinstance(node, ast.Call) and call_name(node) == func_name:
            yield node


def _arg_for_param(call: ast.Call, fn: ast.FunctionDef, name: str):
    idx = _param_index(fn, name)
    if idx is not None and idx < len(call.args):
        return call.args[idx]
    for kw in call.keywords:
        if kw.arg == name:
            return kw.value
    return None


def _divisor_guarded(mod, fn, name: str, depth: int = 0) -> bool:
    """Is block-size `name`, used as a divisor/block inside `fn`, covered
    by a divisibility guard anywhere reachable?

    1. a `% name` anywhere in the module (e.g. flash_attention
       `_pallas_ok`'s `q.shape[2] % block_q == 0`, rms_norm's
       `n % block != 0` fallback);
    2. `name` passed onward to a module function whose matching
       parameter is `%`-guarded in that callee;
    3. `name` assigned from a call to a guarded block-selector
       (varlen `_vfa_block`: selected so `s % b == 0`);
    4. `name` is a parameter of `fn` and every module call site binds it
       to a guarded expression (selector call or a name guarded in the
       calling function).
    """
    if _has_mod_guard(mod.tree, name):
        return True
    selectors = _selector_functions(mod)
    # (2) forwarded into a guarded callee parameter
    for node in ast.walk(fn):
        if isinstance(node, ast.Call):
            cn = call_name(node)
            callee = mod.functions.get(cn) if cn else None
            if callee is None or callee is fn:
                continue
            for i, a in enumerate(node.args):
                if isinstance(a, ast.Name) and a.id == name:
                    params = [p.arg for p in callee.args.posonlyargs
                              + callee.args.args]
                    if i < len(params) and \
                            _has_mod_guard(callee, params[i]):
                        return True
    # (3) assigned from a guarded selector
    assigned = _local_assignment(fn, name)
    if assigned is not None and _expr_calls_selector(assigned, selectors):
        return True
    # (4) parameter: every call site must hand in a guarded value
    if depth < 2 and _param_index(fn, name) is not None:
        sites = list(_call_sites(mod, fn.name))
        if sites:
            ok = True
            for call in sites:
                arg = _arg_for_param(call, fn, name)
                if arg is None:
                    ok = False
                    break
                if _expr_calls_selector(arg, selectors):
                    continue
                caller = mod.enclosing_function(call)
                if caller is not None and isinstance(arg, ast.Name) and \
                        _divisor_guarded(mod, caller, arg.id, depth + 1):
                    continue
                ok = False
                break
            if ok:
                return True
    return False


# ---------------------------------------------------------------------------
# locating pallas grids / block specs
# ---------------------------------------------------------------------------

def _pallas_calls(mod):
    for node in ast.walk(mod.tree):
        if isinstance(node, ast.Call) and call_name(node) == "pallas_call":
            yield node


def _grid_expr(mod, call: ast.Call):
    for kw in call.keywords:
        if kw.arg == "grid":
            v = kw.value
            if isinstance(v, ast.Name):
                fn = mod.enclosing_function(call)
                if fn is not None:
                    resolved = _local_assignment(fn, v.id)
                    if resolved is not None:
                        return resolved
            return v
    return None


@rule("PT301", "error",
      "pallas grid `x // block` without a divisibility guard "
      "floor-truncates: trailing x % block elements are never computed")
def check_grid_floor_division(mod):
    for call in _pallas_calls(mod):
        grid = _grid_expr(mod, call)
        if grid is None:
            continue
        fn = mod.enclosing_function(call)
        if fn is None:
            continue
        for node in ast.walk(grid):
            if not (isinstance(node, ast.BinOp)
                    and isinstance(node.op, ast.FloorDiv)):
                continue
            div = node.right
            if isinstance(div, ast.Constant):
                # constant divisor: accept only if the module carries any
                # %-based divisibility compare at all
                if any(_has_any_divisibility_compare(f)
                       for f in mod.functions.values()):
                    continue
                name = repr(div.value)
            elif isinstance(div, ast.Name):
                if _divisor_guarded(mod, fn, div.id):
                    continue
                name = div.id
            else:
                continue  # complex divisor expression: out of scope
            yield (node.lineno, node.col_offset,
                   f"grid uses '// {name}' with no reachable "
                   f"divisibility guard ('% {name} == 0' check, guarded "
                   f"block selector, or reference fallback): a block "
                   f"that merely fits silently drops the trailing "
                   f"remainder rows (the varlen 640/768/896 bug); "
                   f"select the block so it divides, or gate with a "
                   f"fallback")


@rule("PT302", "error",
      "BlockSpec block built from an unguarded min()/max() clamp")
def check_blockspec_clamp(mod):
    for node in ast.walk(mod.tree):
        if not (isinstance(node, ast.Call)
                and call_name(node) == "BlockSpec" and node.args):
            continue
        shape = node.args[0]
        elements = shape.elts if isinstance(shape, (ast.Tuple, ast.List)) \
            else [shape]
        fn = mod.enclosing_function(node)
        for el in elements:
            clamp = None
            name = None
            if isinstance(el, ast.Call) and \
                    call_name(el) in ("min", "max"):
                clamp = el
            elif isinstance(el, ast.Name) and fn is not None:
                assigned = _local_assignment(fn, el.id)
                if isinstance(assigned, ast.Call) and \
                        call_name(assigned) in ("min", "max"):
                    clamp = assigned
                    name = el.id
            if clamp is None:
                continue
            if name is not None and fn is not None and \
                    _divisor_guarded(mod, fn, name):
                continue
            what = name or "an inline min()/max()"
            yield (el.lineno, el.col_offset,
                   f"BlockSpec block '{what}' comes from a "
                   f"{call_name(clamp)}() clamp with no '%' divisibility "
                   f"guard: a clamp guarantees the block fits, not that "
                   f"it divides — the grid drops the remainder (pre-fix "
                   f"varlen pattern)")


@rule("PT303", "warning",
      "pltpu.TPUCompilerParams: removed from jax (renamed CompilerParams)")
def check_pltpu_renamed_attr(mod):
    for node in ast.walk(mod.tree):
        if isinstance(node, ast.Attribute) and \
                isinstance(node.value, ast.Name) and \
                node.value.id == "pltpu" and \
                node.attr in _PLTPU_REMOVED:
            yield (node.lineno, node.col_offset,
                   f"'pltpu.{node.attr}' does not exist on the installed "
                   f"jax; use pltpu.CompilerParams")
