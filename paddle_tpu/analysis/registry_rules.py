"""PT4xx — registry and observability consistency.

The op registry (ops/registry.py) is a name -> jax-function table and
the *entire* dispatch story on TPU: `register()` happily overwrites, so
a duplicate name is a silent kernel replacement decided by import order
(PT401).  Everything registered is eventually called through the
dispatcher funnel `core.dispatch.apply(fn, *tensor_args)`, so an entry
whose signature cannot take a single positional argument — or that is a
generator — can never be dispatched (PT402).

PT403 guards the observability contract from the other side: every
metric name emitted in code must be declared in
``tools/trace_report.py``'s ``KNOWN_METRICS`` (the set the triage
report and the README document).  A counter that isn't in the known set
is invisible to the tooling — exactly the drift the README's
one-source-of-truth policy exists to prevent.  Dynamic names (f-strings,
concatenation) are out of static reach and are covered by the ``*``
patterns in the known set.

PT404 extends the same policy to trace spans: the names passed to the
tracing helpers (``tracing.span`` / ``tracing.record_span`` /
``RecordEvent``) must be literal strings.  Span names are the join key
for everything downstream — the flight recorder's counter deltas, the
chrome-trace merge in ``tools/trace_report.py``, and the span summary
table all aggregate BY NAME — so a name built at runtime (f-string per
request, concatenated ids) explodes the cardinality of every one of
those views and makes cross-host merges meaningless.  Variable data
belongs in the span's ``args``, not its name.  A literal family prefix
(``RecordEvent("op::" + name)``) is allowed — the prefix keeps the
family aggregatable, the same escape hatch the ``*`` patterns give
KNOWN_METRICS.
"""
from __future__ import annotations

import ast
import os
from typing import Dict, List, Optional, Tuple

from .engine import call_name, match_known, rule

_METRIC_FACTORIES = {"counter", "gauge", "histogram"}
_METRIC_EMITTERS = {"inc", "set_gauge", "observe"}


# ---------------------------------------------------------------------------
# registration extraction (static)
# ---------------------------------------------------------------------------

def _literal_all(mod) -> List[str]:
    """Module __all__ when it is a literal list/tuple of strings."""
    for node in mod.tree.body:
        if isinstance(node, ast.Assign):
            for t in node.targets:
                if isinstance(t, ast.Name) and t.id == "__all__":
                    v = node.value
                    if isinstance(v, (ast.List, ast.Tuple)):
                        return [e.value for e in v.elts
                                if isinstance(e, ast.Constant)
                                and isinstance(e.value, str)]
    return []


def _register_wrappers(mod) -> set:
    """Local functions that forward their first parameter as the name of
    a register() call (e.g. ops/nn_compat.py `_reg`)."""
    out = set()
    for name, fn in mod.functions.items():
        params = [a.arg for a in fn.args.posonlyargs + fn.args.args]
        if not params:
            continue
        for node in ast.walk(fn):
            if isinstance(node, ast.Call) and \
                    call_name(node) == "register" and node.args and \
                    isinstance(node.args[0], ast.Name) and \
                    node.args[0].id == params[0]:
                out.add(name)
                break
    return out


def _loop_values_for(mod, call: ast.Call, var: str) -> List[str]:
    """String values `var` takes when it is the target (or a member of a
    tuple target) of an enclosing literal-iterable For loop."""
    node = call
    while node is not None:
        node = getattr(node, "_pt_parent", None)
        if not isinstance(node, ast.For):
            continue
        target, it = node.target, node.iter
        pos = None
        if isinstance(target, ast.Name) and target.id == var:
            pos = -1                      # scalar target
        elif isinstance(target, ast.Tuple):
            for i, el in enumerate(target.elts):
                if isinstance(el, ast.Name) and el.id == var:
                    pos = i
        if pos is None:
            continue
        if isinstance(it, ast.Name) and it.id == "__all__":
            return list(_literal_all(mod)) if pos == -1 else []
        if not isinstance(it, (ast.List, ast.Tuple)):
            return []
        vals = []
        for el in it.elts:
            if pos == -1:
                if isinstance(el, ast.Constant) and \
                        isinstance(el.value, str):
                    vals.append(el.value)
            elif isinstance(el, (ast.Tuple, ast.List)) and \
                    pos < len(el.elts):
                item = el.elts[pos]
                if isinstance(item, ast.Constant) and \
                        isinstance(item.value, str):
                    vals.append(item.value)
        return vals
    return []


def _registrations(mod) -> List[Tuple[str, ast.Call, Optional[str]]]:
    """(op_name, call_node, fn_source_name) triples statically provable
    in this module. fn_source_name is the module-level function the
    second argument resolves to ('<same>' when it equals op_name via
    globals()[var])."""
    if mod.relpath.endswith("ops/registry.py"):
        return []       # the definition site, not a user
    wrappers = _register_wrappers(mod)
    reg_names = {"register"} | wrappers
    out = []
    for node in ast.walk(mod.tree):
        if not (isinstance(node, ast.Call)
                and call_name(node) in reg_names and node.args):
            continue
        # inside a wrapper definition, the register(name, ...) call's
        # name is the wrapper's parameter — skip; the wrapper's callers
        # are the real registration sites
        fn = mod.enclosing_function(node)
        if fn is not None and fn.name in wrappers and \
                call_name(node) == "register":
            continue
        name_arg = node.args[0]
        fn_src = _fn_source(node, name_arg)
        if isinstance(name_arg, ast.Constant) and \
                isinstance(name_arg.value, str):
            out.append((name_arg.value, node, fn_src))
        elif isinstance(name_arg, ast.Name):
            for v in _loop_values_for(mod, node, name_arg.id):
                out.append((v, node, fn_src))
    return out


def _fn_source(call: ast.Call, name_arg) -> Optional[str]:
    """How the registered callable is named: a plain Name, or '<same>'
    for the globals()[<name var>] idiom (fn name == op name)."""
    if len(call.args) < 2:
        return None
    fn_arg = call.args[1]
    if isinstance(fn_arg, ast.Name):
        return fn_arg.id
    if isinstance(fn_arg, ast.Subscript) and \
            isinstance(fn_arg.value, ast.Call) and \
            call_name(fn_arg.value) == "globals" and \
            isinstance(name_arg, ast.Name):
        sl = fn_arg.slice
        if isinstance(sl, ast.Name) and sl.id == name_arg.id:
            return "<same>"
    return None


@rule("PT401", "error",
      "duplicate op registration: register() overwrites silently, the "
      "surviving kernel is decided by import order", scope="project")
def check_duplicate_registrations(project):
    seen: Dict[str, Tuple[str, int]] = {}
    for mod in project.modules:
        for name, call, _src in _registrations(mod):
            prev = seen.get(name)
            here = (mod.relpath, call.lineno)
            if prev is not None and prev != here:
                yield (mod, call.lineno, call.col_offset,
                       f"op '{name}' registered here and at "
                       f"{prev[0]}:{prev[1]}; register() overwrites "
                       f"silently — rename one or drop the loser")
            else:
                seen[name] = here


def _signature_problem(fn) -> Optional[str]:
    """Why this def can't be called through apply(fn, *tensors)."""
    a = fn.args
    n_pos = len(a.posonlyargs) + len(a.args)
    if n_pos == 0 and a.vararg is None:
        return "takes no positional arguments, so apply(fn, tensor) " \
               "cannot pass the operand"
    required_kwonly = [p.arg for p, d in zip(a.kwonlyargs, a.kw_defaults)
                       if d is None]
    if required_kwonly:
        return (f"has required keyword-only parameter(s) "
                f"{required_kwonly} the dispatcher funnel never passes")
    for node in ast.walk(fn):
        if isinstance(node, (ast.Yield, ast.YieldFrom)):
            owner = node
            while owner is not None and not isinstance(
                    owner, (ast.FunctionDef, ast.AsyncFunctionDef,
                            ast.Lambda)):
                owner = getattr(owner, "_pt_parent", None)
            if owner is fn:
                return "is a generator; generators cannot be traced " \
                       "through the dispatch funnel"
    return None


@rule("PT402", "error",
      "registered op whose signature cannot satisfy the dispatcher "
      "funnel (core.dispatch.apply)")
def check_registered_signatures(mod):
    for name, call, fn_src in _registrations(mod):
        if fn_src is None:
            continue
        target_name = name if fn_src == "<same>" else fn_src
        fn = mod.functions.get(target_name)
        if fn is None:
            continue
        problem = _signature_problem(fn)
        if problem:
            yield (call.lineno, call.col_offset,
                   f"registered op '{name}' -> {target_name}() "
                   f"{problem}")


# ---------------------------------------------------------------------------
# PT403 — metric names vs tools/trace_report.py KNOWN_METRICS
# ---------------------------------------------------------------------------

def _find_known_metrics(start_path: str) -> Optional[Tuple[str, List[str]]]:
    """Walk up from a module path for tools/trace_report.py and pull its
    KNOWN_METRICS literal (statically — the linter imports nothing)."""
    cur = os.path.dirname(os.path.abspath(start_path))
    for _ in range(12):
        cand = os.path.join(cur, "tools", "trace_report.py")
        if os.path.isfile(cand):
            try:
                tree = ast.parse(open(cand, encoding="utf-8").read())
            except SyntaxError:
                return None
            for node in tree.body:
                if isinstance(node, ast.Assign):
                    for t in node.targets:
                        if isinstance(t, ast.Name) and \
                                t.id == "KNOWN_METRICS":
                            v = node.value
                            if isinstance(v, ast.Call) and v.args:
                                v = v.args[0]   # frozenset({...})
                            if isinstance(v, (ast.Set, ast.List,
                                              ast.Tuple)):
                                return cand, [
                                    e.value for e in v.elts
                                    if isinstance(e, ast.Constant)
                                    and isinstance(e.value, str)]
            return None
        nxt = os.path.dirname(cur)
        if nxt == cur:
            return None
        cur = nxt
    return None


def _is_metrics_receiver(node) -> bool:
    """`_metrics.counter`, `metrics.gauge`, `profiler.metrics.inc`, ..."""
    if isinstance(node, ast.Name):
        return node.id in ("_metrics", "metrics")
    if isinstance(node, ast.Attribute):
        return node.attr in ("metrics", "_metrics")
    return False


@rule("PT403", "warning",
      "metric name emitted in code but absent from "
      "tools/trace_report.py KNOWN_METRICS")
def check_metric_names(mod):
    found = _find_known_metrics(mod.path)
    if found is None:
        return
    _, known = found
    for node in ast.walk(mod.tree):
        if not (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr in _METRIC_FACTORIES | _METRIC_EMITTERS
                and _is_metrics_receiver(node.func.value)
                and node.args):
            continue
        arg = node.args[0]
        if not (isinstance(arg, ast.Constant)
                and isinstance(arg.value, str)):
            continue    # dynamic name: covered by '*' patterns
        if not match_known(arg.value, known):
            yield (node.lineno, node.col_offset,
                   f"metric '{arg.value}' is not in "
                   f"tools/trace_report.py KNOWN_METRICS — the triage "
                   f"report and README metric inventory won't know it; "
                   f"add it there (or fix the name)")


# ---------------------------------------------------------------------------
# PT404 — span names passed to tracing helpers must be literal strings
# ---------------------------------------------------------------------------

_SPAN_HELPERS = {"span", "phase", "record_span"}


def _is_tracing_receiver(node) -> bool:
    """`tracing.span`, `_tracing.record_span`, `profiler.tracing.span`"""
    if isinstance(node, ast.Name):
        return node.id in ("tracing", "_tracing")
    if isinstance(node, ast.Attribute):
        return node.attr in ("tracing", "_tracing")
    return False


@rule("PT404", "warning",
      "span name built at runtime: tracing helpers aggregate by name, "
      "so non-literal names explode trace cardinality")
def check_span_name_literals(mod):
    if mod.relpath.endswith("profiler/tracing.py"):
        return      # the definition site forwards caller-supplied names
    for node in ast.walk(mod.tree):
        if not isinstance(node, ast.Call) or not node.args:
            continue
        f = node.func
        is_helper = (isinstance(f, ast.Attribute)
                     and f.attr in _SPAN_HELPERS
                     and _is_tracing_receiver(f.value)) \
            or (isinstance(f, ast.Name) and f.id == "RecordEvent") \
            or (isinstance(f, ast.Attribute) and f.attr == "RecordEvent")
        if not is_helper:
            continue
        arg = node.args[0]
        if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
            continue
        # literal family prefix: "op::" + name stays aggregatable
        if isinstance(arg, ast.BinOp) and isinstance(arg.op, ast.Add) \
                and isinstance(arg.left, ast.Constant) \
                and isinstance(arg.left.value, str) and arg.left.value:
            continue
        helper = f.attr if isinstance(f, ast.Attribute) else f.id
        yield (node.lineno, node.col_offset,
               f"span name passed to {helper}() is not a string "
               f"literal — span names are the aggregation key for the "
               f"flight recorder, trace merge, and span summary; put "
               f"variable data in the span's args instead")
