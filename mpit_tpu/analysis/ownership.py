"""MT-D9xx — buffer ownership across the donation seam.

The PR 13 bug class: ``HbmSlot.apply_wire_chunk`` hands its grad
argument to a donated jit via ``jnp.asarray``, which on the CPU backend
*aliases* aligned host memory instead of copying.  If the caller passes
a view into a receive ring (``as_bytes_view`` / ``frombuffer`` /
``split_wire``), the donated apply reads memory the socket loop is
already overwriting — flaky garbage that only shows up under load.  The
fix was an ownership seam (``_chunk_owned`` / ``device_copy``); this
module makes the seam machine-checked instead of conventional.

A small ownership lattice is evaluated over the shared call graph
(mpit_tpu.analysis.callgraph) at every *declared* sink (the
OwnedSink/OwnedPath/DonatedSlot rows in
mpit_tpu.analysis.disciplines):

- **OWNED** — freshly allocated or explicitly copied: ``_chunk_owned``,
  ``device_copy``, ``np.array/empty/zeros/...``, ``.copy()``, a receive
  frame lent out of its rotation (``_GradFrames.lend``), or a same-file
  helper all of whose returns classify OWNED.
- **UNOWNED** — a view into memory someone else recycles:
  ``as_bytes_view``, ``frombuffer``, ``memoryview``, ``split_wire``,
  or ``.view()`` of a non-owned base.
- **UNKNOWN** — a parameter, attribute or expression the lattice cannot
  classify.  At a declared sink, UNKNOWN is still a finding: the
  registry says this path must be *provably* owned.

Rules: **MT-D901** an UNOWNED buffer reaches a donated apply argument;
**MT-D902** a reader of a donated slot uses the bare device buffer
outside any materialize/replicate call; **MT-D903** the declared
ownership wrapper is dropped (an OwnedPath inner call escapes its
wrapper, or a sink argument classifies UNKNOWN).
"""

from __future__ import annotations

import ast
from typing import List, Optional, Sequence, Set, Tuple

from mpit_tpu.analysis import callgraph, disciplines
from mpit_tpu.analysis.core import (ERROR, Finding, SourceFile, callee_name,
                                    register_rules)

register_rules({
    "MT-D901": (ERROR, "unowned buffer view reaches a donated apply"),
    "MT-D902": (ERROR, "donated slot read without materialize guard"),
    "MT-D903": (ERROR, "ownership wrapper dropped on a declared owned path"),
})

OWNED, UNOWNED, UNKNOWN = "owned", "unowned", "unknown"

#: calls that hand back freshly owned memory — or, ``lend``, memory
#: that is out of the receive rotation until the apply handed it has
#: run (ps/server.py ``_GradFrames``): owned for as long as it is read.
_OWNING_CALLS = {
    "_chunk_owned", "lend", "device_copy", "_device_copy", "copy", "deepcopy",
    "empty", "zeros", "ones", "full", "array", "arange", "concatenate",
    "stack", "empty_like", "zeros_like", "ones_like", "full_like",
    "frombuffer_copy", "tobytes",
}
#: calls that alias recycled memory (the receive-ring producers).
_UNOWNED_CALLS = {
    "as_bytes_view", "frombuffer", "memoryview", "getbuffer", "split_wire",
}
#: ownership-transparent calls: classify their first argument.
_PASSTHROUGH_CALLS = {"asarray", "ascontiguousarray", "place_flat"}
#: ownership-transparent methods: classify their receiver.
_PASSTHROUGH_METHODS = {"view", "reshape", "ravel", "squeeze", "astype"}


def _combine(states: Sequence[str]) -> str:
    if any(s == UNOWNED for s in states):
        return UNOWNED
    if states and all(s == OWNED for s in states):
        return OWNED
    return UNKNOWN


def _resolve(graph: callgraph.CallGraph, fn: callgraph.FnInfo,
             call: ast.Call) -> List[callgraph.FnInfo]:
    """Same-file resolution for a raw ast.Call (mirrors
    CallGraph.resolve's bare/self/cls receiver rule)."""
    func = call.func
    if isinstance(func, ast.Name):
        receiver = ""
    elif isinstance(func, ast.Attribute):
        if not (isinstance(func.value, ast.Name)
                and func.value.id in ("self", "cls")):
            return []
        receiver = func.value.id
    else:
        return []
    del receiver
    name = callee_name(call)
    return graph.by_file.get(fn.src.rel, {}).get(name or "", [])


def classify(expr: ast.AST, fn: callgraph.FnInfo,
             graph: callgraph.CallGraph,
             _seen: Optional[Set[Tuple[int, int]]] = None
             ) -> Tuple[str, str]:
    """(state, why) for an expression evaluated inside ``fn``."""
    seen = _seen if _seen is not None else set()
    key = (id(fn.node), id(expr))
    if key in seen:
        return UNKNOWN, "recursive binding"
    seen.add(key)

    if isinstance(expr, ast.Call):
        name = callee_name(expr) or ""
        if name in _UNOWNED_CALLS:
            return UNOWNED, f"{name}() view (line {expr.lineno})"
        if name in _OWNING_CALLS:
            return OWNED, f"{name}() copy"
        if name in _PASSTHROUGH_CALLS:
            if expr.args:
                state, why = classify(expr.args[0], fn, graph, seen)
                return state, f"{name}() of {why}"
            return UNKNOWN, f"{name}() without arguments"
        if (name in _PASSTHROUGH_METHODS
                and isinstance(expr.func, ast.Attribute)):
            state, why = classify(expr.func.value, fn, graph, seen)
            return state, f".{name}() of {why}"
        targets = _resolve(graph, fn, expr)
        if targets:
            states, whys = [], []
            for target in targets:
                if not target.returns:
                    return UNKNOWN, f"{name}() returns nothing trackable"
                for ret in target.returns:
                    state, why = classify(ret, target, graph, seen)
                    states.append(state)
                    whys.append(why)
            return _combine(states), f"{name}() -> {whys[0]}"
        return UNKNOWN, f"call to {name}() (line {expr.lineno})"

    if isinstance(expr, ast.Name):
        if expr.id in fn.params:
            return UNKNOWN, f"parameter '{expr.id}'"
        bindings = fn.assigns.get(expr.id)
        if bindings:
            states, whys = [], []
            for value in bindings:
                state, why = classify(value, fn, graph, seen)
                states.append(state)
                whys.append(why)
            bad = next((w for s, w in zip(states, whys) if s == UNOWNED),
                       whys[0])
            return _combine(states), f"'{expr.id}' = {bad}"
        return UNKNOWN, f"unbound name '{expr.id}'"

    if isinstance(expr, (ast.List, ast.Tuple)):
        if not expr.elts:
            return OWNED, "empty literal"
        states, whys = zip(*(classify(e, fn, graph, seen)
                             for e in expr.elts))
        return _combine(states), whys[0]

    if isinstance(expr, (ast.ListComp, ast.GeneratorExp)):
        state, why = classify(expr.elt, fn, graph, seen)
        return state, f"comprehension of {why}"

    if isinstance(expr, ast.IfExp):
        states, whys = zip(*(classify(e, fn, graph, seen)
                             for e in (expr.body, expr.orelse)))
        return _combine(states), whys[0]

    if isinstance(expr, ast.Starred):
        return classify(expr.value, fn, graph, seen)

    if isinstance(expr, ast.Attribute):
        try:
            return UNKNOWN, f"attribute {ast.unparse(expr)}"
        except Exception:  # pragma: no cover
            return UNKNOWN, "attribute"

    if isinstance(expr, ast.Subscript):
        # a slice/index of any array is a view of it
        state, why = classify(expr.value, fn, graph, seen)
        if state == UNOWNED:
            return UNOWNED, f"subscript of {why}"
        return UNKNOWN, f"subscript of {why}"

    return UNKNOWN, type(expr).__name__


# -- MT-D901 / MT-D903 at declared sinks -------------------------------------


def sink_sites(graph: callgraph.CallGraph, sink: "disciplines.OwnedSink"
               ) -> List[Tuple[callgraph.FnInfo, callgraph.CallSite]]:
    return [(fn, cs)
            for fn in graph.functions_in(sink.file)
            if not sink.fn or fn.name == sink.fn
            for cs in fn.calls
            if cs.callee == sink.callee
            and sink.receiver.lower() in cs.receiver.lower()
            and len(cs.node.args) > sink.arg]


def sink_findings(graph: callgraph.CallGraph, sink: "disciplines.OwnedSink"
                  ) -> List[Finding]:
    findings = []
    for fn, cs in sink_sites(graph, sink):
        state, why = classify(cs.node.args[sink.arg], fn, graph)
        if state == UNOWNED:
            findings.append(fn.src.finding(
                "MT-D901", cs.line,
                f"{fn.qual} passes an unowned buffer ({why}) as argument "
                f"{sink.arg} of {sink.callee}() at the declared donation "
                f"seam '{sink.name}' — the donated apply aliases it while "
                f"the receive path recycles it; copy via _chunk_owned()/"
                f"device_copy() first"))
        elif state == UNKNOWN:
            findings.append(fn.src.finding(
                "MT-D903", cs.line,
                f"{fn.qual} drops the ownership wrapper at the declared "
                f"donation seam '{sink.name}': argument {sink.arg} of "
                f"{sink.callee}() ({why}) cannot be proven owned — route "
                f"it through _chunk_owned()/device_copy()"))
    return findings


# -- MT-D903 on declared wrapper paths ---------------------------------------


def _inner_calls(fn: callgraph.FnInfo, inner: str, wrapper: str
                 ) -> List[Tuple[ast.Call, bool]]:
    """(inner call, wrapped?) for every ``inner(...)`` in ``fn``:
    wrapped means some enclosing Call's terminal name is ``wrapper``."""
    out = []

    def visit(node: ast.AST, enclosing: Tuple[str, ...]) -> None:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.Lambda)):
            return
        if isinstance(node, ast.Call):
            name = callee_name(node) or ""
            if name == inner:
                out.append((node, wrapper in enclosing))
            enclosing = enclosing + (name,)
        for child in ast.iter_child_nodes(node):
            visit(child, enclosing)

    for child in ast.iter_child_nodes(fn.node):
        visit(child, ())
    return out


def path_sites(graph: callgraph.CallGraph, path: "disciplines.OwnedPath"
               ) -> List[Tuple[callgraph.FnInfo, ast.Call, bool]]:
    return [(fn, call, wrapped)
            for fn in graph.functions_in(path.file, path.fn)
            for call, wrapped in _inner_calls(fn, path.inner, path.wrapper)]


def path_findings(graph: callgraph.CallGraph, path: "disciplines.OwnedPath"
                  ) -> List[Finding]:
    return [fn.src.finding(
        "MT-D903", call.lineno,
        f"{fn.qual} calls {path.inner}() outside the declared "
        f"{path.wrapper}() wrapper of owned path '{path.name}' — the "
        f"result aliases host memory that enters the donated apply "
        f"chain; {path.doc}")
        for fn, call, wrapped in path_sites(graph, path) if not wrapped]


# -- MT-D902 on donated slot readers -----------------------------------------


def slot_fns(graph: callgraph.CallGraph, slot: "disciplines.DonatedSlot"
             ) -> List[callgraph.FnInfo]:
    return [fn for name in slot.fns
            for fn in graph.functions_in(slot.file, name)]


def slot_findings(graph: callgraph.CallGraph, slot: "disciplines.DonatedSlot"
                  ) -> List[Finding]:
    findings = []
    for fn in slot_fns(graph, slot):

        def visit(node: ast.AST, in_call: bool) -> None:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.Lambda)):
                return
            if (isinstance(node, ast.Attribute)
                    and isinstance(node.ctx, ast.Load)
                    and isinstance(node.value, ast.Name)
                    and node.value.id == "self"
                    and node.attr in slot.attrs
                    and not in_call):
                findings.append(fn.src.finding(
                    "MT-D902", node.lineno,
                    f"{fn.qual} uses the donated slot self.{node.attr} "
                    f"outside any materialize/replicate call (discipline "
                    f"'{slot.name}') — the next apply donates the buffer "
                    f"out from under the exposed reference; wrap it in "
                    f"np.asarray()/device_copy() before it escapes"))
            inside = in_call or isinstance(node, ast.Call)
            for child in ast.iter_child_nodes(node):
                visit(child, inside)

        for child in ast.iter_child_nodes(fn.node):
            visit(child, False)
    return findings


# -- engine entry ------------------------------------------------------------


def check(files: Sequence[SourceFile],
          graph: Optional[callgraph.CallGraph] = None) -> List[Finding]:
    if graph is None:
        graph = callgraph.build_graph(files)
    findings: List[Finding] = []
    for sink in disciplines.SINKS:
        findings += sink_findings(graph, sink)
    for path in disciplines.PATHS:
        findings += path_findings(graph, path)
    for slot in disciplines.SLOTS:
        findings += slot_findings(graph, slot)
    return findings
