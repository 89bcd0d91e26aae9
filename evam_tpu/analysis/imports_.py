"""Package-level import pass: cycles and layers.

PR 7 tied a hub⇄fleet knot that only surfaced at import time; the fix
was a deliberate function-level deferred import.  The cycle check
builds the module graph from *top-level* imports only (deferred
imports inside function bodies are exactly the sanctioned cycle
breakers and are ignored) and reports every strongly-connected
component of size > 1.

The layer check reads EVERY import, deferred ones too: a deferred
import breaks a cycle at import time, it does not change which layer
knows of which.  ``LAYERS`` orders the packages bottom to top; an
import from a package to one above it is ``layer-up:<from>-><to>``,
one finding a package pair.
"""

from __future__ import annotations

import ast
from pathlib import Path

from .core import Finding, SourceFile

#: the packages under evam_tpu/, bottom to top: ONE total order. A
#: package imports only from those before it; what does not yet is an
#: allowlist entry that names its debt.
LAYERS = (
    "config", "obs", "analysis", "native", "ops", "modelproc", "models",
    "parallel", "graph", "media", "sched", "state", "aot", "engine",
    "fleet", "stages", "publish", "extensions", "server", "eii", "cli",
)


def _top_level_imports(tree: ast.Module) -> list[ast.stmt]:
    """Module-body imports, descending through top-level try/if blocks
    (conditional imports still execute at import time)."""
    out: list[ast.stmt] = []
    stack: list[ast.stmt] = list(tree.body)
    while stack:
        node = stack.pop()
        if isinstance(node, (ast.Import, ast.ImportFrom)):
            out.append(node)
        elif isinstance(node, ast.Try):
            stack.extend(node.body + node.orelse + node.finalbody)
            for h in node.handlers:
                stack.extend(h.body)
        elif isinstance(node, ast.If):
            stack.extend(node.body + node.orelse)
    return out


def _edges(sf: SourceFile, known: set[str],
           nodes: list[ast.stmt]) -> set[str]:
    """The intra-package files that the import statements ``nodes`` of
    ``sf`` reach, as repo-relative paths."""
    self_pkg = sf.rel.split("/")[:-1]
    targets: set[str] = set()

    def add_module(parts: list[str], names: list[str] | None) -> None:
        base = "/".join(parts)
        if names is None:
            for cand in (base + ".py", base + "/__init__.py"):
                if cand in known:
                    targets.add(cand)
            return
        # `from pkg import name`: a name that is itself a submodule
        # binds WITHOUT requiring pkg/__init__'s body to finish (the
        # interpreter falls back to the submodule in sys.modules), so
        # it depends only on the submodule.  A plain symbol, on the
        # other hand, must exist on the module object — that is a real
        # edge to the module (or package __init__) body.
        for n in names:
            sub = None
            for cand in (f"{base}/{n}.py", f"{base}/{n}/__init__.py"):
                if cand in known:
                    sub = cand
                    break
            if sub is not None:
                targets.add(sub)
            else:
                for cand in (base + ".py", base + "/__init__.py"):
                    if cand in known:
                        targets.add(cand)

    for node in nodes:
        if isinstance(node, ast.Import):
            for alias in node.names:
                if alias.name == "evam_tpu" or alias.name.startswith("evam_tpu."):
                    add_module(alias.name.split("."), None)
        else:
            names = [a.name for a in node.names]
            if node.level:
                base = self_pkg[:len(self_pkg) - (node.level - 1)]
                if node.module:
                    base = base + node.module.split(".")
                add_module(base, names)
            elif node.module and (node.module == "evam_tpu"
                                  or node.module.startswith("evam_tpu.")):
                add_module(node.module.split("."), names)
    targets.discard(sf.rel)
    return targets


def _tarjan_sccs(graph: dict[str, set[str]]) -> list[list[str]]:
    index: dict[str, int] = {}
    low: dict[str, int] = {}
    on_stack: set[str] = set()
    stack: list[str] = []
    sccs: list[list[str]] = []
    counter = [0]

    def strongconnect(v: str) -> None:
        # iterative Tarjan (the package is deep enough to bust the
        # recursion limit on pathological graphs)
        work = [(v, iter(sorted(graph.get(v, ()))))]
        index[v] = low[v] = counter[0]
        counter[0] += 1
        stack.append(v)
        on_stack.add(v)
        while work:
            node, it = work[-1]
            advanced = False
            for w in it:
                if w not in index:
                    index[w] = low[w] = counter[0]
                    counter[0] += 1
                    stack.append(w)
                    on_stack.add(w)
                    work.append((w, iter(sorted(graph.get(w, ())))))
                    advanced = True
                    break
                elif w in on_stack:
                    low[node] = min(low[node], index[w])
            if advanced:
                continue
            work.pop()
            if work:
                parent = work[-1][0]
                low[parent] = min(low[parent], low[node])
            if low[node] == index[node]:
                scc = []
                while True:
                    w = stack.pop()
                    on_stack.discard(w)
                    scc.append(w)
                    if w == node:
                        break
                sccs.append(scc)

    for v in sorted(graph):
        if v not in index:
            strongconnect(v)
    return sccs


def _package(rel: str) -> str:
    """``evam_tpu/engine/hub.py`` -> ``engine``; ``evam_tpu/native.py``
    -> ``native``."""
    return rel.split("/")[1].removesuffix(".py")


def _layer_findings(files: list[SourceFile],
                    known: set[str]) -> list[Finding]:
    rank = {pkg: i for i, pkg in enumerate(LAYERS)}
    findings: dict[str, Finding] = {}
    for sf in files:
        src = _package(sf.rel)
        if src not in rank or not isinstance(sf.tree, ast.Module):
            continue
        for node in ast.walk(sf.tree):
            if not isinstance(node, (ast.Import, ast.ImportFrom)):
                continue
            for target in sorted(_edges(sf, known, [node])):
                dst = _package(target)
                if rank.get(dst, -1) > rank[src]:
                    ident = f"layer-up:{src}->{dst}"
                    findings.setdefault(ident, Finding(
                        "imports", sf.rel, node.lineno, ident,
                        f"{src} imports {dst}, a layer above it "
                        f"(imports_.LAYERS): hand it in from above or "
                        f"move what is shared down"))
    return list(findings.values())


def run(root: Path, files: list[SourceFile]) -> list[Finding]:
    known = {sf.rel for sf in files}
    graph = {sf.rel: _edges(sf, known, _top_level_imports(sf.tree))
             for sf in files if isinstance(sf.tree, ast.Module)}
    findings = _layer_findings(files, known)
    for scc in _tarjan_sccs(graph):
        if len(scc) < 2:
            continue
        cycle = sorted(scc)
        findings.append(Finding(
            "imports", cycle[0], 1,
            "import-cycle:" + "+".join(cycle),
            "package-level import cycle: " + " <-> ".join(cycle)
            + "; break it with a function-level deferred import"))
    return findings
