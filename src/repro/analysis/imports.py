"""Static import-graph report: which ``src/repro`` modules are dead weight.

Parses every module under ``src/repro`` with ``ast`` (nothing is
imported or executed), resolves ``import``/``from``-imports — including
relative and function-local ones — to edges between repo modules, and
walks reachability from the engine's entry packages
(:data:`ROOT_PACKAGES`). Modules no root can reach are *unreachable*:
nothing the engine, the experiment registry or the coordinator runs can
ever import them.

The report is *actionable*, not informational: every unreachable module
must either be wired into an entry package or carry an explicit
:data:`QUARANTINED` entry naming why it is parked. An unreachable module
with no quarantine entry — or a quarantine entry that went stale (its
modules vanished or became reachable) — exits nonzero, which is what the
CI ``lint`` leg gates on (``python -m repro.analysis --imports``).

Resolution rules:

  * ``from repro.a.b import c`` edges to ``repro.a.b.c`` when that is a
    module, else to ``repro.a.b``;
  * importing ``repro.a.b`` also edges to package ``repro.a`` (its
    ``__init__`` runs) — namespace dirs without an ``__init__.py`` (e.g.
    ``repro`` itself, ``coord``, ``serve``) contribute no such edge;
  * relative imports resolve against the importing module's package;
  * imports of modules outside ``src/repro`` are ignored.

>>> g = build_graph()
>>> "repro.core.sim" in g.modules
True
>>> "repro.kernels.event_loop.i32pair" in g.reachable()
True
"""
from __future__ import annotations

import ast
from dataclasses import dataclass, field
from pathlib import Path

__all__ = ["ROOT_PACKAGES", "QUARANTINED", "ImportGraph", "build_graph",
           "report", "classify"]

#: reachability roots: the packages whose public surface the engine, the
#: scenario registry and the coordinator expose. For a namespace package
#: (no ``__init__.py``) the roots are its direct child modules.
#: ``repro.analysis.__main__`` is the lint CLI itself — an executable
#: entry, reached by ``python -m``, not by imports.
ROOT_PACKAGES = ("repro.core", "repro.kernels", "repro.workloads",
                 "repro.experiments", "repro.coord",
                 "repro.analysis", "repro.analysis.__main__")

#: Explicitly parked module trees: unreachable from every root *on
#: purpose*, with the reason recorded here. A prefix covers the module
#: itself and everything below it. Anything unreachable and NOT covered
#: fails the ``--imports`` gate; so does a stale entry (no unreachable
#: module under the prefix anymore — delete the entry when the tree is
#: wired in or removed).
QUARANTINED: dict[str, str] = {
    # the dead seed stack (repro.models / repro.configs / repro.serve,
    # plus the empty repro.train / repro.launch dirs) was deleted outright
    "repro.core.tla": "TLA+ spec emitter — developer tooling invoked by "
                      "hand, deliberately outside the engine's import "
                      "surface",
    "repro.kernels.alock_tick": "superseded by kernels.event_loop (the "
                                "event-driven engine); retained for the "
                                "kernel-evolution narrative in docs",
    "repro.kernels.flash_attention": "exemplar Pallas kernel from the "
                                     "seed, unrelated to the lock "
                                     "simulator; reference material only",
    "repro.kernels.ssd_scan": "exemplar Pallas kernel from the seed, "
                              "unrelated to the lock simulator; "
                              "reference material only",
}


def _src_root() -> Path:
    return Path(__file__).resolve().parent.parent


@dataclass
class ImportGraph:
    modules: dict = field(default_factory=dict)   # name -> Path
    edges: dict = field(default_factory=dict)     # name -> set[str]

    def roots(self) -> list:
        out = []
        for pkg in ROOT_PACKAGES:
            if pkg in self.modules:               # real package: __init__
                out.append(pkg)
            else:                                 # namespace: direct children
                prefix = pkg + "."
                out += [m for m in self.modules
                        if m.startswith(prefix)
                        and "." not in m[len(prefix):]]
        return sorted(set(out))

    def reachable(self) -> set:
        seen, todo = set(), list(self.roots())
        while todo:
            m = todo.pop()
            if m in seen:
                continue
            seen.add(m)
            todo += [d for d in self.edges.get(m, ()) if d not in seen]
        return seen

    def unreachable(self) -> list:
        return sorted(set(self.modules) - self.reachable())


def _module_name(path: Path, src: Path) -> str:
    rel = path.relative_to(src).with_suffix("")
    parts = ("repro",) + rel.parts
    if parts[-1] == "__init__":
        parts = parts[:-1]
    return ".".join(parts)


def _resolve(target: str, modules: dict) -> list:
    """Longest known prefix of a dotted import target (with its package
    chain), or [] for anything outside the repo."""
    out = []
    parts = target.split(".")
    for i in range(len(parts), 0, -1):
        cand = ".".join(parts[:i])
        if cand in modules:
            out.append(cand)
            # packages up the chain run their __init__ on import
            for j in range(i - 1, 0, -1):
                pkg = ".".join(parts[:j])
                if pkg in modules:
                    out.append(pkg)
            break
    return out


def build_graph(src: Path | None = None) -> ImportGraph:
    src = Path(src) if src is not None else _src_root()
    g = ImportGraph()
    for path in sorted(src.rglob("*.py")):
        g.modules[_module_name(path, src)] = path
    for name, path in g.modules.items():
        deps = g.edges.setdefault(name, set())
        try:
            tree = ast.parse(path.read_text(encoding="utf-8"))
        except SyntaxError:
            continue
        pkg_parts = name.split(".")[:-1] if not _is_pkg(name, g.modules) \
            else name.split(".")
        for node in ast.walk(tree):
            if isinstance(node, ast.Import):
                for alias in node.names:
                    deps.update(_resolve(alias.name, g.modules))
            elif isinstance(node, ast.ImportFrom):
                if node.level:                    # relative import
                    base = pkg_parts[:len(pkg_parts) - node.level + 1]
                    mod = ".".join(base + ([node.module]
                                           if node.module else []))
                else:
                    mod = node.module or ""
                for alias in node.names:
                    hits = _resolve(f"{mod}.{alias.name}", g.modules) \
                        or _resolve(mod, g.modules)
                    deps.update(hits)
        deps.discard(name)
    return g


def _is_pkg(name: str, modules: dict) -> bool:
    path = modules.get(name)
    return path is not None and path.name == "__init__.py"


def _covering(module: str) -> str | None:
    """The QUARANTINED prefix covering ``module``, if any."""
    for prefix in QUARANTINED:
        if module == prefix or module.startswith(prefix + "."):
            return prefix
    return None


def classify(src: Path | None = None) -> tuple:
    """Split the graph's unreachable set against :data:`QUARANTINED`.

    Returns ``(quarantined, unexpected, stale)``: unreachable modules
    covered by a quarantine prefix, unreachable modules covered by
    nothing (gate failures), and quarantine prefixes that no longer
    cover any unreachable module (stale entries — also gate failures).
    """
    g = build_graph(src)
    dead = g.unreachable()
    quarantined = [m for m in dead if _covering(m)]
    unexpected = [m for m in dead if not _covering(m)]
    hit = {_covering(m) for m in quarantined}
    stale = sorted(p for p in QUARANTINED if p not in hit)
    return quarantined, unexpected, stale


def report(src: Path | None = None) -> tuple:
    """The ``--imports`` gate: ``(human-readable text, exit code)``.

    Exit 0 iff every unreachable module is explicitly quarantined and
    every quarantine entry still earns its keep.
    """
    g = build_graph(src)
    quarantined, unexpected, stale = classify(src)
    dead = g.unreachable()
    rel = _src_root()
    lines = [f"import graph: {len(g.modules)} modules under src/repro, "
             f"{len(g.roots())} roots, "
             f"{len(g.reachable())} reachable, {len(dead)} unreachable "
             f"({len(quarantined)} quarantined, {len(unexpected)} "
             f"unexpected)",
             f"roots: {', '.join(ROOT_PACKAGES)}", ""]
    if quarantined:
        lines.append("quarantined (unreachable on purpose — see "
                     "repro.analysis.imports.QUARANTINED):")
        last = None
        for m in quarantined:
            prefix = _covering(m)
            if prefix != last:
                lines.append(f"  [{prefix}] {QUARANTINED[prefix]}")
                last = prefix
            lines.append(f"    {m}  ({g.modules[m].relative_to(rel)})")
        lines.append("")
    if unexpected:
        lines.append("UNEXPECTED unreachable modules — wire them into an "
                     "entry package, delete them, or quarantine them "
                     "with a reason:")
        for m in unexpected:
            lines.append(f"  {m}  ({g.modules[m].relative_to(rel)})")
        lines.append("")
    if stale:
        lines.append("STALE quarantine entries — every module under the "
                     "prefix is now reachable (or gone); delete the "
                     "entry:")
        for p in stale:
            lines.append(f"  {p}")
        lines.append("")
    ok = not unexpected and not stale
    lines.append("imports gate: "
                 + ("clean." if ok else "FAILED (see above)."))
    return "\n".join(lines), (0 if ok else 1)
