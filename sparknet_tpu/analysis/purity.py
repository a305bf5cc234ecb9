"""TP rules — trace purity.

Functions reachable from a trace root must be pure with respect to the
host: an ``os.environ`` read inside a jitted function evaluates once at
trace time and bakes a constant into the executable (making jit cache
keys lie); clocks, host RNG, file IO and ``print`` similarly
run at trace time, not step time.

Trace roots recognised (project conventions included):

- ``@jax.jit`` / ``@partial(jax.jit, ...)`` / ``@jax.custom_vjp`` /
  ``@jax.custom_jvp`` / ``@jax.remat`` decorated functions
- functions passed to ``jit`` / ``grad`` / ``value_and_grad`` /
  ``vmap`` / ``pmap`` / ``pallas_call`` / ``checkpoint`` call sites
- both arguments of ``f.defvjp(fwd, bwd)``
- ``apply`` methods of ``@register_layer`` classes (the layer registry
  dispatches through a dict, which a name-based call graph cannot see,
  but every ``apply`` runs under the jitted step)

Reachability is a name-based intra-project call graph: calls through
locals, ``self``, imported modules and ``from``-imported functions are
followed; dynamic dispatch stops the walk (sound-enough in practice —
the registry ``apply`` convention above plugs the one big hole).

Rules:
  TP001  env read under trace (os.environ / os.getenv / knobs.*)
  TP002  clock read under trace (time.time/perf_counter/...)
  TP003  host RNG under trace (random.* / np.random.* / os.urandom)
  TP004  file IO under trace (open / io.open / Path.read_text...)
  TP005  print under trace
  TP006  np.asarray/np.array of a function parameter (forces a tracer
         to host — ConcretizationError at best, silent const at worst)
"""

from __future__ import annotations

import ast

from .core import Finding, Project, SourceFile, dotted

SEVERITY = "error"

_ROOT_DECOS = ("jit", "custom_vjp", "custom_jvp", "remat")
_ROOT_CALLS = {"jit", "grad", "value_and_grad", "vmap", "pmap",
               "pallas_call", "checkpoint", "remat"}
_CLOCK_CALLS = {"time", "perf_counter", "perf_counter_ns", "monotonic",
                "monotonic_ns", "process_time", "sleep", "time_ns"}
_FILE_CALLS = {"open"}
_PATH_IO_ATTRS = {"read_text", "read_bytes", "write_text", "write_bytes"}
_KNOB_ACCESSORS = {"raw", "is_set", "get_str", "get_int", "get_float",
                   "get_bool"}


class _Module:
    """Per-file indexes: functions by qualname, classes/methods, and
    the import alias maps used for cross-module call resolution."""

    def __init__(self, sf: SourceFile) -> None:
        self.sf = sf
        self.funcs: dict[str, ast.AST] = {}          # top-level name -> node
        self.methods: dict[tuple[str, str], ast.AST] = {}  # (cls, m) -> node
        self.layer_classes: list[str] = []           # @register_layer classes
        self.mod_alias: dict[str, str] = {}          # name -> dotted module
        self.sym_import: dict[str, tuple[str, str]] = {}  # name -> (mod, sym)
        self._index()

    def _index(self) -> None:
        sf = self.sf
        for node in ast.walk(sf.tree):
            if isinstance(node, ast.Import):
                for alias in node.names:
                    local = alias.asname or alias.name.split(".")[0]
                    self.mod_alias[local] = (alias.name if alias.asname
                                             else alias.name.split(".")[0])
            elif isinstance(node, ast.ImportFrom):
                base = self._resolve_from(node)
                for alias in node.names:
                    if alias.name == "*":
                        continue
                    local = alias.asname or alias.name
                    self.sym_import[local] = (base, alias.name)
        for child in ast.iter_child_nodes(sf.tree):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self.funcs[child.name] = child
            elif isinstance(child, ast.ClassDef):
                is_layer = any(
                    dotted(d.func if isinstance(d, ast.Call) else d)
                    .endswith("register_layer") for d in child.decorator_list)
                if is_layer:
                    self.layer_classes.append(child.name)
                for item in child.body:
                    if isinstance(item, (ast.FunctionDef,
                                         ast.AsyncFunctionDef)):
                        self.methods[(child.name, item.name)] = item

    def _resolve_from(self, node: ast.ImportFrom) -> str:
        if node.level == 0:
            return node.module or ""
        parts = self.sf.module.split(".")
        # level 1 strips the module name itself; for package __init__
        # files sf.module IS the package, so one less to strip
        drop = node.level - (1 if self.sf.rel.endswith("__init__.py")
                             else 0)
        base_parts = parts[:len(parts) - drop] if drop else parts
        if node.module:
            base_parts = base_parts + node.module.split(".")
        return ".".join(base_parts)


class _CallGraph:
    def __init__(self, project: Project) -> None:
        self.project = project
        self.mods = {sf.module: _Module(sf) for sf in project.files}
        # node identity: (module, qualname)
        self.nodes: dict[tuple[str, str], ast.AST] = {}
        for mname, m in self.mods.items():
            for fname, fnode in m.funcs.items():
                self.nodes[(mname, fname)] = fnode
            for (cls, meth), fnode in m.methods.items():
                self.nodes[(mname, f"{cls}.{meth}")] = fnode

    # -- root discovery -----------------------------------------------------

    def roots(self) -> set[tuple[str, str]]:
        out: set[tuple[str, str]] = set()
        for mname, m in self.mods.items():
            for key, fnode in self._iter_defs(m):
                if self._has_root_deco(fnode):
                    out.add((mname, key))
            for cls in m.layer_classes:
                for meth in ("apply",):
                    if (cls, meth) in m.methods:
                        out.add((mname, f"{cls}.{meth}"))
            # call-site roots: jit(f), grad(f), f.defvjp(fwd, bwd), ...
            for node in ast.walk(m.sf.tree):
                if not isinstance(node, ast.Call):
                    continue
                name = dotted(node.func)
                leaf = name.rpartition(".")[2]
                args: list[ast.AST] = []
                if leaf in _ROOT_CALLS:
                    args = list(node.args[:1])
                elif leaf == "defvjp":
                    args = list(node.args[:2])
                for a in args:
                    tgt = self._resolve_ref(m, a, enclosing_cls=None)
                    if tgt:
                        out.add(tgt)
        return out

    @staticmethod
    def _iter_defs(m: "_Module"):
        for fname, fnode in m.funcs.items():
            yield fname, fnode
        for (cls, meth), fnode in m.methods.items():
            yield f"{cls}.{meth}", fnode

    @staticmethod
    def _has_root_deco(fnode: ast.AST) -> bool:
        for d in getattr(fnode, "decorator_list", ()):
            target = d.func if isinstance(d, ast.Call) else d
            name = dotted(target)
            leaf = name.rpartition(".")[2]
            if leaf in _ROOT_DECOS:
                return True
            # @partial(jax.jit, ...): the root marker is the first arg
            if leaf == "partial" and isinstance(d, ast.Call) and d.args:
                if dotted(d.args[0]).rpartition(".")[2] in _ROOT_DECOS:
                    return True
        return False

    # -- edge resolution ----------------------------------------------------

    def _module_for_alias(self, m: _Module, name: str) -> str | None:
        if name in m.mod_alias:
            cand = m.mod_alias[name]
            if cand in self.mods:
                return cand
        if name in m.sym_import:
            mod, sym = m.sym_import[name]
            if f"{mod}.{sym}" in self.mods:
                return f"{mod}.{sym}"
        return None

    def _resolve_ref(self, m: _Module, node: ast.AST,
                     enclosing_cls: str | None) -> tuple[str, str] | None:
        """A function reference (not a call) -> call-graph node."""
        if isinstance(node, ast.Name):
            if node.id in m.funcs:
                return (m.sf.module, node.id)
            if node.id in m.sym_import:
                mod, sym = m.sym_import[node.id]
                if (mod, sym) in self.nodes:
                    return (mod, sym)
        elif isinstance(node, ast.Attribute) and \
                isinstance(node.value, ast.Name):
            base = node.value.id
            if base == "self" and enclosing_cls:
                key = (m.sf.module, f"{enclosing_cls}.{node.attr}")
                if key in self.nodes:
                    return key
            tmod = self._module_for_alias(m, base)
            if tmod and (tmod, node.attr) in self.nodes:
                return (tmod, node.attr)
        return None

    def edges(self, mname: str, qual: str) -> set[tuple[str, str]]:
        m = self.mods[mname]
        fnode = self.nodes[(mname, qual)]
        cls = qual.split(".")[0] if "." in qual else None
        out: set[tuple[str, str]] = set()
        for node in ast.walk(fnode):
            if isinstance(node, ast.Call):
                tgt = self._resolve_ref(m, node.func, enclosing_cls=cls)
                if tgt:
                    out.add(tgt)
        return out

    def reachable(self) -> set[tuple[str, str]]:
        seen = set()
        work = list(self.roots())
        while work:
            key = work.pop()
            if key in seen or key not in self.nodes:
                continue
            seen.add(key)
            work.extend(self.edges(*key))
        return seen


def _param_names(fnode: ast.AST) -> set[str]:
    a = fnode.args
    names = {p.arg for p in (*a.posonlyargs, *a.args, *a.kwonlyargs)}
    names.discard("self")
    names.discard("cls")
    return names


def _check_function(project: Project, m: _Module, qual: str,
                    fnode: ast.AST) -> list[Finding]:
    sf = m.sf
    params = _param_names(fnode)
    findings: list[Finding] = []

    def hit(rule: str, node: ast.AST, msg: str, fix: str) -> None:
        f = project.finding(sf, rule, SEVERITY, node.lineno,
                            f"{msg} (trace-reachable via {qual})", fix)
        if f:
            findings.append(f)

    for node in ast.walk(fnode):
        if isinstance(node, ast.Attribute) and node.attr == "environ" and \
                isinstance(node.value, ast.Name) and node.value.id == "os":
            hit("TP001", node, "os.environ access under trace",
                "read the knob before the traced function and pass the "
                "value in (latch at construction), or baseline a "
                "deliberate trace-time knob")
            continue
        if not isinstance(node, ast.Call):
            continue
        name = dotted(node.func)
        leaf = name.rpartition(".")[2]
        head = name.partition(".")[0]
        if name == "os.getenv":
            hit("TP001", node, "os.getenv under trace",
                "latch the value outside the traced function")
        elif head == "knobs" and leaf in _KNOB_ACCESSORS:
            hit("TP001", node, f"knob read {name}() under trace",
                "latch the knob outside the traced function, or baseline "
                "a deliberate trace-time knob")
        elif head == "time" and leaf in _CLOCK_CALLS:
            hit("TP002", node, f"clock call {name}() under trace",
                "time outside the traced function; a traced clock reads "
                "once at trace time")
        elif (head == "random" or name.startswith("np.random.") or
              name.startswith("numpy.random.") or name == "os.urandom"):
            hit("TP003", node, f"host RNG {name}() under trace",
                "thread a jax.random key through instead")
        elif name in _FILE_CALLS or name == "io.open":
            hit("TP004", node, f"file IO {name}() under trace",
                "load the data before tracing and close over the array")
        elif leaf in _PATH_IO_ATTRS and isinstance(node.func, ast.Attribute):
            hit("TP004", node, f".{leaf}() file IO under trace",
                "load the data before tracing")
        elif name == "print":
            hit("TP005", node, "print under trace",
                "use jax.debug.print, or log outside the traced function")
        elif leaf in ("asarray", "array", "copy") and \
                head in ("np", "numpy") and node.args and \
                isinstance(node.args[0], ast.Name) and \
                node.args[0].id in params:
            hit("TP006", node,
                f"{name}() of parameter {node.args[0].id!r} forces a "
                f"tracer to host",
                "use jnp equivalents on traced values")
    return findings


def check(project: Project) -> list[Finding]:
    graph = _CallGraph(project)
    findings: list[Finding] = []
    seen_sites: set[tuple[str, str, int]] = set()
    for mname, qual in sorted(graph.reachable()):
        m = graph.mods[mname]
        if m.sf.rel == "sparknet_tpu/utils/knobs.py":
            # the sanctioned accessor: every registry read bottoms out in
            # knobs.raw()'s os.environ.get — callers are flagged, not it
            continue
        fnode = graph.nodes[(mname, qual)]
        for f in _check_function(project, m, qual, fnode):
            site = (f.rule, f.path, f.line)
            if site not in seen_sites:  # nested defs overlap parents
                seen_sites.add(site)
                findings.append(f)
    return findings
