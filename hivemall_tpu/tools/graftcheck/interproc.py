"""graftcheck interprocedural layer — call graph + function summaries.

PR 11's rules were intra-module and syntactic: GC02 lost a tainted clock
value the moment it crossed a function boundary, GC04 only saw attribute
writes lexically inside a thread entry method, GC01 never looked at what
a factory's *caller* does with the product. This module gives the rules
a project-wide view without whole-program dataflow: one cheap pass per
file builds a :class:`FunctionSummary` per ``def`` (what it returns,
which attributes it writes on which parameter, which functions it calls
and under which locks, whether it performs a host transfer), a
name-based call graph links the summaries, and small fixpoint loops
close the transitive facts (returns-tainted, returns-fresh-jit).

Resolution is deliberately best-effort and NAME-BASED (no type
inference): ``self.m()`` resolves inside the enclosing class,
``helper()`` to the module's own top-level def or an imported symbol,
``mod.f()`` through the module's import map. Anything unresolvable —
dynamic dispatch, getattr, builtins, third-party — degrades to
"unknown", never to false certainty: a summary field the analysis
cannot prove stays at its conservative default.

Shared low-level AST helpers used by both this pass and the rule
implementations live here (rules.py imports them) so the two layers
agree on what counts as a jit creation, a lock, a thread constructor.
"""

from __future__ import annotations

import ast
import re
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Set, Tuple

__all__ = [
    "FUNCS", "LOOPS", "FunctionSummary", "CallSite", "ModuleInfo",
    "ModuleFacts", "InterProcIndex", "build_index", "extract_module",
    "assemble_index", "dec_name", "is_cache_decorator",
    "is_memo_decorated", "is_jit_name", "is_jit_creation",
    "is_jit_decorator", "is_partial", "is_thread_ctor", "LOCKISH",
    "under_lock", "is_transfer_call", "module_name_of", "call_key",
    "is_acquisition", "donated_positions_of",
]

FUNCS = (ast.FunctionDef, ast.AsyncFunctionDef)
LOOPS = (ast.For, ast.AsyncFor, ast.While)

LOCKISH = re.compile(r"lock|mutex|cond|\b_?cv\b", re.IGNORECASE)

_CACHE_NAMES = {"lru_cache", "_lru_cache", "cache", "cached"}
_FACTORY_NAMES = {"instrument_factory", "_instrument"}

#: host<->device transfer surface GC07 polices: a fetch forces a device
#: sync; inside a per-step loop it serializes the pipeline per iteration
_TRANSFER_ATTRS = {"block_until_ready", "device_get"}

#: compile-wrapper surface GC09 treats as tracing roots: a function
#: handed to any of these has TRACER parameters, not arrays
_TRACE_WRAPPER_NAMES = {"jit", "pjit", "pmap", "shard_map"}

#: attribute reads on a tracer that yield CONCRETE Python values (static
#: under trace) — they KILL tracer taint
_CONCRETE_ATTRS = {"shape", "dtype", "ndim", "size", "weak_type",
                   "sharding", "aval"}

#: numpy module aliases whose calls force host concretization of a
#: tracer (GC09's np-call hazard; jnp is the traced twin)
_NP_ALIASES = {"np", "numpy"}

#: builtins that concretize a tracer argument (TracerConversionError
#: under jit, silent per-trace recompute otherwise)
_CONCRETIZE_BUILTINS = {"float", "int", "bool", "complex"}

#: method calls that force a device sync + host conversion
_CONCRETIZE_METHODS = {"item", "tolist"}

#: resource-acquiring expressions GC12 polices (kind tags for messages).
#: ``open`` is the builtin; the rest are attribute calls on their module
#: or on a socket object.
_ACQUIRE_NAME_CALLS = {"open": "file"}
_ACQUIRE_ATTR_CALLS = {
    # (base name, attr) -> kind; base None = any base object
    ("socket", "socket"): "socket",
    ("socket", "create_connection"): "socket",
    ("socket", "create_server"): "socket",
    ("socket", "socketpair"): "socket",
    ("mmap", "mmap"): "mmap",
    ("os", "fdopen"): "file",
    (None, "makefile"): "file",
    (None, "accept"): "socket",
    # http-level wrappers that own a socket until .close()
    (None, "HTTPConnection"): "http-conn",
    ("request", "urlopen"): "http-response",
    (None, "urlopen"): "http-response",
}


def is_acquisition(node: ast.AST) -> Optional[str]:
    """Resource kind acquired by this Call expression, or None."""
    if not isinstance(node, ast.Call):
        return None
    f = node.func
    if isinstance(f, ast.Name):
        return _ACQUIRE_NAME_CALLS.get(f.id)
    if isinstance(f, ast.Attribute):
        base = f.value.id if isinstance(f.value, ast.Name) else None
        kind = _ACQUIRE_ATTR_CALLS.get((base, f.attr))
        if kind is not None:
            return kind
        return _ACQUIRE_ATTR_CALLS.get((None, f.attr))
    return None


def _int_tuple_literal(node: ast.AST) -> Tuple[int, ...]:
    """(0, 1)-style literal -> ints; anything else -> ()."""
    if isinstance(node, ast.Constant) and isinstance(node.value, int):
        return (node.value,)
    if isinstance(node, (ast.Tuple, ast.List)):
        out = []
        for e in node.elts:
            if isinstance(e, ast.Constant) and isinstance(e.value, int):
                out.append(e.value)
            else:
                return ()
        return tuple(out)
    return ()


def _jit_call_kwargs(node: ast.AST, kw: str) -> Tuple[int, ...]:
    """``donate_argnums``/``static_argnums`` literal of a jit creation:
    ``jax.jit(f, kw=(0,1))``, ``partial(jax.jit, kw=(0,1))(f)`` or the
    same shapes in decorator position."""
    calls: List[ast.Call] = []
    if isinstance(node, ast.Call):
        calls.append(node)
        if isinstance(node.func, ast.Call):
            calls.append(node.func)      # partial(jax.jit, ...)(f)
    for c in calls:
        for k in c.keywords:
            if k.arg == kw:
                got = _int_tuple_literal(k.value)
                if got:
                    return got
    return ()


def donated_positions_of(fn: ast.AST) -> Tuple[int, ...]:
    """donate_argnums positions a def's jit decorator declares, () when
    the def is not donation-jitted (or the literal is not static)."""
    for d in getattr(fn, "decorator_list", []):
        if is_jit_decorator(d):
            got = _jit_call_kwargs(d, "donate_argnums")
            if got:
                return got
    return ()


def dec_name(dec: ast.AST) -> str:
    """The rightmost identifier of a (possibly called) decorator/callee."""
    target = dec.func if isinstance(dec, ast.Call) else dec
    if isinstance(target, ast.Attribute):
        return target.attr
    if isinstance(target, ast.Name):
        return target.id
    return ""


def is_cache_decorator(dec: ast.AST) -> bool:
    return dec_name(dec) in _CACHE_NAMES


def is_memo_decorated(fn: ast.AST) -> bool:
    """lru_cache / instrument_factory on the def: a memoized compile
    factory — jit creations inside it happen once per config key."""
    return any(dec_name(d) in (_CACHE_NAMES | _FACTORY_NAMES)
               for d in getattr(fn, "decorator_list", []))


def is_jit_name(node: ast.AST) -> bool:
    return (isinstance(node, ast.Name) and node.id == "jit") or \
        (isinstance(node, ast.Attribute) and node.attr == "jit")


def is_partial(node: ast.AST) -> bool:
    return isinstance(node, ast.Call) and dec_name(node) in (
        "partial", "_partial")


def is_jit_creation(node: ast.AST) -> bool:
    """A Call producing a jit-compiled callable: ``jax.jit(f)``,
    ``jit(f)``, or ``partial(jax.jit, ...)(f)``."""
    if not isinstance(node, ast.Call):
        return False
    if is_jit_name(node.func):
        return True
    if isinstance(node.func, ast.Call) and is_partial(node.func) \
            and node.func.args and is_jit_name(node.func.args[0]):
        return True
    return False


def is_jit_decorator(dec: ast.AST) -> bool:
    if is_jit_name(dec):
        return True
    if is_partial(dec) and dec.args and is_jit_name(dec.args[0]):
        return True
    if isinstance(dec, ast.Call) and is_jit_name(dec.func):
        return True
    return False


def is_thread_ctor(call: ast.Call) -> bool:
    return dec_name(call) == "Thread"


def is_transfer_call(node: ast.AST) -> bool:
    """``np.asarray(...)``, ``jax.device_get(...)``,
    ``x.block_until_ready()`` — a forced device->host sync."""
    if not isinstance(node, ast.Call):
        return False
    f = node.func
    if isinstance(f, ast.Attribute):
        if f.attr in _TRANSFER_ATTRS:
            return True
        if f.attr == "asarray" and isinstance(f.value, ast.Name) \
                and f.value.id in ("np", "numpy"):
            return True
    elif isinstance(f, ast.Name) and f.id in ("device_get",
                                              "block_until_ready"):
        return True
    return False


def under_lock(ctx: Any, node: ast.AST, top: Optional[ast.AST]) -> bool:
    """Is ``node`` lexically inside a ``with <…lock…>:`` block below
    ``top`` (exclusive)? Shared by GC04 and the summary builder so the
    static guard test is one definition. The per-With verdict is
    memoized on the context — this runs for every call site and every
    attribute write, and unparse is the expensive part."""
    memo = getattr(ctx, "_lockish_withs", None)
    if memo is None:
        memo = {}
        ctx._lockish_withs = memo
    for a in ctx.ancestors(node):
        if isinstance(a, ast.With):
            verdict = memo.get(id(a))
            if verdict is None:
                verdict = False
                for item in a.items:
                    try:
                        src = ast.unparse(item.context_expr)
                    except Exception:  # noqa: BLE001 — odd nodes
                        src = ""
                    if LOCKISH.search(src):
                        verdict = True
                        break
                memo[id(a)] = verdict
            if verdict:
                return True
        if a is top:
            break
    return False


def module_name_of(relpath: str) -> str:
    """Dotted module name a scan-root-relative path imports as:
    ``hivemall_tpu/serve/engine.py`` -> ``hivemall_tpu.serve.engine``,
    ``chip_smoke.py`` -> ``chip_smoke``; packages drop the ``__init__``."""
    p = relpath[:-3] if relpath.endswith(".py") else relpath
    parts = [x for x in p.split("/") if x]
    if parts and parts[-1] == "__init__":
        parts = parts[:-1]
    return ".".join(parts)


# ---------------------------------------------------------------------------
# summaries
# ---------------------------------------------------------------------------

#: a function's identity across the project: (relpath, dotted qualname)
FuncId = Tuple[str, str]


@dataclass
class CallSite:
    """One call expression inside a function body."""
    line: int
    callee: Optional[FuncId]          # resolved target, None = unknown
    under_lock: bool                  # lexically inside `with <lock>:`
    self_arg_positions: Tuple[int, ...] = ()   # positions passing bare
    #                                            `self` (GC04 escape)
    callee_repr: str = ""             # for messages on resolved calls
    #: structural callee key (resolved into ``callee`` once the whole
    #: project's name tables exist — extraction stays per-module pure,
    #: which is what lets the engine fan the summary pass across cores)
    key: Optional[Tuple] = None
    #: positional args carrying param-derived taint: (pos, (param, ...))
    #: — the GC09 propagation edges (a traced value handed to a callee
    #: taints the callee's parameter at that position)
    arg_taints: Tuple[Tuple[int, Tuple[str, ...]], ...] = ()
    #: same for keyword args: (kwarg name, (param, ...))
    kw_taints: Tuple[Tuple[str, Tuple[str, ...]], ...] = ()


@dataclass
class FunctionSummary:
    """What one ``def`` does, as far as name-based analysis can prove.

    Every field defaults to the conservative "nothing proven" value —
    an exotic construct (decorators we don't know, dynamic dispatch,
    lambdas) leaves the default in place rather than inventing facts.
    """
    fid: FuncId
    name: str
    lineno: int
    class_name: Optional[str] = None  # enclosing class, if a method
    is_method: bool = False
    self_name: Optional[str] = None   # first positional arg of a method
    params: Tuple[str, ...] = ()
    memoized: bool = False            # lru_cache/instrument_factory'd
    #: returns an expression derived from time.time() (direct taint)
    returns_wall_direct: bool = False
    #: callees whose return value this function returns (taint/jit chains)
    return_call_targets: List[FuncId] = field(default_factory=list)
    #: returns a FRESH jit closure per call (False when memoized)
    returns_fresh_jit_direct: bool = False
    #: attr writes on `self`: (attr, line, guarded_at_site)
    self_attr_writes: List[Tuple[str, int, bool]] = field(
        default_factory=list)
    #: attr writes on non-self params: param name -> [(attr, line,
    #: guarded_at_site)] — how a cross-module helper mutates an object
    #: the caller passed in
    param_attr_writes: Dict[str, List[Tuple[str, int, bool]]] = field(
        default_factory=dict)
    calls: List[CallSite] = field(default_factory=list)
    #: calls np.asarray/device_get/block_until_ready directly (GC07
    #: follows exactly ONE function boundary, so no transitive closure)
    transfer_direct: bool = False
    has_while_loop: bool = False
    #: `self.<attr>` event names gating a while loop (`while not
    #: self._stop.is_set()` / `.wait(t)`) — GC08 poison-pill evidence
    loop_event_gates: Set[str] = field(default_factory=set)
    # -- v3 facts (GC09-GC12) -----------------------------------------
    #: params traced when this def is jit/pjit/pmap/shard_map-DECORATED
    #: (static_argnums positions excluded) — a GC09 tracing root
    jit_params: Tuple[str, ...] = ()
    #: donate_argnums positions of this def's jit decorator (GC11)
    donated_positions: Tuple[int, ...] = ()
    #: host-concretizing calls on param-derived values: param ->
    #: [(line, kind, repr)] with kind np|cast|item (np is --fix-able)
    param_np_calls: Dict[str, List[Tuple[int, str, str]]] = field(
        default_factory=dict)
    #: Python control flow (if/while/assert truthiness) on a
    #: param-derived value: param -> [line, ...]
    param_branches: Dict[str, List[int]] = field(default_factory=dict)
    #: functions this body hands to jit/pjit/pmap/shard_map — local
    #: nested defs resolve at extraction (fids), module/imported names
    #: resolve later (keys); each with its static_argnums positions
    jit_root_fids: List[Tuple[FuncId, Tuple[int, ...]]] = field(
        default_factory=list)
    jit_root_keys: List[Tuple[Tuple, Tuple[int, ...]]] = field(
        default_factory=list)
    #: functions this body hands to lax.scan as the scan BODY (GC10)
    scan_body_fids: List[FuncId] = field(default_factory=list)
    scan_body_keys: List[Tuple] = field(default_factory=list)
    #: return value is a raw acquired resource (socket/file/mmap kind)
    returns_resource_direct: Optional[str] = None
    #: returns a donate-jitted closure (direct evidence only)
    returns_donated_direct: Tuple[int, ...] = ()
    #: callee keys whose return value this function returns (resolved
    #: into return_call_targets by assemble_index)
    return_call_keys: List[Tuple] = field(default_factory=list)
    # transitive facts, filled by the fixpoint in build_index()
    returns_wall: bool = False
    returns_fresh_jit: bool = False
    returns_resource: Optional[str] = None
    returns_donated: Tuple[int, ...] = ()


@dataclass
class ModuleInfo:
    """Per-module resolution state."""
    relpath: str
    modname: str
    is_package: bool = False             # an __init__.py
    #: local name -> dotted module it stands for (import x.y as z)
    import_modules: Dict[str, str] = field(default_factory=dict)
    #: local name -> (dotted module, symbol)  (from m import f)
    import_symbols: Dict[str, Tuple[str, str]] = field(
        default_factory=dict)
    #: top-level def name -> FuncId
    toplevel: Dict[str, FuncId] = field(default_factory=dict)
    #: class name -> {method name -> FuncId}
    classes: Dict[str, Dict[str, FuncId]] = field(default_factory=dict)


def call_key(call: ast.Call) -> Optional[Tuple]:
    """Picklable structural key of a call's callee expression —
    resolution against the project name tables happens later (and
    possibly in another process), so extraction never needs the index:
    ``("n", f)`` bare name, ``("a", base, attr)`` one-level attribute,
    ``("d", dotted, attr)`` dotted chain, None unresolvable."""
    f = call.func
    if isinstance(f, ast.Name):
        return ("n", f.id)
    if isinstance(f, ast.Attribute):
        v = f.value
        if isinstance(v, ast.Name):
            return ("a", v.id, f.attr)
        if isinstance(v, ast.Attribute):
            try:
                dotted = ast.unparse(v)
            except Exception:  # noqa: BLE001 — odd nodes
                return None
            return ("d", dotted, f.attr)
    return None


class InterProcIndex:
    """Project-wide function summaries + name-based resolution."""

    def __init__(self) -> None:
        self.functions: Dict[FuncId, FunctionSummary] = {}
        self.modules: Dict[str, ModuleInfo] = {}      # modname -> info
        self.modules_by_path: Dict[str, ModuleInfo] = {}
        #: (FuncId, param name) pairs provably reachable as TRACED
        #: values from a jit/scan/shard_map root (GC09's worklist
        #: closure over the forwarding edges)
        self.traced: Set[Tuple[FuncId, str]] = set()
        #: functions used as a lax.scan BODY anywhere in the project
        self.scan_bodies: Set[FuncId] = set()

    # -- resolution -----------------------------------------------------
    def resolve_symbol(self, modname: str, symbol: str) \
            -> Optional[FuncId]:
        """``symbol`` as a top-level def of ``modname`` (following one
        from-import hop so re-exports resolve)."""
        mi = self.modules.get(modname)
        if mi is None:
            return None
        fid = mi.toplevel.get(symbol)
        if fid is not None:
            return fid
        hop = mi.import_symbols.get(symbol)
        if hop is not None:
            m2, s2 = hop
            mi2 = self.modules.get(m2)
            if mi2 is not None:
                return mi2.toplevel.get(s2)
        return None

    def resolve_key(self, mi: ModuleInfo, key: Optional[Tuple],
                    class_name: Optional[str],
                    self_name: Optional[str]) -> Optional[FuncId]:
        """Best-effort callee for a :func:`call_key` as seen from a
        function inside class ``class_name`` of module ``mi``."""
        if key is None:
            return None
        tag = key[0]
        if tag == "n":
            fid = mi.toplevel.get(key[1])
            if fid is not None:
                return fid
            hop = mi.import_symbols.get(key[1])
            if hop is not None:
                return self.resolve_symbol(*hop)
            return None
        if tag == "a":
            _, base, attr = key
            if self_name is not None and base == self_name \
                    and class_name is not None:
                methods = mi.classes.get(class_name, {})
                return methods.get(attr)
            target_mod = mi.import_modules.get(base)
            if target_mod is not None:
                return self.resolve_symbol(target_mod, attr)
            hop = mi.import_symbols.get(base)
            if hop is not None:
                # `from pkg import mod` then `mod.f()`
                return self.resolve_symbol(f"{hop[0]}.{hop[1]}", attr)
            return None
        if tag == "d":
            # dotted module chain: x.y.f() under `import x.y` or
            # `import pkg.x as x` — the HEAD name is the local
            # binding; substituting its target module for it yields
            # the absolute dotted module the chain names
            _, dotted, attr = key
            head, _sep, rest = dotted.partition(".")
            if head in mi.import_modules:
                base = mi.import_modules[head]
                mod = f"{base}.{rest}" if rest else base
                return self.resolve_symbol(mod, attr)
            return self.resolve_symbol(dotted, attr)
        return None

    def resolve_call(self, mi: ModuleInfo, call: ast.Call,
                     class_name: Optional[str],
                     self_name: Optional[str]) -> Optional[FuncId]:
        """Best-effort callee of ``call`` as seen from a function inside
        class ``class_name`` of module ``mi``. None = unknown."""
        return self.resolve_key(mi, call_key(call), class_name,
                                self_name)


# ---------------------------------------------------------------------------
# per-module extraction
# ---------------------------------------------------------------------------

def _resolve_relative(modname: str, is_package: bool, level: int,
                      module: Optional[str]) -> Optional[str]:
    """Absolute dotted name of a ``from ...x import y`` target.
    ``is_package`` distinguishes ``a/b/__init__.py`` (where ``from .``
    means ``a.b`` itself) from ``a/b.py`` (where it means ``a``) —
    without it, every re-export in an ``__init__.py`` resolved one
    level too high and package-mediated taint went invisible."""
    if level == 0:
        return module
    parts = modname.split(".")
    if is_package:
        parts = parts + ["__init__"]
    if level > len(parts):
        return None
    base = parts[:len(parts) - level]
    if module:
        base.append(module)
    return ".".join(base) if base else None


def _collect_imports(mi: ModuleInfo, tree: ast.Module) -> None:
    for n in ast.walk(tree):
        if isinstance(n, ast.Import):
            for a in n.names:
                if a.asname:
                    mi.import_modules[a.asname] = a.name
                else:
                    mi.import_modules[a.name.split(".")[0]] = \
                        a.name.split(".")[0]
                    mi.import_modules.setdefault(a.name, a.name)
        elif isinstance(n, ast.ImportFrom):
            target = _resolve_relative(mi.modname, mi.is_package,
                                       n.level, n.module)
            if target is None:
                continue
            for a in n.names:
                local = a.asname or a.name
                mi.import_symbols[local] = (target, a.name)


def _wall_call(n: ast.AST, bare_time: bool) -> bool:
    if not isinstance(n, ast.Call):
        return False
    f = n.func
    if isinstance(f, ast.Attribute) and f.attr == "time" \
            and isinstance(f.value, ast.Name) and f.value.id == "time":
        return True
    return bare_time and isinstance(f, ast.Name) and f.id == "time"


def _has_bare_time_import(tree: ast.Module) -> bool:
    for n in ast.walk(tree):
        if isinstance(n, ast.ImportFrom) and n.module == "time":
            if any(a.name == "time" for a in n.names):
                return True
    return False


def _scope_nodes(fn: ast.AST) -> List[ast.AST]:
    """Nodes of ``fn``'s own scope (nested defs/lambdas excluded)."""
    out: List[ast.AST] = []
    stack = list(fn.body)
    while stack:
        n = stack.pop()
        out.append(n)
        if isinstance(n, FUNCS + (ast.Lambda,)):
            continue
        stack.extend(ast.iter_child_nodes(n))
    return out


def _event_gates(fn: ast.AST, self_name: Optional[str]) -> Set[str]:
    """``self.<attr>`` names whose ``.wait()`` / ``.is_set()`` gate a
    while-loop condition — the poison-pill discipline GC08 credits."""
    gates: Set[str] = set()
    if self_name is None:
        return gates
    for n in ast.walk(fn):
        if not isinstance(n, ast.While):
            continue
        for c in ast.walk(n.test):
            if isinstance(c, ast.Call) \
                    and isinstance(c.func, ast.Attribute) \
                    and c.func.attr in ("wait", "is_set"):
                v = c.func.value
                if isinstance(v, ast.Attribute) \
                        and isinstance(v.value, ast.Name) \
                        and v.value.id == self_name:
                    gates.add(v.attr)
    return gates


#: builtins whose results are CONCRETE even on tracer args (static
#: under trace) — they kill taint inside branch tests and expressions
_STATIC_BUILTINS = {"len", "isinstance", "callable", "hasattr",
                    "getattr", "type", "id", "repr", "str"}


def _taint_origins(expr: ast.AST, origins: Dict[str, Set[str]],
                   branch: bool = False) -> Set[str]:
    """Root params whose (possibly derived) values feed ``expr``.
    Concrete-under-trace constructs are skipped: ``x.shape``-style
    attribute reads, static builtins, nested function definitions.
    ``branch=True`` additionally skips ``is``/``is not`` comparisons —
    ``if val is None`` branches on static None-ness, not on a tracer."""
    out: Set[str] = set()
    stack = [expr]
    while stack:
        n = stack.pop()
        if isinstance(n, ast.Attribute) and n.attr in _CONCRETE_ATTRS:
            continue
        if isinstance(n, ast.Call) and isinstance(n.func, ast.Name) \
                and n.func.id in _STATIC_BUILTINS:
            continue
        if branch and isinstance(n, ast.Compare) \
                and all(isinstance(op, (ast.Is, ast.IsNot))
                        for op in n.ops):
            continue
        if isinstance(n, FUNCS + (ast.Lambda,)):
            continue
        if isinstance(n, ast.Name) and isinstance(n.ctx, ast.Load):
            out |= origins.get(n.id, set())
            continue
        stack.extend(ast.iter_child_nodes(n))
    return out


def _assign_edges(nodes: List[ast.AST]) \
        -> List[Tuple[List[str], ast.AST]]:
    """(target names, value expr) pairs for taint propagation: plain and
    annotated assignments, augmented assignment, and for-loop bindings
    (an iterable's taint reaches its loop variable)."""
    edges: List[Tuple[List[str], ast.AST]] = []

    def names_of(t: ast.AST) -> List[str]:
        if isinstance(t, ast.Name):
            return [t.id]
        if isinstance(t, (ast.Tuple, ast.List)):
            return [x for e in t.elts for x in names_of(e)]
        if isinstance(t, ast.Starred):
            return names_of(t.value)
        return []

    for n in nodes:
        if isinstance(n, ast.Assign):
            tg = [x for t in n.targets for x in names_of(t)]
            if tg:
                edges.append((tg, n.value))
        elif isinstance(n, ast.AnnAssign) and n.value is not None:
            tg = names_of(n.target)
            if tg:
                edges.append((tg, n.value))
        elif isinstance(n, ast.AugAssign):
            tg = names_of(n.target)
            if tg:
                edges.append((tg, n.value))
        elif isinstance(n, (ast.For, ast.AsyncFor)):
            tg = names_of(n.target)
            if tg:
                edges.append((tg, n.iter))
        elif isinstance(n, ast.withitem) and n.optional_vars is not None:
            tg = names_of(n.optional_vars)
            if tg:
                edges.append((tg, n.context_expr))
    return edges


def _propagate_taint(edges, origins: Dict[str, Set[str]]) -> None:
    """Close name-level taint over the assignment edges (flow-insensitive
    fixpoint; scopes are small, 2-3 rounds in practice)."""
    for _ in range(8):
        changed = False
        for targets, value in edges:
            o = _taint_origins(value, origins)
            if not o:
                continue
            for t in targets:
                cur = origins.setdefault(t, set())
                if not o <= cur:
                    cur |= o
                    changed = True
        if not changed:
            return


def _is_trace_wrapper_call(n: ast.Call) -> bool:
    """jit/pjit/pmap/shard_map applied as a CALL: ``jax.jit(f)``,
    ``shard_map(f, ...)``, ``partial(jax.jit, ...)(f)``."""
    if is_jit_creation(n):
        return True
    return dec_name(n) in _TRACE_WRAPPER_NAMES


def _is_scan_call(n: ast.Call) -> bool:
    f = n.func
    if isinstance(f, ast.Attribute) and f.attr == "scan":
        try:
            base = ast.unparse(f.value)
        except Exception:  # noqa: BLE001 — odd nodes
            return False
        return base.endswith("lax")
    return False


def _is_traced_def(fn: ast.AST) -> bool:
    """def decorated with any compile wrapper (jit/pjit/pmap/shard_map,
    bare or through partial) — its params are tracers."""
    for d in getattr(fn, "decorator_list", []):
        if is_jit_decorator(d) or dec_name(d) in _TRACE_WRAPPER_NAMES:
            return True
    return False


def _static_positions_of(fn: ast.AST) -> Tuple[int, ...]:
    for d in getattr(fn, "decorator_list", []):
        if is_jit_decorator(d) or dec_name(d) in _TRACE_WRAPPER_NAMES:
            got = _jit_call_kwargs(d, "static_argnums")
            if got:
                return got
    return ()


def _summarize_function(ctx: Any, mi: ModuleInfo, fn: ast.AST,
                        class_name: Optional[str], direct_method: bool,
                        bare_time: bool) -> FunctionSummary:
    qual = ctx.qualname(fn)
    fid: FuncId = (ctx.relpath, qual)
    args = fn.args
    params = tuple(a.arg for a in
                   list(args.posonlyargs) + list(args.args))
    is_method = direct_method and class_name is not None \
        and bool(params) \
        and not any(dec_name(d) == "staticmethod"
                    for d in fn.decorator_list)
    # a closure nested under a class method captures the literal `self`
    # from its enclosing method — its self.<attr> writes and self.m()
    # calls belong to the class exactly like a method's do
    self_name = params[0] if is_method else (
        "self" if class_name is not None and not direct_method else None)
    s = FunctionSummary(
        fid=fid, name=fn.name, lineno=fn.lineno, class_name=class_name,
        is_method=is_method, self_name=self_name, params=params,
        memoized=is_memo_decorated(fn),
    )

    nodes = _scope_nodes(fn)

    # local taint: names assigned from time.time()-derived expressions,
    # names assigned from fresh jit creations, names assigned from calls
    tainted: Set[str] = set()
    jit_named: Set[str] = set()
    for n in nodes:
        targets: List[ast.expr] = []
        value: Optional[ast.expr] = None
        if isinstance(n, ast.Assign):
            targets, value = list(n.targets), n.value
        elif isinstance(n, ast.AnnAssign) and n.value is not None:
            targets, value = [n.target], n.value
        if value is None:
            continue
        names = [t.id for t in targets if isinstance(t, ast.Name)]
        if not names:
            continue
        if any(_wall_call(x, bare_time) for x in ast.walk(value)):
            tainted.update(names)
        if is_jit_creation(value):
            jit_named.update(names)

    def derives_wall(expr: ast.AST) -> bool:
        for x in ast.walk(expr):
            if _wall_call(x, bare_time):
                return True
            if isinstance(x, ast.Name) and x.id in tainted \
                    and isinstance(x.ctx, ast.Load):
                return True
        return False

    # nested @jit defs whose NAME is returned count as fresh-jit returns
    jit_defs = {n.name for n in ast.walk(fn)
                if isinstance(n, FUNCS) and n is not fn
                and any(is_jit_decorator(d) for d in n.decorator_list)}
    # nested defs by name (jit/scan root targets resolve locally: the
    # ops/ factories jit a `def core` defined right inside themselves)
    nested_defs: Dict[str, ast.AST] = {}
    for d in ast.walk(fn):
        if isinstance(d, FUNCS) and d is not fn \
                and d.name not in nested_defs:
            nested_defs[d.name] = d
    donated_named: Dict[str, Tuple[int, ...]] = {}
    donated_defs = {name: donated_positions_of(d)
                    for name, d in nested_defs.items()
                    if donated_positions_of(d)}
    acq_named: Dict[str, str] = {}       # name -> acquired resource kind
    for n in nodes:
        if isinstance(n, ast.Assign) and isinstance(n.value, ast.Call):
            tgt_names = [t.id for t in n.targets
                         if isinstance(t, ast.Name)]
            if not tgt_names:
                continue
            dp = _jit_call_kwargs(n.value, "donate_argnums")
            if is_jit_creation(n.value) and dp:
                for t in tgt_names:
                    donated_named[t] = dp
            kind = is_acquisition(n.value)
            if kind is not None:
                for t in tgt_names:
                    acq_named[t] = kind

    for n in nodes:
        if isinstance(n, ast.Return) and n.value is not None:
            v = n.value
            if derives_wall(v):
                s.returns_wall_direct = True
            if is_jit_creation(v) or (
                    isinstance(v, ast.Name)
                    and (v.id in jit_named or v.id in jit_defs)):
                s.returns_fresh_jit_direct = True
            if not s.returns_donated_direct:
                if isinstance(v, ast.Call):
                    dp = _jit_call_kwargs(v, "donate_argnums")
                    if is_jit_creation(v) and dp:
                        s.returns_donated_direct = dp
                elif isinstance(v, ast.Name):
                    s.returns_donated_direct = donated_named.get(
                        v.id, donated_defs.get(v.id, ()))
            if s.returns_resource_direct is None:
                if isinstance(v, ast.Call):
                    s.returns_resource_direct = is_acquisition(v)
                elif isinstance(v, ast.Name):
                    s.returns_resource_direct = acq_named.get(v.id)

    # return-value call edges (taint/jit/resource chains), by key
    s.return_call_keys = _return_call_keys(nodes)

    # -- v3: tracer-taint origins, hazards, compile roots ---------------
    # local-shadow guard: a bare-Name callee that is a parameter, a
    # locally-assigned name or a nested def must NOT resolve against
    # the module's top-level table (a param named like a module def
    # would misattribute facts to the wrong function)
    edges = _assign_edges(nodes)
    shadowed = set(params) | set(nested_defs)
    for tg, _v in edges:
        shadowed.update(tg)
    origins: Dict[str, Set[str]] = {p: {p} for p in params}
    _propagate_taint(edges, origins)

    if _is_traced_def(fn):
        static = set(_static_positions_of(fn))
        s.jit_params = tuple(p for i, p in enumerate(params)
                             if i not in static)
    s.donated_positions = donated_positions_of(fn)

    def root_target(call: ast.Call):
        """(fid, None) for a local nested def handed to a wrapper,
        (None, key) for a module-level/imported name, (None, None) for
        anything opaque (a param, a local variable, a lambda)."""
        args = call.args
        # partial(jax.jit, ...)(f): the wrapped fn is the OUTER call's arg
        if not args:
            return None, None
        a = args[0]
        if is_jit_name(a) or is_partial(a):
            return None, None            # the partial(jax.jit, ...) form:
        #                                  handled via the outer call
        if isinstance(a, ast.Name):
            d = nested_defs.get(a.id)
            if d is not None:
                return (ctx.relpath, ctx.qualname(d)), None
            if a.id in shadowed:
                return None, None
            return None, ("n", a.id)
        return None, None

    for n in nodes:
        if not isinstance(n, ast.Call):
            continue
        if _is_trace_wrapper_call(n):
            fid, key = root_target(n)
            statics = _jit_call_kwargs(n, "static_argnums")
            if fid is not None:
                s.jit_root_fids.append((fid, statics))
            elif key is not None:
                s.jit_root_keys.append((key, statics))
        elif _is_scan_call(n):
            fid, key = root_target(n)
            if fid is not None:
                s.scan_body_fids.append(fid)
            elif key is not None:
                s.scan_body_keys.append(key)
        f = n.func
        if isinstance(f, ast.Attribute) and isinstance(f.value, ast.Name) \
                and f.value.id in _NP_ALIASES:
            o: Set[str] = set()
            for a in list(n.args) + [k.value for k in n.keywords]:
                o |= _taint_origins(a, origins)
            for p in o:
                s.param_np_calls.setdefault(p, []).append(
                    (n.lineno, "np", f"{f.value.id}.{f.attr}"))
        elif isinstance(f, ast.Name) and f.id in _CONCRETIZE_BUILTINS \
                and n.args:
            for p in _taint_origins(n.args[0], origins):
                s.param_np_calls.setdefault(p, []).append(
                    (n.lineno, "cast", f"{f.id}()"))
        elif isinstance(f, ast.Attribute) \
                and f.attr in _CONCRETIZE_METHODS:
            for p in _taint_origins(f.value, origins):
                s.param_np_calls.setdefault(p, []).append(
                    (n.lineno, "item", f".{f.attr}()"))
    for n in nodes:
        test = None
        if isinstance(n, (ast.If, ast.While)):
            test = n.test
        elif isinstance(n, ast.Assert):
            test = n.test
        elif isinstance(n, ast.IfExp):
            test = n.test
        if test is None:
            continue
        for p in _taint_origins(test, origins, branch=True):
            s.param_branches.setdefault(p, []).append(n.lineno)

    # attr writes on self / params, call sites, loops, transfers
    watched = set(params) | ({self_name} if self_name else set())
    for n in nodes:
        tgts: List[ast.Attribute] = []
        if isinstance(n, ast.Assign):
            tgts = [t for t in n.targets if isinstance(t, ast.Attribute)]
        elif isinstance(n, (ast.AugAssign, ast.AnnAssign)) \
                and isinstance(n.target, ast.Attribute):
            tgts = [n.target]
        for t in tgts:
            if isinstance(t.value, ast.Name) and t.value.id in watched:
                rec = (t.attr, n.lineno, under_lock(ctx, n, fn))
                if t.value.id == self_name:
                    s.self_attr_writes.append(rec)
                else:
                    s.param_attr_writes.setdefault(
                        t.value.id, []).append(rec)
        if isinstance(n, ast.While):
            s.has_while_loop = True
        if is_transfer_call(n):
            s.transfer_direct = True
        if isinstance(n, ast.Call):
            self_pos: Tuple[int, ...] = ()
            if self_name is not None:
                self_pos = tuple(
                    i for i, a in enumerate(n.args)
                    if isinstance(a, ast.Name) and a.id == self_name)
            try:
                crepr = ast.unparse(n.func)
            except Exception:  # noqa: BLE001 — odd nodes
                crepr = dec_name(n)
            key = call_key(n)
            if key is not None and key[0] == "n" \
                    and key[1] in shadowed:
                key = None               # local-shadow guard (above)
            at = tuple((i, tuple(sorted(o)))
                       for i, a in enumerate(n.args)
                       for o in [_taint_origins(a, origins)] if o)
            kt = tuple((k.arg, tuple(sorted(o)))
                       for k in n.keywords if k.arg is not None
                       for o in [_taint_origins(k.value, origins)] if o)
            s.calls.append(CallSite(
                line=n.lineno, callee=None,
                under_lock=under_lock(ctx, n, fn),
                self_arg_positions=self_pos, callee_repr=crepr,
                key=key, arg_taints=at, kw_taints=kt))

    s.loop_event_gates = _event_gates(fn, self_name)
    return s


def _return_call_keys(nodes: List[ast.AST]) -> List[Tuple]:
    """Callee keys whose return value this function returns (directly or
    through one local name) — the taint/jit/resource chain edges,
    resolved by :func:`assemble_index` once the name tables exist."""
    out: List[Tuple] = []
    call_named: Dict[str, ast.Call] = {}
    for n in nodes:
        if isinstance(n, ast.Assign) and isinstance(n.value, ast.Call):
            for t in n.targets:
                if isinstance(t, ast.Name):
                    call_named[t.id] = n.value
    for n in nodes:
        if not (isinstance(n, ast.Return) and n.value is not None):
            continue
        calls: List[ast.Call] = []
        if isinstance(n.value, ast.Call):
            calls.append(n.value)
        elif isinstance(n.value, ast.Name) \
                and n.value.id in call_named:
            calls.append(call_named[n.value.id])
        else:
            # `return now() - t0` style: every call inside the returned
            # expression can carry taint into the return value
            calls.extend(x for x in ast.walk(n.value)
                         if isinstance(x, ast.Call))
        for c in calls:
            key = call_key(c)
            if key is not None:
                out.append(key)
    return out


@dataclass
class ModuleFacts:
    """Everything one module contributes to the project index, extracted
    WITHOUT any cross-module resolution — plain picklable data, so the
    engine can fan this pass across worker processes and ship the facts
    back (call sites carry structural :func:`call_key` keys that
    :func:`assemble_index` resolves once every module's name tables
    exist)."""
    info: ModuleInfo
    summaries: List[FunctionSummary] = field(default_factory=list)
    #: *_STUB const name -> top-level literal keys (GC05 raw material)
    stubs: Dict[str, Tuple[str, ...]] = field(default_factory=dict)
    #: alias function name -> *_STUB const it stands for
    stub_aliases: Dict[str, str] = field(default_factory=dict)


def extract_module(ctx: Any) -> ModuleFacts:
    """Pure per-module extraction: import maps, def tables, function
    summaries with UNRESOLVED callee keys. Runs with no project state —
    safe to execute in a worker process."""
    mi = ModuleInfo(ctx.relpath, module_name_of(ctx.relpath),
                    is_package=ctx.relpath.endswith("__init__.py"))
    _collect_imports(mi, ctx.tree)
    for n in ctx.tree.body:
        if isinstance(n, FUNCS):
            mi.toplevel[n.name] = (ctx.relpath, n.name)
        elif isinstance(n, ast.ClassDef):
            methods = {}
            for m in n.body:
                if isinstance(m, FUNCS):
                    methods[m.name] = (ctx.relpath,
                                       f"{n.name}.{m.name}")
            mi.classes[n.name] = methods
    facts = ModuleFacts(info=mi)
    bare = _has_bare_time_import(ctx.tree)
    for fn in ast.walk(ctx.tree):
        if not isinstance(fn, FUNCS):
            continue
        # NEAREST enclosing class (nested closures inherit it via
        # the captured `self`); direct methods get param-0 self
        cls = None
        for a in ctx.ancestors(fn):
            if isinstance(a, ast.ClassDef):
                cls = a.name
                break
        direct = isinstance(ctx.parent(fn), ast.ClassDef)
        try:
            facts.summaries.append(
                _summarize_function(ctx, mi, fn, cls, direct, bare))
        except Exception:  # noqa: BLE001 — one intractable function
            pass           # degrades ALONE to "unknown"; the module's
            #                imports, stubs and sibling summaries (GC05's
            #                raw material) must survive it
    # GC05 raw material (rules.collect_project folds these project-wide)
    for n in ctx.tree.body:
        if isinstance(n, ast.Assign) and len(n.targets) == 1 \
                and isinstance(n.targets[0], ast.Name) \
                and n.targets[0].id.endswith("_STUB") \
                and isinstance(n.value, ast.Dict):
            facts.stubs[n.targets[0].id] = tuple(
                k.value for k in n.value.keys
                if isinstance(k, ast.Constant)
                and isinstance(k.value, str))
        elif isinstance(n, FUNCS):
            refs = {x.id for x in ast.walk(n)
                    if isinstance(x, ast.Name)
                    and x.id.endswith("_STUB")}
            if len(refs) == 1:
                facts.stub_aliases[n.name] = refs.pop()
    return facts


def assemble_index(all_facts: List[Any]) -> InterProcIndex:
    """Resolve every module's structural keys against the now-complete
    project name tables, then run the transitive fixpoints (wall-clock
    taint, fresh-jit, resource, donation) and the traced-parameter
    worklist closure GC09/GC10 consume."""
    idx = InterProcIndex()
    for facts in all_facts:
        idx.modules[facts.info.modname] = facts.info
        idx.modules_by_path[facts.info.relpath] = facts.info
        for s in facts.summaries:
            idx.functions[s.fid] = s
    for facts in all_facts:
        mi = facts.info
        for s in facts.summaries:
            for c in s.calls:
                if c.callee is None and c.key is not None:
                    c.callee = idx.resolve_key(mi, c.key, s.class_name,
                                               s.self_name)
            s.return_call_targets = [
                fid for key in s.return_call_keys
                for fid in (idx.resolve_key(mi, key, s.class_name,
                                            s.self_name),)
                if fid is not None]
            for key, statics in s.jit_root_keys:
                fid = idx.resolve_key(mi, key, s.class_name, s.self_name)
                if fid is not None:
                    s.jit_root_fids.append((fid, statics))
            s.jit_root_keys = []         # resolved — keep idempotent
            for key in s.scan_body_keys:
                fid = idx.resolve_key(mi, key, s.class_name, s.self_name)
                if fid is not None:
                    s.scan_body_fids.append(fid)
            s.scan_body_keys = []
    _fixpoint(idx)
    _close_traced(idx)
    return idx


def build_index(contexts: List[Any]) -> InterProcIndex:
    """Serial convenience: extract every module in-process, then
    assemble (the engine's parallel path runs :func:`extract_module` in
    worker processes and calls :func:`assemble_index` itself)."""
    return assemble_index([extract_module(ctx) for ctx in contexts])


def _close_traced(idx: InterProcIndex) -> None:
    """GC09's worklist closure: (function, param) pairs provably reached
    by TRACED values. Seeds are compile-wrapper surfaces — jit-decorated
    defs, functions handed to jit/pjit/pmap/shard_map (minus their
    static_argnums positions), and lax.scan bodies — and taint flows
    along call edges whose arguments derive from an already-traced
    parameter."""
    traced = idx.traced
    for s in idx.functions.values():
        for p in s.jit_params:
            traced.add((s.fid, p))
        for fid, statics in s.jit_root_fids:
            t = idx.functions.get(fid)
            if t is not None:
                skip = set(statics)
                for i, p in enumerate(t.params):
                    if i not in skip:
                        traced.add((t.fid, p))
        for fid in s.scan_body_fids:
            t = idx.functions.get(fid)
            if t is not None:
                idx.scan_bodies.add(t.fid)
                for p in t.params:
                    traced.add((t.fid, p))
    work = list(traced)
    while work:
        fid, p = work.pop()
        s = idx.functions.get(fid)
        if s is None:
            continue
        for c in s.calls:
            if c.callee is None:
                continue
            t = idx.functions.get(c.callee)
            if t is None:
                continue
            # `self.m(x)`: positional arg 0 lands on params[1] (self
            # occupies slot 0 of the method's parameter tuple)
            off = 1 if (t.is_method and c.key is not None
                        and c.key[0] == "a"
                        and c.key[1] == s.self_name) else 0
            for pos, origins in c.arg_taints:
                if p in origins and pos + off < len(t.params):
                    tp = (t.fid, t.params[pos + off])
                    if tp not in traced:
                        traced.add(tp)
                        work.append(tp)
            for kw, origins in c.kw_taints:
                if p in origins and kw in t.params:
                    tp = (t.fid, kw)
                    if tp not in traced:
                        traced.add(tp)
                        work.append(tp)


def _fixpoint(idx: InterProcIndex) -> None:
    """Close returns_wall / returns_fresh_jit / returns_resource /
    returns_donated over the call graph. Monotone lattices (booleans,
    first-resource-kind-wins, first-donation-tuple-wins) -> terminates."""
    for s in idx.functions.values():
        s.returns_wall = s.returns_wall_direct
        # a memoized factory hands back the SAME closure per config key:
        # calling it per step is a cache hit, not a fresh compile
        s.returns_fresh_jit = s.returns_fresh_jit_direct \
            and not s.memoized
        s.returns_resource = s.returns_resource_direct
        # donation is a property of the returned callable's SIGNATURE —
        # a memoized factory still hands back a donating callable, so
        # (unlike fresh-jit) memoization does not clear the fact
        s.returns_donated = s.returns_donated_direct
    changed = True
    while changed:
        changed = False
        for s in idx.functions.values():
            if not s.returns_wall:
                for t in s.return_call_targets:
                    ts = idx.functions.get(t)
                    if ts is not None and ts.returns_wall:
                        s.returns_wall = True
                        changed = True
                        break
            if not s.returns_fresh_jit and not s.memoized:
                for t in s.return_call_targets:
                    ts = idx.functions.get(t)
                    if ts is not None and ts.returns_fresh_jit:
                        s.returns_fresh_jit = True
                        changed = True
                        break
            if s.returns_resource is None:
                for t in s.return_call_targets:
                    ts = idx.functions.get(t)
                    if ts is not None and ts.returns_resource:
                        s.returns_resource = ts.returns_resource
                        changed = True
                        break
            if not s.returns_donated:
                for t in s.return_call_targets:
                    ts = idx.functions.get(t)
                    if ts is not None and ts.returns_donated:
                        s.returns_donated = ts.returns_donated
                        changed = True
                        break


# ---------------------------------------------------------------------------
# GC04 helper: transitive attr-write collection from a thread entry
# ---------------------------------------------------------------------------

def collect_entry_writes(idx: InterProcIndex, ctx: Any,
                         entry_fid: FuncId, max_depth: int = 4) \
        -> List[Tuple[str, int, bool, str]]:
    """Every ``self.<attr>`` write reachable from thread entry point
    ``entry_fid`` by following method calls on self (and helper calls
    that receive self as an argument), with the lock context each call
    edge carries: a write is *guarded* when its own site sits under a
    ``with <lock>:`` OR every call edge leading to it held a lock.

    Returns ``(attr, report_line, guarded, via)`` where ``report_line``
    is always a line in the ENTRY's module (cross-module writes are
    reported at the call site that reaches them) and ``via`` names the
    callee chain for the finding message ("" for direct writes).
    """
    out: List[Tuple[str, int, bool, str]] = []
    seen: Set[Tuple[FuncId, bool]] = set()

    def visit(fid: FuncId, lock_held: bool, depth: int,
              report_line: Optional[int], via: str) -> None:
        if depth > max_depth or (fid, lock_held) in seen:
            return
        seen.add((fid, lock_held))
        s = idx.functions.get(fid)
        if s is None:
            return
        for attr, line, guarded in s.self_attr_writes:
            out.append((attr, report_line if report_line is not None
                        else line, guarded or lock_held, via))
        for c in s.calls:
            if c.callee is None:
                continue
            t = idx.functions.get(c.callee)
            if t is None:
                continue
            edge_locked = lock_held or c.under_lock
            nxt_via = c.callee_repr if not via \
                else f"{via} -> {c.callee_repr}"
            # same-class method on self: follow with the callee's own
            # line numbers when it lives in the same module (precise
            # report), else pin the report to this call site
            same_module = c.callee[0] == fid[0]
            rl = report_line if report_line is not None else (
                None if same_module else c.line)
            if t.is_method and t.class_name == s.class_name \
                    and same_module:
                visit(c.callee, edge_locked, depth + 1, rl, nxt_via)
            elif t.param_attr_writes or t.calls:
                # helper receiving self positionally: its writes to that
                # param are writes to our object
                for pos in c.self_arg_positions:
                    if pos < len(t.params):
                        pname = t.params[pos]
                        for attr, line, guarded in \
                                t.param_attr_writes.get(pname, []):
                            out.append((
                                attr,
                                report_line if report_line is not None
                                else c.line,
                                guarded or edge_locked, nxt_via))

    visit(entry_fid, False, 0, None, "")
    return out
