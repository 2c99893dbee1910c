"""ckcheck shared model: pure-``ast`` scanning of a Python package into
the structures every pass consumes.

No imports of the scanned code, ever — the same contract as
``tools/lint_obs.py``: the analyzer must run on rigs where jax (or the
package itself) is broken, because "the analyzer is down" and "the
runtime is down" must never be the same outage.

What one scan produces (:class:`Package`):

- **Lock inventory** — every ``self._x = threading.Lock()`` /
  ``RLock()`` / ``Condition()`` assignment and every module-level lock,
  as :class:`Lock` records with a stable ``lock_id``
  (``module.Class.attr``).  Lock identity is CLASS-level (lockdep-style
  lock classes): every ``Worker.lock`` instance is one node in the
  order graph.
- **Function inventory** — every function/method (including nested
  closures, which run on OTHER threads in this codebase: driver-queue
  dispatch closures must not inherit the submitter's held-set).
- **Receiver typing** — a small, deliberately under-approximate type
  resolver: ``self``, annotated parameters, ``x = ClassName(...)``
  locals, ``self.x = ClassName(...)`` attributes recorded from any
  method, module-level singletons (``TRACER = Tracer()``) resolved
  through package-internal imports, and ``for w in self.workers`` loops
  over attributes typed as lists.  Anything unresolved produces NO call
  edge / NO lock event — under-approximation keeps the passes' findings
  worth reading (a missed edge is a known blind spot the dynamic
  witness covers; a fabricated edge is analyzer noise forever).
- **Per-function flow events** — lock acquisitions with the locally
  held set at each point, call sites with targets + held set, ``self``
  attribute reads/writes, registry get-or-create calls, telemetry
  calls, ``json.dumps`` sites, zero-arg blocking calls: everything
  the five passes need, from ONE walk per function.

Suppression vocabulary (trailing comments, same line or the line
above)::

    # ckcheck: guarded-by <lock-attr>   -- this access IS protected (by
    #                                       protocol the analyzer cannot
    #                                       see); treat as locked
    # ckcheck: ok <reason>              -- finding acknowledged as
    #                                       intentional; suppressed
    # ckcheck: cold <reason>            -- on a `def` line: hot-path
    #                                       reachability stops here
"""

from __future__ import annotations

import ast
import hashlib
import os
import re
from dataclasses import dataclass, field

__all__ = [
    "Finding",
    "Lock",
    "FuncInfo",
    "Module",
    "Package",
    "scan_package",
]

_SUPPRESS_RE = re.compile(
    r"#\s*ckcheck:\s*(ok|guarded-by|cold)\b[ \t]*([^\n]*)")

#: threading factory callables that create a lock-like object.
_LOCK_FACTORIES = {
    "Lock": ("lock", False),
    "RLock": ("rlock", True),
    "Condition": ("condition", False),
}

#: Registry get-or-create method names (the hot-path pass's target).
REGISTRY_FACTORIES = ("counter", "gauge", "histogram")

#: Method names whose calls mutate their receiver in place — a call
#: ``self.attr.append(x)`` is a WRITE of ``self.attr`` for the lockset
#: pass.
_MUTATORS = {
    "append", "appendleft", "extend", "insert", "remove", "pop",
    "popleft", "clear", "add", "discard", "update", "setdefault",
}

#: Methods excluded from the lockset pass: construction and teardown
#: run single-threaded by contract.
LIFECYCLE_METHODS = {"__init__", "__new__", "__del__", "__exit__",
                     "dispose", "close", "shutdown", "stop"}


@dataclass(frozen=True)
class Finding:
    """One analyzer finding.  The fingerprint deliberately excludes the
    line number so the ratchet baseline survives unrelated edits above
    the finding; ``subject`` carries the stable identity (lock ids,
    ``Class.attr``, callee) instead."""

    pass_id: str
    rule: str
    path: str
    line: int
    subject: str
    message: str

    @property
    def fingerprint(self) -> str:
        raw = f"{self.pass_id}:{self.rule}:{self.path}:{self.subject}"
        return hashlib.sha1(raw.encode()).hexdigest()[:16]

    def render(self) -> str:
        return (f"[{self.fingerprint}] {self.pass_id}/{self.rule} "
                f"{self.path}:{self.line}: {self.message}")

    def to_row(self) -> dict:
        return {
            "fingerprint": self.fingerprint,
            "pass": self.pass_id,
            "rule": self.rule,
            "path": self.path,
            "line": self.line,
            "subject": self.subject,
            "message": self.message,
        }


@dataclass(frozen=True)
class Lock:
    lock_id: str          # "core.worker.Worker.lock" / "native.build._lock"
    attr: str             # attribute or module-global name
    owner: str | None     # owning class qualname, None for module-level
    module: str
    path: str
    line: int
    reentrant: bool
    kind: str             # lock | rlock | condition


@dataclass
class AcqSite:
    """One lock acquisition point inside a function."""

    lock: Lock
    line: int
    held: tuple           # lock_ids locally held when acquiring
    receiver: str         # "self" | "singleton" | "name" | "attr"
    conditional: bool     # an `x if c else nullcontext()` style item


@dataclass
class CallSite:
    targets: tuple        # resolved callee qualnames (possibly empty)
    line: int
    held: tuple           # lock_ids locally held at the call


@dataclass
class AttrAccess:
    attr: str
    line: int
    held: tuple
    is_write: bool
    via_mutator: bool = False
    owner: str | None = None   # owning class qualname (self OR typed receiver)


@dataclass
class RegistryCall:
    method: str           # counter | gauge | histogram
    name: str | None      # literal first arg when present
    line: int


@dataclass
class TelemetryCall:
    api: str              # "span" (tracer) | "event" (flight) | "decision"
    method: str           # record | instant | span | event
    kind: str | None      # literal first arg
    line: int
    computed_args: bool   # any argument allocates (f-string/concat/call)
    enabled_guarded: bool # lexically inside `if X.enabled:` / `if X.active():`


@dataclass
class JsonDumpCall:
    line: int
    has_allow_nan_false: bool
    sanitized: bool       # first arg wrapped in json_safe(...)


@dataclass
class BlockingCall:
    """A zero-argument ``.join()`` / ``.wait()`` / ``.get()`` call —
    the unbounded-blocking shapes (Thread.join, Condition/Event.wait,
    Queue.get) that hang shutdown when the counterpart thread died.
    Any argument bounds the wait (a timeout) or marks a non-blocking
    receiver (``str.join(parts)``, ``dict.get(key)``), so only the
    bare form is recorded."""

    method: str           # join | wait | get
    line: int


@dataclass
class SubscriptAssign:
    base: str             # name of the subscripted variable
    key: str | None       # literal string key when present
    line: int
    stmt_index: int       # order within the enclosing function body walk


@dataclass
class FuncInfo:
    qualname: str
    module: str
    cls: str | None
    path: str
    node: ast.AST
    lineno: int
    is_nested: bool = False
    cold: str | None = None          # reason when annotated `# ckcheck: cold`
    acq_sites: list = field(default_factory=list)
    call_sites: list = field(default_factory=list)
    attr_accesses: list = field(default_factory=list)
    registry_calls: list = field(default_factory=list)
    telemetry_calls: list = field(default_factory=list)
    json_calls: list = field(default_factory=list)
    blocking_calls: list = field(default_factory=list)
    subscript_assigns: list = field(default_factory=list)
    dict_literal_headline: list = field(default_factory=list)  # bad lines

    @property
    def is_public(self) -> bool:
        name = self.qualname.rsplit(".", 1)[-1]
        return not name.startswith("_") or (
            name.startswith("__") and name.endswith("__"))


@dataclass
class ClassInfo:
    qualname: str
    module: str
    bases: tuple = ()                 # package-internal base qualnames
    methods: dict = field(default_factory=dict)   # name -> FuncInfo
    attr_types: dict = field(default_factory=dict)  # attr -> ("inst"|"list", cls)
    locks: dict = field(default_factory=dict)       # attr -> Lock
    attr_init_lines: dict = field(default_factory=dict)  # attr -> first line


@dataclass
class Module:
    modname: str
    path: str             # repo-relative
    tree: ast.AST
    suppress: dict        # line -> (kind, arg)
    comment_lines: frozenset = frozenset()  # comment-only line numbers
    imports: dict = field(default_factory=dict)   # local name -> fully.qualified
    spawns_threads: bool = False

    def suppressed(self, line: int, kinds=("ok", "guarded-by")):
        """Suppression record covering ``line``: on the line itself, or
        anywhere in the contiguous block of comment-only lines directly
        above it (a multi-line justification keeps working)."""
        rec = self.suppress.get(line)
        if rec is not None and rec[0] in kinds:
            return rec
        ln = line - 1
        while ln > 0 and ln in self.comment_lines:
            rec = self.suppress.get(ln)
            if rec is not None and rec[0] in kinds:
                return rec
            ln -= 1
        return None


_THREAD_SPAWN_RE = re.compile(
    r"threading\.Thread\(|Thread\(|ThreadPoolExecutor\(|"
    r"ThreadingHTTPServer\(|_DriverQueue\(|\.start\(\)"
)


class Package:
    """Everything the passes need, from one scan."""

    def __init__(self, root: str, pkg_name: str):
        self.root = root
        self.pkg_name = pkg_name
        self.modules: dict[str, Module] = {}
        self.classes: dict[str, ClassInfo] = {}
        self.functions: dict[str, FuncInfo] = {}
        self.locks: dict[str, Lock] = {}
        self.singletons: dict[str, str] = {}   # "mod.NAME" -> class qualname

    # -- lookups -------------------------------------------------------------
    def class_lock(self, cls: str, attr: str) -> Lock | None:
        """Lock ``attr`` on ``cls``, walking package-internal bases."""
        seen = set()
        while cls and cls not in seen:
            seen.add(cls)
            ci = self.classes.get(cls)
            if ci is None:
                return None
            if attr in ci.locks:
                return ci.locks[attr]
            cls = ci.bases[0] if ci.bases else None
        return None

    def class_method(self, cls: str, name: str) -> FuncInfo | None:
        seen = set()
        while cls and cls not in seen:
            seen.add(cls)
            ci = self.classes.get(cls)
            if ci is None:
                return None
            if name in ci.methods:
                return ci.methods[name]
            cls = ci.bases[0] if ci.bases else None
        return None

    def class_attr_type(self, cls: str, attr: str):
        seen = set()
        while cls and cls not in seen:
            seen.add(cls)
            ci = self.classes.get(cls)
            if ci is None:
                return None
            if attr in ci.attr_types:
                return ci.attr_types[attr]
            cls = ci.bases[0] if ci.bases else None
        return None

    def locks_named(self, attr: str, module: str | None = None) -> list[Lock]:
        out = [l for l in self.locks.values() if l.attr == attr]
        if module is not None:
            mod_out = [l for l in out if l.module == module]
            if mod_out:
                return mod_out
        return out


# ---------------------------------------------------------------------------
# scanning
# ---------------------------------------------------------------------------

def _collect_suppressions(source: str):
    """(line → suppression, comment-only line set)."""
    out = {}
    comments = set()
    for i, line in enumerate(source.splitlines(), 1):
        if line.lstrip().startswith("#"):
            comments.add(i)
        m = _SUPPRESS_RE.search(line)
        if m:
            out[i] = (m.group(1), m.group(2).strip())
    return out, frozenset(comments)


def _iter_py_files(root: str):
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames[:] = sorted(
            d for d in dirnames if d != "__pycache__" and not d.startswith(".")
        )
        for fn in sorted(filenames):
            if fn.endswith(".py"):
                yield os.path.join(dirpath, fn)


def _modname(root: str, path: str, pkg_name: str) -> str:
    rel = os.path.relpath(path, root)
    mod = rel[:-3].replace(os.sep, ".")
    if mod.endswith(".__init__"):
        mod = mod[: -len(".__init__")]
    if mod == "__init__":
        mod = pkg_name
    return mod


def _lock_factory(call: ast.expr):
    """(kind, reentrant) when ``call`` is threading.Lock()/RLock()/
    Condition() (or a bare imported name), else None."""
    if not isinstance(call, ast.Call):
        return None
    fn = call.func
    name = None
    if isinstance(fn, ast.Attribute) and isinstance(fn.value, ast.Name):
        if fn.value.id == "threading":
            name = fn.attr
    elif isinstance(fn, ast.Name):
        name = fn.id
    return _LOCK_FACTORIES.get(name) if name else None


def scan_package(root: str, pkg_name: str | None = None,
                 extra_paths: tuple = (), repo_root: str | None = None
                 ) -> Package:
    """Parse every ``.py`` under ``root`` (plus ``extra_paths`` files,
    scanned for the invariant pass only) into a :class:`Package`.
    ``repo_root`` anchors the repo-relative paths findings carry."""
    pkg_name = pkg_name or os.path.basename(os.path.normpath(root))
    repo_root = repo_root or os.path.dirname(os.path.normpath(root))
    pkg = Package(root, pkg_name)

    paths = [(p, _modname(root, p, pkg_name)) for p in _iter_py_files(root)]
    for p in extra_paths:
        rel = os.path.relpath(p, repo_root)
        paths.append((p, rel[:-3].replace(os.sep, ".")))

    # phase A: parse, inventory classes/locks/singletons/imports
    for path, modname in paths:
        with open(path) as f:
            source = f.read()
        try:
            tree = ast.parse(source)
        except SyntaxError as e:  # a broken file is itself a finding later
            tree = ast.Module(body=[], type_ignores=[])
            tree._ckcheck_syntax_error = str(e)  # type: ignore[attr-defined]
        suppress, comment_lines = _collect_suppressions(source)
        mod = Module(
            modname=modname,
            path=os.path.relpath(path, repo_root),
            tree=tree,
            suppress=suppress,
            comment_lines=comment_lines,
            spawns_threads=bool(_THREAD_SPAWN_RE.search(source)),
        )
        pkg.modules[modname] = mod
        _inventory_module(pkg, mod)

    # phase B: resolve singletons and attribute types now that EVERY
    # class is known (phase A's file order must not decide whether
    # `self.workers = [Worker(...)]` resolves)
    for mod in pkg.modules.values():
        _inventory_singletons(pkg, mod)
    for mod in pkg.modules.values():
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.ClassDef):
                qual = _class_qual_in_module(mod, node)
                ci = pkg.classes.get(qual)
                if ci is None:
                    continue
                for item in node.body:
                    if isinstance(item, (ast.FunctionDef,
                                         ast.AsyncFunctionDef)):
                        _inventory_attr_types(pkg, mod, ci, item)

    # phase C: per-function flow walks (needs full inventory)
    for mod in pkg.modules.values():
        _walk_module_functions(pkg, mod)
    return pkg


def _resolve_import(mod: Module, pkg: Package, node: ast.ImportFrom) -> None:
    """Map ``from ..x.y import NAME`` to ``x.y.NAME`` within the
    package (absolute or relative)."""
    if node.module is None and node.level == 0:
        return
    if node.level > 0:
        parts = mod.modname.split(".")
        # level=1 strips the module's own name, deeper levels strip
        # parents; for a package __init__ the modname IS the package
        base = parts[: len(parts) - node.level]
        target = ".".join(base + (node.module.split(".") if node.module else []))
    else:
        target = node.module or ""
        if target.startswith(pkg.pkg_name + "."):
            target = target[len(pkg.pkg_name) + 1:]
        elif target == pkg.pkg_name:
            target = ""
    for alias in node.names:
        local = alias.asname or alias.name
        mod.imports[local] = f"{target}.{alias.name}" if target else alias.name


def _inventory_module(pkg: Package, mod: Module) -> None:
    for node in mod.tree.body:
        if isinstance(node, ast.ImportFrom):
            _resolve_import(mod, pkg, node)
        elif isinstance(node, ast.Assign) and len(node.targets) == 1:
            t = node.targets[0]
            fac = _lock_factory(node.value)
            if isinstance(t, ast.Name) and fac:
                lock = Lock(
                    lock_id=f"{mod.modname}.{t.id}", attr=t.id, owner=None,
                    module=mod.modname, path=mod.path, line=node.lineno,
                    reentrant=fac[1], kind=fac[0],
                )
                pkg.locks[lock.lock_id] = lock
        elif isinstance(node, ast.ClassDef):
            _inventory_class(pkg, mod, node)


def _inventory_class(pkg: Package, mod: Module, node: ast.ClassDef) -> None:
    qual = f"{mod.modname}.{node.name}"
    bases = []
    for b in node.bases:
        if isinstance(b, ast.Name):
            target = mod.imports.get(b.id, b.id)
            bases.append(target if "." in target else f"{mod.modname}.{b.id}")
    ci = ClassInfo(qualname=qual, module=mod.modname, bases=tuple(bases))
    pkg.classes[qual] = ci
    for item in node.body:
        if isinstance(item, (ast.FunctionDef, ast.AsyncFunctionDef)):
            fi = FuncInfo(
                qualname=f"{qual}.{item.name}", module=mod.modname,
                cls=qual, path=mod.path, node=item, lineno=item.lineno,
            )
            rec = mod.suppress.get(item.lineno) or mod.suppress.get(
                item.lineno - 1)
            if rec and rec[0] == "cold":
                fi.cold = rec[1] or "annotated cold"
            ci.methods[item.name] = fi
            pkg.functions[fi.qualname] = fi
            _inventory_self_assigns(pkg, mod, ci, item)
        elif isinstance(item, ast.ClassDef):
            _inventory_class(pkg, mod, item)  # nested class (rare)


def _self_attr_assigns(fn: ast.AST):
    """(target_attr, value, line) for every ``self.X = ...`` /
    ``self.X: T = ...`` in ``fn``, skipping nested functions."""
    for node in ast.walk(fn):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                and node is not fn:
            continue
        if isinstance(node, ast.AnnAssign):
            t, value = node.target, node.value
        elif isinstance(node, ast.Assign) and len(node.targets) == 1:
            t, value = node.targets[0], node.value
        else:
            continue
        if isinstance(t, ast.Attribute) and \
                isinstance(t.value, ast.Name) and t.value.id == "self":
            yield t.attr, value, node.lineno


def _inventory_self_assigns(pkg: Package, mod: Module, ci: ClassInfo,
                            fn: ast.AST) -> None:
    """Phase A: lock attributes + attribute init lines (syntactic —
    needs no cross-module class knowledge)."""
    for attr, value, lineno in _self_attr_assigns(fn):
        ci.attr_init_lines.setdefault(attr, lineno)
        fac = _lock_factory(value) if value is not None else None
        if fac:
            lock = Lock(
                lock_id=f"{ci.qualname}.{attr}", attr=attr,
                owner=ci.qualname, module=mod.modname, path=mod.path,
                line=lineno, reentrant=fac[1], kind=fac[0],
            )
            ci.locks[attr] = lock
            pkg.locks[lock.lock_id] = lock


def _inventory_attr_types(pkg: Package, mod: Module, ci: ClassInfo,
                          fn: ast.AST) -> None:
    """Phase B: ``self.X = ClassName(...)`` / ``[ClassName(...)]`` /
    an annotated parameter: receiver types, resolved against the COMPLETE
    class inventory."""
    params = {a.arg: a.annotation for a in getattr(fn.args, "args", ())
              if a.annotation is not None}
    for attr, value, _lineno in _self_attr_assigns(fn):
        if value is None or attr in ci.locks:
            continue
        cls = _constructed_class(mod, pkg, value)
        if cls is None and isinstance(value, ast.Name):
            # ``self.X = x`` where ``x`` is a parameter annotated with a
            # package class: a collaborator handed in at construction
            cls = _annotated_class(mod, pkg, params.get(value.id))
        if cls:
            ci.attr_types.setdefault(attr, cls)


def _annotated_class(mod: Module, pkg: Package, ann):
    """("inst"|"list", qualname) for a parameter annotation ``ClassName``
    / ``list[ClassName]`` naming a class of the package."""
    kind = "inst"
    if isinstance(ann, ast.Subscript) and isinstance(ann.value, ast.Name) \
            and ann.value.id in ("list", "List", "Sequence"):
        kind, ann = "list", ann.slice
    if isinstance(ann, ast.Name):
        qual = mod.imports.get(ann.id) or f"{mod.modname}.{ann.id}"
        if qual in pkg.classes:
            return (kind, qual)
    return None


def _constructed_class(mod: Module, pkg: Package, value: ast.expr):
    """("inst"|"list", qualname) for ``ClassName(...)`` /
    ``[ClassName(...) ...]`` / ``REGISTRY.counter(...)`` values."""
    if isinstance(value, ast.Call):
        fn = value.func
        if isinstance(fn, ast.Name):
            target = mod.imports.get(fn.id, None)
            qual = target if target else f"{mod.modname}.{fn.id}"
            if qual in pkg.classes:
                return ("inst", qual)
        if isinstance(fn, ast.Attribute) and isinstance(fn.value, ast.Name):
            # REGISTRY.counter(...) -> metrics.registry.Counter etc.
            recv = fn.value.id
            sing = mod.imports.get(recv, f"{mod.modname}.{recv}")
            cls = pkg.singletons.get(sing)
            if cls and fn.attr in REGISTRY_FACTORIES:
                owner_mod = cls.rsplit(".", 1)[0]
                target = f"{owner_mod}.{fn.attr.capitalize()}"
                if target in pkg.classes:
                    return ("inst", target)
    if isinstance(value, (ast.List, ast.ListComp)):
        elts = value.elts if isinstance(value, ast.List) else [value.elt]
        for e in elts:
            r = _constructed_class(mod, pkg, e)
            if r and r[0] == "inst":
                return ("list", r[1])
    return None


def _class_qual_in_module(mod: Module, node: ast.ClassDef) -> str:
    return f"{mod.modname}.{node.name}"


def _inventory_singletons(pkg: Package, mod: Module) -> None:
    for node in mod.tree.body:
        if isinstance(node, ast.Assign) and len(node.targets) == 1 and \
                isinstance(node.targets[0], ast.Name) and \
                isinstance(node.value, ast.Call) and \
                isinstance(node.value.func, ast.Name):
            cls = f"{mod.modname}.{node.value.func.id}"
            if cls in pkg.classes:
                pkg.singletons[f"{mod.modname}.{node.targets[0].id}"] = cls


# ---------------------------------------------------------------------------
# per-function flow walk
# ---------------------------------------------------------------------------

class _FuncWalker:
    """One walk of one function body: locally-held lock tracking,
    typed receiver resolution, event recording."""

    def __init__(self, pkg: Package, mod: Module, fi: FuncInfo,
                 outer_types: dict | None = None):
        self.pkg = pkg
        self.mod = mod
        self.fi = fi
        # local name -> class qualname (under-approximate)
        self.types: dict[str, str] = dict(outer_types or {})
        # local name -> tuple of method qualnames (bound-method aliases:
        # `engine = self._run_a if c else self._run_b; engine(...)`)
        self.method_aliases: dict[str, tuple] = {}
        self.stmt_counter = 0
        self._collect_param_types()

    # -- typing --------------------------------------------------------------
    def _class_by_name(self, name: str) -> str | None:
        target = self.mod.imports.get(name)
        qual = target if target else f"{self.mod.modname}.{name}"
        return qual if qual in self.pkg.classes else None

    def _collect_param_types(self) -> None:
        node = self.fi.node
        args = getattr(node, "args", None)
        if args is None:
            return
        for a in list(args.posonlyargs) + list(args.args) + \
                list(args.kwonlyargs):
            ann = a.annotation
            name = None
            if isinstance(ann, ast.Name):
                name = ann.id
            elif isinstance(ann, ast.Constant) and isinstance(ann.value, str):
                name = ann.value.split(".")[-1]
            elif isinstance(ann, ast.BinOp):  # "Worker | None"
                for side in (ann.left, ann.right):
                    if isinstance(side, ast.Name) and side.id != "None":
                        name = side.id
                        break
            if name:
                cls = self._class_by_name(name)
                if cls:
                    self.types[a.arg] = cls

    def expr_type(self, node: ast.expr) -> str | None:
        if isinstance(node, ast.Name):
            if node.id == "self" and self.fi.cls:
                return self.fi.cls
            if node.id in self.types:
                return self.types[node.id]
            sing = self.mod.imports.get(node.id, f"{self.mod.modname}.{node.id}")
            return self.pkg.singletons.get(sing)
        if isinstance(node, ast.Attribute):
            base = self.expr_type(node.value)
            if base:
                t = self.pkg.class_attr_type(base, node.attr)
                if t and t[0] == "inst":
                    return t[1]
            return None
        if isinstance(node, ast.IfExp):
            return self.expr_type(node.body) or self.expr_type(node.orelse)
        if isinstance(node, ast.Call):
            if isinstance(node.func, ast.Name):
                cls = self._class_by_name(node.func.id)
                if cls:
                    return cls
        return None

    # -- lock resolution -----------------------------------------------------
    def resolve_lock(self, node: ast.expr):
        """(Lock, receiver_kind) or None for a with-item / enter_context
        argument."""
        if isinstance(node, ast.IfExp):
            for branch in (node.body, node.orelse):
                r = self.resolve_lock(branch)
                if r:
                    return (r[0], r[1], True)
            return None
        if isinstance(node, ast.Attribute):
            base_t = self.expr_type(node.value)
            if base_t:
                lock = self.pkg.class_lock(base_t, node.attr)
                if lock:
                    recv = ("self" if isinstance(node.value, ast.Name)
                            and node.value.id == "self" else
                            ("singleton" if isinstance(node.value, ast.Name)
                             and self.pkg.singletons.get(
                                 self.mod.imports.get(
                                     node.value.id,
                                     f"{self.mod.modname}.{node.value.id}"))
                             else "name"))
                    return (lock, recv, False)
            # fall back: unique attribute name (module first, package next)
            cands = self.pkg.locks_named(node.attr, self.mod.modname)
            if len(cands) == 1:
                return (cands[0], "attr", False)
            return None
        if isinstance(node, ast.Name):
            lid = f"{self.mod.modname}.{node.id}"
            if lid in self.pkg.locks:
                return (self.pkg.locks[lid], "name", False)
            imported = self.mod.imports.get(node.id)
            if imported and imported in self.pkg.locks:
                return (self.pkg.locks[imported], "name", False)
        return None

    # -- call resolution -----------------------------------------------------
    def _method_ref(self, node: ast.expr) -> tuple:
        """Qualnames a bound-method REFERENCE (no call) resolves to."""
        if isinstance(node, ast.IfExp):
            return self._method_ref(node.body) + self._method_ref(node.orelse)
        if isinstance(node, ast.Attribute):
            base_t = self.expr_type(node.value)
            if base_t:
                m = self.pkg.class_method(base_t, node.attr)
                if m is not None:
                    return (m.qualname,)
        return ()

    def resolve_call(self, node: ast.Call) -> tuple:
        fn = node.func
        if isinstance(fn, ast.Name):
            if fn.id in self.method_aliases:
                return self.method_aliases[fn.id]
            qual = self.mod.imports.get(fn.id, f"{self.mod.modname}.{fn.id}")
            if qual in self.pkg.functions:
                return (qual,)
            return ()
        if isinstance(fn, ast.Attribute):
            base_t = self.expr_type(fn.value)
            if base_t:
                m = self.pkg.class_method(base_t, fn.attr)
                if m is not None:
                    return (m.qualname,)
            # ClassName.method(...) (static-style)
            if isinstance(fn.value, ast.Name):
                cls = self._class_by_name(fn.value.id)
                if cls:
                    m = self.pkg.class_method(cls, fn.attr)
                    if m is not None:
                        return (m.qualname,)
        return ()

    def registry_call(self, node: ast.Call):
        """(method, literal name) when this is a REGISTRY get-or-create."""
        fn = node.func
        if not (isinstance(fn, ast.Attribute)
                and fn.attr in REGISTRY_FACTORIES):
            return None
        recv_is_registry = False
        if isinstance(fn.value, ast.Name):
            if fn.value.id == "REGISTRY":  # conventional singleton name
                recv_is_registry = True
            else:
                t = self.expr_type(fn.value)
                recv_is_registry = bool(t and t.endswith("MetricsRegistry"))
        if not recv_is_registry:
            return None
        name = None
        if node.args and isinstance(node.args[0], ast.Constant) \
                and isinstance(node.args[0].value, str):
            name = node.args[0].value
        return (fn.attr, name)

    def telemetry_call(self, node: ast.Call):
        """(api, method, literal kind) for tracer/flight/decision
        record sites."""
        fn = node.func
        if not isinstance(fn, ast.Attribute):
            return None
        api = None
        if fn.attr in ("record", "instant", "span"):
            t = self.expr_type(fn.value)
            named = isinstance(fn.value, ast.Name) and fn.value.id == "TRACER"
            if named or (t and t.endswith(".Tracer")):
                api = "span"
            elif fn.attr == "record":
                # the decision log shares the tracer's method name;
                # receiver disambiguates (DECISIONS singleton / a typed
                # DecisionLog)
                named_d = isinstance(fn.value, ast.Name) and \
                    fn.value.id == "DECISIONS"
                if named_d or (t and t.endswith(".DecisionLog")):
                    api = "decision"
        elif fn.attr == "event":
            t = self.expr_type(fn.value)
            named = isinstance(fn.value, ast.Name) and \
                fn.value.id in ("FLIGHT",)
            if named or (t and t.endswith(".FlightRecorder")):
                api = "event"
            else:
                # the request-lifecycle recorder shares the method
                # name; receiver disambiguates (REQTRACE singleton / a
                # typed ReqTrace), and its kind is the SECOND
                # positional — event(rid, kind, **fields)
                named_r = isinstance(fn.value, ast.Name) and \
                    fn.value.id == "REQTRACE"
                if named_r or (t and t.endswith(".ReqTrace")):
                    api = "reqevent"
        if api is None:
            return None
        kind = None
        kind_i = 1 if api == "reqevent" else 0
        if len(node.args) > kind_i \
                and isinstance(node.args[kind_i], ast.Constant) \
                and isinstance(node.args[kind_i].value, str):
            kind = node.args[kind_i].value
        return (api, fn.attr, kind)

    # -- the walk ------------------------------------------------------------
    def walk(self) -> None:
        body = getattr(self.fi.node, "body", [])
        self._walk_stmts(body, held=(), enabled_guard=False)

    def _walk_stmts(self, stmts, held: tuple, enabled_guard: bool) -> None:
        for st in stmts:
            self.stmt_counter += 1
            if isinstance(st, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self._nested_function(st)
                continue
            if isinstance(st, ast.With):
                new_held = held
                for item in st.items:
                    ctx = item.context_expr
                    r = self.resolve_lock(ctx)
                    if r:
                        lock, recv, cond = (r + (False,))[:3]
                        self.fi.acq_sites.append(AcqSite(
                            lock=lock, line=ctx.lineno, held=new_held,
                            receiver=recv, conditional=bool(cond)))
                        if lock.lock_id not in new_held:
                            new_held = new_held + (lock.lock_id,)
                    else:
                        self._scan_expr(ctx, new_held, enabled_guard)
                # `stack.enter_context(<lock>)` acquisitions anywhere in
                # the body (the ExitStack all-worker-locks ladder) hold
                # for the remainder of the with block — approximated as
                # held for the WHOLE body, which only over-holds the
                # statements before the enter_context call
                for lock, recv, line in self._enter_context_locks(st.body):
                    self.fi.acq_sites.append(AcqSite(
                        lock=lock, line=line, held=new_held,
                        receiver=recv, conditional=False))
                    if lock.lock_id not in new_held:
                        new_held = new_held + (lock.lock_id,)
                self._walk_stmts(st.body, new_held, enabled_guard)
                continue
            if isinstance(st, ast.If):
                self._scan_expr(st.test, held, enabled_guard)
                guard = enabled_guard or self._is_enabled_test(st.test)
                self._walk_stmts(st.body, held, guard)
                self._walk_stmts(st.orelse, held, enabled_guard)
                continue
            if isinstance(st, (ast.For, ast.AsyncFor)):
                self._type_loop_target(st)
                self._scan_expr(st.iter, held, enabled_guard)
                self._walk_stmts(st.body, held, enabled_guard)
                self._walk_stmts(st.orelse, held, enabled_guard)
                continue
            if isinstance(st, ast.While):
                self._scan_expr(st.test, held, enabled_guard)
                self._walk_stmts(st.body, held, enabled_guard)
                self._walk_stmts(st.orelse, held, enabled_guard)
                continue
            if isinstance(st, ast.Try):
                self._walk_stmts(st.body, held, enabled_guard)
                for h in st.handlers:
                    self._walk_stmts(h.body, held, enabled_guard)
                self._walk_stmts(st.orelse, held, enabled_guard)
                self._walk_stmts(st.finalbody, held, enabled_guard)
                continue
            if isinstance(st, ast.Assign):
                self._record_assign(st, held)
                self._scan_expr(st.value, held, enabled_guard)
                for t in st.targets:
                    self._scan_target(t, held)
                continue
            if isinstance(st, ast.AugAssign):
                self._scan_expr(st.value, held, enabled_guard)
                self._record_augassign(st, held)
                continue
            if isinstance(st, (ast.Expr, ast.Return)):
                if st.value is not None:
                    self._scan_expr(st.value, held, enabled_guard)
                continue
            if isinstance(st, ast.AnnAssign):
                if st.value is not None:
                    self._scan_expr(st.value, held, enabled_guard)
                continue
            # other statements: scan child expressions generically
            for child in ast.iter_child_nodes(st):
                if isinstance(child, ast.expr):
                    self._scan_expr(child, held, enabled_guard)

    def _enter_context_locks(self, body) -> list:
        """``enter_context(<resolvable lock>)`` calls in ``body``,
        skipping nested function definitions (closures run elsewhere)."""
        out = []
        stack = list(body)
        while stack:
            st = stack.pop()
            if isinstance(st, (ast.FunctionDef, ast.AsyncFunctionDef,
                               ast.Lambda)):
                continue
            for n in ast.iter_child_nodes(st):
                stack.append(n)
            if isinstance(st, ast.Call) and \
                    isinstance(st.func, ast.Attribute) and \
                    st.func.attr == "enter_context" and st.args:
                r = self.resolve_lock(st.args[0])
                if r:
                    out.append((r[0], r[1], st.lineno))
        return out

    @staticmethod
    def _is_enabled_test(test: ast.expr) -> bool:
        """``X.enabled``, or ``X.active()`` — the tracer's two-sink test
        (ring or profiler session, trace/spans.py)."""
        for n in ast.walk(test):
            if isinstance(n, ast.Attribute) and n.attr == "enabled":
                return True
            if isinstance(n, ast.Call) and isinstance(n.func, ast.Attribute) \
                    and n.func.attr == "active" and not n.args:
                return True
        return False

    def _nested_function(self, node) -> None:
        """Closures get their own FuncInfo with an EMPTY held-set: in
        this codebase nested defs are dispatch closures that run on
        driver threads, never under the definer's locks."""
        qual = f"{self.fi.qualname}.<locals>.{node.name}"
        fi = FuncInfo(
            qualname=qual, module=self.fi.module, cls=self.fi.cls,
            path=self.fi.path, node=node, lineno=node.lineno, is_nested=True,
        )
        self.pkg.functions[qual] = fi
        _FuncWalker(self.pkg, self.mod, fi, outer_types=self.types).walk()

    def _type_loop_target(self, st) -> None:
        """``for w in self.workers`` / ``for i, w in enumerate(...)``."""
        it = st.iter
        elt_cls = None
        if isinstance(it, ast.Call) and isinstance(it.func, ast.Name) \
                and it.func.id == "enumerate" and it.args:
            inner = it.args[0]
        else:
            inner = it
        t = None
        if isinstance(inner, ast.Attribute):
            base = self.expr_type(inner.value)
            if base:
                t = self.pkg.class_attr_type(base, inner.attr)
        elif isinstance(inner, ast.Name) and inner.id in self.types:
            pass  # plain instance — not iterable typing
        if t and t[0] == "list":
            elt_cls = t[1]
        if elt_cls is None:
            return
        tgt = st.target
        if isinstance(it, ast.Call) and isinstance(it.func, ast.Name) \
                and it.func.id == "enumerate" and isinstance(tgt, ast.Tuple) \
                and len(tgt.elts) == 2 and isinstance(tgt.elts[1], ast.Name):
            self.types[tgt.elts[1].id] = elt_cls
        elif isinstance(tgt, ast.Name):
            self.types[tgt.id] = elt_cls

    def _type_comp_target(self, gen: ast.comprehension) -> None:
        inner = gen.iter
        if isinstance(inner, ast.Call) and isinstance(inner.func, ast.Name) \
                and inner.func.id == "enumerate" and inner.args:
            src, tgt_idx = inner.args[0], 1
        else:
            src, tgt_idx = inner, None
        t = None
        if isinstance(src, ast.Attribute):
            base = self.expr_type(src.value)
            if base:
                t = self.pkg.class_attr_type(base, src.attr)
        if not (t and t[0] == "list"):
            return
        tgt = gen.target
        if tgt_idx is not None and isinstance(tgt, ast.Tuple) and \
                len(tgt.elts) == 2 and isinstance(tgt.elts[1], ast.Name):
            self.types[tgt.elts[1].id] = t[1]
        elif tgt_idx is None and isinstance(tgt, ast.Name):
            self.types[tgt.id] = t[1]

    def _record_assign(self, st: ast.Assign, held: tuple) -> None:
        if len(st.targets) == 1 and isinstance(st.targets[0], ast.Name):
            cls = self.expr_type(st.value)
            if cls:
                self.types[st.targets[0].id] = cls
            refs = self._method_ref(st.value)
            if refs:
                self.method_aliases[st.targets[0].id] = refs
        for t in st.targets:
            if isinstance(t, ast.Subscript) and isinstance(t.value, ast.Name):
                key = None
                sl = t.slice
                if isinstance(sl, ast.Constant) and isinstance(sl.value, str):
                    key = sl.value
                self.fi.subscript_assigns.append(SubscriptAssign(
                    base=t.value.id, key=key, line=st.lineno,
                    stmt_index=self.stmt_counter))
            if isinstance(t, ast.Tuple):
                # `a, self.x = ...` swaps count as attribute writes
                for e in t.elts:
                    self._maybe_attr_write(e, held)
            else:
                self._maybe_attr_write(t, held)

    def _record_augassign(self, st: ast.AugAssign, held: tuple) -> None:
        self._maybe_attr_write(st.target, held)
        # `self.x[k] += v` / `self.x |= v` hit the same attribute
        t = st.target
        if isinstance(t, ast.Subscript):
            self._maybe_attr_write(t.value, held)

    def _attr_owner(self, node: ast.Attribute) -> str | None:
        """Owning package class of an attribute access — the receiver's
        resolved type (``self`` or a typed variable like ``w: Worker``)."""
        owner = self.expr_type(node.value)
        return owner if owner in self.pkg.classes else None

    def _maybe_attr_write(self, node: ast.expr, held: tuple) -> None:
        if isinstance(node, ast.Subscript):
            node = node.value
        if isinstance(node, ast.Attribute):
            owner = self._attr_owner(node)
            if owner:
                self.fi.attr_accesses.append(AttrAccess(
                    attr=node.attr, line=node.lineno, held=held,
                    is_write=True, owner=owner))

    def _scan_target(self, node: ast.expr, held: tuple) -> None:
        # subscript stores `self.x[k] = v` count as writes of self.x
        if isinstance(node, ast.Subscript) and \
                isinstance(node.value, ast.Attribute):
            owner = self._attr_owner(node.value)
            if owner:
                self.fi.attr_accesses.append(AttrAccess(
                    attr=node.value.attr, line=node.lineno, held=held,
                    is_write=True, owner=owner))

    def _scan_expr(self, node: ast.expr, held: tuple,
                   enabled_guard: bool) -> None:
        # comprehension loop vars first: `[w.x for w in self.workers]`
        # must type `w` before the body's attribute reads resolve
        for n in ast.walk(node):
            if isinstance(n, (ast.ListComp, ast.SetComp, ast.DictComp,
                              ast.GeneratorExp)):
                for gen in n.generators:
                    self._type_comp_target(gen)
        for n in ast.walk(node):
            if isinstance(n, ast.Call):
                self._record_call(n, held, enabled_guard)
            elif isinstance(n, ast.Attribute) and isinstance(n.ctx, ast.Load):
                owner = self._attr_owner(n)
                if owner:
                    self.fi.attr_accesses.append(AttrAccess(
                        attr=n.attr, line=n.lineno, held=held,
                        is_write=False, owner=owner))
            elif isinstance(n, (ast.Lambda, ast.ListComp, ast.SetComp,
                                ast.DictComp, ast.GeneratorExp)):
                pass  # walked generically; held-set applies unchanged

    def _record_call(self, node: ast.Call, held: tuple,
                     enabled_guard: bool) -> None:
        fn = node.func
        # enter_context(<lock>) acquisitions are recorded by the With
        # handler's body pre-scan (they hold for the rest of the block)
        if isinstance(fn, ast.Attribute) and fn.attr == "enter_context" \
                and node.args and self.resolve_lock(node.args[0]):
            return
        # mutator calls on resolvable attributes are writes
        if isinstance(fn, ast.Attribute) and fn.attr in _MUTATORS:
            tgt = fn.value
            if isinstance(tgt, ast.Subscript):
                tgt = tgt.value
            if isinstance(tgt, ast.Attribute):
                owner = self._attr_owner(tgt)
                if owner:
                    self.fi.attr_accesses.append(AttrAccess(
                        attr=tgt.attr, line=node.lineno, held=held,
                        is_write=True, via_mutator=True, owner=owner))
        reg = self.registry_call(node)
        if reg:
            self.fi.registry_calls.append(RegistryCall(
                method=reg[0], name=reg[1], line=node.lineno))
        tel = self.telemetry_call(node)
        if tel:
            computed = any(
                not isinstance(a, (ast.Constant, ast.Name, ast.Attribute))
                for a in list(node.args) + [k.value for k in node.keywords]
            )
            self.fi.telemetry_calls.append(TelemetryCall(
                api=tel[0], method=tel[1], kind=tel[2], line=node.lineno,
                computed_args=computed, enabled_guarded=enabled_guard))
        # zero-arg blocking primitives: join()/wait()/get() with no
        # timeout and no operands (pass 5, unbounded-blocking)
        if isinstance(fn, ast.Attribute) and \
                fn.attr in ("join", "wait", "get") and \
                not node.args and not node.keywords:
            self.fi.blocking_calls.append(
                BlockingCall(method=fn.attr, line=node.lineno))
        # json.dumps / json.dump
        if isinstance(fn, ast.Attribute) and fn.attr in ("dumps", "dump") \
                and isinstance(fn.value, ast.Name) and fn.value.id == "json":
            allow_nan_false = any(
                k.arg == "allow_nan" and
                isinstance(k.value, ast.Constant) and k.value.value is False
                for k in node.keywords
            )
            sanitized = bool(
                node.args and isinstance(node.args[0], ast.Call) and
                isinstance(node.args[0].func, ast.Name) and
                node.args[0].func.id in ("json_safe", "_json_safe")
            )
            self.fi.json_calls.append(JsonDumpCall(
                line=node.lineno, has_allow_nan_false=allow_nan_false,
                sanitized=sanitized))
        # dict literals with a non-final "headline" key
        for a in list(node.args) + [k.value for k in node.keywords]:
            if isinstance(a, ast.Dict):
                self._check_headline_dict(a)
        targets = self.resolve_call(node)
        self.fi.call_sites.append(CallSite(
            targets=targets, line=node.lineno, held=held))

    def _check_headline_dict(self, node: ast.Dict) -> None:
        keys = [k.value for k in node.keys
                if isinstance(k, ast.Constant) and isinstance(k.value, str)]
        if "headline" in keys and keys and keys[-1] != "headline":
            self.fi.dict_literal_headline.append(node.lineno)


def _walk_module_functions(pkg: Package, mod: Module) -> None:
    for node in mod.tree.body:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            fi = FuncInfo(
                qualname=f"{mod.modname}.{node.name}", module=mod.modname,
                cls=None, path=mod.path, node=node, lineno=node.lineno,
            )
            rec = mod.suppress.get(node.lineno) or mod.suppress.get(
                node.lineno - 1)
            if rec and rec[0] == "cold":
                fi.cold = rec[1] or "annotated cold"
            pkg.functions[fi.qualname] = fi
            _FuncWalker(pkg, mod, fi).walk()
        elif isinstance(node, ast.ClassDef):
            _walk_class_functions(pkg, mod, node)
        elif isinstance(node, (ast.Assign, ast.Expr, ast.If, ast.Try)):
            # module-level code: walk as an anonymous entry (rare)
            pass


def _walk_class_functions(pkg: Package, mod: Module,
                          node: ast.ClassDef, prefix: str = "") -> None:
    qual = f"{mod.modname}.{prefix}{node.name}"
    for item in node.body:
        if isinstance(item, (ast.FunctionDef, ast.AsyncFunctionDef)):
            fi = pkg.functions.get(f"{qual}.{item.name}")
            if fi is None:
                fi = FuncInfo(
                    qualname=f"{qual}.{item.name}", module=mod.modname,
                    cls=qual, path=mod.path, node=item, lineno=item.lineno,
                )
                pkg.functions[fi.qualname] = fi
            _FuncWalker(pkg, mod, fi).walk()
        elif isinstance(item, ast.ClassDef):
            _walk_class_functions(pkg, mod, item, prefix=f"{prefix}{node.name}.")
