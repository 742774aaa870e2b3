"""Shared infrastructure: source parsing, annotation extraction, the
class/function index, receiver-type resolution, and the lock-state walk
used by the lock-discipline and no-blocking-under-lock checkers."""

from __future__ import annotations

import ast
import contextlib
import hashlib
import io
import re
import tokenize
from dataclasses import dataclass, field
from pathlib import Path

# The documented pipeline roles (docs/ARCHITECTURE.md thread contracts).
ROLES = frozenset({
    "executor",        # the user's compute/drive thread
    "h2d-worker",      # SerialWorker "offload-h2d" staging thread
    "writer",          # SerialWorker "offload-gradwrite" thread
    "optim-worker",    # SerialWorker "offload-optim" thread
    "optim-prefetch",  # SerialWorker "offload-optim-prefetch" thread
    "store-worker",    # store aio / direct-nvme pool threads
    "adam-tile",       # OffloadedAdam's "offload-adam-tile" update workers
    "any",             # thread-safe: callable from every role
})

CHECKERS = ("lock-discipline", "lock-blocking", "thread-affinity",
            "resource-lifecycle", "annotation")

_GUARDED_RE = re.compile(r"#\s*guarded-by:\s*([A-Za-z_]\w*)")
_THREAD_RE = re.compile(r"#\s*thread:\s*([A-Za-z][\w, -]*)")
_HOLDS_RE = re.compile(r"#\s*analyze:\s*holds\(([A-Za-z_]\w*)\)")
_BLOCKING_RE = re.compile(r"#\s*analyze:\s*blocking\b")
_PRESHARE_RE = re.compile(r"#\s*analyze:\s*pre-share\b")
_IGNORE_RE = re.compile(r"#\s*analyze:\s*ignore(?:\[([\w\-, ]+)\])?")

_LOCK_FACTORIES = {"Lock", "RLock", "Condition"}


@dataclass(frozen=True)
class Finding:
    path: str          # repo-relative, forward slashes
    line: int
    checker: str
    symbol: str        # "Class.method" / "function" / "<module>"
    message: str

    def format(self) -> str:
        return (f"{self.path}:{self.line}: [{self.checker}] "
                f"{self.symbol}: {self.message}")

    @property
    def fingerprint(self) -> str:
        """Line-number-free identity used by the committed baseline, so a
        baselined finding survives unrelated edits above it."""
        digest = hashlib.sha1(self.message.encode()).hexdigest()[:12]
        return f"{self.path}::{self.checker}::{self.symbol}::{digest}"


@dataclass
class FunctionInfo:
    node: ast.AST                       # FunctionDef | AsyncFunctionDef
    module: SourceModule
    qualname: str
    cls: ClassInfo | None = None
    roles: frozenset[str] | None = None
    holds: set[str] = field(default_factory=set)
    blocking: bool = False
    pre_share: bool = False

    @property
    def name(self) -> str:
        return self.node.name


@dataclass
class ClassInfo:
    node: ast.ClassDef
    module: SourceModule
    name: str
    bases: list[str] = field(default_factory=list)
    lock_attrs: set[str] = field(default_factory=set)
    guarded: dict[str, str] = field(default_factory=dict)  # field -> lock
    attr_types: dict[str, str] = field(default_factory=dict)
    methods: dict[str, FunctionInfo] = field(default_factory=dict)


def _comment_map(source: str) -> dict[int, str]:
    out: dict[int, str] = {}
    with contextlib.suppress(tokenize.TokenError):
        for tok in tokenize.generate_tokens(io.StringIO(source).readline):
            if tok.type == tokenize.COMMENT:
                out[tok.start[0]] = tok.string
    return out


def _first_class_name(node: ast.AST | None) -> str | None:
    """First plain Name inside an annotation — resolves e.g.
    ``SpillableKVCache | None`` to ``SpillableKVCache``."""
    if node is None:
        return None
    for sub in ast.walk(node):
        if isinstance(sub, ast.Name):
            return sub.id
        if isinstance(sub, ast.Constant) and isinstance(sub.value, str):
            # string annotation: "ClassName | None"
            head = re.match(r"[A-Za-z_]\w*", sub.value)
            if head:
                return head.group(0)
    return None


def attr_chain(node: ast.AST) -> str | None:
    """Dotted source text of a Name/Attribute chain (``self.store``) or
    None if the expression is anything more complex."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


class SourceModule:
    """One parsed file: AST + comments + annotations + suppressions."""

    def __init__(self, path: Path, rel: str) -> None:
        self.path = path
        self.rel = rel
        self.source = path.read_text()
        self.tree = ast.parse(self.source, filename=str(path))
        self.comments = _comment_map(self.source)
        self.suppress: dict[int, set[str]] = {}
        for line, text in self.comments.items():
            m = _IGNORE_RE.search(text)
            if m:
                ids = m.group(1)
                self.suppress[line] = (
                    {s.strip() for s in ids.split(",") if s.strip()}
                    if ids else set(CHECKERS))
        self.classes: dict[str, ClassInfo] = {}
        self.functions: dict[str, FunctionInfo] = {}
        self.guarded_registry: dict[str, str] = {}  # "Cls.field" -> lock
        self.annotation_errors: list[Finding] = []
        self._index()

    # -- annotation extraction ------------------------------------------------

    def suppressed(self, line: int, checker: str) -> bool:
        return checker in self.suppress.get(line, ())

    def _def_comments(self, node: ast.AST) -> str:
        """Comments that can annotate a def: trailing on the def line plus
        any comment-only lines directly above it (or above its first
        decorator)."""
        first = min([node.lineno]
                    + [d.lineno for d in getattr(node, "decorator_list", [])])
        texts = [self.comments.get(node.lineno, "")]
        line = first - 1
        while line in self.comments:
            texts.append(self.comments[line])
            line -= 1
        return "\n".join(texts)

    def _lines_of(self, node: ast.AST) -> str:
        end = getattr(node, "end_lineno", node.lineno) or node.lineno
        return "\n".join(self.comments.get(i, "")
                         for i in range(node.lineno, end + 1))

    def _index(self) -> None:
        for stmt in self.tree.body:
            if isinstance(stmt, ast.ClassDef):
                self._index_class(stmt)
            elif isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self.functions[stmt.name] = self._function_info(stmt, None)
            elif isinstance(stmt, ast.Assign):
                self._maybe_registry(stmt)

    def _maybe_registry(self, stmt: ast.Assign) -> None:
        # module-level  GUARDED_BY = {"Cls.field": "_lock", ...}
        if not (len(stmt.targets) == 1
                and isinstance(stmt.targets[0], ast.Name)
                and stmt.targets[0].id == "GUARDED_BY"
                and isinstance(stmt.value, ast.Dict)):
            return
        for k, v in zip(stmt.value.keys, stmt.value.values, strict=True):
            if (isinstance(k, ast.Constant) and isinstance(k.value, str)
                    and isinstance(v, ast.Constant)
                    and isinstance(v.value, str)):
                self.guarded_registry[k.value] = v.value

    def _function_info(self, node: ast.AST,
                       cls: ClassInfo | None) -> FunctionInfo:
        text = self._def_comments(node)
        qual = f"{cls.name}.{node.name}" if cls else node.name
        info = FunctionInfo(node=node, module=self, qualname=qual, cls=cls)
        m = _THREAD_RE.search(text)
        if m:
            roles = {r.strip() for r in m.group(1).split(",") if r.strip()}
            bad = roles - ROLES
            if bad:
                self.annotation_errors.append(Finding(
                    self.rel, node.lineno, "annotation", qual,
                    f"unknown thread role(s) {sorted(bad)}; valid: "
                    f"{sorted(ROLES)}"))
            info.roles = frozenset(roles & ROLES) or None
        for m in _HOLDS_RE.finditer(text):
            info.holds.add(m.group(1))
        info.blocking = bool(_BLOCKING_RE.search(text))
        info.pre_share = bool(_PRESHARE_RE.search(text))
        return info

    def _index_class(self, node: ast.ClassDef) -> None:
        ci = ClassInfo(node=node, module=self, name=node.name,
                       bases=[b for b in (attr_chain(x) for x in node.bases)
                              if b])
        self.classes[node.name] = ci
        for stmt in node.body:
            if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
                ci.methods[stmt.name] = self._function_info(stmt, ci)
                self._scan_self_assigns(ci, stmt)
            elif isinstance(stmt, ast.AnnAssign) and isinstance(
                    stmt.target, ast.Name):
                # class-level annotated field (dataclass style)
                ann = attr_chain(stmt.annotation)
                if ann and ann.split(".")[-1] in _LOCK_FACTORIES:
                    ci.lock_attrs.add(stmt.target.id)
                t = _first_class_name(stmt.annotation)
                if t:
                    ci.attr_types.setdefault(stmt.target.id, t)
                self._maybe_guarded(ci, stmt, stmt.target.id)

    def _scan_self_assigns(self, ci: ClassInfo, fn: ast.AST) -> None:
        for stmt in ast.walk(fn):
            target: ast.expr | None = None
            value: ast.expr | None = None
            annotation: ast.expr | None = None
            if isinstance(stmt, ast.Assign) and len(stmt.targets) == 1:
                target, value = stmt.targets[0], stmt.value
            elif isinstance(stmt, ast.AnnAssign):
                target, value, annotation = (stmt.target, stmt.value,
                                             stmt.annotation)
            if not (isinstance(target, ast.Attribute)
                    and isinstance(target.value, ast.Name)
                    and target.value.id == "self"):
                continue
            attr = target.attr
            # lock discovery:  self._lock = threading.Lock()/Condition(..)
            chain = (attr_chain(value.func)
                     if isinstance(value, ast.Call) else None)
            if chain and chain.split(".")[-1] in _LOCK_FACTORIES:
                ci.lock_attrs.add(attr)
            # attr type:  self.pool = PinnedBufferPool(...)   or
            #             self.kv: SpillableKVCache | None = None
            if isinstance(value, ast.Call) and chain and "." not in chain:
                ci.attr_types.setdefault(attr, chain)
            t = _first_class_name(annotation)
            if t:
                ci.attr_types.setdefault(attr, t)
            self._maybe_guarded(ci, stmt, attr)

    def _maybe_guarded(self, ci: ClassInfo, stmt: ast.AST,
                       attr: str) -> None:
        m = _GUARDED_RE.search(self._lines_of(stmt))
        if m:
            ci.guarded[attr] = m.group(1)


class Project:
    """All modules under the analyzed roots, plus cross-module lookups."""

    def __init__(self, modules: list[SourceModule]) -> None:
        self.modules = modules
        self.class_index: dict[str, ClassInfo] = {}
        self.function_index: dict[str, FunctionInfo] = {}
        for mod in modules:
            for ci in mod.classes.values():
                self.class_index.setdefault(ci.name, ci)
            for fi in mod.functions.values():
                self.function_index.setdefault(fi.qualname, fi)
        self._apply_registries()

    @classmethod
    def load(cls, paths: list[Path], root: Path) -> Project:
        files: list[Path] = []
        for p in paths:
            if p.is_dir():
                files.extend(sorted(p.rglob("*.py")))
            else:
                files.append(p)
        modules = []
        for f in files:
            try:
                rel = f.resolve().relative_to(root.resolve()).as_posix()
            except ValueError:
                rel = f.as_posix()
            modules.append(SourceModule(f, rel))
        return cls(modules)

    def _apply_registries(self) -> None:
        for mod in self.modules:
            for key, lock in mod.guarded_registry.items():
                cls_name, _, attr = key.partition(".")
                ci = mod.classes.get(cls_name) or self.class_index.get(
                    cls_name)
                if ci is not None and attr:
                    ci.guarded[attr] = lock
                else:
                    mod.annotation_errors.append(Finding(
                        mod.rel, 1, "annotation", "<module>",
                        f"GUARDED_BY entry {key!r} names an unknown class"))

    # -- lookups --------------------------------------------------------------

    def resolve_class(self, name: str | None) -> ClassInfo | None:
        return self.class_index.get(name) if name else None

    def lookup_method(self, ci: ClassInfo | None,
                      name: str) -> FunctionInfo | None:
        seen = set()
        while ci is not None and ci.name not in seen:
            seen.add(ci.name)
            if name in ci.methods:
                return ci.methods[name]
            ci = next((self.class_index[b] for b in ci.bases
                       if b in self.class_index), None)
        return None

    def class_guarded(self, ci: ClassInfo) -> dict[str, str]:
        """Guarded fields including ones inherited from known bases."""
        out: dict[str, str] = {}
        chain, seen = [], set()
        cur: ClassInfo | None = ci
        while cur is not None and cur.name not in seen:
            seen.add(cur.name)
            chain.append(cur)
            cur = next((self.class_index[b] for b in cur.bases
                        if b in self.class_index), None)
        for c in reversed(chain):
            out.update(c.guarded)
        return out

    def class_locks(self, ci: ClassInfo) -> set[str]:
        out: set[str] = set()
        chain, seen = [ci], {ci.name}
        cur = ci
        while True:
            nxt = next((self.class_index[b] for b in cur.bases
                        if b in self.class_index
                        and b not in seen), None)
            if nxt is None:
                break
            seen.add(nxt.name)
            chain.append(nxt)
            cur = nxt
        for c in chain:
            out |= c.lock_attrs
        return out


# -- execution-order lock-state walk ------------------------------------------

class LockWalk:
    """Walks a function body in source order, tracking which of the given
    ``self.<lock>`` locks are held, and invoking ``visit(node, held)`` for
    every expression node.  Approximation: branches of if/try are walked
    sequentially with shared state — explicit ``self.X.release()`` /
    ``.acquire()`` calls toggle the held set, which is exactly the pattern
    ``SpillableKVCache._spill`` uses to drop the lock around a store
    write."""

    def __init__(self, locks: set[str], visit) -> None:
        self.locks = locks
        self.visit = visit
        self.held: set[str] = set()

    def _lock_of(self, node: ast.AST) -> str | None:
        chain = attr_chain(node)
        if chain and chain.startswith("self."):
            attr = chain.split(".", 1)[1]
            if attr in self.locks:
                return attr
        return None

    def run(self, fn: ast.AST, initially: set[str]) -> None:
        self.held = set(initially)
        self._stmts(fn.body)

    def _stmts(self, body: list[ast.stmt]) -> None:
        for stmt in body:
            self._stmt(stmt)

    def _stmt(self, stmt: ast.stmt) -> None:
        if isinstance(stmt, ast.With):
            entered: list[str] = []
            for item in stmt.items:
                self._expr(item.context_expr)
                lock = self._lock_of(item.context_expr)
                if lock is not None:
                    entered.append(lock)
            snapshot = set(self.held)
            self.held.update(entered)
            self._stmts(stmt.body)
            self.held = snapshot
        elif isinstance(stmt, ast.Try):
            self._stmts(stmt.body)
            for h in stmt.handlers:
                self._stmts(h.body)
            self._stmts(stmt.orelse)
            self._stmts(stmt.finalbody)
        elif isinstance(stmt, (ast.If, ast.For, ast.While)):
            for f in stmt._fields:
                v = getattr(stmt, f)
                if isinstance(v, list) and v and isinstance(v[0], ast.stmt):
                    self._stmts(v)
                elif isinstance(v, ast.expr):
                    self._expr(v)
        elif isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef,
                               ast.ClassDef)):
            pass        # nested defs run later, on an unknown thread
        else:
            self._expr(stmt)

    def _expr(self, node: ast.AST) -> None:
        if isinstance(node, ast.Call):
            chain = attr_chain(node.func)
            if chain and chain.startswith("self."):
                parts = chain.split(".")
                if len(parts) == 3 and parts[1] in self.locks:
                    if parts[2] == "release":
                        self.visit(node, self.held)
                        self.held.discard(parts[1])
                        return
                    if parts[2] == "acquire":
                        self.visit(node, self.held)
                        self.held.add(parts[1])
                        return
        for child in ast.iter_child_nodes(node):
            self._expr(child)
        self.visit(node, self.held)


def run_checkers(project: Project) -> list[Finding]:
    from . import affinity, lifecycle, lock_blocking, lock_discipline
    findings: list[Finding] = []
    for mod in project.modules:
        findings.extend(mod.annotation_errors)
    findings.extend(lock_discipline.check(project))
    findings.extend(lock_blocking.check(project))
    findings.extend(affinity.check(project))
    findings.extend(lifecycle.check(project))
    findings.sort(key=lambda f: (f.path, f.line, f.checker))
    return findings
