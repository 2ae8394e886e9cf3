"""The six dgolint rules.

Each rule is deliberately conservative: it encodes one invariant the
repo already states in prose (ROADMAP compat policy, PR-3 cache
centralization, PR-7 determinism contract, serving lock discipline,
kernels package layout) and flags only syntactic patterns that violate
it.  False-negative-tolerant, false-positive-averse: a finding should
always be actionable.
"""
from __future__ import annotations

import ast
from pathlib import Path
from typing import Iterable, Sequence

from tools.dgolint import Finding, Rule, SourceFile


# ---------------------------------------------------------------------------
# shared AST helpers
# ---------------------------------------------------------------------------

def dotted_name(node: ast.AST) -> str | None:
    """Render a Name/Attribute chain as ``a.b.c`` (None if not a pure
    chain)."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def call_leaf(call: ast.Call) -> str | None:
    """Last component of the callee (``jax.lax.while_loop`` -> ``while_loop``)."""
    f = call.func
    if isinstance(f, ast.Attribute):
        return f.attr
    if isinstance(f, ast.Name):
        return f.id
    return None


def names_in(node: ast.AST) -> set[str]:
    return {n.id for n in ast.walk(node) if isinstance(n, ast.Name)}


def _path_parts(src: SourceFile) -> tuple[str, ...]:
    return Path(src.path).parts


# ---------------------------------------------------------------------------
# DGL001 — compat bypass
# ---------------------------------------------------------------------------

_COMPAT_NAMES = {"shard_map", "AxisType", "AbstractMesh", "axis_size"}


class CompatBypassRule(Rule):
    """Version-moved JAX APIs must be imported via ``repro.compat``.

    ``shard_map``, ``AxisType``, ``AbstractMesh`` and ``axis_size`` have
    all moved between JAX releases; routing every use through the one
    module keeps the next move a one-file change.  Flags (a) any
    ``from jax... import <name>`` / ``import jax.experimental.shard_map``
    and (b) attribute chains rooted at ``jax`` ending in one of the
    names, everywhere except ``src/repro/compat.py``.
    """

    code = "DGL001"
    name = "compat-bypass"
    rationale = ("version-moved JAX APIs are only touched through "
                 "src/repro/compat.py (ROADMAP compat policy)")

    def _exempt(self, src: SourceFile) -> bool:
        return src.path.endswith("repro/compat.py")

    def check_file(self, src: SourceFile) -> Iterable[Finding]:
        if self._exempt(src):
            return
        for node in ast.walk(src.tree):
            if isinstance(node, ast.ImportFrom):
                mod = node.module or ""
                if mod == "jax" or mod.startswith("jax."):
                    for alias in node.names:
                        if alias.name in _COMPAT_NAMES:
                            yield Finding(
                                self.code, src.path, node.lineno,
                                node.col_offset,
                                f"import of '{alias.name}' from '{mod}' "
                                f"bypasses repro.compat — import it from "
                                f"'repro.compat' instead")
            elif isinstance(node, ast.Import):
                for alias in node.names:
                    if (alias.name.startswith("jax.")
                            and alias.name.split(".")[-1] in _COMPAT_NAMES):
                        yield Finding(
                            self.code, src.path, node.lineno,
                            node.col_offset,
                            f"import of '{alias.name}' bypasses "
                            f"repro.compat")
            elif isinstance(node, ast.Attribute):
                if node.attr in _COMPAT_NAMES:
                    full = dotted_name(node)
                    if full and (full.startswith("jax.")
                                 or full == f"jax.{node.attr}"):
                        yield Finding(
                            self.code, src.path, node.lineno,
                            node.col_offset,
                            f"attribute use '{full}' bypasses repro.compat "
                            f"— use the 'repro.compat' shim")


# ---------------------------------------------------------------------------
# DGL002 — rogue memoization
# ---------------------------------------------------------------------------

_MEMO_DECOS = {"lru_cache", "cache"}
_BUILDER_PREFIXES = ("make_", "build_")


def _is_compiled_builder_call(expr: ast.AST) -> bool:
    """Does ``expr`` contain a call that plausibly produces a compiled
    callable (``jax.jit``/``jit``/``shard_map``/``make_*``/``build_*``/
    ``compile*``)?"""
    for node in ast.walk(expr):
        if not isinstance(node, ast.Call):
            continue
        leaf = call_leaf(node)
        if leaf is None:
            continue
        if leaf in {"jit", "shard_map", "pjit", "pmap"}:
            return True
        if leaf.startswith(_BUILDER_PREFIXES) or leaf.startswith("compile"):
            return True
    return False


class RogueMemoRule(Rule):
    """All memoization of compiled callables goes through
    ``core/cache.py`` (`CompileCache` registries) so hits/misses/
    evictions show up in bench and serving stats.  Flags (a) any
    reference to ``functools.lru_cache``/``functools.cache`` and (b)
    module-level dicts used as memo tables for compiled callables
    (subscript-store whose value contains a ``jit``/``shard_map``/
    ``make_*``/``build_*``/``compile*`` call), outside ``core/cache.py``.
    """

    code = "DGL002"
    name = "rogue-memoization"
    rationale = ("memoization outside core/cache.py hides hit/eviction "
                 "stats from BENCH_distributed and serving metrics")

    def _exempt(self, src: SourceFile) -> bool:
        return src.path.endswith("core/cache.py")

    def check_file(self, src: SourceFile) -> Iterable[Finding]:
        if self._exempt(src):
            return
        # (a) functools memo decorators, by reference
        functools_memo_aliases: set[str] = set()
        for node in ast.walk(src.tree):
            if isinstance(node, ast.ImportFrom) and node.module == "functools":
                for alias in node.names:
                    if alias.name in _MEMO_DECOS:
                        functools_memo_aliases.add(alias.asname or alias.name)
                        yield Finding(
                            self.code, src.path, node.lineno,
                            node.col_offset,
                            f"import of 'functools.{alias.name}' — use a "
                            f"named core/cache.CompileCache registry "
                            f"(get_cache) so stats are observable")
            elif isinstance(node, ast.Attribute):
                full = dotted_name(node)
                if full in {"functools.lru_cache", "functools.cache"}:
                    yield Finding(
                        self.code, src.path, node.lineno, node.col_offset,
                        f"use of '{full}' — use a named "
                        f"core/cache.CompileCache registry (get_cache) "
                        f"so stats are observable")
        # (b) module-level dict memos of compiled callables
        module_dicts: set[str] = set()
        body = getattr(src.tree, "body", [])
        for stmt in body:
            targets: list[ast.expr] = []
            if isinstance(stmt, ast.Assign):
                targets, value = stmt.targets, stmt.value
            elif isinstance(stmt, ast.AnnAssign) and stmt.value is not None:
                targets, value = [stmt.target], stmt.value
            else:
                continue
            is_dict = isinstance(value, ast.Dict) or (
                isinstance(value, ast.Call)
                and call_leaf(value) in {"dict", "OrderedDict"})
            if not is_dict:
                continue
            for t in targets:
                if isinstance(t, ast.Name):
                    module_dicts.add(t.id)
        if module_dicts:
            for node in ast.walk(src.tree):
                if not isinstance(node, ast.Assign):
                    continue
                for t in node.targets:
                    if (isinstance(t, ast.Subscript)
                            and isinstance(t.value, ast.Name)
                            and t.value.id in module_dicts
                            and _is_compiled_builder_call(node.value)):
                        yield Finding(
                            self.code, src.path, node.lineno,
                            node.col_offset,
                            f"module-level dict '{t.value.id}' memoizes a "
                            f"compiled callable — use "
                            f"core/cache.get_cache(...) instead")


# ---------------------------------------------------------------------------
# DGL003 — trace leak (host sync inside compiled bodies)
# ---------------------------------------------------------------------------

_TRACED_ENTRY_CALLS = {"while_loop", "fori_loop", "cond", "scan", "jit",
                       "shard_map", "vmap", "pmap", "switch"}
_HOST_SYNC_BUILTINS = {"float", "int", "bool"}
_HOST_SYNC_DOTTED = {"np.asarray", "numpy.asarray", "np.array",
                     "numpy.array", "jax.device_get"}


def _static_argnames(call_or_deco: ast.AST) -> set[str]:
    """Extract ``static_argnames`` string constants from a ``jit`` call
    or ``partial(jax.jit, static_argnames=...)`` decorator."""
    out: set[str] = set()
    if not isinstance(call_or_deco, ast.Call):
        return out
    for kw in call_or_deco.keywords:
        if kw.arg in {"static_argnames", "static_argnums"}:
            for node in ast.walk(kw.value):
                if isinstance(node, ast.Constant) and isinstance(node.value,
                                                                 str):
                    out.add(node.value)
    return out


class TraceLeakRule(Rule):
    """No host synchronization inside compiled loop bodies.

    Host-sync calls (``float()``/``int()``/``bool()``/``.item()``/
    ``np.asarray``) on traced values either crash under ``jit``
    (ConcretizationTypeError) or — worse — silently force a
    device->host round-trip per iteration, turning the paper's
    one-dispatch engine back into dispatch-per-iteration.  Roots are
    functions passed by name to ``lax.while_loop``/``fori_loop``/
    ``cond``/``scan``/``jit``/``shard_map`` or decorated with ``jit``;
    the rule walks direct same-file call edges from the roots and
    flags host-sync calls whose arguments are tainted by function
    parameters (``static_argnames`` params are exempt).
    """

    code = "DGL003"
    name = "trace-leak"
    rationale = ("host sync in compiled bodies breaks one-dispatch "
                 "execution (or crashes under jit)")

    def check_file(self, src: SourceFile) -> Iterable[Finding]:
        funcs: dict[str, ast.FunctionDef] = {}
        for node in ast.walk(src.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                funcs.setdefault(node.name, node)
        if not funcs:
            return

        roots: dict[str, set[str]] = {}  # func name -> static param names

        def add_root(name: str, statics: set[str]) -> None:
            if name in funcs:
                cur = roots.setdefault(name, set())
                cur |= statics

        # functions passed by name into traced-entry calls
        for node in ast.walk(src.tree):
            if isinstance(node, ast.Call):
                leaf = call_leaf(node)
                if leaf in _TRACED_ENTRY_CALLS:
                    statics = _static_argnames(node)
                    for arg in list(node.args) + [kw.value
                                                  for kw in node.keywords]:
                        if isinstance(arg, ast.Name):
                            add_root(arg.id, statics)
        # jit-decorated functions (plain or partial(jax.jit, ...))
        for fn in funcs.values():
            for deco in fn.decorator_list:
                statics: set[str] = set()
                hit = False
                if isinstance(deco, ast.Call):
                    dleaf = call_leaf(deco)
                    if dleaf in {"jit", "pjit"}:
                        hit, statics = True, _static_argnames(deco)
                    elif dleaf == "partial" and deco.args:
                        inner = deco.args[0]
                        iname = (dotted_name(inner) or "").split(".")[-1]
                        if iname in {"jit", "pjit"}:
                            hit, statics = True, _static_argnames(deco)
                else:
                    dname = (dotted_name(deco) or "").split(".")[-1]
                    if dname in {"jit", "pjit"}:
                        hit = True
                if hit:
                    add_root(fn.name, statics)

        # reachability over direct same-file Name-call edges
        reachable: dict[str, set[str]] = {}  # name -> statics (roots only)
        work = list(roots.items())
        while work:
            name, statics = work.pop()
            if name in reachable:
                continue
            reachable[name] = statics
            fn = funcs[name]
            for node in ast.walk(fn):
                if isinstance(node, ast.Call):
                    leaf = call_leaf(node)
                    if (leaf in funcs and leaf not in reachable
                            and isinstance(node.func, ast.Name)):
                        work.append((leaf, set()))

        for name, statics in reachable.items():
            fn = funcs[name]
            yield from self._check_function(src, fn, statics)

    def _check_function(self, src: SourceFile, fn: ast.FunctionDef,
                        statics: set[str]) -> Iterable[Finding]:
        params = {a.arg for a in (fn.args.posonlyargs + fn.args.args
                                  + fn.args.kwonlyargs)}
        if fn.args.vararg:
            params.add(fn.args.vararg.arg)
        tainted = {p for p in params if p not in statics and p != "self"}
        if not tainted:
            return

        findings: list[Finding] = []

        def expr_tainted(node: ast.AST) -> bool:
            return bool(names_in(node) & tainted)

        def visit_stmts(stmts: Sequence[ast.stmt]) -> None:
            for stmt in stmts:
                # flag host syncs anywhere in the statement first
                for node in ast.walk(stmt):
                    if isinstance(node, (ast.FunctionDef,
                                         ast.AsyncFunctionDef)):
                        continue
                    if not isinstance(node, ast.Call):
                        continue
                    leaf = call_leaf(node)
                    full = dotted_name(node.func)
                    if (isinstance(node.func, ast.Name)
                            and leaf in _HOST_SYNC_BUILTINS
                            and any(expr_tainted(a) for a in node.args)):
                        findings.append(Finding(
                            self.code, src.path, node.lineno,
                            node.col_offset,
                            f"'{leaf}()' on traced value in '{fn.name}' "
                            f"(reachable from a compiled body) forces a "
                            f"host sync"))
                    elif (isinstance(node.func, ast.Attribute)
                          and node.func.attr == "item"
                          and expr_tainted(node.func.value)):
                        findings.append(Finding(
                            self.code, src.path, node.lineno,
                            node.col_offset,
                            f"'.item()' on traced value in '{fn.name}' "
                            f"(reachable from a compiled body) forces a "
                            f"host sync"))
                    elif (full in _HOST_SYNC_DOTTED
                          and any(expr_tainted(a) for a in node.args)):
                        findings.append(Finding(
                            self.code, src.path, node.lineno,
                            node.col_offset,
                            f"'{full}()' on traced value in '{fn.name}' "
                            f"(reachable from a compiled body) forces a "
                            f"host sync"))
                # then propagate taint through simple assignments
                if isinstance(stmt, (ast.Assign, ast.AugAssign,
                                     ast.AnnAssign)):
                    value = stmt.value
                    if value is not None and expr_tainted(value):
                        targets = (stmt.targets
                                   if isinstance(stmt, ast.Assign)
                                   else [stmt.target])
                        for t in targets:
                            for node in ast.walk(t):
                                if isinstance(node, ast.Name):
                                    tainted.add(node.id)
                elif isinstance(stmt, ast.For):
                    if expr_tainted(stmt.iter):
                        for node in ast.walk(stmt.target):
                            if isinstance(node, ast.Name):
                                tainted.add(node.id)

        visit_stmts(fn.body)
        yield from findings


# ---------------------------------------------------------------------------
# DGL004 — nondeterminism in the chaos/serving substrate
# ---------------------------------------------------------------------------

_DGL004_DIRS = {"serving", "runtime", "core"}


class NondeterminismRule(Rule):
    """The PR-7 contract: every fault/serving decision is a pure
    function of ``(seed, kind, index)`` so chaos runs replay exactly.
    Flags wall-clock (``time.time``) and unseeded randomness
    (stdlib ``random.*``, legacy ``np.random.<dist>``, zero-arg
    ``default_rng()``/``RandomState()``) in ``serving/``, ``runtime/``
    and ``core/`` code.  ``time.monotonic``/``perf_counter`` (interval
    measurement) and seeded ``default_rng(seed)`` are allowed.
    """

    code = "DGL004"
    name = "nondeterminism"
    rationale = ("fault/serving decisions must be pure functions of "
                 "(seed, kind, index) — the PR-7 replay contract")

    def _in_scope(self, src: SourceFile) -> bool:
        return bool(_DGL004_DIRS & set(_path_parts(src)[:-1]))

    def check_file(self, src: SourceFile) -> Iterable[Finding]:
        if not self._in_scope(src):
            return
        has_stdlib_random = any(
            isinstance(node, ast.Import)
            and any(a.name == "random" for a in node.names)
            for node in ast.walk(src.tree))
        for node in ast.walk(src.tree):
            if not isinstance(node, ast.Call):
                continue
            full = dotted_name(node.func) or ""
            if full == "time.time":
                yield Finding(
                    self.code, src.path, node.lineno, node.col_offset,
                    "wall-clock 'time.time()' in deterministic scope — "
                    "use a seeded schedule or time.monotonic for "
                    "intervals")
            elif full.startswith("random.") and has_stdlib_random:
                yield Finding(
                    self.code, src.path, node.lineno, node.col_offset,
                    f"stdlib '{full}()' is unseeded global RNG — use "
                    f"np.random.default_rng(seed)")
            elif (full.split(".")[-1] in {"default_rng", "RandomState"}
                  and ("random" in full or isinstance(node.func, ast.Name))
                  and not node.args and not node.keywords):
                yield Finding(
                    self.code, src.path, node.lineno, node.col_offset,
                    f"'{full or call_leaf(node)}()' without a seed breaks "
                    f"the (seed, kind, index) replay contract")
            elif (full.startswith(("np.random.", "numpy.random."))
                  and full.split(".")[-1] not in {"default_rng",
                                                  "RandomState", "Generator",
                                                  "SeedSequence"}):
                yield Finding(
                    self.code, src.path, node.lineno, node.col_offset,
                    f"legacy global-state '{full}()' — use a seeded "
                    f"np.random.default_rng(seed) generator")


# ---------------------------------------------------------------------------
# DGL005 — lock discipline on the serving thread boundary
# ---------------------------------------------------------------------------

_LOCK_CTORS = {"Lock", "RLock", "Condition", "Semaphore", "BoundedSemaphore"}


class LockDisciplineRule(Rule):
    """In ``serving/*.py``: an attribute ever *written* inside a
    ``with self.<lock>:`` block is lock-guarded; reading or writing it
    outside such a block (in any method) is a race.  Escape hatches:
    ``__init__``/``__post_init__`` (construction happens-before
    publication), methods named ``*_locked`` (caller-holds-lock
    convention), and inline ``# dgolint: disable=DGL005`` for
    intentionally racy snapshot reads.
    """

    code = "DGL005"
    name = "lock-discipline"
    rationale = ("attrs written under a lock must not be touched "
                 "without it — lightweight race detector for serving/")

    _EXEMPT_METHODS = {"__init__", "__post_init__"}

    def _in_scope(self, src: SourceFile) -> bool:
        return "serving" in _path_parts(src)[:-1]

    def check_file(self, src: SourceFile) -> Iterable[Finding]:
        if not self._in_scope(src):
            return
        for node in src.tree.body:
            if isinstance(node, ast.ClassDef):
                yield from self._check_class(src, node)

    def _check_class(self, src: SourceFile,
                     cls: ast.ClassDef) -> Iterable[Finding]:
        methods = [n for n in cls.body
                   if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))]
        # lock attributes: self.X = threading.Lock()/RLock()/Condition(...)
        locks: set[str] = set()
        for m in methods:
            for node in ast.walk(m):
                if not isinstance(node, ast.Assign):
                    continue
                if not (isinstance(node.value, ast.Call)
                        and call_leaf(node.value) in _LOCK_CTORS):
                    continue
                for t in node.targets:
                    if (isinstance(t, ast.Attribute)
                            and isinstance(t.value, ast.Name)
                            and t.value.id == "self"):
                        locks.add(t.attr)
        if not locks:
            return

        def lock_items(with_node: ast.With) -> bool:
            for item in with_node.items:
                expr = item.context_expr
                if (isinstance(expr, ast.Attribute)
                        and isinstance(expr.value, ast.Name)
                        and expr.value.id == "self"
                        and expr.attr in locks):
                    return True
            return False

        # pass 1: attrs written under a lock anywhere in the class
        guarded: set[str] = set()

        def scan_writes(stmts: Sequence[ast.stmt], depth: int) -> None:
            for stmt in stmts:
                d = depth
                if isinstance(stmt, ast.With) and lock_items(stmt):
                    d += 1
                if d > 0:
                    for node in ast.walk(stmt):
                        target_lists = []
                        if isinstance(node, ast.Assign):
                            target_lists = node.targets
                        elif isinstance(node, (ast.AugAssign, ast.AnnAssign)):
                            target_lists = [node.target]
                        for t in target_lists:
                            for sub in ast.walk(t):
                                if (isinstance(sub, ast.Attribute)
                                        and isinstance(sub.value, ast.Name)
                                        and sub.value.id == "self"
                                        and sub.attr not in locks):
                                    guarded.add(sub.attr)
                else:
                    # recurse into compound statements to find nested withs
                    for field in ("body", "orelse", "finalbody", "handlers"):
                        sub = getattr(stmt, field, None)
                        if isinstance(sub, list):
                            stmts2 = []
                            for s in sub:
                                if isinstance(s, ast.ExceptHandler):
                                    stmts2.extend(s.body)
                                elif isinstance(s, ast.stmt):
                                    stmts2.append(s)
                            scan_writes(stmts2, d)

        for m in methods:
            if m.name in self._EXEMPT_METHODS:
                continue
            scan_writes(m.body, 0)
        if not guarded:
            return

        # pass 2: touches of guarded attrs outside any lock block
        for m in methods:
            if m.name in self._EXEMPT_METHODS or m.name.endswith("_locked"):
                continue
            yield from self._scan_unlocked(src, cls, m, m.body, guarded,
                                           lock_items, 0)

    def _scan_unlocked(self, src, cls, method, stmts, guarded,
                       lock_items, depth) -> Iterable[Finding]:
        for stmt in stmts:
            d = depth
            if isinstance(stmt, ast.With) and lock_items(stmt):
                d += 1
            if d == 0 and not isinstance(stmt, (ast.With, ast.If, ast.For,
                                                ast.While, ast.Try)):
                for node in ast.walk(stmt):
                    if (isinstance(node, ast.Attribute)
                            and isinstance(node.value, ast.Name)
                            and node.value.id == "self"
                            and node.attr in guarded):
                        yield Finding(
                            self.code, src.path, node.lineno,
                            node.col_offset,
                            f"'{cls.name}.{method.name}' touches "
                            f"'self.{node.attr}' outside a lock, but it is "
                            f"written under one elsewhere — hold the lock "
                            f"or rename the method '*_locked'")
            else:
                # compound statement: check its own header expr, then recurse
                if d == 0:
                    header_exprs: list[ast.AST] = []
                    if isinstance(stmt, (ast.If, ast.While)):
                        header_exprs.append(stmt.test)
                    elif isinstance(stmt, ast.For):
                        header_exprs.extend([stmt.target, stmt.iter])
                    elif isinstance(stmt, ast.With):
                        header_exprs.extend(
                            i.context_expr for i in stmt.items)
                    for expr in header_exprs:
                        for node in ast.walk(expr):
                            if (isinstance(node, ast.Attribute)
                                    and isinstance(node.value, ast.Name)
                                    and node.value.id == "self"
                                    and node.attr in guarded):
                                yield Finding(
                                    self.code, src.path, node.lineno,
                                    node.col_offset,
                                    f"'{cls.name}.{method.name}' touches "
                                    f"'self.{node.attr}' outside a lock, "
                                    f"but it is written under one "
                                    f"elsewhere — hold the lock or rename "
                                    f"the method '*_locked'")
                for field in ("body", "orelse", "finalbody"):
                    sub = getattr(stmt, field, None)
                    if isinstance(sub, list) and sub:
                        yield from self._scan_unlocked(
                            src, cls, method, sub, guarded, lock_items, d)
                handlers = getattr(stmt, "handlers", None)
                if handlers:
                    for h in handlers:
                        yield from self._scan_unlocked(
                            src, cls, method, h.body, guarded, lock_items, d)


# ---------------------------------------------------------------------------
# DGL006 — kernels triple + guarded pallas_call backend selection
# ---------------------------------------------------------------------------

_TRIPLE = ("kernel.py", "ref.py", "ops.py")


class KernelTripleRule(Rule):
    """Every ``kernels/<name>/`` package ships the full triple —
    ``kernel.py`` (Pallas), ``ref.py`` (pure-JAX reference), ``ops.py``
    (public entry + fallback dispatch) — and every ``pl.pallas_call``
    site threads a computed ``interpret=`` (the ``resolve_interpret``
    autodetect), never a hard-coded literal and never omitted.
    """

    code = "DGL006"
    name = "kernel-triple"
    rationale = ("kernel/ref/ops triple + autodetected interpret= is "
                 "what keeps kernels testable off-TPU")

    def check_file(self, src: SourceFile) -> Iterable[Finding]:
        for node in ast.walk(src.tree):
            if not isinstance(node, ast.Call):
                continue
            if call_leaf(node) != "pallas_call":
                continue
            interp = next((kw for kw in node.keywords
                           if kw.arg == "interpret"), None)
            if interp is None:
                yield Finding(
                    self.code, src.path, node.lineno, node.col_offset,
                    "pallas_call without 'interpret=' — thread the "
                    "resolve_interpret() autodetect through the call")
            elif isinstance(interp.value, ast.Constant):
                yield Finding(
                    self.code, src.path, node.lineno, node.col_offset,
                    f"pallas_call with hard-coded "
                    f"interpret={interp.value.value!r} — backend "
                    f"selection must go through the resolve_interpret() "
                    f"autodetect")

    def check_project(self, files: Sequence[SourceFile],
                      roots: Sequence[Path]) -> Iterable[Finding]:
        kernel_dirs: dict[Path, SourceFile] = {}
        for src in files:
            parent = src.abspath.parent
            if parent.parent.name == "kernels" and parent.name != "kernels":
                kernel_dirs.setdefault(parent, src)
        for d, anchor in sorted(kernel_dirs.items()):
            missing = [f for f in _TRIPLE if not (d / f).exists()]
            if missing:
                yield Finding(
                    self.code, anchor.path, 1, 0,
                    f"kernels package '{d.name}' is missing "
                    f"{', '.join(missing)} — every kernel ships the "
                    f"kernel.py/ref.py/ops.py triple")


# ---------------------------------------------------------------------------
# DGL007 — multi-process JAX APIs go through repro.compat
# ---------------------------------------------------------------------------

_MULTIPROC_LEAVES = {"process_index", "process_count"}


class MultiProcessBypassRule(Rule):
    """Multi-process runtime APIs must be reached via ``repro.compat``.

    ``jax.distributed.initialize`` grew/renamed kwargs across the 0.4/0.5
    matrix and needs the gloo cpu-collectives config set BEFORE it runs;
    ``jax.process_index``/``jax.process_count`` exist everywhere but the
    repo routes them through ``repro.compat`` so single-process callers
    never import distributed machinery.  Flags (a) any import of
    ``jax.distributed`` (module or from-import), and (b) attribute
    chains rooted at ``jax`` reaching ``distributed`` or the process
    topology calls — everywhere except ``src/repro/compat.py``.
    """

    code = "DGL007"
    name = "multiprocess-bypass"
    rationale = ("jax.distributed / process-topology APIs are only "
                 "touched through src/repro/compat.py (same policy as "
                 "DGL001; the gloo collectives config must precede "
                 "initialize)")

    def _exempt(self, src: SourceFile) -> bool:
        return src.path.endswith("repro/compat.py")

    def check_file(self, src: SourceFile) -> Iterable[Finding]:
        if self._exempt(src):
            return
        for node in ast.walk(src.tree):
            if isinstance(node, ast.ImportFrom):
                mod = node.module or ""
                if mod == "jax.distributed" or mod.startswith(
                        "jax.distributed."):
                    yield Finding(
                        self.code, src.path, node.lineno, node.col_offset,
                        f"import from '{mod}' bypasses repro.compat — use "
                        f"repro.compat.distributed_initialize / "
                        f"process_index / process_count")
                elif mod == "jax":
                    for alias in node.names:
                        if (alias.name == "distributed"
                                or alias.name in _MULTIPROC_LEAVES):
                            yield Finding(
                                self.code, src.path, node.lineno,
                                node.col_offset,
                                f"import of '{alias.name}' from 'jax' "
                                f"bypasses repro.compat — use the "
                                f"'repro.compat' multi-process shims")
            elif isinstance(node, ast.Import):
                for alias in node.names:
                    if (alias.name == "jax.distributed"
                            or alias.name.startswith("jax.distributed.")):
                        yield Finding(
                            self.code, src.path, node.lineno,
                            node.col_offset,
                            f"import of '{alias.name}' bypasses "
                            f"repro.compat")
            elif isinstance(node, ast.Attribute):
                full = dotted_name(node)
                # match the chain exactly once: at the 'jax.distributed'
                # root, or at a process-topology leaf hanging off jax
                if full == "jax.distributed" or (
                        full and full.startswith("jax.")
                        and node.attr in _MULTIPROC_LEAVES):
                    yield Finding(
                        self.code, src.path, node.lineno, node.col_offset,
                        f"attribute use '{full}' bypasses repro.compat — "
                        f"use the 'repro.compat' multi-process shims")


def ALL_RULES() -> list[Rule]:
    return [
        CompatBypassRule(),
        RogueMemoRule(),
        TraceLeakRule(),
        NondeterminismRule(),
        LockDisciplineRule(),
        KernelTripleRule(),
        MultiProcessBypassRule(),
    ]
