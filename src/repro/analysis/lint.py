"""repro-lint — codebase-specific static analysis (DESIGN.md §14).

Five rules encode hazard classes this codebase has actually been bitten
by (tracer leaks, silent recompiles, hidden host↔device syncs) plus the
structural conventions the kernel and simulator planes depend on:

  RL001  tracer-leak        Python ``if``/``while``/``bool()``/``int()``
                            /``float()``/``.item()`` on a jnp value
                            inside a function reachable from a
                            ``@jax.jit`` / ``_jitted`` / ``pallas_call``
                            entry.  Params listed in ``static_argnames``
                            / ``static_argnums`` are exempt, as are
                            ``x is None`` identity tests.
  RL002  recompile-hazard   A dynamically-sized array (size derived from
                            ``len()``/``.size``/``.shape``) crossing a
                            jit boundary (``jnp.asarray``/``jnp.array``/
                            ``jax.device_put`` or a known jit entry)
                            without passing through a pow2 bucketing
                            idiom (any ``*bucket*`` helper, e.g.
                            ``shape_bucket``/``_decode_bucket``); also a
                            dynamic scalar flowing into a jit entry's
                            ``static_argnames`` keyword.
  RL003  host-sync          ``np.asarray``/``jax.device_get``/
                            ``jax.block_until_ready``/``int()``/
                            ``float()``/``.item()`` on device values
                            inside the serve hot path (``decode_round``,
                            ``step``, ``submit``, ``*fused*``, and
                            anything they call) outside the allowlisted
                            ``@metered`` decorator or a
                            ``# repro-lint: allow(RL003)`` pragma.
  RL004  kernel-contract    Every ``kernels/<name>/`` directory keeps
                            the ``kernel.py``/``ref.py``/``ops.py``
                            triple, ``ref.py`` never imports pallas, and
                            the pallas side resolves tiles via
                            ``autotune.tiles_for`` (never hard-coded).
  RL005  determinism        No unseeded ``random.*`` module calls, no
                            global ``np.random.*`` samplers, and no
                            ``datetime.now``-family wall-clock reads in
                            the ``dht/`` / ``core/`` simulation planes
                            (the DES↔vectorized twin checks replay off
                            seeds; a wall-clock read silently unpins
                            them).

All analysis is stdlib ``ast`` — no new dependencies.  The rules are
deliberately codebase-specific heuristics, not a general JAX linter:
precision comes from knowing this repo's idioms (``tiles_for``,
``shape_bucket``, ``_jitted``, the serve hot-path names), and the
committed ``baseline.json`` ratchet (see ``baseline.py``) absorbs the
residue: legacy findings are allowed to exist, NEW findings fail CI.

Suppression:

  * ``# repro-lint: allow(RL003)`` (or ``allow(RL001, RL003)`` /
    ``allow(*)``) on the flagged line — or the line above it —
    suppresses those rules there.  Suppressions are counted in the
    report, never silent.
  * a decorator whose name contains ``metered`` marks a function as an
    allowlisted metering site for RL003 (see ``metering.metered``).
"""
from __future__ import annotations

import ast
import re
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

__all__ = ["Finding", "LintReport", "RULES", "run_lint", "collect_files"]

RULES: Dict[str, str] = {
    "RL001": "tracer-leak: Python control flow / coercion on a traced value",
    "RL002": "recompile-hazard: unbucketed dynamic size crossing a jit "
             "boundary",
    "RL003": "host-sync: device materialization inside the serve hot path",
    "RL004": "kernel-contract: kernels/<name>/ triple or tiles_for broken",
    "RL005": "determinism: unseeded randomness / wall-clock in a sim plane",
}

_HINTS: Dict[str, str] = {
    "RL001": "use jnp.where/lax.cond/lax.select, or mark the argument "
             "static via static_argnames",
    "RL002": "round the size through a pow2 bucket helper "
             "(kernels.autotune.shape_bucket / _decode_bucket) so the "
             "jit sees a bounded shape set",
    "RL003": "keep the sync out of the round loop, fuse it into the "
             "jitted program, or mark a metering site with @metered / "
             "'# repro-lint: allow(RL003) <why>'",
    "RL004": "keep kernel.py (pallas) / ref.py (oracle, pallas-free) / "
             "ops.py (jit wrapper); resolve tiles via autotune.tiles_for",
    "RL005": "thread a seeded random.Random / np.random.default_rng / "
             "jax.random key through the caller instead",
}

# serve hot-path roots (RL003): the per-round / per-request functions a
# hidden host sync taxes on EVERY call
_HOT_ROOTS = {"decode_round", "step", "submit"}
# names assigned from jax.jit in serve's Replica: results are device vals
_DEVICE_ATTR_RE = re.compile(r"^_?(decode|prefill)")
_PRAGMA_RE = re.compile(r"#\s*repro-lint:\s*allow\(([^)]*)\)")
# module-level `random.<fn>` calls that consume the global (unseeded) RNG
_RANDOM_FNS = {
    "random", "randint", "randrange", "choice", "choices", "shuffle",
    "sample", "uniform", "gauss", "normalvariate", "expovariate",
    "betavariate", "gammavariate", "lognormvariate", "vonmisesvariate",
    "paretovariate", "weibullvariate", "triangular", "getrandbits",
    "seed",
}
_NP_RANDOM_OK = {"default_rng", "Generator", "RandomState", "SeedSequence",
                 "PRNGKey"}
_WALLCLOCK = {"now", "utcnow", "today"}


@dataclass(frozen=True)
class Finding:
    rule: str
    path: str          # scan-root-relative posix path
    line: int
    scope: str         # enclosing function qualname, or "<module>"
    message: str
    hint: str = ""

    @property
    def key(self) -> str:
        """Baseline-ratchet identity: line-free, so unrelated edits that
        shift line numbers never churn the baseline."""
        return f"{self.rule}:{self.path}:{self.scope}:{self.message}"

    def __str__(self) -> str:
        return f"{self.path}:{self.line}: {self.rule} [{self.scope}] " \
               f"{self.message}" + (f"  (fix: {self.hint})" if self.hint
                                    else "")


@dataclass
class LintReport:
    findings: List[Finding] = field(default_factory=list)
    suppressed: List[Finding] = field(default_factory=list)
    files: int = 0

    def by_rule(self) -> Dict[str, int]:
        out: Dict[str, int] = {}
        for f in self.findings:
            out[f.rule] = out.get(f.rule, 0) + 1
        return out


# ---------------------------------------------------------------------------
# module indexing
# ---------------------------------------------------------------------------

def _dotted(node: ast.AST) -> Optional[str]:
    """'jnp.argmax' for Attribute chains rooted at a Name, else None."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _root(node: ast.AST) -> Optional[str]:
    d = _dotted(node)
    return d.split(".", 1)[0] if d else None


@dataclass
class FuncInfo:
    qualname: str
    node: ast.AST                        # FunctionDef | AsyncFunctionDef
    module: "ModuleInfo"
    params: List[str]
    static_params: Set[str]              # via static_argnames/argnums
    is_entry: bool = False
    metered: bool = False
    calls: Set[str] = field(default_factory=set)   # simple-name targets


@dataclass
class ModuleInfo:
    path: Path                           # absolute
    rel: str                             # scan-root-relative posix
    tree: ast.Module
    lines: List[str]
    funcs: Dict[str, FuncInfo] = field(default_factory=dict)  # simple name
    imports: Dict[str, Tuple[str, str]] = field(default_factory=dict)
    # local name -> (resolved module key, original name)

    def pragma_allows(self, line: int, rule: str) -> bool:
        for ln in (line, line - 1):
            if 1 <= ln <= len(self.lines):
                m = _PRAGMA_RE.search(self.lines[ln - 1])
                if m:
                    allowed = {s.strip() for s in m.group(1).split(",")}
                    if "*" in allowed or rule in allowed:
                        return True
        return False


def _static_params_of(fn: ast.AST) -> Set[str]:
    """Params pinned static by a partial(jax.jit, static_arg...) deco."""
    out: Set[str] = set()
    args = fn.args
    names = [a.arg for a in args.posonlyargs + args.args + args.kwonlyargs]
    for deco in fn.decorator_list:
        if not (isinstance(deco, ast.Call)
                and _root(deco.func) in ("partial", "functools")):
            continue
        for kw in deco.keywords:
            if kw.arg == "static_argnames":
                for el in ast.walk(kw.value):
                    if isinstance(el, ast.Constant) and \
                            isinstance(el.value, str):
                        out.add(el.value)
            elif kw.arg == "static_argnums":
                for el in ast.walk(kw.value):
                    if isinstance(el, ast.Constant) and \
                            isinstance(el.value, int) and \
                            el.value < len(names):
                        out.add(names[el.value])
    return out


def _is_jit_decorated(fn: ast.AST) -> bool:
    for deco in fn.decorator_list:
        target = deco.func if isinstance(deco, ast.Call) else deco
        d = _dotted(target)
        if d in ("jax.jit", "jit"):
            return True
        if isinstance(deco, ast.Call) and _root(deco.func) in (
                "partial", "functools"):
            for arg in deco.args:
                if _dotted(arg) in ("jax.jit", "jit"):
                    return True
    return False


def _is_metered(fn: ast.AST) -> bool:
    for deco in fn.decorator_list:
        target = deco.func if isinstance(deco, ast.Call) else deco
        d = _dotted(target)
        if d and "metered" in d.split(".")[-1]:
            return True
    return False


class _Indexer(ast.NodeVisitor):
    """One pass per module: functions, imports, call edges, jit entries."""

    def __init__(self, mod: ModuleInfo):
        self.mod = mod
        self.stack: List[str] = []
        self.fn_stack: List[FuncInfo] = []

    # -- imports ---------------------------------------------------------
    def visit_ImportFrom(self, node: ast.ImportFrom) -> None:
        mod = node.module or ""
        key = f"{'.' * node.level}{mod}"
        for alias in node.names:
            self.mod.imports[alias.asname or alias.name] = (key, alias.name)
        self.generic_visit(node)

    # -- functions -------------------------------------------------------
    def _visit_func(self, node) -> None:
        qual = ".".join(self.stack + [node.name]) if self.stack else node.name
        args = node.args
        params = [a.arg for a in
                  args.posonlyargs + args.args + args.kwonlyargs]
        info = FuncInfo(qual, node, self.mod, params,
                        _static_params_of(node),
                        is_entry=_is_jit_decorated(node),
                        metered=_is_metered(node))
        # functions defined inside a `*_jitted*` factory are jit bodies
        if any("_jitted" in s for s in self.stack):
            info.is_entry = True
        self.mod.funcs.setdefault(node.name, info)
        self.stack.append(node.name)
        self.fn_stack.append(info)
        self.generic_visit(node)
        self.fn_stack.pop()
        self.stack.pop()

    visit_FunctionDef = _visit_func
    visit_AsyncFunctionDef = _visit_func

    def visit_ClassDef(self, node: ast.ClassDef) -> None:
        self.stack.append(node.name)
        self.generic_visit(node)
        self.stack.pop()

    # -- calls -----------------------------------------------------------
    def visit_Call(self, node: ast.Call) -> None:
        d = _dotted(node.func)
        name = None
        if isinstance(node.func, ast.Name):
            name = node.func.id
        elif isinstance(node.func, ast.Attribute) and \
                isinstance(node.func.value, ast.Name) and \
                node.func.value.id in ("self", "cls"):
            name = node.func.attr
        if name and self.fn_stack:
            self.fn_stack[-1].calls.add(name)
        # jax.jit(f) / pallas_call(body) / shard_map(body): f is an entry
        if d in ("jax.jit", "jit") or (d and (
                d.split(".")[-1] in ("pallas_call", "shard_map"))):
            for arg in node.args[:1]:
                if isinstance(arg, ast.Name):
                    fi = self.mod.funcs.get(arg.id)
                    if fi is not None:
                        fi.is_entry = True
                    else:       # forward ref: mark after full pass
                        self.mod._late_entries.add(arg.id)  # type: ignore
        self.generic_visit(node)


def _index_module(path: Path, rel: str) -> Optional[ModuleInfo]:
    try:
        src = path.read_text()
        tree = ast.parse(src)
    except (SyntaxError, UnicodeDecodeError, OSError):
        return None
    mod = ModuleInfo(path=path, rel=rel, tree=tree,
                     lines=src.splitlines())
    mod._late_entries = set()            # type: ignore[attr-defined]
    _Indexer(mod).visit(tree)
    for name in mod._late_entries:       # type: ignore[attr-defined]
        if name in mod.funcs:
            mod.funcs[name].is_entry = True
    return mod


# ---------------------------------------------------------------------------
# cross-module reachability
# ---------------------------------------------------------------------------

def _module_key(rel: str) -> str:
    return rel[:-3].replace("/", ".") if rel.endswith(".py") else rel


def _resolve_import(mods: Dict[str, ModuleInfo], cur: ModuleInfo,
                    spec: str) -> Optional[str]:
    """Best-effort: map an import spec to a scanned module key."""
    if spec.startswith("."):
        level = len(spec) - len(spec.lstrip("."))
        base = _module_key(cur.rel).split(".")
        base = base[:-level] if level <= len(base) else []
        tail = spec.lstrip(".")
        parts = base + (tail.split(".") if tail else [])
        cand = ".".join(parts)
    else:
        cand = spec
    if cand in mods:
        return cand
    # absolute imports may carry a prefix the scan root strips (e.g.
    # `repro.kernels.x` scanned as `src.repro.kernels.x`): suffix-match
    for key in mods:
        if key == cand or key.endswith("." + cand) or \
                cand.endswith("." + key):
            return key
    return None


def _reachable(mods: Dict[str, ModuleInfo],
               roots: Iterable[FuncInfo]) -> Set[int]:
    """BFS over the (module-resolved) simple-name call graph; returns
    id()s of reachable FuncInfos."""
    seen: Set[int] = set()
    work = list(roots)
    while work:
        fi = work.pop()
        if id(fi) in seen:
            continue
        seen.add(id(fi))
        for callee in fi.calls:
            target = fi.module.funcs.get(callee)
            if target is None and callee in fi.module.imports:
                spec, orig = fi.module.imports[callee]
                mkey = _resolve_import(mods, fi.module, spec)
                if mkey is not None:
                    target = mods[mkey].funcs.get(orig)
            if target is not None and id(target) not in seen:
                work.append(target)
    return seen


# ---------------------------------------------------------------------------
# taint engine (shared by RL001 / RL003)
# ---------------------------------------------------------------------------

_JNP_ROOTS = ("jnp", "jax", "lax")


def _is_jnp_call(node: ast.AST) -> bool:
    return isinstance(node, ast.Call) and _root(node.func) in _JNP_ROOTS


# attribute reads on a tracer that yield STATIC python values during a
# trace (aval metadata) — branching on them never leaks the tracer
_STATIC_ATTRS = {"shape", "ndim", "dtype", "size", "nbytes", "weak_type"}


class _Taint:
    """Forward, order-of-statements taint over one function body."""

    def __init__(self, tainted: Set[str]):
        self.tainted = set(tainted)

    def expr_tainted(self, node: ast.AST) -> bool:
        if isinstance(node, ast.Attribute) and node.attr in _STATIC_ATTRS:
            return False
        if isinstance(node, ast.Name):
            return node.id in self.tainted
        if _is_jnp_call(node):
            return True
        if isinstance(node, ast.Call) and _dotted(node.func) == "len":
            return False        # len(tracer) is the static leading dim
        return any(self.expr_tainted(c)
                   for c in ast.iter_child_nodes(node))

    def bind(self, target: ast.AST, tainted: bool) -> None:
        for sub in ast.walk(target):
            if isinstance(sub, ast.Name):
                if tainted:
                    self.tainted.add(sub.id)
                else:
                    self.tainted.discard(sub.id)


def _is_none_check(test: ast.AST) -> bool:
    """`x is None` / `x is not None`: identity on a tracer is safe."""
    return isinstance(test, ast.Compare) and all(
        isinstance(op, (ast.Is, ast.IsNot)) for op in test.ops)


def _iter_body_stmts(fn: ast.AST):
    """Statements of fn in SOURCE order (DFS pre-order — taint binding
    must see a definition before its uses), skipping nested function/
    class bodies (they are analyzed as their own FuncInfo)."""

    def walk(stmts):
        for stmt in stmts:
            yield stmt
            if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.ClassDef)):
                continue
            for name in ("body", "orelse", "finalbody"):
                yield from walk(getattr(stmt, name, []) or [])
            for h in getattr(stmt, "handlers", []) or []:
                yield from walk(h.body)

    yield from walk(fn.body)


def _shallow_walk(stmt: ast.AST):
    """Walk the statement's OWN expressions only — nested statement
    lists are visited by ``_iter_body_stmts`` in their own right, and
    walking them here would double-count every finding."""
    for fname, value in ast.iter_fields(stmt):
        if fname in ("body", "orelse", "finalbody", "handlers"):
            continue
        vals = value if isinstance(value, list) else [value]
        for v in vals:
            if isinstance(v, ast.AST):
                yield from ast.walk(v)


# ---------------------------------------------------------------------------
# RL001 — tracer leak
# ---------------------------------------------------------------------------

_RL001_EXCLUDED_PARAMS = {"self", "cls", "model", "cfg", "config", "mesh"}


def _resolve_callee(mods: Dict[str, ModuleInfo], fi: FuncInfo,
                    name: str) -> Optional[FuncInfo]:
    target = fi.module.funcs.get(name)
    if target is None and name in fi.module.imports:
        spec, orig = fi.module.imports[name]
        mkey = _resolve_import(mods, fi.module, spec)
        if mkey is not None:
            target = mods[mkey].funcs.get(orig)
    return target


def _rl001(mods: Dict[str, ModuleInfo], emit) -> None:
    entries = [fi for m in mods.values() for fi in m.funcs.values()
               if fi.is_entry]
    reach = _reachable(mods, entries)
    infos = [fi for m in mods.values() for fi in m.funcs.values()
             if id(fi) in reach]

    def seedable(fi: FuncInfo, p: str) -> bool:
        return p not in _RL001_EXCLUDED_PARAMS and \
            p not in fi.static_params and p in fi.params

    # interprocedural taint, two layers:
    #   * an ENTRY's params are tracers by definition;
    #   * a reachable helper's param is a tracer only if some call site
    #     inside traced code passes it a tainted argument (blanket param
    #     taint would flag every host-scalar helper the trace consults —
    #     tile pickers, activation-name switches).
    # fixpoint over call sites, then one emitting pass.
    param_taint: Dict[int, Set[str]] = {
        id(fi): ({p for p in fi.params if seedable(fi, p)}
                 if fi.is_entry else set())
        for fi in infos}

    def analyze(fi: FuncInfo, check) -> None:
        t = _Taint(set(param_taint[id(fi)]))
        for stmt in _iter_body_stmts(fi.node):
            check(fi, t, stmt)
            if isinstance(stmt, ast.Assign):
                tainted = t.expr_tainted(stmt.value)
                for tgt in stmt.targets:
                    t.bind(tgt, tainted)
            elif isinstance(stmt, (ast.AugAssign, ast.AnnAssign)) and \
                    stmt.value is not None:
                t.bind(stmt.target, t.expr_tainted(stmt.value))

    def propagate(fi: FuncInfo, t: _Taint, stmt: ast.AST) -> None:
        for sub in _shallow_walk(stmt):
            if not isinstance(sub, ast.Call):
                continue
            name = None
            if isinstance(sub.func, ast.Name):
                name = sub.func.id
            elif isinstance(sub.func, ast.Attribute) and \
                    isinstance(sub.func.value, ast.Name) and \
                    sub.func.value.id in ("self", "cls"):
                name = sub.func.attr
            callee = _resolve_callee(mods, fi, name) if name else None
            if callee is None or id(callee) not in param_taint:
                continue
            # method resolution: skip a leading self/cls param
            params = callee.params
            if params and params[0] in ("self", "cls") and \
                    isinstance(sub.func, ast.Attribute):
                params = params[1:]
            for i, arg in enumerate(sub.args):
                if i < len(params) and t.expr_tainted(arg) and \
                        seedable(callee, params[i]):
                    if params[i] not in param_taint[id(callee)]:
                        param_taint[id(callee)].add(params[i])
                        propagate.changed = True
            for kw in sub.keywords:
                if kw.arg and t.expr_tainted(kw.value) and \
                        seedable(callee, kw.arg):
                    if kw.arg not in param_taint[id(callee)]:
                        param_taint[id(callee)].add(kw.arg)
                        propagate.changed = True

    for _ in range(6):                     # call-graph-depth fixpoint
        propagate.changed = False
        for fi in infos:
            analyze(fi, propagate)
        if not propagate.changed:
            break

    def check(fi: FuncInfo, t: _Taint, stmt: ast.AST) -> None:
        m = fi.module

        def flag(node, what):
            emit(Finding("RL001", m.rel, node.lineno, fi.qualname,
                         f"{what} on traced value "
                         f"`{ast.unparse(node)[:60]}`",
                         _HINTS["RL001"]), m)

        if isinstance(stmt, (ast.If, ast.While)) and \
                not _is_none_check(stmt.test) and \
                t.expr_tainted(stmt.test):
            flag(stmt.test, type(stmt).__name__.lower() + " branch")
        if isinstance(stmt, ast.Assert) and t.expr_tainted(stmt.test):
            flag(stmt.test, "assert")
        for sub in _shallow_walk(stmt):
            if isinstance(sub, ast.IfExp) and \
                    not _is_none_check(sub.test) and \
                    t.expr_tainted(sub.test):
                flag(sub.test, "conditional-expression test")
            if isinstance(sub, ast.Call):
                fname = _dotted(sub.func)
                if fname in ("bool", "int", "float") and sub.args \
                        and t.expr_tainted(sub.args[0]):
                    flag(sub, f"{fname}() coercion")
                if isinstance(sub.func, ast.Attribute) and \
                        sub.func.attr == "item" and \
                        t.expr_tainted(sub.func.value):
                    flag(sub, ".item() materialization")

    for fi in infos:
        analyze(fi, check)


# ---------------------------------------------------------------------------
# RL002 — recompile hazard
# ---------------------------------------------------------------------------

_DYN_SOURCES = {"len"}
_SIZE_ATTRS = {"size", "nbytes"}
_CONSTRUCTORS = {"zeros", "ones", "full", "empty", "arange", "linspace"}
_BOUNDARIES = {"jnp.asarray", "jnp.array", "jax.device_put"}


def _is_round_to_multiple(node: ast.AST) -> bool:
    """`(s + c - 1) // c * c` — the round-up-to-multiple idiom.  Like a
    `*bucket*` helper it bounds the shape set the jit sees (the chunked
    prefill loop only ever dispatches length-c segments), so a value
    computed this way is treated as bucketed."""
    return isinstance(node, ast.BinOp) and isinstance(node.op, ast.Mult) \
        and isinstance(node.left, ast.BinOp) \
        and isinstance(node.left.op, ast.FloorDiv)


def _dyn_expr(node: ast.AST, dyn: Set[str], bucketed: Set[str]) -> bool:
    """Does the expression carry an unbucketed dynamic size?"""
    if _is_round_to_multiple(node):
        return False
    for sub in ast.walk(node):
        if isinstance(sub, ast.Call):
            d = _dotted(sub.func)
            if d and "bucket" in d.split(".")[-1]:
                return False              # routed through the idiom
            if d in _DYN_SOURCES:
                return True
        if isinstance(sub, ast.Attribute) and sub.attr in _SIZE_ATTRS:
            return True
        if isinstance(sub, ast.Subscript) and \
                isinstance(sub.value, ast.Attribute) and \
                sub.value.attr == "shape":
            return True
        if isinstance(sub, ast.Name) and sub.id in dyn and \
                sub.id not in bucketed:
            return True
    return False


def _rl002(mods: Dict[str, ModuleInfo], emit) -> None:
    for m in mods.values():
        jit_names = {n for n, fi in m.funcs.items() if fi.is_entry}
        for alias, (spec, orig) in m.imports.items():
            mkey = _resolve_import(mods, m, spec)
            if mkey and orig in mods[mkey].funcs and \
                    mods[mkey].funcs[orig].is_entry:
                jit_names.add(alias)
        for fi in m.funcs.values():
            dyn: Set[str] = set()        # unbucketed dynamic scalars
            bucketed: Set[str] = set()
            dyn_arrays: Set[str] = set()  # arrays with dynamic shapes
            for stmt in _iter_body_stmts(fi.node):
                for sub in _shallow_walk(stmt):
                    if not isinstance(sub, ast.Call):
                        continue
                    d = _dotted(sub.func) or ""
                    tail = d.split(".")[-1]
                    # boundary crossing?
                    target_fi = m.funcs.get(tail) if tail in jit_names \
                        else None
                    is_boundary = d in _BOUNDARIES or tail in jit_names
                    if is_boundary:
                        for arg in list(sub.args) + \
                                [k.value for k in sub.keywords]:
                            names = {n.id for n in ast.walk(arg)
                                     if isinstance(n, ast.Name)}
                            if names & dyn_arrays:
                                emit(Finding(
                                    "RL002", m.rel, sub.lineno,
                                    fi.qualname,
                                    "dynamically-shaped array "
                                    f"`{ast.unparse(arg)[:50]}` crosses "
                                    f"jit boundary `{d or tail}` "
                                    "unbucketed", _HINTS["RL002"]), m)
                        # dynamic scalar into a static argname: retrace
                        # per distinct value
                        statics = target_fi.static_params if target_fi \
                            else set()
                        for kw in sub.keywords:
                            if kw.arg in statics and _dyn_expr(
                                    kw.value, dyn, bucketed):
                                emit(Finding(
                                    "RL002", m.rel, sub.lineno,
                                    fi.qualname,
                                    f"dynamic scalar flows into static "
                                    f"argname `{kw.arg}` of `{tail}`",
                                    _HINTS["RL002"]), m)
                if isinstance(stmt, ast.Assign) and len(stmt.targets) == 1 \
                        and isinstance(stmt.targets[0], ast.Name):
                    name = stmt.targets[0].id
                    v = stmt.value
                    d = (_dotted(v.func) or "") if isinstance(v, ast.Call) \
                        else ""
                    tail = d.split(".")[-1]
                    if (isinstance(v, ast.Call) and "bucket" in tail) or \
                            _is_round_to_multiple(v):
                        bucketed.add(name)
                        dyn.discard(name)
                        dyn_arrays.discard(name)
                    elif isinstance(v, ast.Call) and \
                            tail in _CONSTRUCTORS and v.args and \
                            _dyn_expr(v.args[0], dyn, bucketed):
                        dyn_arrays.add(name)
                    elif _dyn_expr(v, dyn, bucketed):
                        dyn.add(name)
                        dyn_arrays.discard(name)
                    else:
                        dyn.discard(name)
                        dyn_arrays.discard(name)


# ---------------------------------------------------------------------------
# RL003 — host sync in the serve hot path
# ---------------------------------------------------------------------------

def _rl003(mods: Dict[str, ModuleInfo], emit) -> None:
    serve_mods = {k: m for k, m in mods.items()
                  if "serve/" in m.rel or "/serve" in m.rel.rsplit("/", 1)[0]}
    if not serve_mods:
        return
    roots = [fi for m in serve_mods.values() for n, fi in m.funcs.items()
             if n in _HOT_ROOTS or "fused" in n]
    hot = _reachable(serve_mods, roots)
    # kernel wrappers imported into serve return device values
    for m in serve_mods.values():
        kernel_imports = {alias for alias, (spec, _) in m.imports.items()
                          if "kernel" in spec}
        for fi in m.funcs.values():
            if id(fi) not in hot or fi.metered:
                continue
            t = _Taint(set())

            def device_expr(node: ast.AST) -> bool:
                if isinstance(node, ast.Call):
                    d = _dotted(node.func) or ""
                    parts = d.split(".")
                    # np.* / device_get results live on the HOST — the
                    # sync is the call itself (flagged by `check`), not
                    # later uses of its result
                    if parts[0] in ("np", "numpy") or \
                            parts[-1] == "device_get":
                        return False
                    if _is_jnp_call(node):
                        return True
                    if isinstance(node.func, ast.Name) and \
                            node.func.id in kernel_imports:
                        return True
                    if isinstance(node.func, ast.Attribute) and \
                            _DEVICE_ATTR_RE.match(node.func.attr) and \
                            isinstance(node.func.value, ast.Name):
                        return True
                if isinstance(node, ast.Name):
                    return node.id in t.tainted
                return any(device_expr(c)
                           for c in ast.iter_child_nodes(node))

            def check(sub: ast.Call) -> Optional[str]:
                d = _dotted(sub.func) or ""
                tail = d.split(".")[-1]
                if tail == "block_until_ready":
                    return "jax.block_until_ready"
                if tail == "device_get":
                    return "jax.device_get"
                if d in ("np.asarray", "numpy.asarray", "np.array",
                         "numpy.array") and sub.args and \
                        device_expr(sub.args[0]):
                    return d
                if d in ("int", "float") and sub.args and \
                        device_expr(sub.args[0]):
                    return f"{d}()"
                if isinstance(sub.func, ast.Attribute) and \
                        sub.func.attr == "item" and \
                        device_expr(sub.func.value):
                    return ".item()"
                return None

            for stmt in _iter_body_stmts(fi.node):
                for sub in _shallow_walk(stmt):
                    if isinstance(sub, ast.Call):
                        what = check(sub)
                        if what:
                            emit(Finding(
                                "RL003", m.rel, sub.lineno, fi.qualname,
                                f"host sync `{what}` in serve hot path "
                                f"(`{ast.unparse(sub)[:60]}`)",
                                _HINTS["RL003"]), m)
                if isinstance(stmt, ast.Assign):
                    value_dev = device_expr(stmt.value)
                    for tgt in stmt.targets:
                        t.bind(tgt, value_dev)


# ---------------------------------------------------------------------------
# RL004 — kernel directory contract
# ---------------------------------------------------------------------------

def _imports_pallas(tree: ast.Module) -> Optional[int]:
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                if "pallas" in a.name:
                    return node.lineno
        elif isinstance(node, ast.ImportFrom):
            if "pallas" in (node.module or ""):
                return node.lineno
            for a in node.names:
                if "pallas" in a.name:
                    return node.lineno
    return None


def _mentions_tiles_for(tree: ast.Module) -> bool:
    for node in ast.walk(tree):
        if isinstance(node, ast.Name) and node.id == "tiles_for":
            return True
        if isinstance(node, ast.Attribute) and node.attr == "tiles_for":
            return True
        if isinstance(node, (ast.Import, ast.ImportFrom)) and any(
                a.name == "tiles_for" or a.asname == "tiles_for"
                for a in node.names):
            return True
    return False


def _rl004(mods: Dict[str, ModuleInfo], emit) -> None:
    by_dir: Dict[Path, Dict[str, ModuleInfo]] = {}
    for m in mods.values():
        parent = m.path.parent
        if parent.parent.name == "kernels" and \
                parent.name != "__pycache__":
            by_dir.setdefault(parent, {})[m.path.name] = m
    for d, files in sorted(by_dir.items()):
        rel_dir = next(iter(files.values())).rel.rsplit("/", 1)[0]
        anchor = next(iter(files.values()))
        missing = {"kernel.py", "ref.py", "ops.py"} - set(files)
        if missing:
            emit(Finding("RL004", rel_dir, 1, "<dir>",
                         f"kernel dir missing {sorted(missing)} of the "
                         "kernel/ref/ops triple", _HINTS["RL004"]), anchor)
        ref = files.get("ref.py")
        if ref is not None:
            ln = _imports_pallas(ref.tree)
            if ln is not None:
                emit(Finding("RL004", ref.rel, ln, "<module>",
                             "ref.py imports pallas — the oracle must "
                             "run without the kernel toolchain",
                             _HINTS["RL004"]), ref)
        impl = [files[n] for n in ("kernel.py", "ops.py") if n in files]
        if impl and not any(_mentions_tiles_for(m.tree) for m in impl):
            emit(Finding("RL004", impl[0].rel, 1, "<module>",
                         "kernel tiles not resolved via "
                         "autotune.tiles_for", _HINTS["RL004"]), impl[0])


# ---------------------------------------------------------------------------
# RL005 — determinism in the simulation planes
# ---------------------------------------------------------------------------

def _rl005(mods: Dict[str, ModuleInfo], emit) -> None:
    for m in mods.values():
        if not ("/dht/" in f"/{m.rel}" or "/core/" in f"/{m.rel}"):
            continue
        scope = "<module>"
        for node in ast.walk(m.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                scope = node.name        # coarse but stable
            if not isinstance(node, ast.Call):
                continue
            d = _dotted(node.func)
            if not d:
                continue
            parts = d.split(".")
            if parts[0] == "random" and len(parts) == 2 and \
                    parts[1] in _RANDOM_FNS:
                emit(Finding("RL005", m.rel, node.lineno, scope,
                             f"unseeded global RNG call `{d}()`",
                             _HINTS["RL005"]), m)
            elif len(parts) >= 3 and parts[0] in ("np", "numpy") and \
                    parts[1] == "random" and parts[2] not in _NP_RANDOM_OK:
                emit(Finding("RL005", m.rel, node.lineno, scope,
                             f"global numpy RNG call `{d}()`",
                             _HINTS["RL005"]), m)
            elif parts[-1] in _WALLCLOCK and "datetime" in parts or \
                    (len(parts) == 2 and parts[0] in ("datetime", "date")
                     and parts[1] in _WALLCLOCK):
                emit(Finding("RL005", m.rel, node.lineno, scope,
                             f"wall-clock read `{d}()` in a sim plane",
                             _HINTS["RL005"]), m)


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

def collect_files(paths: Sequence[Path]) -> List[Path]:
    out: List[Path] = []
    for p in paths:
        p = Path(p)
        if p.is_file() and p.suffix == ".py":
            out.append(p)
        elif p.is_dir():
            out.extend(f for f in sorted(p.rglob("*.py"))
                       if "__pycache__" not in f.parts)
    return out


def run_lint(paths: Sequence, root: Optional[Path] = None,
             rules: Optional[Set[str]] = None) -> LintReport:
    """Lint ``paths`` (files or directories); findings carry paths
    relative to ``root`` (default: cwd) so baseline keys are stable
    across checkouts."""
    root = Path(root) if root is not None else Path.cwd()
    files = collect_files([Path(p) for p in paths])
    mods: Dict[str, ModuleInfo] = {}
    for f in files:
        try:
            rel = f.resolve().relative_to(root.resolve()).as_posix()
        except ValueError:
            rel = f.as_posix()
        mod = _index_module(f, rel)
        if mod is not None:
            mods[_module_key(rel)] = mod
    report = LintReport(files=len(mods))

    def emit(finding: Finding, mod: ModuleInfo) -> None:
        if rules is not None and finding.rule not in rules:
            return
        if mod.pragma_allows(finding.line, finding.rule):
            report.suppressed.append(finding)
        else:
            report.findings.append(finding)

    _rl001(mods, emit)
    _rl002(mods, emit)
    _rl003(mods, emit)
    _rl004(mods, emit)
    _rl005(mods, emit)
    report.findings.sort(key=lambda f: (f.path, f.line, f.rule))
    return report
