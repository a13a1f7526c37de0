"""The five alink-lint rules.

Each rule is a function ``(index, config, registry) -> List[Finding]``.
``run_lint`` composes them; the rule semantics are specified in each
docstring and pinned by the fixture self-tests
(``tests/lint_fixtures/``, one minimal positive and negative case per
rule).
"""

from __future__ import annotations

import ast
import fnmatch
import os
from dataclasses import dataclass, field
from typing import Any, Dict, Iterable, List, Optional, Sequence, Set, Tuple

from .analyzer import (EnvRead, Finding, FunctionInfo, ModuleIndex,
                       bound_names, const_str, dotted_name, env_reads_in,
                       free_names, iter_statements, reachable_functions,
                       repo_root)

# ---------------------------------------------------------------------------
# configuration
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class FactoryRoot:
    """A function that builds/caches compiled programs or persistent
    signatures. ``dims``: the cache-key dimensions (flags.py constants)
    its keys span — a flag read reachable from here must fold into at
    least one of them, or be declared key-neutral."""
    path: str          # repo-relative file
    qualname: str      # "Class.method" or "fn"
    dims: frozenset


@dataclass(frozen=True)
class LintConfig:
    package_dirs: Tuple[str, ...]
    factory_roots: Tuple[FactoryRoot, ...]
    # files where raw lax collectives ARE the sanctioned implementation
    collective_allowed: Tuple[str, ...]
    # path globs whose modules compile into device programs (the
    # HOST-CALLBACK-FREE surface)
    compiled_path_globs: Tuple[str, ...]
    # files whose env reads ENV-KEY-FOLD skips: the registry's own
    # accessor plumbing reads os.environ with a parameter name by
    # construction — the CALL SITES carry the literal names it checks
    env_read_exempt: Tuple[str, ...] = (
        "alink_tpu/common/flags.py",)
    max_depth: int = 10


_COMQ = "alink_tpu/engine/comqueue.py"
_FTRL = "alink_tpu/operator/stream/onlinelearning/ftrl.py"
_TREES = "alink_tpu/operator/common/tree/trainers.py"
_PLAN = "alink_tpu/common/plan.py"

_PC = "program_cache"
_CKS = "checkpoint_signature"
_LRU = "step_lru"


def default_config() -> LintConfig:
    """The configuration for the real ``alink_tpu`` tree.

    ISSUE 19 collapsed the per-subsystem factory roots (engine ``_run``,
    FTRL ``link_from`` + its seven lru step factories, the serving /
    sharded / fleet program factories, the sweep queue builder) onto
    ``common/plan.py`` — every one of those cache keys is now DERIVED
    from an :class:`ExecutionPlan` built at exactly one of the plan
    constructors below, so the env-read → key-fold discipline is
    checked where the key is born instead of at ~15 consumption sites.
    The lru_cache structural backstop in :func:`rule_env_key_fold`
    still sweeps every ``@lru_cache`` factory for UNDECLARED reads, so
    a new factory that bypasses plan.py does not dodge the rule."""
    roots = [
        # the ONE engine plan-derivation site: IterativeComQueue._run
        # builds its program-cache key and checkpoint signature from
        # engine_plan()/engine_flags() (ISSUE 19) — flag resolution
        # happens here and nowhere else
        FactoryRoot(_PLAN, "engine_flags", frozenset({_PC, _CKS})),
        FactoryRoot(_PLAN, "engine_plan", frozenset({_PC, _CKS})),
        # the ONE FTRL plan-derivation site: the drain's lru step keys
        # and stream checkpoint signature unpack from ftrl_plan()
        FactoryRoot(_PLAN, "ftrl_plan", frozenset({_LRU, _CKS})),
        # the ONE sweep plan-derivation site (ISSUE 12's program key is
        # now legacy_sweep_program_key(sweep_plan(...)))
        FactoryRoot(_PLAN, "sweep_plan", frozenset({_PC})),
        # tree trainers: set_program_key callers (fused-hist fold) —
        # their key tuples predate ExecutionPlan and stay direct roots
        FactoryRoot(_TREES, "gbdt_train", frozenset({_PC})),
        FactoryRoot(_TREES, "forest_train", frozenset({_PC})),
        # the Pallas kernel tier (ISSUE 13): the serving-kernel build
        # resolves ALINK_TPU_SERVE_FUSED/_DTYPE into the ServingKernel
        # signature (the serving program-cache key, which ServingPlan /
        # serving_event_plan consume as an opaque value)
        FactoryRoot("alink_tpu/operator/common/linear/mapper.py",
                    "LinearModelMapper.serving_kernel", frozenset({_PC})),
        FactoryRoot("alink_tpu/kernels/serve.py",
                    "resolve_serve_kernel", frozenset({_PC})),
    ]
    return LintConfig(
        package_dirs=("alink_tpu",),
        factory_roots=tuple(roots),
        collective_allowed=(
            # the manifest-recording primitives themselves
            "alink_tpu/engine/communication.py",
            # ctx.all_reduce_sum — records through record_collective,
            # i.e. the same manifest path as the stage classes
            "alink_tpu/engine/context.py",
        ),
        compiled_path_globs=(
            "alink_tpu/engine/*",
            "alink_tpu/kernels/*",
            "alink_tpu/ops/*",
            "alink_tpu/operator/common/*",
            "alink_tpu/operator/stream/onlinelearning/*",
            "alink_tpu/serving/*",
            "alink_tpu/tuning/*",
            "alink_tpu/common/profiling.py",
            "alink_tpu/common/health.py",
        ),
    )


# ---------------------------------------------------------------------------
# ENV-KEY-FOLD
# ---------------------------------------------------------------------------

def rule_env_key_fold(index: ModuleIndex, config: LintConfig,
                      registry) -> List[Finding]:
    """An env read reachable from a program/step factory must be a
    registry-declared flag that either folds into (at least one of)
    that factory's key dimensions or is declared key-neutral.
    Undeclared names and dynamic (non-literal) reads always fail —
    the registry cannot vouch for what it cannot see."""
    out: List[Finding] = []
    seen: Set[Tuple[str, str, str]] = set()
    for root in config.factory_roots:
        mod = index.by_path.get(root.path)
        fi = mod.functions.get(root.qualname) if mod else None
        if fi is None:
            out.append(Finding(
                "ENV-KEY-FOLD", root.path, 1, f"missing-root:{root.qualname}",
                f"configured factory root {root.qualname!r} not found — "
                f"update tools/lint/rules.py default_config()"))
            continue
        for reached in reachable_functions(index, fi, config.max_depth):
            rmod = reached.fn.module
            if rmod.path in config.env_read_exempt:
                continue
            for read in env_reads_in(reached.fn.node, rmod, index):
                flag = registry.get(read.name) \
                    if read.name != "<dynamic>" else None
                if flag is not None and (
                        flag.key_neutral
                        or (set(flag.folds_into) & root.dims)):
                    continue
                dedup = (root.qualname, rmod.path, read.name)
                if dedup in seen:
                    continue
                seen.add(dedup)
                via = " -> ".join(reached.chain)
                if read.name == "<dynamic>":
                    msg = (f"dynamic env read (via {via}) reachable from "
                           f"factory {root.qualname!r}: the registry "
                           f"cannot check a computed name")
                elif flag is None:
                    msg = (f"env read of undeclared flag {read.name!r} "
                           f"(via {via}) reachable from factory "
                           f"{root.qualname!r}: declare it in "
                           f"alink_tpu/common/flags.py with folds_into= "
                           f"or key_neutral=")
                else:
                    msg = (f"flag {read.name!r} (via {via}) is reachable "
                           f"from factory {root.qualname!r} whose keys "
                           f"span {sorted(root.dims)}, but it declares "
                           f"folds_into={sorted(flag.folds_into)} and no "
                           f"key_neutral justification — a toggle could "
                           f"serve a stale compiled program/snapshot")
                out.append(Finding("ENV-KEY-FOLD", rmod.path, read.line,
                                   read.name, msg, flag=read.name))

    # structural backstop: a NEW cached program factory nobody added to
    # default_config() must not silently escape the rule (the exact
    # growth path ROADMAP items 1-2 predict). Any lru_cache-decorated
    # function that is not a configured root but can reach a
    # key-affecting env read (anything not declared key-neutral) is
    # flagged until it is registered with its key dimensions.
    root_names = {(r.path, r.qualname) for r in config.factory_roots}
    for mod in index.by_path.values():
        for fi in mod.functions.values():
            decs = getattr(fi.node, "decorator_list", [])
            if not any(_is_lru_decorator(d, mod) for d in decs):
                continue
            if (mod.path, fi.qualname) in root_names:
                continue
            for reached in reachable_functions(index, fi, config.max_depth):
                rmod = reached.fn.module
                if rmod.path in config.env_read_exempt:
                    continue
                for read in env_reads_in(reached.fn.node, rmod, index):
                    flag = registry.get(read.name) \
                        if read.name != "<dynamic>" else None
                    if flag is not None and flag.key_neutral:
                        continue
                    dedup = (f"unreg:{fi.qualname}", rmod.path, read.name)
                    if dedup in seen:
                        continue
                    seen.add(dedup)
                    via = " -> ".join(reached.chain)
                    out.append(Finding(
                        "ENV-KEY-FOLD", mod.path, fi.node.lineno,
                        f"unregistered-factory:{fi.qualname}",
                        f"lru_cache'd factory {fi.qualname!r} is not a "
                        f"configured factory root but reaches the env "
                        f"read of {read.name!r} (via {via}) — register "
                        f"it in tools/lint/rules.py default_config() "
                        f"with its key dimensions so the fold is "
                        f"checked, or declare the flag key_neutral"))
    return out


def _is_lru_decorator(dec: ast.AST, mod) -> bool:
    """``@functools.lru_cache(...)`` / ``@lru_cache`` / ``@functools.
    cache`` under any import alias — the cached-program-factory marker
    this codebase uses for every jit/step factory."""
    target = dec.func if isinstance(dec, ast.Call) else dec
    dn = dotted_name(target)
    if not dn:
        return False
    fq = _resolve_call_fq(dn, mod)
    return fq in ("functools.lru_cache", "functools.cache", "lru_cache")


# ---------------------------------------------------------------------------
# TRACED-CAPTURE
# ---------------------------------------------------------------------------

_DEVICE_PRODUCER_PREFIXES = (
    "jnp.", "jax.numpy.", "jax.random.", "jax.device_put",
    "jax.make_array_from", "jax.pmap", "jax.device_put_replicated",
    "jax.device_put_sharded",
)
_MUTATORS = frozenset({"append", "extend", "insert", "add", "update",
                       "setdefault", "pop", "popitem", "clear", "remove",
                       "discard", "appendleft"})


def _is_device_producer(expr: ast.AST) -> bool:
    if not isinstance(expr, ast.Call):
        return False
    dn = dotted_name(expr.func)
    return bool(dn) and (dn.startswith(_DEVICE_PRODUCER_PREFIXES)
                         or dn in ("jax.device_put", "device_put"))


def _is_mutable_container(expr: ast.AST) -> bool:
    if isinstance(expr, (ast.Dict, ast.List, ast.Set, ast.DictComp,
                         ast.ListComp, ast.SetComp)):
        return True
    if isinstance(expr, ast.Call):
        dn = dotted_name(expr.func)
        return dn in ("dict", "list", "set", "collections.OrderedDict",
                      "OrderedDict", "collections.defaultdict",
                      "defaultdict", "collections.deque", "deque")
    return False


def _name_mutated(name: str, scopes: Iterable[ast.AST]) -> Optional[int]:
    """Line of the first mutation of ``name`` (method mutator call,
    subscript store/del, aug-assign through subscript) in any scope."""
    for scope in scopes:
        for n in ast.walk(scope):
            if isinstance(n, ast.Call) and isinstance(n.func, ast.Attribute):
                v = n.func.value
                if isinstance(v, ast.Name) and v.id == name \
                        and n.func.attr in _MUTATORS:
                    return n.lineno
            elif isinstance(n, ast.Subscript) and isinstance(
                    n.ctx, (ast.Store, ast.Del)):
                if isinstance(n.value, ast.Name) and n.value.id == name:
                    return n.lineno
    return None


def _traced_candidates(mod) -> List[Tuple[str, ast.AST, List[ast.AST]]]:
    """(label, function node, enclosing-scope chain innermost-first) for
    every function that enters a compiled program: first positional arg
    of ``jax.jit``/``jit``/``lazy_jit``/``shard_map``/``pallas_call``,
    or registered as a comqueue stage via ``.add(fn)``."""
    # def-name -> (node, enclosing chain)
    defs: Dict[int, List[ast.AST]] = {}

    def collect(node, chain):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                defs[id(child)] = chain
                collect(child, [child] + chain)
            else:
                collect(child, chain)

    collect(mod.tree, [])

    # name -> last def node seen anywhere in the module (good enough:
    # the real tree and the fixtures use unique candidate names)
    by_name: Dict[str, ast.AST] = {}
    for n in ast.walk(mod.tree):
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)):
            by_name[n.name] = n

    out: List[Tuple[str, ast.AST, List[ast.AST]]] = []
    seen: Set[int] = set()
    for n in ast.walk(mod.tree):
        if not isinstance(n, ast.Call):
            continue
        target: Optional[ast.AST] = None
        label = ""
        dn = dotted_name(n.func)
        short = dn.rsplit(".", 1)[-1] if dn else ""
        if short in ("jit", "shard_map", "lazy_jit", "pallas_call") \
                and n.args:
            a0 = n.args[0]
            if isinstance(a0, ast.Name):
                target = by_name.get(a0.id)
                label = a0.id
            elif isinstance(a0, ast.Lambda):
                target = a0
                label = f"<lambda:{a0.lineno}>"
        elif isinstance(n.func, ast.Attribute) and n.func.attr == "add" \
                and len(n.args) == 1 and isinstance(n.args[0], ast.Name):
            cand = by_name.get(n.args[0].id)
            # only functions taking a single ctx-like arg are stages —
            # filters out set.add(elem) style false positives
            if cand is not None and len(getattr(cand, "args",
                                                ast.arguments()).args) == 1:
                target = cand
                label = n.args[0].id
        if target is not None and id(target) not in seen:
            seen.add(id(target))
            out.append((label, target, defs.get(id(target), [])))
    return out


def rule_traced_capture(index: ModuleIndex, config: LintConfig,
                        registry) -> List[Finding]:
    """A function that enters a compiled program (jitted / shard_mapped
    / added as a comqueue stage) must not capture, via closure cell or
    module global: (a) a value produced by a device-array constructor
    (``jnp.*``, ``jax.device_put``, ``jax.random.*``) — its CONTENT
    bakes into the trace while the structural cache guard tokenizes it
    by shape/dtype only; or (b) a mutable container that is mutated —
    trace-time host state that a cached program will silently go stale
    against. The runtime twin of this rule is the RuntimeWarning in
    ``engine/comqueue.py`` (same rule name)."""
    out: List[Finding] = []
    for mod in index.by_path.values():
        candidates = _traced_candidates(mod)
        if not candidates:
            continue
        # module-level simple assignments (globals a traced fn may read)
        mod_assigns: Dict[str, ast.AST] = {}
        for stmt in mod.tree.body:
            tgt = None
            if isinstance(stmt, ast.Assign) and len(stmt.targets) == 1 \
                    and isinstance(stmt.targets[0], ast.Name):
                tgt = stmt.targets[0].id
            elif isinstance(stmt, ast.AnnAssign) \
                    and isinstance(stmt.target, ast.Name) \
                    and stmt.value is not None:
                tgt = stmt.target.id
            if tgt is not None:
                mod_assigns[tgt] = stmt.value
        by_name = {n.name: n for n in ast.walk(mod.tree)
                   if isinstance(n, (ast.FunctionDef,
                                     ast.AsyncFunctionDef))}
        for label, fnode, chain in candidates:
            # follow locally-called helpers one level: the comqueue
            # pattern is shard_map(run) -> run() -> superstep() with the
            # capture in superstep
            extra = []
            for n in ast.walk(fnode):
                if isinstance(n, ast.Call) and isinstance(n.func, ast.Name):
                    callee = by_name.get(n.func.id)
                    if callee is not None and callee is not fnode:
                        extra.append((n.func.id, callee))
            for scope, scope_label in [(fnode, label)] + \
                    [(s, f"{label}/{sl}") for sl, s in extra]:
                for name in sorted(free_names(scope)):
                    binding = None
                    # innermost enclosing def's direct assignments first
                    for enc in chain:
                        for stmt in iter_statements(enc.body):
                            v = None
                            if isinstance(stmt, ast.Assign) and any(
                                    isinstance(t, ast.Name) and t.id == name
                                    for t in stmt.targets):
                                v = stmt.value
                            elif isinstance(stmt, ast.AnnAssign) and \
                                    isinstance(stmt.target, ast.Name) and \
                                    stmt.target.id == name and stmt.value:
                                v = stmt.value
                            if v is not None:
                                binding = v
                        if binding is not None:
                            break
                    if binding is None:
                        binding = mod_assigns.get(name)
                    if binding is None:
                        continue
                    if _is_device_producer(binding):
                        out.append(Finding(
                            "TRACED-CAPTURE", mod.path, binding.lineno,
                            f"{scope_label}:{name}",
                            f"traced function {scope_label!r} captures "
                            f"{name!r}, bound from a device-array "
                            f"constructor — its content bakes into the "
                            f"trace while the program cache tokenizes it "
                            f"by shape/dtype only; route it through "
                            f"partitioned/broadcast inputs"))
                    elif _is_mutable_container(binding):
                        mut = _name_mutated(
                            name, [scope] + list(chain))
                        if mut is not None:
                            out.append(Finding(
                                "TRACED-CAPTURE", mod.path, mut,
                                f"{scope_label}:{name}",
                                f"traced function {scope_label!r} "
                                f"captures mutable container {name!r} "
                                f"which is mutated (line {mut}) — "
                                f"trace-time host state a cached "
                                f"program goes silently stale against"))
    return out


# ---------------------------------------------------------------------------
# DONATE-USE-AFTER
# ---------------------------------------------------------------------------

def _donate_positions(call: ast.Call) -> Optional[Set[int]]:
    """The literal ``donate_argnums`` positions of a ``jax.jit`` call
    (None when absent/empty). An ``(a, b) if flag else ()`` conditional
    counts as "may donate" — take the non-empty branch."""
    dn = dotted_name(call.func)
    if not dn or dn.rsplit(".", 1)[-1] != "jit":
        return None
    for kw in call.keywords:
        if kw.arg != "donate_argnums":
            continue
        expr = kw.value
        if isinstance(expr, ast.IfExp):
            for branch in (expr.body, expr.orelse):
                if isinstance(branch, ast.Tuple) and branch.elts:
                    expr = branch
                    break
        pos: Set[int] = set()
        if isinstance(expr, ast.Tuple):
            for e in expr.elts:
                if isinstance(e, ast.Constant) and isinstance(e.value, int):
                    pos.add(e.value)
        elif isinstance(expr, ast.Constant) and isinstance(expr.value, int):
            pos.add(expr.value)
        return pos or None
    return None


def _donating_returns(fnode: ast.AST) -> Optional[Dict[Optional[int], Set[int]]]:
    """For a factory function: map of returned-tuple index (None for a
    bare return) -> donated positions, when any return donates."""
    got: Dict[Optional[int], Set[int]] = {}
    for n in ast.walk(fnode):
        if not isinstance(n, ast.Return) or n.value is None:
            continue
        v = n.value
        if isinstance(v, ast.Tuple):
            for i, e in enumerate(v.elts):
                if isinstance(e, ast.Call):
                    pos = _donate_positions(e)
                    if pos:
                        got[i] = pos
        elif isinstance(v, ast.Call):
            pos = _donate_positions(v)
            if pos:
                got[None] = pos
    return got or None


def rule_donate_use_after(index: ModuleIndex, config: LintConfig,
                          registry) -> List[Finding]:
    """Within one function body (statements in source order): once a
    name is passed at a ``donate_argnums`` position of a donating
    callable, XLA may alias its buffer away — reading it again before
    rebinding raises ``Array has been deleted`` at runtime (or worse,
    on backends that skip the runtime check, reads garbage). Donating
    callables are recognized from ``jax.jit(..., donate_argnums=...)``
    assignments (module- or function-local, including nested factory
    defs) and from calls to factories whose returns are such jits."""
    out: List[Finding] = []
    # pass 1: factories (module level, any module)
    factories: Dict[Tuple[str, str], Dict[Optional[int], Set[int]]] = {}
    for mod in index.by_path.values():
        for q, fi in mod.functions.items():
            got = _donating_returns(fi.node)
            if got:
                factories[(mod.modname, q)] = got

    for mod in index.by_path.values():
        for q, fi in mod.functions.items():
            out.extend(_donate_scan_function(index, mod, fi, factories))
    return out


def _passthrough_wrappers(fnode: ast.AST) -> Set[str]:
    """Names of local defs shaped ``def w(f, *args): ... f(*args)`` —
    higher-order pass-through wrappers (the FTRL drain's ``run_step``).
    A donating callable handed to one as the first argument still
    donates, with every ``donate_argnums`` position shifted one right
    in the wrapper's own argument list; without this, routing a step
    call through a telemetry wrapper silently blinds DONATE-USE-AFTER
    in the exact loop the rule was built for."""
    out: Set[str] = set()
    for n in ast.walk(fnode):
        if not isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        a = n.args
        if len(a.args) != 1 or a.vararg is None or a.kwonlyargs \
                or getattr(a, "posonlyargs", None):
            continue
        fparam, vparam = a.args[0].arg, a.vararg.arg
        for c in ast.walk(n):
            if isinstance(c, ast.Call) and isinstance(c.func, ast.Name) \
                    and c.func.id == fparam \
                    and any(isinstance(s, ast.Starred)
                            and isinstance(s.value, ast.Name)
                            and s.value.id == vparam for s in c.args):
                out.add(n.name)
                break
    return out


def _stmt_own_nodes(stmt: ast.stmt) -> List[ast.AST]:
    """The AST nodes whose reads/donations belong to THIS statement.
    Compound statements contribute only their header expressions —
    their bodies come back as separate statements from
    ``iter_statements`` (walking the whole subtree here would count a
    donation inside an ``if`` body once for the ``if`` and once for the
    nested assign, breaking the same-statement-rebind sanction)."""
    if isinstance(stmt, (ast.If, ast.While)):
        return [stmt.test]
    if isinstance(stmt, (ast.For, ast.AsyncFor)):
        return [stmt.iter]
    if isinstance(stmt, (ast.With, ast.AsyncWith)):
        return [i.context_expr for i in stmt.items]
    if isinstance(stmt, (ast.Try, ast.FunctionDef, ast.AsyncFunctionDef,
                         ast.ClassDef)):
        return []
    return [stmt]


def _donate_scan_function(index, mod, fi, factories) -> List[Finding]:
    out: List[Finding] = []
    # nested donating factories local to this function
    local_factories: Dict[str, Dict[Optional[int], Set[int]]] = {}
    for n in ast.walk(fi.node):
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                and n is not fi.node:
            got = _donating_returns(n)
            if got:
                local_factories[n.name] = got

    donating: Dict[str, Set[int]] = {}     # callable name -> positions
    consumed: Dict[str, int] = {}          # var -> line it was donated
    wrappers = _passthrough_wrappers(fi.node)

    def expr_key(e: ast.AST) -> Optional[str]:
        if isinstance(e, ast.Name):
            return e.id
        if isinstance(e, ast.Subscript) and isinstance(e.value, ast.Name):
            idx = e.slice
            if isinstance(idx, ast.Constant):
                return f"{e.value.id}[{idx.value!r}]"
        return None

    def callee_key(call: ast.Call) -> Optional[str]:
        return expr_key(call.func)

    def factory_positions(call: ast.Call
                          ) -> Optional[Dict[Optional[int], Set[int]]]:
        if isinstance(call.func, ast.Name):
            name = call.func.id
            if name in local_factories:
                return local_factories[name]
            got = index.resolve_call(call, mod,
                                     cls_name=fi.qualname.split(".")[0]
                                     if "." in fi.qualname else "")
            if got is not None:
                return factories.get((got.module.modname, got.qualname))
        return None

    def assign_targets(stmt) -> List[str]:
        names: List[str] = []
        if isinstance(stmt, ast.Assign):
            for t in stmt.targets:
                if isinstance(t, ast.Name):
                    names.append(t.id)
                elif isinstance(t, (ast.Tuple, ast.List)):
                    names.extend(e.id for e in t.elts
                                 if isinstance(e, ast.Name))
        elif isinstance(stmt, (ast.AnnAssign, ast.AugAssign)) and \
                isinstance(stmt.target, ast.Name):
            names.append(stmt.target.id)
        elif isinstance(stmt, ast.For) and isinstance(stmt.target, ast.Name):
            names.append(stmt.target.id)
        elif isinstance(stmt, (ast.With, ast.AsyncWith)):
            names.extend(i.optional_vars.id for i in stmt.items
                         if isinstance(i.optional_vars, ast.Name))
        return names

    for stmt in iter_statements(fi.node.body):
        own = [w for node in _stmt_own_nodes(stmt) for w in ast.walk(node)]
        # (1) reads of already-consumed names anywhere in this statement
        for n in own:
            if isinstance(n, ast.Name) and isinstance(n.ctx, ast.Load) \
                    and n.id in consumed:
                out.append(Finding(
                    "DONATE-USE-AFTER", mod.path, n.lineno,
                    f"{fi.qualname}:{n.id}",
                    f"{n.id!r} was passed at a donate_argnums position "
                    f"(line {consumed[n.id]}) and read again before "
                    f"rebinding — the donated buffer is dead after the "
                    f"call (jax raises 'Array has been deleted'); fetch "
                    f"what you need BEFORE the donating call or rebind "
                    f"from its outputs"))
                consumed.pop(n.id, None)   # one finding per donation
        # (2) this statement's donations
        newly: List[str] = []
        for n in own:
            if not isinstance(n, ast.Call):
                continue
            key = callee_key(n)
            pos = donating.get(key) if key else None
            if pos is None and isinstance(n.func, ast.Name) \
                    and n.func.id in donating:
                pos = donating[n.func.id]
            if pos is None and key in wrappers and n.args:
                # run_step(step, *rest): the wrapped callable's donated
                # positions, shifted past the callable argument itself
                inner = expr_key(n.args[0])
                ipos = donating.get(inner) if inner else None
                if ipos:
                    pos = {p + 1 for p in ipos}
            if pos:
                for p in pos:
                    if p < len(n.args) and isinstance(n.args[p], ast.Name):
                        newly.append(n.args[p].id)
        # (3) this statement's bindings: donating-callable defs + rebinds
        targets = assign_targets(stmt)
        value = getattr(stmt, "value", None)
        if isinstance(value, ast.Call):
            jitpos = _donate_positions(value)
            fpos = factory_positions(value)
            if jitpos and len(targets) == 1:
                donating[targets[0]] = jitpos
            elif fpos is not None:
                if None in fpos and len(targets) == 1:
                    donating[targets[0]] = fpos[None]
                else:
                    for i, t in enumerate(targets):
                        if i in fpos:
                            donating[t] = fpos[i]
            # subscript store: sparse_step[0] = factory(...)
            if isinstance(stmt, ast.Assign) and len(stmt.targets) == 1 \
                    and isinstance(stmt.targets[0], ast.Subscript):
                sub = stmt.targets[0]
                if isinstance(sub.value, ast.Name) and \
                        isinstance(sub.slice, ast.Constant):
                    key = f"{sub.value.id}[{sub.slice.value!r}]"
                    if jitpos:
                        donating[key] = jitpos
                    elif fpos is not None and None in fpos:
                        donating[key] = fpos[None]
        # consumption recorded AFTER rebind handling: a name that is
        # both donated and rebound by the same statement (z, n, _ =
        # step(..., z, n)) is the sanctioned idiom
        for name in newly:
            if name not in targets:
                consumed[name] = stmt.lineno
        for name in targets:
            consumed.pop(name, None)
    return out


# ---------------------------------------------------------------------------
# COLLECTIVE-SITE
# ---------------------------------------------------------------------------

_COLLECTIVES = frozenset({
    "psum", "psum_scatter", "pmax", "pmin", "pmean", "all_gather",
    "ppermute", "pshuffle", "all_to_all", "pswapaxes",
})


def _enclosing_fn_finder(mod):
    """Smallest-enclosing-function lookup for a module: returns
    ``fn_at(line) -> name`` (``"<module>"`` at top level)."""
    encl: List[Tuple[int, int, str]] = []
    for n in ast.walk(mod.tree):
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)):
            end = getattr(n, "end_lineno", n.lineno)
            encl.append((n.lineno, end, n.name))

    def fn_at(line: int) -> str:
        best = "<module>"
        blen = 1 << 30
        for lo, hi, nm in encl:
            if lo <= line <= hi and hi - lo < blen:
                best, blen = nm, hi - lo
        return best

    return fn_at


def _resolve_call_fq(dn: str, mod) -> str:
    """The call target's fully qualified dotted name: the leading
    binding resolves through the module's import map, so aliases
    (``import jax.lax as L`` / ``from jax import lax as jlax`` /
    ``from jax.lax import psum as p``) cannot smuggle a call past the
    name-based rules below. Unresolvable roots return ``dn`` verbatim
    (conservative: a bare unimported ``psum`` still matches)."""
    root, dot, rest = dn.partition(".")
    fq = mod.imports.get(root)
    if fq is None:
        return dn
    return fq + dot + rest


def rule_collective_site(index: ModuleIndex, config: LintConfig,
                         registry) -> List[Finding]:
    """Raw ``lax.<collective>`` calls outside the sanctioned modules
    (``engine/communication.py`` and the manifest-recording
    ``ctx.all_reduce_sum``) escape the collective manifest — they run
    real inter-chip traffic the accounting, the scaling evidence and
    the planned ROADMAP-item-1 psum fusion cannot see."""
    out: List[Finding] = []
    for mod in index.by_path.values():
        if mod.path in config.collective_allowed:
            continue
        fn_at = _enclosing_fn_finder(mod)
        for n in ast.walk(mod.tree):
            if not isinstance(n, ast.Call):
                continue
            dn = dotted_name(n.func)
            if not dn:
                continue
            parts = _resolve_call_fq(dn, mod).split(".")
            if parts[-1] in _COLLECTIVES and (
                    len(parts) == 1 or parts[-2] == "lax"):
                out.append(Finding(
                    "COLLECTIVE-SITE", mod.path, n.lineno,
                    f"{fn_at(n.lineno)}:{parts[-1]}",
                    f"raw lax.{parts[-1]} outside engine/communication.py "
                    f"— it escapes the collective manifest; use the "
                    f"AllReduce/AllGather stages or ctx.all_reduce_sum, "
                    f"or baseline with a justification"))
    return out


# ---------------------------------------------------------------------------
# HOST-CALLBACK-FREE
# ---------------------------------------------------------------------------

_CALLBACKS = frozenset({"io_callback", "pure_callback"})


def rule_host_callback_free(index: ModuleIndex, config: LintConfig,
                            registry) -> List[Finding]:
    """Host callbacks (``io_callback``/``pure_callback``/
    ``jax.debug.print``/``jax.debug.callback``) inside compiled-path
    modules put a host round trip INSIDE the device program — the
    dispatch-floor class every perf PR fought. The durability tests pin
    'no host callbacks in compiled programs' at the HLO level for the
    engine; this rule holds it at the source level for every
    compiled-path module."""
    out: List[Finding] = []
    for mod in index.by_path.values():
        if not any(fnmatch.fnmatch(mod.path, g)
                   for g in config.compiled_path_globs):
            continue
        fn_at = _enclosing_fn_finder(mod)
        for n in ast.walk(mod.tree):
            if not isinstance(n, ast.Call):
                continue
            dn = dotted_name(n.func)
            if not dn:
                continue
            parts = _resolve_call_fq(dn, mod).split(".")
            hit = None
            if parts[-1] in _CALLBACKS:
                hit = parts[-1]
            elif len(parts) >= 2 and parts[-2] == "debug" \
                    and parts[-1] in ("print", "callback"):
                hit = f"debug.{parts[-1]}"
            if hit:
                out.append(Finding(
                    "HOST-CALLBACK-FREE", mod.path, n.lineno,
                    f"{fn_at(n.lineno)}:{hit}",
                    f"{dn} inside compiled-path module {mod.path} — a "
                    f"host callback in a compiled program serializes "
                    f"the device on the host round trip"))
    return out


# ---------------------------------------------------------------------------
# composition
# ---------------------------------------------------------------------------

RULES = (
    rule_env_key_fold,
    rule_traced_capture,
    rule_donate_use_after,
    rule_collective_site,
    rule_host_callback_free,
)


def run_lint(root: Optional[str] = None,
             config: Optional[LintConfig] = None,
             registry=None,
             index: Optional[ModuleIndex] = None) -> List[Finding]:
    """Run all five rules; returns findings sorted by (file, line)."""
    from .analyzer import load_flag_registry
    root = root or repo_root()
    config = config or default_config()
    if registry is None:
        registry = load_flag_registry()
    if index is None:
        index = ModuleIndex.build(root, config.package_dirs)
    # a file that failed to parse is itself a finding: the rules never
    # saw it, so "clean" would be a lie
    findings: List[Finding] = list(index.parse_errors)
    for rule in RULES:
        findings.extend(rule(index, config, registry))
    return sorted(findings, key=lambda f: (f.file, f.line, f.rule, f.ident))
