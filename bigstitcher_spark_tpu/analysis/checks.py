"""The core ``bst lint`` invariant checks (pure stdlib ``ast``).

Each check is a function ``(files: list[FileCtx]) -> list[Finding]`` over
the whole parsed package, so cross-file invariants (the lock-order
graph, the metric-name registry, the config-knob declarations) see every
module at once. All checks are approximations by design — they encode
the conventions the codebase actually follows, and anything cleverer
than the convention earns a ``# bst-lint: off=<check>`` suppression with
the reasoning next to it.

The concurrency-discipline suite (lock-order, blocking-under-lock,
thread-spawn, cancel-coverage, socket-hygiene) lives in
``analysis/concurrency.py`` and registers into ``ALL_CHECKS`` below.

Checks
------
``host-sync``
    In ``ops/`` and ``models/``: flags blocking host conversions
    (``np.asarray``/``np.array``, ``float``/``int``/``bool``, ``.item()``/
    ``.tolist()``, ``if``/``while`` truthiness) applied to values that
    dataflow from ``jnp.``/``lax.``/``jax.device_put`` calls — the hidden
    device round-trips of ADVICE r5 #1. ``jax.device_get`` and
    ``.block_until_ready()`` are the allowlisted drain points: fetches
    must be explicit, so the reader (and the next reviewer) can see every
    sync on the hot path.

``lock-discipline``
    State mutated at least once inside a ``with <lock>:`` block is
    lock-guarded; mutating the same attribute/global outside any lock
    block (outside ``__init__`` and ``*_locked`` helpers, which assume
    the caller holds it) is a finding. Acquisition ORDER is the
    ``lock-order`` check's job (concurrency.py): it builds the whole
    interprocedural graph rather than matching single-file pairs.

``config-registry``
    Bans raw ``os.environ``/``os.getenv`` access to ``BST_*`` names
    anywhere outside ``config.py``, and checks every name passed to
    ``config.get*()`` is declared in the registry.

``env-mutation``
    Bans MUTATING the process environment for ``BST_*`` names anywhere in
    the package, ``config.py`` included (assignment, ``del``,
    ``setdefault``/``pop``/``update``, ``os.putenv``). One process now
    hosts many jobs (``bst serve``): an env write from one job's code path
    leaks into every concurrent job and the daemon itself. Per-job
    configuration goes through ``config.overrides()`` — a contextvars
    layer the worker threads inherit — never the shared environment.

``metric-name``
    Every ``bst_*`` string literal in the package must be declared in
    ``observe/metric_names.py`` (a typo'd counter otherwise reports zero
    forever), metric constructors must be called with literal names, and
    the registry itself must declare each name exactly once.

``span-name``
    The trace/span twin of ``metric-name``: every name passed to
    ``profiling.span`` / ``trace.instant`` / ``trace.record`` must be a
    literal declared once in ``observe/metric_names.py``'s ``SPANS``
    table. Dynamic span-name construction is banned outright — a
    constructed name fractures both the span aggregates and the
    flight-recorder timeline into unmergeable series; dynamic identity
    (device, block offset, pair id, bytes) belongs in the attribution
    kwargs.
"""

from __future__ import annotations

import ast
import re
from dataclasses import dataclass, field


@dataclass(frozen=True)
class Finding:
    check: str
    path: str          # posix relpath from the scanned root
    line: int
    message: str
    snippet: str       # stripped source line — the stable baseline key

    @property
    def key(self) -> str:
        return f"{self.check}|{self.path}|{self.snippet}"

    def render(self) -> str:
        return f"{self.path}:{self.line}: [{self.check}] {self.message}"


@dataclass
class FileCtx:
    relpath: str
    tree: ast.Module
    lines: list[str] = field(default_factory=list)

    def snippet(self, lineno: int) -> str:
        if 1 <= lineno <= len(self.lines):
            return self.lines[lineno - 1].strip()
        return ""

    def finding(self, check: str, node: ast.AST, message: str) -> Finding:
        line = getattr(node, "lineno", 1)
        return Finding(check, self.relpath, line, message, self.snippet(line))


def dotted(node: ast.AST) -> str | None:
    """``a.b.c`` for Name/Attribute chains; ``__import__("os").x`` resolves
    the base to ``os`` (the inline-import idiom the analyzer must see
    through, or the ban it enforces has a one-call escape hatch)."""
    if isinstance(node, ast.Name):
        return node.id
    if isinstance(node, ast.Attribute):
        base = dotted(node.value)
        return f"{base}.{node.attr}" if base else None
    if (isinstance(node, ast.Call) and isinstance(node.func, ast.Name)
            and node.func.id == "__import__" and node.args
            and isinstance(node.args[0], ast.Constant)
            and isinstance(node.args[0].value, str)):
        return node.args[0].value
    return None


# --------------------------------------------------------------------------
# host-sync
# --------------------------------------------------------------------------

_TAINT_PREFIXES = ("jnp.", "lax.", "jax.numpy.", "jax.lax.")
_TAINT_EXACT = {"jax.device_put"}
_DRAIN_EXACT = {"jax.device_get", "device_get", "profiling.device_sync"}
# reading these never leaves the host / never forces a device sync
_NEUTRAL_ATTRS = {"shape", "dtype", "ndim", "size", "nbytes", "itemsize",
                  "sharding", "device", "devices", "weak_type", "aval"}
_NP_SINKS = {"np.asarray", "np.array", "np.ascontiguousarray",
             "numpy.asarray", "numpy.array", "numpy.ascontiguousarray"}
_BUILTIN_SINKS = {"float", "int", "bool"}
_METHOD_SINKS = {"item", "tolist"}
_HOST_SYNC_SCOPES = ("ops/", "models/")


class _TaintEnv:
    def __init__(self, ops_aliases: frozenset[str] = frozenset(),
                 ops_fns: frozenset[str] = frozenset()):
        self.tainted: set[str] = set()
        # names bound to ops kernel modules (``from ..ops import fusion as
        # F``) and functions imported straight from them: the kernel layer
        # returns DEVICE arrays, so its results are taint sources — the
        # exact provenance of the ADVICE r5 blocking-fetch bug
        self.ops_aliases = ops_aliases
        self.ops_fns = ops_fns

    def mark(self, name: str, on: bool) -> None:
        (self.tainted.add if on else self.tainted.discard)(name)


def _ops_imports(ctx: FileCtx) -> tuple[frozenset[str], frozenset[str]]:
    """(module aliases, directly-imported function names) that resolve into
    the ops kernel package, from this file's import statements."""
    aliases: set[str] = set()
    fns: set[str] = set()
    in_ops = ctx.relpath.startswith("ops/")
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.ImportFrom):
            continue
        mod = node.module or ""
        if mod == "ops" or mod.endswith(".ops"):
            for a in node.names:           # from ..ops import fusion as F
                aliases.add(a.asname or a.name)
        elif ("ops." in mod or mod.startswith("ops")
              or (in_ops and node.level == 1 and mod)):
            # from ..ops.fusion import fuse_block / ops-internal siblings
            for a in node.names:
                fns.add(a.asname or a.name)
    return frozenset(aliases), frozenset(fns)


def _expr_tainted(e: ast.AST, env: _TaintEnv) -> bool:
    if isinstance(e, ast.Name):
        return e.id in env.tainted
    if isinstance(e, ast.Call):
        d = dotted(e.func)
        if d in _DRAIN_EXACT:
            return False
        if isinstance(e.func, ast.Attribute):
            if e.func.attr == "block_until_ready":
                return False
            # method on a device value returns a device value (.astype,
            # .reshape, .sum, ...) — neutral attrs are handled below
            if _expr_tainted(e.func.value, env):
                return True
        if d and (d.startswith(_TAINT_PREFIXES) or d in _TAINT_EXACT):
            return True
        if d and d.split(".", 1)[0] in env.ops_aliases:
            return True        # F.fuse_block_shift(...) and friends
        if isinstance(e.func, ast.Name) and (e.func.id in env.ops_fns
                                             or e.func.id in env.tainted):
            # directly-imported kernel fn, or calling a callable a kernel
            # factory returned (fuser = F.make_...(); fuser(...))
            return True
        return False
    if isinstance(e, ast.Attribute):
        if e.attr in _NEUTRAL_ATTRS:
            return False
        return _expr_tainted(e.value, env)
    if isinstance(e, ast.Subscript):
        return _expr_tainted(e.value, env)
    if isinstance(e, ast.BinOp):
        return _expr_tainted(e.left, env) or _expr_tainted(e.right, env)
    if isinstance(e, ast.UnaryOp):
        return _expr_tainted(e.operand, env)
    if isinstance(e, ast.Compare):
        # identity tests (`x is None`) never touch device values — they
        # compare references on the host
        if all(isinstance(op, (ast.Is, ast.IsNot)) for op in e.ops):
            return False
        return (_expr_tainted(e.left, env)
                or any(_expr_tainted(c, env) for c in e.comparators))
    if isinstance(e, ast.BoolOp):
        return any(_expr_tainted(v, env) for v in e.values)
    if isinstance(e, ast.IfExp):
        return _expr_tainted(e.body, env) or _expr_tainted(e.orelse, env)
    if isinstance(e, (ast.Tuple, ast.List)):
        return any(_expr_tainted(v, env) for v in e.elts)
    if isinstance(e, ast.Starred):
        return _expr_tainted(e.value, env)
    if isinstance(e, ast.NamedExpr):
        return _expr_tainted(e.value, env)
    return False


def _sink_findings(e: ast.AST, env: _TaintEnv, ctx: FileCtx,
                   out: list[Finding]) -> None:
    """Detect conversion sinks in one expression tree (current env)."""
    for node in ast.walk(e):
        if not isinstance(node, ast.Call) or not node.args:
            continue
        d = dotted(node.func)
        arg0 = node.args[0]
        if d in _NP_SINKS and _expr_tainted(arg0, env):
            out.append(ctx.finding(
                "host-sync", node,
                f"blocking host fetch: {d}() on a value that dataflows "
                f"from a jax call — fetch via jax.device_get at an "
                f"explicit drain point"))
        elif (isinstance(node.func, ast.Name)
              and node.func.id in _BUILTIN_SINKS
              and _expr_tainted(arg0, env)):
            out.append(ctx.finding(
                "host-sync", node,
                f"blocking host fetch: {node.func.id}() on a device "
                f"value — jax.device_get first (or keep it on device)"))
    for node in ast.walk(e):
        if (isinstance(node, ast.Call) and not node.args
                and isinstance(node.func, ast.Attribute)
                and node.func.attr in _METHOD_SINKS
                and _expr_tainted(node.func.value, env)):
            out.append(ctx.finding(
                "host-sync", node,
                f".{node.func.attr}() on a device value blocks on the "
                f"device — jax.device_get at an explicit drain point"))


def _assign_taint(target: ast.AST, value_tainted: bool,
                  env: _TaintEnv) -> None:
    if isinstance(target, ast.Name):
        env.mark(target.id, value_tainted)
    elif isinstance(target, (ast.Tuple, ast.List)):
        for t in target.elts:
            _assign_taint(t, value_tainted, env)
    elif isinstance(target, ast.Starred):
        _assign_taint(target.value, value_tainted, env)
    # attribute/subscript targets: no name-level tracking


def _walk_function(fn: ast.AST, ctx: FileCtx, out: list[Finding],
                   imports: tuple[frozenset, frozenset]) -> None:
    env = _TaintEnv(*imports)

    def stmt(s: ast.stmt) -> None:
        if isinstance(s, (ast.FunctionDef, ast.AsyncFunctionDef)):
            # fresh env for nested defs
            _walk_function(s, ctx, out, (env.ops_aliases, env.ops_fns))
            return
        if isinstance(s, ast.Assign):
            _sink_findings(s.value, env, ctx, out)
            tainted = _expr_tainted(s.value, env)
            if (len(s.targets) == 1 and isinstance(s.targets[0], ast.Tuple)
                    and isinstance(s.value, ast.Tuple)
                    and len(s.targets[0].elts) == len(s.value.elts)):
                for t, v in zip(s.targets[0].elts, s.value.elts):
                    _assign_taint(t, _expr_tainted(v, env), env)
            else:
                for t in s.targets:
                    _assign_taint(t, tainted, env)
            return
        if isinstance(s, ast.AnnAssign) and s.value is not None:
            _sink_findings(s.value, env, ctx, out)
            _assign_taint(s.target, _expr_tainted(s.value, env), env)
            return
        if isinstance(s, ast.AugAssign):
            _sink_findings(s.value, env, ctx, out)
            if isinstance(s.target, ast.Name):
                env.mark(s.target.id,
                         s.target.id in env.tainted
                         or _expr_tainted(s.value, env))
            return
        if isinstance(s, ast.Return) and s.value is not None:
            _sink_findings(s.value, env, ctx, out)
            return
        if isinstance(s, ast.Expr):
            _sink_findings(s.value, env, ctx, out)
            return
        if isinstance(s, (ast.If, ast.While)):
            _sink_findings(s.test, env, ctx, out)
            if _expr_tainted(s.test, env):
                out.append(ctx.finding(
                    "host-sync", s.test,
                    "implicit host sync: truthiness of a device value — "
                    "jax.device_get (or bool(jax.device_get(...))) at an "
                    "explicit drain point"))
            for b in (*s.body, *s.orelse):
                stmt(b)
            return
        if isinstance(s, ast.Assert):
            _sink_findings(s.test, env, ctx, out)
            if _expr_tainted(s.test, env):
                out.append(ctx.finding(
                    "host-sync", s.test,
                    "implicit host sync: assert on a device value"))
            return
        if isinstance(s, ast.For):
            _sink_findings(s.iter, env, ctx, out)
            _assign_taint(s.target, _expr_tainted(s.iter, env), env)
            for b in (*s.body, *s.orelse):
                stmt(b)
            return
        if isinstance(s, ast.With):
            for item in s.items:
                _sink_findings(item.context_expr, env, ctx, out)
                if item.optional_vars is not None:
                    _assign_taint(item.optional_vars,
                                  _expr_tainted(item.context_expr, env), env)
            for b in s.body:
                stmt(b)
            return
        if isinstance(s, ast.Try):
            for b in (*s.body, *[h for hh in s.handlers for h in hh.body],
                      *s.orelse, *s.finalbody):
                stmt(b)
            return
        # other statements: still scan contained expressions for sinks
        for child in ast.iter_child_nodes(s):
            if isinstance(child, ast.expr):
                _sink_findings(child, env, ctx, out)

    for s in fn.body:
        stmt(s)


def check_host_sync(files: list[FileCtx]) -> list[Finding]:
    out: list[Finding] = []
    for ctx in files:
        if not ctx.relpath.startswith(_HOST_SYNC_SCOPES):
            continue
        imports = _ops_imports(ctx)
        for node in ctx.tree.body:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                _walk_function(node, ctx, out, imports)
            elif isinstance(node, ast.ClassDef):
                for sub in node.body:
                    if isinstance(sub, (ast.FunctionDef,
                                        ast.AsyncFunctionDef)):
                        _walk_function(sub, ctx, out, imports)
    return out


# --------------------------------------------------------------------------
# lock-discipline
# --------------------------------------------------------------------------

_MUTATORS = {"append", "extend", "insert", "add", "update", "setdefault",
             "pop", "popitem", "remove", "discard", "clear", "move_to_end",
             "appendleft", "popleft"}
_LOCK_RE = re.compile(r"lock", re.IGNORECASE)
_EXEMPT_FNS = {"__init__", "__new__", "__post_init__"}


def _is_lock_expr(e: ast.AST) -> str | None:
    """The lock's dotted text when ``e`` names a lock (last path component
    contains 'lock'), else None."""
    d = dotted(e)
    if d and _LOCK_RE.search(d.rsplit(".", 1)[-1]):
        return d
    return None


def _mutation_base(node: ast.AST) -> ast.AST | None:
    """The object being mutated: ``self.x[...] = v`` -> self.x,
    ``x.append(v)`` -> x. Returns the base expression node."""
    if isinstance(node, (ast.Assign, ast.AugAssign, ast.AnnAssign)):
        targets = (node.targets if isinstance(node, ast.Assign)
                   else [node.target])
        for t in targets:
            base = t
            while isinstance(base, ast.Subscript):
                base = base.value
            if isinstance(base, (ast.Attribute, ast.Name)):
                # plain rebinding of a local name is not shared-state
                # mutation; subscript/attribute writes are
                if isinstance(t, ast.Subscript) or isinstance(
                        base, ast.Attribute):
                    return base
                if isinstance(base, ast.Name):
                    return base    # caller filters to module globals
    if isinstance(node, ast.Delete):
        for t in node.targets:
            base = t
            while isinstance(base, ast.Subscript):
                base = base.value
            if isinstance(base, (ast.Attribute, ast.Name)):
                return base
    if (isinstance(node, ast.Expr) and isinstance(node.value, ast.Call)
            and isinstance(node.value.func, ast.Attribute)
            and node.value.func.attr in _MUTATORS):
        return node.value.func.value
    return None


def _target_key(base: ast.AST, class_name: str | None,
                module_globals: set[str]) -> str | None:
    d = dotted(base)
    if d is None:
        return None
    if d.startswith("self."):
        return f"{class_name or ''}:{d}" if class_name else None
    root = d.split(".", 1)[0]
    if root in module_globals:
        return f"<module>:{d}"
    return None


@dataclass
class _MutSite:
    key: str
    node: ast.AST
    in_lock: bool
    fn_name: str


def _module_globals(tree: ast.Module) -> set[str]:
    names: set[str] = set()
    for node in tree.body:
        if isinstance(node, ast.Assign):
            for t in node.targets:
                if isinstance(t, ast.Name):
                    names.add(t.id)
        elif isinstance(node, ast.AnnAssign) and isinstance(
                node.target, ast.Name):
            names.add(node.target.id)
    return names


def check_lock_discipline(files: list[FileCtx]) -> list[Finding]:
    out: list[Finding] = []
    for ctx in files:
        mglobals = _module_globals(ctx.tree)
        sites: list[_MutSite] = []

        def scan_fn(fn, class_name: str | None) -> None:
            exempt = (fn.name in _EXEMPT_FNS
                      or fn.name.endswith("_locked"))
            lock_stack: list[str] = []

            def walk(stmts) -> None:
                for s in stmts:
                    if isinstance(s, (ast.FunctionDef,
                                      ast.AsyncFunctionDef)):
                        scan_fn(s, class_name)
                        continue
                    if isinstance(s, ast.With):
                        lock_texts = [t for t in
                                      (_is_lock_expr(i.context_expr)
                                       for i in s.items) if t]
                        lock_stack.extend(lock_texts)
                        walk(s.body)
                        for _ in lock_texts:
                            lock_stack.pop()
                        continue
                    base = _mutation_base(s)
                    if base is not None and not exempt:
                        key = _target_key(base, class_name, mglobals)
                        if key is not None:
                            sites.append(_MutSite(
                                key, s, bool(lock_stack), fn.name))
                    for child in ast.iter_child_nodes(s):
                        if isinstance(child, ast.stmt):
                            walk([child])
                        elif hasattr(child, "body") and isinstance(
                                getattr(child, "body", None), list):
                            walk(child.body)
                    # bodies of If/For/While/Try reached via iter_child
                    # statements above

            walk(fn.body)

        for node in ctx.tree.body:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                scan_fn(node, None)
            elif isinstance(node, ast.ClassDef):
                for sub in node.body:
                    if isinstance(sub, (ast.FunctionDef,
                                        ast.AsyncFunctionDef)):
                        scan_fn(sub, node.name)

        guarded: dict[str, _MutSite] = {}
        for site in sites:
            if site.in_lock and site.key not in guarded:
                guarded[site.key] = site
        for site in sites:
            if not site.in_lock and site.key in guarded:
                g = guarded[site.key]
                name = site.key.split(":", 1)[1]
                out.append(ctx.finding(
                    "lock-discipline", site.node,
                    f"{name} is mutated here without the lock that guards "
                    f"it in {g.fn_name}() (line {g.node.lineno}); hold the "
                    f"lock or rename the helper *_locked"))
    return out


# --------------------------------------------------------------------------
# config-registry
# --------------------------------------------------------------------------

_ENV_GETTERS = {"os.environ.get", "environ.get", "os.getenv", "getenv",
                "os.environ.setdefault", "os.environ.pop",
                "environ.setdefault", "environ.pop"}
_ENV_SUBSCRIPTS = {"os.environ", "environ"}
_CONFIG_GETTERS = {"config.get", "config.get_bool", "config.get_int",
                   "config.get_bytes", "config.get_str", "config.get_float",
                   "config.raw_value", "config.source"}
_CONFIG_FILE = "config.py"


def _declared_knobs(files: list[FileCtx]) -> set[str] | None:
    """Knob names declared via ``_knob("NAME", ...)`` in config.py, or
    None when the scanned tree has no config module (fixture runs)."""
    for ctx in files:
        if ctx.relpath == _CONFIG_FILE:
            names: set[str] = set()
            for node in ast.walk(ctx.tree):
                if (isinstance(node, ast.Call)
                        and isinstance(node.func, ast.Name)
                        and node.func.id == "_knob" and node.args
                        and isinstance(node.args[0], ast.Constant)
                        and isinstance(node.args[0].value, str)):
                    names.add(node.args[0].value)
            return names
    return None


def check_config_registry(files: list[FileCtx]) -> list[Finding]:
    out: list[Finding] = []
    declared = _declared_knobs(files)
    if declared is None:
        try:
            from .. import config as _config

            declared = set(_config.KNOBS)
        except Exception:
            declared = set()
    for ctx in files:
        if ctx.relpath == _CONFIG_FILE:
            continue
        for node in ast.walk(ctx.tree):
            key = None
            if isinstance(node, ast.Call):
                d = dotted(node.func)
                if d in _ENV_GETTERS and node.args and isinstance(
                        node.args[0], ast.Constant):
                    key = node.args[0].value
                elif (d in _CONFIG_GETTERS and node.args
                        and isinstance(node.args[0], ast.Constant)
                        and isinstance(node.args[0].value, str)):
                    if node.args[0].value not in declared:
                        out.append(ctx.finding(
                            "config-registry", node,
                            f"config knob {node.args[0].value!r} is not "
                            f"declared in config.py"))
                    continue
            elif isinstance(node, ast.Subscript):
                d = dotted(node.value)
                if d in _ENV_SUBSCRIPTS and isinstance(
                        node.slice, ast.Constant):
                    key = node.slice.value
            if isinstance(key, str) and key.startswith("BST_"):
                out.append(ctx.finding(
                    "config-registry", node,
                    f"raw environment access to {key} — read it through "
                    f"bigstitcher_spark_tpu.config (call-time, typed, "
                    f"documented)"))
    return out


# --------------------------------------------------------------------------
# env-mutation
# --------------------------------------------------------------------------

_ENV_MUTATORS = {"os.environ.setdefault", "environ.setdefault",
                 "os.environ.pop", "environ.pop",
                 "os.environ.update", "environ.update",
                 "os.putenv", "putenv"}


def _bst_const(node: ast.AST) -> str | None:
    if (isinstance(node, ast.Constant) and isinstance(node.value, str)
            and node.value.startswith("BST_")):
        return node.value
    return None


def check_env_mutation(files: list[FileCtx]) -> list[Finding]:
    """Flag every write to a ``BST_*`` process-environment name. Unlike
    config-registry (read hygiene, config.py exempt) this check has no
    exempt file: nothing in the package may mutate the shared env — the
    override layer (config.overrides) is the per-job mechanism."""
    out: list[Finding] = []
    msg = ("mutating the {name} process environment leaks across daemon "
           "jobs — use config.overrides() for per-job values")
    for ctx in files:
        for node in ast.walk(ctx.tree):
            if isinstance(node, (ast.Assign, ast.AugAssign, ast.AnnAssign,
                                 ast.Delete)):
                targets = (node.targets if isinstance(node, (ast.Assign,
                                                             ast.Delete))
                           else [node.target])
                for t in targets:
                    if (isinstance(t, ast.Subscript)
                            and dotted(t.value) in _ENV_SUBSCRIPTS):
                        name = _bst_const(t.slice)
                        if name:
                            out.append(ctx.finding(
                                "env-mutation", node,
                                msg.format(name=name)))
            elif isinstance(node, ast.Call):
                d = dotted(node.func)
                if d not in _ENV_MUTATORS or not node.args:
                    continue
                # environ.update takes a dict of names; the others take
                # the name first. setdefault/update/putenv WRITE;
                # environ.pop only reads-and-removes, but removal is
                # mutation too
                if (isinstance(node.args[0], ast.Dict)
                        and any(_bst_const(k) for k in node.args[0].keys)):
                    out.append(ctx.finding(
                        "env-mutation", node, msg.format(name="BST_*")))
                else:
                    name = _bst_const(node.args[0])
                    if name:
                        out.append(ctx.finding(
                            "env-mutation", node, msg.format(name=name)))
    return out


# --------------------------------------------------------------------------
# metric-name
# --------------------------------------------------------------------------

_METRIC_RE = re.compile(r"^bst_[a-z0-9]+(?:_[a-z0-9]+)*$")
_METRIC_REGISTRY_FILE = "observe/metric_names.py"
_METRIC_IMPL_FILE = "observe/metrics.py"
_METRIC_CTORS = {"counter", "gauge", "histogram"}


def _registry_names(files: list[FileCtx]) -> tuple[set[str], list[Finding]]:
    for ctx in files:
        if ctx.relpath == _METRIC_REGISTRY_FILE:
            names: set[str] = set()
            dupes: list[Finding] = []
            for node in ast.walk(ctx.tree):
                if isinstance(node, ast.Dict):
                    for k in node.keys:
                        if isinstance(k, ast.Constant) and isinstance(
                                k.value, str):
                            if k.value in names:
                                dupes.append(ctx.finding(
                                    "metric-name", k,
                                    f"metric {k.value!r} declared more "
                                    f"than once in the registry"))
                            names.add(k.value)
            return names, dupes
    try:
        from ..observe import metric_names as _mn

        return set(_mn.METRICS), []
    except Exception:
        return set(), []


def check_metric_names(files: list[FileCtx]) -> list[Finding]:
    declared, out = _registry_names(files)
    for ctx in files:
        if ctx.relpath in (_METRIC_REGISTRY_FILE, _METRIC_IMPL_FILE):
            continue
        for node in ast.walk(ctx.tree):
            if (isinstance(node, ast.Constant)
                    and isinstance(node.value, str)
                    and _METRIC_RE.match(node.value)
                    and node.value not in declared):
                out.append(ctx.finding(
                    "metric-name", node,
                    f"metric name {node.value!r} is not declared in "
                    f"observe/metric_names.py — typo'd series silently "
                    f"report zero"))
            if (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr in _METRIC_CTORS
                    and (dotted(node.func.value) or "").split(".")[-1]
                    in ("metrics", "_metrics")
                    and node.args
                    and not (isinstance(node.args[0], ast.Constant)
                             and isinstance(node.args[0].value, str))):
                out.append(ctx.finding(
                    "metric-name", node,
                    "dynamic metric name — construct series from literal "
                    "names declared in observe/metric_names.py"))
    return out


# --------------------------------------------------------------------------
# span-name
# --------------------------------------------------------------------------

# call sites that name a span/trace series: <module>.<fn> where the fn is
# a recorder entry point — matched by the LAST TWO dotted components so
# both `profiling.span(...)` and an aliased `_trace.instant(...)` resolve
_SPAN_FNS = {"span": ("profiling",),
             "instant": ("trace", "_trace"),
             "record": ("trace", "_trace")}
# the declaring/implementing modules are exempt (they manipulate names)
_SPAN_EXEMPT_FILES = {_METRIC_REGISTRY_FILE, "profiling.py",
                      "observe/trace.py"}


def _span_registry(files: list[FileCtx]) -> tuple[set[str], list[Finding]]:
    """Names declared in metric_names.SPANS (+ duplicate findings); falls
    back to the live registry when the scanned tree has no copy (fixture
    runs)."""
    for ctx in files:
        if ctx.relpath == _METRIC_REGISTRY_FILE:
            names: set[str] = set()
            dupes: list[Finding] = []
            for node in ctx.tree.body:
                # SPANS = {...} plain or annotated (SPANS: dict[...] = {...})
                target = (node.targets[0] if isinstance(node, ast.Assign)
                          and len(node.targets) == 1
                          else node.target if isinstance(node, ast.AnnAssign)
                          else None)
                if not (isinstance(target, ast.Name)
                        and target.id == "SPANS"
                        and isinstance(getattr(node, "value", None),
                                       ast.Dict)):
                    continue
                for k in node.value.keys:
                    if isinstance(k, ast.Constant) and isinstance(
                            k.value, str):
                        if k.value in names:
                            dupes.append(ctx.finding(
                                "span-name", k,
                                f"span {k.value!r} declared more than "
                                f"once in the SPANS registry"))
                        names.add(k.value)
            return names, dupes
    try:
        from ..observe import metric_names as _mn

        return set(_mn.declared_spans()), []
    except Exception:
        return set(), []


def check_span_names(files: list[FileCtx]) -> list[Finding]:
    declared, out = _span_registry(files)
    for ctx in files:
        if ctx.relpath in _SPAN_EXEMPT_FILES:
            continue
        for node in ast.walk(ctx.tree):
            if not (isinstance(node, ast.Call) and node.args):
                continue
            d = dotted(node.func)
            if not d:
                continue
            parts = d.split(".")
            if len(parts) < 2 or parts[-1] not in _SPAN_FNS \
                    or parts[-2] not in _SPAN_FNS[parts[-1]]:
                continue
            # trace.record's name is the SECOND positional (after ph)
            arg = node.args[1 if parts[-1] == "record"
                            and len(node.args) > 1 else 0]
            if not (isinstance(arg, ast.Constant)
                    and isinstance(arg.value, str)):
                out.append(ctx.finding(
                    "span-name", node,
                    "dynamic span name — span/trace names must be "
                    "literals declared in observe/metric_names.py SPANS; "
                    "put dynamic identity (device, item, bytes) in the "
                    "attribution kwargs"))
            elif arg.value not in declared:
                out.append(ctx.finding(
                    "span-name", node,
                    f"span name {arg.value!r} is not declared in "
                    f"observe/metric_names.py SPANS — a typo'd span "
                    f"silently forks the timeline and the aggregates"))
    return out


ALL_CHECKS = {
    "host-sync": check_host_sync,
    "lock-discipline": check_lock_discipline,
    "config-registry": check_config_registry,
    "env-mutation": check_env_mutation,
    "metric-name": check_metric_names,
    "span-name": check_span_names,
}
# the concurrency-discipline suite (analysis/concurrency.py) registers
# its five checks into ALL_CHECKS when imported; the package __init__
# imports it, so any `analysis.*` import sees the full table
