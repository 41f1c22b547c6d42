"""``recompile``: the static/trace-time gate behind tests/test_recompiles.py.

A hot-path XLA recompile is a multi-hundred-ms p99 cliff; the engine's
whole shape discipline (batch buckets, packed rows, two step variants)
exists to prevent one. Two hazards this rule catches before a soak does:

- **jaxpr drift** — a jitted kernel whose trace depends on mutable Python
  state (a module counter, a rebound closure scalar, wall clock read at
  trace time): two traces under the SAME canonical config and the SAME
  input shapes must produce byte-identical jaxprs. Drift means either a
  recompile per invocation (if the varying value reaches the cache key)
  or — worse — a silently frozen stale value baked into the executable.
- **Python-scalar closure captures** — a function handed to ``jax.jit``
  that closes over a loop variable or a rebound local: the classic
  late-binding bug (`for k: fns.append(jit(lambda x: x * k))`) traces
  every entry with the LAST k. Detected statically over the kernel
  modules.

The dynamic half builds each kernel family under a small canonical config
(CPU backend, trace only — nothing executes) and compares
``jax.make_jaxpr`` output across two value-varied, shape-identical
invocations.
"""

from __future__ import annotations

import ast

from matchmaking_tpu.analysis.core import Finding, SourceFile, dotted_name

RULE = "recompile"

#: Modules whose jit sites get the static closure-capture scan.
KERNEL_MODULES = (
    "matchmaking_tpu/engine/kernels.py",
    "matchmaking_tpu/engine/role_kernels.py",
    "matchmaking_tpu/engine/pallas_kernels.py",
    "matchmaking_tpu/engine/teams.py",
    "matchmaking_tpu/engine/sharded.py",
)


# ---- static: closure captures ----------------------------------------------

def _is_jit_expr(node: ast.AST) -> bool:
    """True for ``jax.jit`` / ``jit`` / ``(functools.)partial(jax.jit, ...)``."""
    name = dotted_name(node)
    if name in ("jax.jit", "jit"):
        return True
    if isinstance(node, ast.Call) and dotted_name(node.func).endswith(
            "partial") and node.args:
        return _is_jit_expr(node.args[0])
    return False


def _bound_names(fn: ast.AST) -> set[str]:
    bound: set[str] = set()
    args = fn.args
    for a in (*args.posonlyargs, *args.args, *args.kwonlyargs):
        bound.add(a.arg)
    if args.vararg:
        bound.add(args.vararg.arg)
    if args.kwarg:
        bound.add(args.kwarg.arg)
    for node in ast.walk(fn):
        if isinstance(node, ast.Name) and isinstance(node.ctx, ast.Store):
            bound.add(node.id)
        elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                               ast.ClassDef)):
            bound.add(node.name)
        elif isinstance(node, (ast.Import, ast.ImportFrom)):
            for alias in node.names:
                bound.add((alias.asname or alias.name).split(".")[0])
    return bound


def _loaded_names(fn: ast.AST) -> set[str]:
    body = fn.body if isinstance(fn.body, list) else [fn.body]
    loads: set[str] = set()
    for stmt in body:
        for node in ast.walk(stmt):
            if isinstance(node, ast.Name) and isinstance(node.ctx, ast.Load):
                loads.add(node.id)
    return loads


def _module_level_names(tree: ast.Module) -> set[str]:
    names: set[str] = set()
    for node in tree.body:
        for sub in ast.walk(node):
            if isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef,
                                ast.ClassDef)):
                names.add(sub.name)
                break  # don't descend into bodies for module names
        if isinstance(node, (ast.Import, ast.ImportFrom)):
            for alias in node.names:
                names.add((alias.asname or alias.name).split(".")[0])
        elif isinstance(node, (ast.Assign, ast.AnnAssign, ast.AugAssign)):
            for sub in ast.walk(node):
                if isinstance(sub, ast.Name) and isinstance(sub.ctx,
                                                            ast.Store):
                    names.add(sub.id)
    return names


class _JitSiteScanner(ast.NodeVisitor):
    """Finds jitted functions and checks their free variables against the
    enclosing function scopes for loop targets / multiple rebinds."""

    def __init__(self, sf: SourceFile):
        self.sf = sf
        self.findings: list[Finding] = []
        self._module_names = _module_level_names(sf.tree)
        self._fn_stack: list[ast.AST] = []

    def _check_captures(self, fn: ast.AST, site_line: int,
                        label: str) -> None:
        import builtins

        free = (_loaded_names(fn) - _bound_names(fn) - self._module_names
                - set(dir(builtins)) - {"self"})
        if not free:
            return
        for name in sorted(free):
            hazard = self._capture_hazard(name)
            if hazard:
                self.findings.append(Finding(
                    RULE, self.sf.path, site_line,
                    f"jitted {label} captures Python variable {name!r} "
                    f"{hazard}: bind it via functools.partial / a default "
                    f"arg, or pass it as a traced argument",
                    label))

    def _capture_hazard(self, name: str) -> str | None:
        """Why capturing ``name`` from an enclosing scope is dangerous
        (None when it's bound exactly once — effectively a constant)."""
        for fn in reversed(self._fn_stack):
            binds = 0
            for node in ast.walk(fn):
                if isinstance(node, (ast.For, ast.AsyncFor)):
                    for t in ast.walk(node.target):
                        if isinstance(t, ast.Name) and t.id == name:
                            return "bound by a for-loop (late binding: " \
                                   "every trace sees the LAST value)"
                if isinstance(node, ast.AugAssign) and isinstance(
                        node.target, ast.Name) and node.target.id == name:
                    return "mutated with augmented assignment in the " \
                           "enclosing scope"
                if isinstance(node, ast.Assign):
                    for tgt in node.targets:
                        for t in ast.walk(tgt):
                            if isinstance(t, ast.Name) and t.id == name:
                                binds += 1
            if binds > 1:
                return "rebound more than once in the enclosing scope"
            if binds == 1 or name in {
                a.arg for a in (*fn.args.posonlyargs, *fn.args.args,
                                *fn.args.kwonlyargs)}:
                return None  # bound once here: a per-factory constant
        return None

    def _enter_fn(self, node):
        for deco in getattr(node, "decorator_list", ()):
            if _is_jit_expr(deco):
                self._check_captures(node, node.lineno, node.name)
        self._fn_stack.append(node)
        self.generic_visit(node)
        self._fn_stack.pop()

    visit_FunctionDef = _enter_fn
    visit_AsyncFunctionDef = _enter_fn

    def visit_Call(self, node: ast.Call) -> None:
        target = None
        if _is_jit_expr(node.func) and node.args:
            target = node.args[0]
        elif (dotted_name(node.func).endswith("named_jit")
              and len(node.args) > 1):
            target = node.args[1]  # named_jit(name, fn, **jit_kwargs)
        if target is not None:
            if isinstance(target, ast.Lambda):
                self._check_captures(target, node.lineno,
                                     f"lambda@{node.lineno}")
            # jit(name)/jit(self._method): nothing lexical to scan here —
            # the def site is scanned when its decorators are walked.
        self.generic_visit(node)


def check_static(sources: list[SourceFile]) -> list[Finding]:
    findings: list[Finding] = []
    for sf in sources:
        if sf.path in KERNEL_MODULES:
            v = _JitSiteScanner(sf)
            v.visit(sf.tree)
            findings.extend(v.findings)
    return findings


# ---- dynamic: jaxpr drift under canonical configs --------------------------

def _canonical_pool(ks, variant: int):
    import jax.numpy as jnp
    import numpy as np

    from matchmaking_tpu.core.pool import PlayerPool

    init = PlayerPool.empty_device_arrays(ks.capacity)
    for name, dt in getattr(ks, "extra_pool_fields", {}).items():
        init[name] = np.zeros(ks.capacity, dt)
    rng = np.random.default_rng(101 + variant)
    n = max(1, ks.capacity // 2)
    for col, vals in (
        ("rating", rng.normal(1500, 150, n)),
        ("rd", rng.uniform(30, 200, n)),
        ("threshold", np.full(n, 90.0 + variant)),
        ("enqueue_t", rng.uniform(0, 3, n)),
    ):
        if col in init:
            init[col][:n] = vals.astype(init[col].dtype)
    if "active" in init:
        init["active"][:n] = True
    return {k: jnp.asarray(v) for k, v in init.items()}


def _canonical_packed(ks, b: int, variant: int):
    import jax.numpy as jnp
    import numpy as np

    rng = np.random.default_rng(33 + variant)
    rows = 9 + (1 if getattr(ks, "is_role", False) else 0)
    packed = rng.uniform(0.0, 1.0, (rows, b)).astype(np.float32)
    packed[0] = ks.capacity  # slot row: all padding lanes
    return jnp.asarray(packed)


def _trace_once(fn, args) -> str:
    """One FRESH trace of ``fn``. jax caches traces on (callable, avals) —
    both inside jit wrappers and inside make_jaxpr itself — so a naive
    second make_jaxpr returns the FIRST trace and drift is structurally
    invisible. Unwrap the jit wrapper to the raw Python callable and trace
    it through a fresh lambda per invocation (distinct callable identity →
    cache miss → the Python body actually re-runs)."""
    import jax

    raw = getattr(fn, "__wrapped__", fn)
    return str(jax.make_jaxpr(lambda *a: raw(*a))(*args))


def _drift(fn, make_args, name: str, context: str,
           findings: list[Finding]) -> None:
    try:
        j0 = _trace_once(fn, make_args(0))
        j1 = _trace_once(fn, make_args(1))
    except Exception as e:  # tracing itself failed: surface, don't crash
        findings.append(Finding(
            RULE, context, 0,
            f"could not trace {name}: {type(e).__name__}: {e}", name))
        return
    if j0 != j1:
        findings.append(Finding(
            RULE, context, 0,
            f"jaxpr drift in {name}: two same-shape traces under the "
            f"canonical config differ — the kernel's trace depends on "
            f"mutable Python state (recompile or stale-constant hazard)",
            name))


def check_dynamic() -> list[Finding]:
    """Trace every kernel family twice under canonical small configs and
    compare jaxprs. Trace-only — nothing executes, so whatever backend the
    host process configured is fine (the CLI pins CPU for itself in
    engine.main; pytest gets conftest's CPU mesh). No process-global
    state is mutated here: the lint node runs inside tier-1, and flipping
    JAX_PLATFORMS mid-suite would silently re-platform every later test."""
    findings: list[Finding] = []

    from matchmaking_tpu.engine.kernels import kernel_set

    for label, kwargs in (
        ("1v1", dict(glicko2=False, widen_per_sec=5.0)),
        ("1v1-glicko2", dict(glicko2=True, widen_per_sec=0.0)),
    ):
        ks = kernel_set(capacity=64, top_k=4, pool_block=32,
                        max_threshold=400.0, pair_rounds=4, **kwargs)
        ctx = "matchmaking_tpu/engine/kernels.py"
        b = 16
        for name in ("search_step_packed", "search_step_packed_nofilter",
                     "search_step_packed_rescan", "admit_packed"):
            fn = getattr(ks, name, None)
            if fn is None:
                continue
            _drift(fn,
                   lambda v: (_canonical_pool(ks, v),
                              _canonical_packed(ks, b, v)),
                   f"kernels.{label}.{name}", ctx, findings)
        evict = getattr(ks, "evict", None)
        if evict is not None:
            import jax.numpy as jnp
            import numpy as np

            def evict_args(v, ks=ks):
                ev = np.full(ks.evict_bucket, ks.capacity, np.int32)
                ev[0] = v  # vary content, not shape
                return (_canonical_pool(ks, v), jnp.asarray(ev))

            _drift(evict, evict_args, f"kernels.{label}.evict", ctx,
                   findings)

    from matchmaking_tpu.engine.role_kernels import role_kernel_set

    rks = role_kernel_set(capacity=32, team_size=2,
                          role_slots=("tank", "dps"), widen_per_sec=5.0,
                          max_threshold=400.0, max_matches=8, rounds=4)
    ctx = "matchmaking_tpu/engine/role_kernels.py"
    for name in ("search_step_packed", "admit_packed"):
        fn = getattr(rks, name, None)
        if fn is None:
            continue
        _drift(fn,
               lambda v: (_canonical_pool(rks, v),
                          _canonical_packed(rks, 16, v)),
               f"role_kernels.{name}", ctx, findings)

    try:
        from matchmaking_tpu.engine.pallas_kernels import (
            pack_batch_rows,
            pack_pool_rows,
            pallas_block_best,
        )
    except ImportError:
        return findings  # pallas unavailable in this build: skip, not fail

    import functools

    import jax.numpy as jnp
    import numpy as np

    P, B = 1024, 64
    pb = functools.partial(
        pallas_block_best, super_blk=256, sub_blk=2048, b_tile=256,
        capacity=P, glicko2=False, widen_per_sec=5.0, max_threshold=400.0,
        interpret=True)

    def pallas_args(v):
        from matchmaking_tpu.core.pool import PlayerPool

        rng = np.random.default_rng(55 + v)
        arrs = PlayerPool.empty_device_arrays(P)
        n = P // 2
        arrs["rating"][:n] = rng.normal(1500, 200, n).astype(np.float32)
        arrs["rd"][:n] = rng.uniform(30, 200, n).astype(np.float32)
        arrs["threshold"][:n] = 100.0 + v
        arrs["active"][:n] = True
        pool = {k: jnp.asarray(x) for k, x in arrs.items()}
        batch = {
            "slot": jnp.asarray(np.arange(B, dtype=np.int32)),
            "rating": jnp.asarray(
                rng.normal(1500, 200, B).astype(np.float32)),
            "rd": jnp.asarray(rng.uniform(30, 200, B).astype(np.float32)),
            "region": jnp.zeros(B, jnp.int32),
            "mode": jnp.zeros(B, jnp.int32),
            "threshold": jnp.full(B, 100.0, jnp.float32),
            "enqueue_t": jnp.asarray(
                rng.uniform(0, 3, B).astype(np.float32)),
            "valid": jnp.ones(B, bool),
        }
        q_thr_eff = jnp.full(B, 100.0 + v, jnp.float32)
        return (pack_pool_rows(pool), pack_batch_rows(batch, q_thr_eff),
                float(1.5))

    _drift(pb, pallas_args, "pallas_block_best",
           "matchmaking_tpu/engine/pallas_kernels.py", findings)
    return findings


def check(sources: list[SourceFile],
          dynamic: bool = True) -> list[Finding]:
    findings = check_static(sources)
    if dynamic:
        findings.extend(check_dynamic())
    return findings
