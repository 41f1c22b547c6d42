"""``device``: the jaxpr-level device-path audit.

The recompile rule (recompile.py) proves each kernel family's TRACE is
stable; this rule audits what the trace actually DOES.  Everything here is
trace-only — ``jax.make_jaxpr`` / ``jax.eval_shape`` under the canonical
small configs recompile.py already defines — so the lint node never
initializes a TPU backend and never executes a kernel (lint stays off
the benchmark's path).

Static half (AST over the kernel/engine modules):

- **host-sync inside kernel modules** — ``.item()`` / ``.tolist()`` /
  ``np.asarray`` / ``jax.device_get`` / ``block_until_ready`` inside any
  function of a kernel module: under jit these either crash at trace time
  (concretization) or, on the host paths threaded through the same
  modules, silently serialize the dispatch pipeline.  ``__init__`` bodies
  and module level are exempt (host-side setup: bucket edges, config).
- **donated-buffer use-after-donation** — the engine's step kernels all
  take ``donate_argnums=0`` (the pool buffer is donated).  Flow-sensitive
  over the dataflow CFG: reading the variable that was passed as the
  donated argument after the call — without rebinding it from the call's
  result — is a use of a dead buffer (``RuntimeError: invalid buffer`` on
  device, silent stale data under some backends).

Trace half (per kernel family under canonical configs):

- **host callbacks inside jit** — ``pure_callback`` / ``io_callback`` /
  ``debug_callback`` / infeed/outfeed primitives anywhere in a kernel
  family's jaxpr: a host round trip per window inside the hot step.
- **dtype preservation & drift** — each step must return the pool with
  EXACTLY the input dtypes (an upcast silently doubles HBM and breaks
  donation reuse), and the shared pool fields must carry the same dtypes
  across kernel FAMILIES (1v1 / glicko2 / team / role) — drift between
  families breaks checkpoint/restore and the breaker's engine swaps.
- **padded-lane contamination** (the QualityAccumKernel shape) — masked
  lanes carry the ``+inf`` dist sentinel; ``0 × inf = NaN``, so a masked
  SUM is NOT a sanitizer — only a ``select``/``where`` gated on a
  validity mask is.  Checked by forward taint over the jaxpr: the
  sentinel-carrying input taints everything it reaches EXCEPT through a
  ``select_n`` whose predicate derives from a sentinel comparison and
  which offers at least one clean branch.  Gather indices do not
  propagate taint (clipped index reads return real pool values).
- **ppermute ring audit** — the sharded families' ``ring_all_gather``
  hops must use one consistent permutation forming a single cycle that
  covers the whole mesh axis (a split or inconsistent ring silently
  drops shards' candidates).  Runs only when ≥ 2 devices are visible
  (the pytest CPU mesh has 8; a bare CLI run skips it).
"""

from __future__ import annotations

import ast
from typing import Any, Callable

from matchmaking_tpu.analysis import dataflow as df
from matchmaking_tpu.analysis.core import (
    Finding,
    SourceFile,
    dotted_name,
    qualname_of,
)
from matchmaking_tpu.analysis.recompile import (
    KERNEL_MODULES,
    _canonical_packed,
    _canonical_pool,
)

RULE = "device"

#: Engine modules whose kernel CALL SITES get the donation audit.
ENGINE_PREFIX = "matchmaking_tpu/engine/"

#: Dotted suffixes that host-sync (full readback / blocking).
_HOST_SYNC_CALLS = {
    "np.asarray": "full-array host readback",
    "numpy.asarray": "full-array host readback",
    "jax.device_get": "blocking D2H transfer",
}
_HOST_SYNC_METHODS = {
    "item": "host-syncs a device scalar (trace-time crash under jit)",
    "tolist": "host-syncs the whole array",
    "block_until_ready": "blocks on device completion",
}

#: Kernel attributes compiled with ``donate_argnums=0`` (the pool arg).
DONATING_KERNELS = frozenset({
    "admit", "evict", "search_step", "admit_packed", "search_step_packed",
    "search_step_packed_nofilter", "search_step_packed_rescan",
    "search_step_packed_ring",
})

#: jaxpr primitives that round-trip through the host.
_CALLBACK_PRIMS = ("callback", "infeed", "outfeed")


# ---- static: host-sync in kernel modules ------------------------------------

class _HostSyncScanner(ast.NodeVisitor):
    def __init__(self, sf: SourceFile):
        self.sf = sf
        self.findings: list[Finding] = []
        self._stack: list[ast.AST] = []

    def _in_scope(self) -> bool:
        fns = [n for n in self._stack
               if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))]
        return bool(fns) and fns[-1].name != "__init__"

    def visit_ClassDef(self, node):
        self._stack.append(node)
        self.generic_visit(node)
        self._stack.pop()

    def _fn(self, node):
        self._stack.append(node)
        self.generic_visit(node)
        self._stack.pop()

    visit_FunctionDef = _fn
    visit_AsyncFunctionDef = _fn

    def visit_Call(self, node: ast.Call) -> None:
        if self._in_scope():
            name = dotted_name(node.func)
            hint = None
            what = name
            for suffix, h in _HOST_SYNC_CALLS.items():
                if name == suffix or name.endswith("." + suffix):
                    hint = h
                    break
            if hint is None and isinstance(node.func, ast.Attribute):
                meth = node.func.attr
                if (meth in _HOST_SYNC_METHODS and not node.args
                        and not node.keywords):
                    hint = _HOST_SYNC_METHODS[meth]
                    what = f".{meth}()"
            if hint is not None:
                self.findings.append(Finding(
                    RULE, self.sf.path, node.lineno,
                    f"host-sync {what!r} in a kernel module: {hint} — "
                    f"kernel math must stay on device; host setup belongs "
                    f"in __init__",
                    qualname_of(self._stack)))
        self.generic_visit(node)


# ---- static: use-after-donation ---------------------------------------------

def _donating_call(call: ast.Call) -> str | None:
    """The donated (first) argument's dotted name when ``call`` invokes a
    donating kernel: ``self.kernels.evict(pool, ...)`` or the bucketed
    ``self._step_fn(batch)(pool, packed)`` shape."""
    func = call.func
    name = dotted_name(func)
    leaf = name.rsplit(".", 1)[-1] if name else ""
    is_donating = leaf in DONATING_KERNELS and "." in name
    if not is_donating and isinstance(func, ast.Call):
        inner = dotted_name(func.func)
        if inner.rsplit(".", 1)[-1] == "_step_fn":
            is_donating = True
    if not is_donating or not call.args:
        return None
    donated = dotted_name(call.args[0])
    return donated or None


class _DonationAnalysis(df.Analysis):
    """State: dotted name → "donated".  A read after donation (before a
    rebind from the call result) is the finding."""

    def __init__(self, sf: SourceFile, qual: str):
        self.sf = sf
        self.qual = qual
        self.findings: list[Finding] = []
        self.report = False
        self._seen: set[tuple] = set()

    def join(self, a, b):
        return a if a == b else "donated"  # donated-on-some-path dominates

    def _stmt_reads(self, stmt: ast.AST) -> set[str]:
        out: set[str] = set()
        targets: set[int] = set()
        if isinstance(stmt, ast.Assign):
            for t in stmt.targets:
                for sub in ast.walk(t):
                    targets.add(id(sub))
        for expr in _header_exprs(stmt):
            for sub in ast.walk(expr):
                if id(sub) in targets:
                    continue
                name = dotted_name(sub)
                if name:
                    out.add(name)
        return out

    def transfer(self, node: df.Node, state, cfg):
        stmt = node.stmt
        if stmt is None:
            return state
        # Reads of donated buffers (the assignment's own RHS counts; its
        # targets do not).
        reads = self._stmt_reads(stmt)
        for name in list(state):
            if state[name] != "donated":
                continue
            if any(r == name or r.startswith(name + ".")
                   or r.startswith(name + "[") for r in reads):
                if self.report:
                    key = ("uad", name, stmt.lineno)
                    if key not in self._seen:
                        self._seen.add(key)
                        self.findings.append(Finding(
                            RULE, self.sf.path, stmt.lineno,
                            f"use of {name!r} after it was DONATED to a "
                            f"kernel call: the buffer is dead (donate_"
                            f"argnums=0) — rebind it from the call's "
                            f"result first",
                            self.qual))
        # Donations + rebinds.
        donated_here: list[str] = []
        for expr in _header_exprs(stmt):
            for sub in ast.walk(expr):
                if isinstance(sub, ast.Call):
                    d = _donating_call(sub)
                    if d is not None:
                        donated_here.append(d)
        rebound: set[str] = set()
        if isinstance(stmt, ast.Assign):
            for t in stmt.targets:
                elts = t.elts if isinstance(t, ast.Tuple) else [t]
                for e in elts:
                    name = dotted_name(e)
                    if name:
                        rebound.add(name)
        for d in donated_here:
            if d not in rebound:
                state[d] = "donated"
        for r in rebound:
            state.pop(r, None)
        return state


_header_exprs = df.header_exprs


def check_static(sources: list[SourceFile]) -> list[Finding]:
    findings: list[Finding] = []
    for sf in sources:
        if sf.path in KERNEL_MODULES:
            v = _HostSyncScanner(sf)
            v.visit(sf.tree)
            findings.extend(v.findings)
        if sf.path.startswith(ENGINE_PREFIX):
            for cls, fn in _iter_functions(sf.tree):
                uses = any(_donating_call(c) for n in ast.walk(fn)
                           for c in ([n] if isinstance(n, ast.Call)
                                     else []))
                if not uses:
                    continue
                qual = f"{cls}.{fn.name}" if cls else fn.name
                cfg = df.CFG(fn)
                analysis = _DonationAnalysis(sf, qual)
                df.solve_and_report(cfg, analysis)
                findings.extend(analysis.findings)
    return findings


_iter_functions = df.iter_functions


# ---- trace half -------------------------------------------------------------

def _walk_jaxpr(jaxpr, visit: Callable[[Any], None]) -> None:
    for eqn in jaxpr.eqns:
        visit(eqn)
        for v in eqn.params.values():
            for sub in _sub_jaxprs(v):
                _walk_jaxpr(sub, visit)


def _sub_jaxprs(v):
    if hasattr(v, "jaxpr"):        # ClosedJaxpr
        yield v.jaxpr
    elif hasattr(v, "eqns"):       # raw Jaxpr
        yield v
    elif isinstance(v, (tuple, list)):
        for x in v:
            yield from _sub_jaxprs(x)


def _trace(fn, *args):
    import jax

    raw = getattr(fn, "__wrapped__", fn)
    return jax.make_jaxpr(lambda *a: raw(*a))(*args)


def _check_callbacks(closed, family: str, ctx: str,
                     findings: list[Finding]) -> None:
    hits: list[str] = []

    def visit(eqn):
        name = eqn.primitive.name
        if any(p in name for p in _CALLBACK_PRIMS):
            hits.append(name)

    _walk_jaxpr(closed.jaxpr, visit)
    for name in sorted(set(hits)):
        findings.append(Finding(
            RULE, ctx, 0,
            f"host callback primitive {name!r} inside jitted kernel "
            f"{family}: a host round trip per window on the hot step",
            family))


def _pool_dtypes(tree) -> dict[str, Any]:
    return {k: v.dtype for k, v in tree.items()}


def _check_pool_preserved(fn, family: str, ctx: str, pool, args,
                          findings: list[Finding],
                          out_pool=None) -> "dict[str, Any] | None":
    """eval_shape the step; the output pool's dtypes must equal the input
    pool's.  Returns the output pool dtype map (for cross-family checks),
    or None when tracing failed (reported)."""
    import jax

    try:
        out = jax.eval_shape(fn, pool, *args)
    except Exception as e:
        findings.append(Finding(
            RULE, ctx, 0,
            f"could not trace {family}: {type(e).__name__}: {e}", family))
        return None
    pool_out = out[0] if isinstance(out, tuple) else out
    want = _pool_dtypes(pool)
    got = _pool_dtypes(pool_out)
    for k in sorted(want):
        if k in got and got[k] != want[k]:
            findings.append(Finding(
                RULE, ctx, 0,
                f"dtype drift in {family}: pool field {k!r} enters "
                f"{want[k]} and leaves {got[k]} — an upcast breaks "
                f"donation reuse and doubles HBM",
                family))
    return got


# ---- padded-lane taint ------------------------------------------------------

_CMP_PRIMS = {"lt", "le", "gt", "ge", "eq", "ne"}
_BOOL_PRIMS = {"and", "or", "not", "xor"}
#: Index-consuming prims: taint flows from the OPERAND, never the indices
#: (a clipped index read returns a real pool value).
_GATHER_PRIMS = {"gather", "dynamic_slice", "take", "argmax", "argmin"}
#: Higher-order primitives whose params carry a sub-jaxpr the taint walk
#: steps into (exact names: ``jit`` — named ``pjit`` before JAX 0.7 —
#: wraps every ``jnp.where``; ``remat2`` is ``jax.checkpoint``).
_SUBJAXPR_PRIMS = {"jit", "scan", "while", "cond", "custom_jvp_call",
                   "custom_vjp_call", "remat2", "closed_call"}


def check_padded_lanes(fn, args, sentinel_arg: int, family: str,
                       ctx: str = "matchmaking_tpu/engine/kernels.py",
                       ) -> list[Finding]:
    """Forward sentinel taint over ``fn``'s jaxpr.  ``sentinel_arg`` is the
    index (into the FLATTENED invars) of the array carrying masked-lane
    sentinels.  A function output still sentinel-tainted means masked
    lanes reach an accumulator without a select-style sanitizer —
    ``0 × inf = NaN`` contamination (the QualityAccumKernel shape)."""
    import jax

    findings: list[Finding] = []
    try:
        closed = _trace(fn, *args)
    except Exception as e:
        findings.append(Finding(
            RULE, ctx, 0,
            f"could not trace {family} for the padded-lane audit: "
            f"{type(e).__name__}: {e}", family))
        return findings
    jaxpr = closed.jaxpr
    flat_in = jaxpr.invars
    taint: dict[int, set[str]] = {}

    def t(v) -> set[str]:
        return taint.get(id(v), set())

    if sentinel_arg >= len(flat_in):
        return findings
    taint[id(flat_in[sentinel_arg])] = {"sentinel"}

    def run(jx) -> None:
        for eqn in jx.eqns:
            name = eqn.primitive.name
            ins = [t(v) for v in eqn.invars]
            flat = set().union(*ins) if ins else set()
            if name in _CMP_PRIMS:
                out: set[str] = {"mask"} if "sentinel" in flat else set()
            elif name in _BOOL_PRIMS:
                out = flat & {"mask"}
            elif name == "select_n":
                pred = ins[0] if ins else set()
                cases = ins[1:]
                if "mask" in pred:
                    # Validity-gated select: sanitizes when any branch is
                    # clean (the masked lanes take the clean branch).
                    out = ({"sentinel"}
                           if cases and all("sentinel" in c for c in cases)
                           else set())
                else:
                    out = {f for c in cases for f in c}
            elif name in _GATHER_PRIMS:
                out = ins[0] if ins else set()
            elif name in _SUBJAXPR_PRIMS:
                # Sub-jaxpr: map argument taints onto the inner invars,
                # run, and map back.
                subs = [s for v in eqn.params.values()
                        for s in _sub_jaxprs(v)]
                if subs:
                    inner = subs[0]
                    n = min(len(inner.invars), len(eqn.invars))
                    for iv, ov in zip(inner.invars[-n:], eqn.invars[-n:]):
                        if t(ov):
                            taint[id(iv)] = set(t(ov))
                    run(inner)
                    m = min(len(inner.outvars), len(eqn.outvars))
                    for iv, ov in zip(inner.outvars[:m], eqn.outvars[:m]):
                        taint[id(ov)] = set(t(iv))
                    continue
                out = flat
            else:
                out = flat
            for v in eqn.outvars:
                taint[id(v)] = set(out)

    run(jaxpr)
    for i, v in enumerate(jaxpr.outvars):
        if "sentinel" in t(v):
            findings.append(Finding(
                RULE, ctx, 0,
                f"padded-lane contamination in {family}: output #{i} is "
                f"reachable from the masked-lane sentinel input without a "
                f"validity select — 0 × inf = NaN poisons the "
                f"accumulator; sanitize with jnp.where(valid, x, 0) "
                f"BEFORE any masked arithmetic",
                family))
    return findings


# ---- ppermute ring audit ----------------------------------------------------

def _check_ring(closed, n_shards: int, family: str, ctx: str,
                findings: list[Finding]) -> None:
    perms: list[tuple] = []

    def visit(eqn):
        if eqn.primitive.name == "ppermute":
            perms.append(tuple(sorted(map(tuple, eqn.params["perm"]))))

    _walk_jaxpr(closed.jaxpr, visit)
    if not perms:
        findings.append(Finding(
            RULE, ctx, 0,
            f"{family}: ring=True but no ppermute in the trace — the ring "
            f"exchange silently fell back to something else", family))
        return
    if len(set(perms)) > 1:
        findings.append(Finding(
            RULE, ctx, 0,
            f"{family}: ppermute hops use INCONSISTENT permutations "
            f"({len(set(perms))} distinct) — every ring hop must rotate "
            f"the same direction or shards merge stale candidates",
            family))
    perm = perms[0]
    srcs = [s for s, _ in perm]
    dsts = [d for _, d in perm]
    ok = (sorted(srcs) == list(range(n_shards))
          and sorted(dsts) == list(range(n_shards)))
    if ok:
        # Single cycle covering the axis: follow the permutation.
        nxt = dict(perm)
        seen = set()
        cur = 0
        for _ in range(n_shards):
            seen.add(cur)
            cur = nxt[cur]
        ok = len(seen) == n_shards and cur == 0
    if not ok:
        findings.append(Finding(
            RULE, ctx, 0,
            f"{family}: ppermute permutation {perm} is not a single "
            f"{n_shards}-cycle over the mesh axis — some shard's "
            f"candidates never reach every peer", family))


# ---- the audit driver -------------------------------------------------------

def check_dynamic() -> list[Finding]:
    """Audit every kernel family under canonical configs.  Trace-only: no
    kernel executes, no TPU backend is touched (jax stays on whatever
    platform the host process configured — the CLI pins CPU)."""
    findings: list[Finding] = []
    import jax

    from matchmaking_tpu.engine.kernels import (
        QualityAccumKernel,
        kernel_set,
    )

    ctx = "matchmaking_tpu/engine/kernels.py"
    family_pool_dtypes: dict[str, dict] = {}
    for label, kwargs in (
        ("1v1", dict(glicko2=False, widen_per_sec=5.0)),
        ("1v1-glicko2", dict(glicko2=True, widen_per_sec=0.0)),
    ):
        ks = kernel_set(capacity=64, top_k=4, pool_block=32,
                        max_threshold=400.0, pair_rounds=4, **kwargs)
        pool = _canonical_pool(ks, 0)
        packed = _canonical_packed(ks, 16, 0)
        for name in ("search_step_packed", "search_step_packed_nofilter",
                     "search_step_packed_rescan", "admit_packed"):
            fn = getattr(ks, name, None)
            if fn is None:
                continue
            family = f"kernels.{label}.{name}"
            try:
                closed = _trace(fn, pool, packed)
            except Exception as e:
                findings.append(Finding(
                    RULE, ctx, 0,
                    f"could not trace {family}: {type(e).__name__}: {e}",
                    family))
                continue
            _check_callbacks(closed, family, ctx, findings)
            got = _check_pool_preserved(fn, family, ctx, pool, (packed,),
                                        findings)
            if got is not None and name == "search_step_packed":
                family_pool_dtypes[label] = got

    # Team family (object windows): same pool layout, own step shape.
    from matchmaking_tpu.engine.teams import team_kernel_set

    tks = team_kernel_set(capacity=64, team_size=2, widen_per_sec=5.0,
                          max_threshold=400.0, max_matches=8, rounds=4)
    tctx = "matchmaking_tpu/engine/teams.py"
    pool = _canonical_pool(tks, 0)
    packed = _canonical_packed(tks, 16, 0)
    try:
        closed = _trace(tks.search_step_packed, pool, packed)
        _check_callbacks(closed, "teams.search_step_packed", tctx, findings)
        got = _check_pool_preserved(tks.search_step_packed,
                                    "teams.search_step_packed", tctx, pool,
                                    (packed,), findings)
        if got is not None:
            family_pool_dtypes["team"] = got
    except Exception as e:
        findings.append(Finding(
            RULE, tctx, 0,
            f"could not trace teams.search_step_packed: "
            f"{type(e).__name__}: {e}", "teams.search_step_packed"))

    # Role family.
    from matchmaking_tpu.engine.role_kernels import role_kernel_set

    rks = role_kernel_set(capacity=32, team_size=2,
                          role_slots=("tank", "dps"), widen_per_sec=5.0,
                          max_threshold=400.0, max_matches=8, rounds=4)
    rctx = "matchmaking_tpu/engine/role_kernels.py"
    pool = _canonical_pool(rks, 0)
    packed = _canonical_packed(rks, 16, 0)
    fn = getattr(rks, "search_step_packed", None)
    if fn is not None:
        try:
            closed = _trace(fn, pool, packed)
            _check_callbacks(closed, "role_kernels.search_step_packed",
                             rctx, findings)
            got = _check_pool_preserved(fn, "role_kernels.search_step_packed",
                                        rctx, pool, (packed,), findings)
            if got is not None:
                family_pool_dtypes["role"] = got
        except Exception as e:
            findings.append(Finding(
                RULE, rctx, 0,
                f"could not trace role_kernels.search_step_packed: "
                f"{type(e).__name__}: {e}", "role_kernels.search_step_packed"))

    # Cross-family drift on the shared pool fields.
    labels = sorted(family_pool_dtypes)
    for i, a in enumerate(labels):
        for b in labels[i + 1:]:
            da, dtb = family_pool_dtypes[a], family_pool_dtypes[b]
            for k in sorted(set(da) & set(dtb)):
                if da[k] != dtb[k]:
                    findings.append(Finding(
                        RULE, ctx, 0,
                        f"dtype drift BETWEEN kernel families: pool field "
                        f"{k!r} is {da[k]} in {a} but {dtb[k]} in {b} — "
                        f"engine swaps (breaker demotion, elastic "
                        f"placement) would reinterpret the checkpoint",
                        f"{a}~{b}"))

    # Padded-lane contamination: the QualityAccumKernel shape.
    import jax.numpy as jnp
    import numpy as np

    from matchmaking_tpu.engine.quality import QualitySpec

    spec = QualitySpec()
    q = QualityAccumKernel(
        capacity=64, widen_per_sec=5.0, max_threshold=400.0,
        rating_edges=spec.rating_edges, n_quality=spec.n_quality,
        wait_edges=spec.wait_edges)
    state = q.init_state()
    b = 16
    rating = jnp.zeros(64, jnp.float32)
    enq = jnp.zeros(64, jnp.float32)
    thr = jnp.zeros(64, jnp.float32)
    out = jnp.zeros((3, b), jnp.float32)
    now = jnp.float32(1.0)
    n_state = len(jax.tree_util.tree_leaves(state))
    findings.extend(check_padded_lanes(
        q.accum, (state, rating, enq, thr, out, now),
        sentinel_arg=n_state + 3, family="QualityAccumKernel.accum"))

    # Sharded ring audit (needs a multi-device mesh; the pytest CPU mesh
    # has 8 virtual devices — a single-device CLI run skips, silently:
    # absence of devices is an environment fact, not a finding).
    n_dev = len(jax.devices())
    if n_dev >= 2:
        from matchmaking_tpu.engine.sharded import sharded_kernel_set

        n = 4 if n_dev >= 4 else 2
        sctx = "matchmaking_tpu/engine/sharded.py"
        try:
            sks = sharded_kernel_set(
                capacity=64, top_k=4, pool_block=16, glicko2=False,
                widen_per_sec=5.0, max_threshold=400.0, n_shards=n,
                ring=True)
            pool = _canonical_pool(sks, 0)
            packed = _canonical_packed(sks, 16, 0)
            closed = _trace(sks.search_step_packed, pool, packed)
            _check_ring(closed, n, "sharded.search_step_packed(ring)",
                        sctx, findings)
            _check_callbacks(closed, "sharded.search_step_packed", sctx,
                             findings)
            _check_pool_preserved(sks.search_step_packed,
                                  "sharded.search_step_packed", sctx,
                                  pool, (packed,), findings)
        except Exception as e:
            findings.append(Finding(
                RULE, sctx, 0,
                f"could not trace the sharded ring family: "
                f"{type(e).__name__}: {e}", "sharded.ring"))
    return findings


def check(sources: list[SourceFile], dynamic: bool = True) -> list[Finding]:
    findings = check_static(sources)
    if dynamic:
        findings.extend(check_dynamic())
    return findings
