"""``blocking-call``: event-loop stalls you can see lexically.

The service is one asyncio loop per process; every millisecond a callback
blocks is a millisecond EVERY queue's consumers, sweepers, and auth RPC
deadlines stall (the p99 killer SURVEY.md §7 names). Engine work is
designed to run off-loop via ``asyncio.to_thread`` — so a blocking call
appearing lexically inside an ``async def`` body is almost always a bug.

Flagged inside async bodies (nested sync ``def``/``lambda`` bodies are
excluded — they execute wherever they are CALLED, usually a worker
thread):

- ``time.sleep(...)`` — use ``await asyncio.sleep``.
- ``open(...)`` — sync file I/O; move to a thread.
- host-sync JAX/numpy readbacks: ``np.asarray(...)``/``jax.device_get``
  on device arrays, ``.item()``, ``(jax.)block_until_ready`` — each one
  parks the loop on a device round trip. Dispatch/readback belongs in the
  engine, off-loop.

Intentional sites (rare admin endpoints, bounded one-shot work) carry
``# matchlint: ignore[blocking-call] <reason>``.
"""

from __future__ import annotations

import ast

from matchmaking_tpu.analysis.core import (
    Finding,
    SourceFile,
    dotted_name,
    in_package,
    qualname_of,
)

RULE = "blocking-call"

#: Dotted-call suffixes that block the loop, with the suggested fix.
BLOCKING_CALLS: dict[str, str] = {
    "time.sleep": "use `await asyncio.sleep(...)`",
    "jax.block_until_ready": "collect via the engine's off-loop readback",
    "jax.device_get": "collect via the engine's off-loop readback",
    "np.asarray": "host-syncs a device array; readback belongs off-loop",
    "numpy.asarray": "host-syncs a device array; readback belongs off-loop",
}
#: Method names that host-sync whatever they're called on.
BLOCKING_METHODS: dict[str, str] = {
    "block_until_ready": "device sync; run via asyncio.to_thread",
    "item": "host-syncs a device scalar; materialize off-loop",
}


class _AsyncBodyScanner(ast.NodeVisitor):
    def __init__(self, sf: SourceFile):
        self.sf = sf
        self.findings: list[Finding] = []
        self._stack: list[ast.AST] = []
        self._async_depth = 0

    def visit_ClassDef(self, node: ast.ClassDef) -> None:
        self._stack.append(node)
        self.generic_visit(node)
        self._stack.pop()

    def visit_FunctionDef(self, node: ast.FunctionDef) -> None:
        # Sync body: not loop code (even when nested in an async def).
        self._stack.append(node)
        depth, self._async_depth = self._async_depth, 0
        self.generic_visit(node)
        self._async_depth = depth
        self._stack.pop()

    def visit_Lambda(self, node: ast.Lambda) -> None:
        depth, self._async_depth = self._async_depth, 0
        self.generic_visit(node)
        self._async_depth = depth

    def visit_AsyncFunctionDef(self, node: ast.AsyncFunctionDef) -> None:
        self._stack.append(node)
        self._async_depth += 1
        self.generic_visit(node)
        self._async_depth -= 1
        self._stack.pop()

    def visit_Call(self, node: ast.Call) -> None:
        if self._async_depth > 0:
            name = dotted_name(node.func)
            hint = None
            what = name
            if name == "open":
                hint = "sync file I/O on the event loop; move to a thread"
            else:
                for suffix, h in BLOCKING_CALLS.items():
                    if name == suffix or name.endswith("." + suffix):
                        hint = h
                        break
            if hint is None and isinstance(node.func, ast.Attribute):
                meth = node.func.attr
                if meth in BLOCKING_METHODS and not node.args \
                        and not node.keywords:
                    hint = BLOCKING_METHODS[meth]
                    what = f".{meth}()"
            if hint is not None:
                self.findings.append(Finding(
                    RULE, self.sf.path, node.lineno,
                    f"blocking call {what!r} in an async body: {hint}",
                    qualname_of(self._stack)))
        self.generic_visit(node)


def check(sources: list[SourceFile]) -> list[Finding]:
    findings: list[Finding] = []
    for sf in sources:
        if not in_package(sf):
            continue
        v = _AsyncBodyScanner(sf)
        v.visit(sf.tree)
        findings.extend(v.findings)
    return findings
