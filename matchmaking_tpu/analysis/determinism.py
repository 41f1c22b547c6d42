"""``determinism``: seeds and clocks that break replay.

The chaos runtime's whole value (utils/chaos.py) is that a fault soak
replays bit-identically: every decision is a pure function of message
identity, never of RNG call order or wall-clock jitter. Two patterns
silently reintroduce the flake class PR 2 eliminated:

- **Unseeded RNGs** — ``random.Random()``, ``np.random.default_rng()``
  with no seed, or the module-level global RNGs (``random.random()``,
  ``np.random.rand(...)``, ``random.seed()``): their draw order depends on
  event-loop scheduling, so accounting differs between identical runs.
  Scanned in the package AND in tests/ (a test that draws from an
  unseeded RNG is flaky by construction).
- **Wall-clock deadlines** — ``deadline = time.time() + N`` or
  ``while time.time() < deadline``: wall clocks step (NTP) and make
  timeout behavior irreproducible; ``time.monotonic()`` is the tool
  (app.py's rescan deadline already uses it). ``time.time()`` for
  TIMESTAMPS (trace marks, enqueue times, TTLs) is correct and not
  flagged — only deadline arithmetic is. The overload subsystem's
  deadline PROPAGATION (service/overload.py) widened the surface, so
  the rule covers the new shapes too: subscript stores whose key names
  a deadline (``headers["x-deadline"] = time.time() + n`` — the header
  must be stamped through ``overload.stamp_deadline(headers, now, n)``,
  which takes the one wall-clock read as a parameter), ``deadline +=
  time.time()`` aug-assigns, and ``f(deadline=time.time() + n)`` keyword
  arguments. The continuous-telemetry sampler (utils/timeseries.py,
  ISSUE 6) added another schedule-shaped surface — next-snapshot /
  next-sample / scrape-due arithmetic — so the same name heuristic covers
  those tokens too: the sanctioned shapes are ``asyncio.sleep(interval)``
  cadence (no stored wake time at all) or ``time.monotonic()``;
  ``time.time()`` remains fine as snapshot DATA (the ring's timestamps).
  The tiered-QoS scheduler (ISSUE 7) added ordering-key surfaces —
  EDF window-cut keys and tier ranks (``edf_key``/``cut_key``/
  ``sort_key``/``tier_key`` tokens): a cut key born from ``time.time()``
  makes window COMPOSITION depend on scheduler jitter, so keys must be
  pure functions of the message (the stamped ``x-deadline`` header via
  ``overload.deadline_of`` + the admission-cached ``delivery.tier``).
  The crash-durability journal (utils/journal.py, ISSUE 15) added the
  newest surface — journal-SEQUENCE arithmetic (``journal_seq`` /
  ``record_seq`` / ``snapshot_seq`` / ``anchor_seq`` tokens): recovery
  replays records in seq order and the crash-soak pins a bit-identical
  recovery transcript, so a seq or compaction anchor derived from
  ``time.time()`` would make replay order a function of wall-clock
  jitter. Seqs are plain counters; fsync-interval pacing uses
  ``time.monotonic()``.
"""

from __future__ import annotations

import ast

from matchmaking_tpu.analysis.core import (
    Finding,
    SourceFile,
    dotted_name,
    qualname_of,
)

RULE = "determinism"

#: Module-global RNG draws (call order = schedule order = flaky).
_GLOBAL_RNG_CALLS = {
    "random.random", "random.randint", "random.randrange", "random.choice",
    "random.choices", "random.shuffle", "random.uniform", "random.sample",
    "random.seed", "random.gauss",
    "np.random.rand", "np.random.randn", "np.random.randint",
    "np.random.random", "np.random.uniform", "np.random.choice",
    "np.random.shuffle", "np.random.seed", "np.random.normal",
    "numpy.random.rand", "numpy.random.randn", "numpy.random.randint",
    "numpy.random.random", "numpy.random.uniform", "numpy.random.choice",
    "numpy.random.shuffle", "numpy.random.seed", "numpy.random.normal",
}
#: Constructors that REQUIRE an explicit seed argument.
_SEED_REQUIRED = {"random.Random", "np.random.default_rng",
                  "numpy.random.default_rng", "random.SystemRandom"}


def _in_scope(sf: SourceFile) -> bool:
    return (sf.path.startswith(("matchmaking_tpu/", "tests/", "scripts/"))
            and not sf.path.startswith("matchmaking_tpu/analysis/"))


def _contains_time_time(node: ast.AST) -> ast.Call | None:
    for sub in ast.walk(node):
        if isinstance(sub, ast.Call) and dotted_name(sub.func) == "time.time":
            return sub
    return None


#: Name substrings that mark a value as schedule-like: wall-clock
#: arithmetic INTO one of these is the replay hazard. "deadline" covers
#: the overload subsystem; the snapshot/sample/scrape tokens cover the
#: telemetry sampler's next-tick shapes (ISSUE 6); the edf/sort-key
#: tokens cover the tiered-QoS window-cut ordering (ISSUE 7) — an EDF
#: key computed from ``time.time()`` would make window COMPOSITION a
#: function of scheduler jitter, so the sanctioned shapes are the stamped
#: ``x-deadline`` header (``overload.deadline_of``) and the cached
#: ``delivery.tier``, both pure functions of the message.
_CLOCKLIKE_TOKENS = ("deadline", "next_snapshot", "snapshot_due",
                     "next_sample", "sample_due", "next_scrape",
                     "scrape_due", "edf_key", "edf", "cut_key", "sort_key",
                     "tier_key", "tier_rank",
                     # Journal-sequence arithmetic (ISSUE 15): the
                     # write-ahead journal's replay order is its monotone
                     # record seq — a seq/anchor born from time.time()
                     # would make recovery replay order (and the
                     # crash-soak's bit-identical transcript) a function
                     # of wall-clock jitter. Seqs are counters; the one
                     # sanctioned clock in the journal is the fsync
                     # INTERVAL check, which already uses monotonic.
                     "journal_seq", "record_seq", "snapshot_seq",
                     "anchor_seq",
                     # Lease/epoch arithmetic (ISSUE 17): fencing decides
                     # which host may write, so a lease deadline, epoch,
                     # ack watermark, or lag figure born from time.time()
                     # would make FAILOVER (and the failover-soak's
                     # bit-identical transcript) a function of wall-clock
                     # jitter. The sanctioned clock for lease state is a
                     # caller-passed time.monotonic() value; epochs and
                     # ack seqs are counters.
                     "lease_deadline", "epoch", "ack_seq", "lag_ms",
                     # Event-spine arithmetic (ISSUE 18): the forensics
                     # spine's causal order IS its monotone counter seq —
                     # a spine/event/incident seq derived from time.time()
                     # deltas would make the incident-soak's bit-identical
                     # transcript (and every postmortem timeline) a
                     # function of wall-clock jitter. The sanctioned
                     # clocks on a spine row are DATA fields: mono_ns
                     # (monotonic, for gap annotation) and wall (display
                     # only) — neither may feed the seq.
                     "spine_seq", "event_seq", "incident_seq", "trigger_seq",
                     "mono_ns", "capture_due", "next_capture",
                     # Retry/backoff/heartbeat arithmetic (ISSUE 20): the
                     # socket transport's reconnect schedule, heartbeat
                     # liveness verdict, and RTT-budgeted lease validity
                     # decide WHEN a peer is declared dead and WHEN a
                     # primary must fence — born from time.time() they
                     # would make failover timing (and the soak's
                     # bit-identical transcript) a function of wall-clock
                     # jitter, and unseeded reconnect jitter would make
                     # two seeded runs dial on different schedules. The
                     # sanctioned shapes: seeded jitter via
                     # hash01(seed, "backoff", conn, attempt) and
                     # caller-passed time.monotonic() values.
                     # (retry_deadline / heartbeat_deadline are already
                     # caught by the "deadline" token above.)
                     "backoff", "next_heartbeat", "rtt_ms", "valid_until",
                     "retry_at", "next_dial")


def _clocklike(text: str) -> bool:
    low = text.lower()
    return any(tok in low for tok in _CLOCKLIKE_TOKENS)


def _name_contains_deadline(node: ast.AST) -> bool:
    if isinstance(node, ast.Name):
        return _clocklike(node.id)
    if isinstance(node, ast.Attribute):
        return _clocklike(node.attr)
    if isinstance(node, ast.Subscript):
        # headers["x-deadline"] = ... — the deadline-propagation header
        # store (service/overload.py) and any dict-carried deadline.
        key = node.slice
        if isinstance(key, ast.Constant) and isinstance(key.value, str):
            return _clocklike(key.value)
        return _name_contains_deadline(node.value)
    return False


class _Scanner(ast.NodeVisitor):
    def __init__(self, sf: SourceFile):
        self.sf = sf
        self.findings: list[Finding] = []
        self._stack: list[ast.AST] = []

    def _ctx(self) -> str:
        return qualname_of(self._stack)

    def visit_ClassDef(self, node):
        self._stack.append(node)
        self.generic_visit(node)
        self._stack.pop()

    visit_FunctionDef = visit_ClassDef
    visit_AsyncFunctionDef = visit_ClassDef

    def visit_Call(self, node: ast.Call) -> None:
        name = dotted_name(node.func)
        if name in _GLOBAL_RNG_CALLS:
            self.findings.append(Finding(
                RULE, self.sf.path, node.lineno,
                f"module-global RNG draw {name!r}: call order depends on "
                f"scheduling — use a seeded instance (random.Random(seed) / "
                f"np.random.default_rng(seed)) or utils.chaos.hash01",
                self._ctx()))
        elif name in _SEED_REQUIRED and not node.args and not node.keywords:
            self.findings.append(Finding(
                RULE, self.sf.path, node.lineno,
                f"unseeded {name}(): seed it explicitly so runs replay "
                f"bit-identically",
                self._ctx()))
        for kw in node.keywords:
            # f(deadline=time.time() + n): the deadline is born from the
            # wall clock at the call site — pass `now` through and derive
            # inside (overload.stamp_deadline is the sanctioned shape).
            if (kw.arg is not None and _clocklike(kw.arg)
                    and _contains_time_time(kw.value) is not None):
                self.findings.append(Finding(
                    RULE, self.sf.path, node.lineno,
                    f"keyword {kw.arg}=... computed from time.time(): wall "
                    f"clocks step (NTP) — take `now` as a parameter "
                    f"(overload.stamp_deadline) or use time.monotonic()",
                    self._ctx()))
        self.generic_visit(node)

    def visit_Assign(self, node: ast.Assign) -> None:
        if any(_name_contains_deadline(t) for t in node.targets):
            tt = _contains_time_time(node.value)
            if tt is not None:
                self.findings.append(Finding(
                    RULE, self.sf.path, tt.lineno,
                    "deadline/schedule value computed from time.time(): wall "
                    "clocks step (NTP) — use time.monotonic()",
                    self._ctx()))
        self.generic_visit(node)

    def visit_AugAssign(self, node: ast.AugAssign) -> None:
        if (_name_contains_deadline(node.target)
                and _contains_time_time(node.value) is not None):
            self.findings.append(Finding(
                RULE, self.sf.path, node.lineno,
                "deadline/schedule value adjusted from time.time(): wall "
                "clocks step (NTP) — use time.monotonic()",
                self._ctx()))
        self.generic_visit(node)

    def visit_Compare(self, node: ast.Compare) -> None:
        sides = [node.left, *node.comparators]
        if (any(isinstance(s, ast.Call)
                and dotted_name(s.func) == "time.time" for s in sides)
                and any(_name_contains_deadline(s) for s in sides)):
            self.findings.append(Finding(
                RULE, self.sf.path, node.lineno,
                "deadline/schedule comparison against time.time(): use "
                "time.monotonic()",
                self._ctx()))
        self.generic_visit(node)


def check(sources: list[SourceFile]) -> list[Finding]:
    findings: list[Finding] = []
    for sf in sources:
        if not _in_scope(sf):
            continue
        v = _Scanner(sf)
        v.visit(sf.tree)
        findings.extend(v.findings)
    return findings
