"""matchlint (matchmaking_tpu/analysis): seeded regression tests.

Every rule gets at least one fixture-triggered POSITIVE (the acceptance
bar: a rule that can't fire is decoration), the PR 2 await-window
double-match pattern is proven statically caught, and the `lint`-marked
node runs the full analyzer over the repo — matchlint wired into tier-1.
"""

import pytest

from matchmaking_tpu.analysis.engine import analyze_repo, analyze_source


def _rules(findings):
    return [f.rule for f in findings]


# ---- await-under-lock ------------------------------------------------------

def test_await_under_lock_fires_on_non_sanctioned_await():
    findings = analyze_source('''
import asyncio

class Runtime:
    def __init__(self):
        self._engine_lock = asyncio.Lock()

    async def flush(self, ctx):
        async with self._engine_lock:
            await self.pipeline.run(ctx)
''', path="matchmaking_tpu/service/fixture.py")
    assert _rules(findings) == ["await-under-lock"]
    assert findings[0].line == 10
    assert "pipeline.run" in findings[0].message


def test_await_under_lock_sanctions_to_thread_and_drain():
    findings = analyze_source('''
import asyncio

class Runtime:
    def __init__(self):
        self._engine_lock = asyncio.Lock()

    async def flush(self, window, now):
        async with self._engine_lock:
            await self._drain_engine(now)
            out = await asyncio.to_thread(self.engine.search, window, now)
        return out
''', path="matchmaking_tpu/service/fixture.py")
    assert findings == []


def test_pr2_await_window_double_match_pattern_is_caught():
    """Re-introducing PR 2's race — pool-state mutation across an await
    inside ``_engine_lock`` (the dup delivery that passed the dedup check
    re-admitting while its twin's window was in flight) — is caught
    STATICALLY, without running chaos."""
    findings = analyze_source('''
import asyncio

class Runtime:
    def __init__(self):
        self._engine_lock = asyncio.Lock()
        # guarded-by: _engine_lock
        self._recent = {}

    async def dispatch(self, pairs, now):
        async with self._engine_lock:
            stale = {p for p, d in pairs if p in self._recent}
            await self.broker.confirm(stale)
            for p, _d in pairs:
                self._recent[p] = now
''', path="matchmaking_tpu/service/fixture.py")
    assert "await-under-lock" in _rules(findings)
    bad = next(f for f in findings if f.rule == "await-under-lock")
    assert "broker.confirm" in bad.message


# ---- guarded-by ------------------------------------------------------------

GUARDED_CLASS = '''
import asyncio

class Runtime:
    def __init__(self):
        self._engine_lock = asyncio.Lock()
        # guarded-by: _engine_lock
        self._inflight_meta = {}

    # holds-lock: _engine_lock
    def _finish(self, tok):
        self._inflight_meta.pop(tok, None)

    def _collect_ready_locked(self, now):
        self._inflight_meta.clear()

    async def good(self, tok, meta):
        async with self._engine_lock:
            self._inflight_meta[tok] = meta
            self._finish(tok)
%s
'''


def test_guarded_by_accepts_disciplined_mutations():
    findings = analyze_source(GUARDED_CLASS % "",
                              path="matchmaking_tpu/service/fixture.py")
    assert findings == []


def test_guarded_by_collects_annotated_assignment_declarations():
    """Regression: `self.x: T = ...` (ast.AnnAssign) must register a
    guarded-by declaration exactly like a plain assignment — app.py's
    `_inflight_meta` declaration is annotated."""
    findings = analyze_source("""
import asyncio

class Runtime:
    def __init__(self):
        self._engine_lock = asyncio.Lock()
        # guarded-by: _engine_lock
        self._inflight_meta: dict[int, str] = {}

    def sweep(self, tok):
        self._inflight_meta.pop(tok, None)
""", path="matchmaking_tpu/service/fixture.py")
    assert _rules(findings) == ["guarded-by"]


def test_guarded_by_flags_unlocked_mutation():
    findings = analyze_source(GUARDED_CLASS % '''
    def sweep(self, tok):
        self._inflight_meta.pop(tok, None)
''', path="matchmaking_tpu/service/fixture.py")
    assert _rules(findings) == ["guarded-by"]
    assert "_inflight_meta" in findings[0].message
    assert findings[0].context == "Runtime.sweep"


def test_guarded_by_flags_unlocked_call_to_holding_method():
    findings = analyze_source(GUARDED_CLASS % '''
    async def tick(self, tok):
        self._finish(tok)
''', path="matchmaking_tpu/service/fixture.py")
    assert _rules(findings) == ["guarded-by"]
    assert "_finish" in findings[0].message


def test_guarded_by_flags_attribute_store_through_guarded_object():
    findings = analyze_source('''
import asyncio

class Runtime:
    def __init__(self):
        self._engine_lock = asyncio.Lock()
        # guarded-by: _engine_lock
        self.engine = None

    async def poke(self):
        self.engine.device_error = None
''', path="matchmaking_tpu/service/fixture.py")
    assert _rules(findings) == ["guarded-by"]


# ---- blocking-call ---------------------------------------------------------

def test_blocking_call_fires_in_async_bodies_only():
    findings = analyze_source('''
import time

async def handler(arr):
    time.sleep(0.1)
    f = open("/tmp/x")
    arr.block_until_ready()
    n = arr.item()

def sync_helper():
    time.sleep(0.1)  # worker-thread code: fine

async def off_loop():
    def run():
        time.sleep(0.1)  # nested sync def: runs via to_thread
    return run
''', path="matchmaking_tpu/service/fixture.py")
    assert _rules(findings) == ["blocking-call"] * 4
    assert all(f.context == "handler" for f in findings)


# ---- determinism -----------------------------------------------------------

def test_determinism_flags_unseeded_rng_and_wallclock_deadlines():
    findings = analyze_source('''
import random
import time
import numpy as np

def faults():
    rng = random.Random()
    g = np.random.default_rng()
    x = random.random()
    deadline = time.time() + 5.0
    while time.time() < deadline:
        pass
''', path="matchmaking_tpu/utils/fixture.py")
    assert _rules(findings) == ["determinism"] * 5
    seeded = analyze_source('''
import random
import time

def fine():
    rng = random.Random(42)
    deadline = time.monotonic() + 5.0
    return rng.random(), deadline
''', path="matchmaking_tpu/utils/fixture.py")
    assert seeded == []


# ---- ignore comments -------------------------------------------------------

def test_inline_ignore_with_reason_suppresses_and_bare_does_not():
    body = '''
import time

async def handler():
    # matchlint: ignore[blocking-call] admin endpoint, bounded one-shot
    time.sleep(0.1)
'''
    assert analyze_source(body,
                          path="matchmaking_tpu/service/fixture.py") == []
    bare = body.replace(" admin endpoint, bounded one-shot", "")
    findings = analyze_source(bare,
                              path="matchmaking_tpu/service/fixture.py")
    assert _rules(findings) == ["blocking-call"]


# ---- recompile -------------------------------------------------------------

def test_recompile_static_flags_loop_variable_capture():
    findings = analyze_source('''
import jax

def build_steps():
    fns = []
    for k in range(3):
        fns.append(jax.jit(lambda x: x * k))
    return fns
''', path="matchmaking_tpu/engine/kernels.py")
    assert _rules(findings) == ["recompile"]
    assert "'k'" in findings[0].message and "for-loop" in findings[0].message


def test_recompile_static_accepts_factory_constants():
    findings = analyze_source('''
import functools

import jax

def kernel_factory(capacity, top_k):
    @functools.partial(jax.jit, donate_argnums=0)
    def step(pool, packed):
        return pool, packed[:top_k] * capacity

    return step
''', path="matchmaking_tpu/engine/kernels.py")
    assert findings == []


def test_recompile_dynamic_catches_jaxpr_drift():
    import jax.numpy as jnp

    from matchmaking_tpu.analysis import recompile

    calls = {"n": 0}

    def drifting(x):
        calls["n"] += 1
        return x + calls["n"]

    out = []
    recompile._drift(drifting, lambda v: (jnp.zeros(4),), "drifting",
                     "fixture", out)
    assert len(out) == 1 and "jaxpr drift" in out[0].message

    def stable(x):
        return x * 2.0

    out = []
    recompile._drift(stable, lambda v: (jnp.full(4, float(v)),), "stable",
                     "fixture", out)
    assert out == []


# ---- the gate itself -------------------------------------------------------

@pytest.mark.lint
def test_repo_is_clean():
    """The tier-1 lint node: the full analyzer (static rules + jaxpr-drift
    tracing) over the repo must report nothing outside the baseline —
    exactly what ``python -m matchmaking_tpu.analysis`` gates in CI."""
    new, _accepted, warnings = analyze_repo()
    assert not warnings, "\n".join(warnings)
    assert not new, "matchlint findings:\n" + "\n".join(
        f.render() for f in new)


def test_determinism_covers_deadline_propagation_arithmetic():
    """ISSUE 5 satellite: the rule covers the overload subsystem's new
    deadline shapes — header-subscript stores, aug-assigns, and
    deadline= keyword arguments computed from time.time()."""
    findings = analyze_source('''
import time

def faults(headers, submit):
    headers["x-deadline"] = time.time() + 5.0
    deadline = 10.0
    deadline += time.time()
    submit(deadline=time.time() + 1.0)
''', path="matchmaking_tpu/service/fixture.py")
    assert _rules(findings) == ["determinism"] * 3
    # The sanctioned shape: the one wall-clock read is a plain argument
    # and every derivation takes `now` as a parameter (overload.py).
    clean = analyze_source('''
def stamp_deadline(headers, now, budget_s):
    headers.setdefault("x-deadline", repr(now + budget_s))

def check(headers, now):
    raw = headers.get("x-deadline")
    return raw is not None and now >= float(raw)
''', path="matchmaking_tpu/service/fixture.py")
    assert clean == []


def test_determinism_covers_snapshot_interval_arithmetic():
    """ISSUE 6 satellite: the continuous-telemetry sampler added a
    schedule-shaped surface — next-snapshot / sample-due arithmetic born
    from time.time() is the same replay hazard as deadline math. The
    sanctioned shapes are asyncio.sleep cadence (no stored wake time) or
    time.monotonic(); time.time() stays legal as snapshot DATA."""
    findings = analyze_source('''
import time

class Sampler:
    def schedule(self, interval):
        self._next_snapshot = time.time() + interval
        sample_due = time.time() + interval
        if time.time() >= self._next_snapshot:
            return True
''', path="matchmaking_tpu/utils/fixture.py")
    assert _rules(findings) == ["determinism"] * 3
    clean = analyze_source('''
import time

class Sampler:
    def sample(self, ring):
        # wall clock as DATA (the ring timestamp), monotonic for cadence
        ring.append(time.time(), {"x": 1.0})
        self._next_snapshot = time.monotonic() + 1.0
''', path="matchmaking_tpu/utils/fixture.py")
    assert clean == []


def test_cross_class_guarded_by_checks_external_serialization():
    """ISSUE 7 satellite (PR 4 carry-over): a class declaring
    ``externally-serialized-by: <lock>`` arms method-CALL checking on
    every attribute guarded by that lock — an off-lock
    ``self.engine.remove(...)`` is now a finding, not a docstring
    violation; declared ``lock-free:`` reads stay exempt."""
    src = '''
import asyncio

# externally-serialized-by: _engine_lock
# lock-free: pool_size
class FakeEngine:
    def expire_deadlines(self, now):
        return []

    def pool_size(self):
        return 0

class Runtime:
    def __init__(self):
        self._engine_lock = asyncio.Lock()
        # guarded-by: _engine_lock
        self.engine = FakeEngine()

    async def bad(self, now):
        return self.engine.expire_deadlines(now)

    async def good_read(self):
        return self.engine.pool_size()

    async def good_locked(self, now):
        async with self._engine_lock:
            return self.engine.expire_deadlines(now)

    # holds-lock: _engine_lock
    def good_helper(self, now):
        return self.engine.expire_deadlines(now)
'''
    findings = analyze_source(src, path="matchmaking_tpu/service/fixture.py")
    guarded = [f for f in findings if f.rule == "guarded-by"]
    assert len(guarded) == 1
    assert "Runtime.bad" in guarded[0].context
    assert "externally-serialized-by" in guarded[0].message
    # Without the class declaration, calls through the attr are unchecked
    # (the pre-cross-class behavior — only mutations/stores were).
    undeclared = src.replace(
        "# externally-serialized-by: _engine_lock\n", "").replace(
        "# lock-free: pool_size\n", "")
    assert [f for f in analyze_source(
        undeclared, path="matchmaking_tpu/service/fixture.py")
        if f.rule == "guarded-by"] == []


def test_determinism_covers_edf_ordering_arithmetic():
    """ISSUE 7 satellite: the EDF window-cut ordering keys are a new
    schedule-shaped surface — a cut key born from time.time() makes
    window COMPOSITION depend on scheduler jitter. The sanctioned shape
    is a pure function of the message (stamped x-deadline header + the
    admission-cached delivery tier)."""
    findings = analyze_source('''
import time

def cut(pending, delivery):
    edf_key = (delivery.tier, time.time() + 0.2)
    cut_key = time.time() + 1.0
    return sorted(pending, key=lambda d: edf_key)
''', path="matchmaking_tpu/service/fixture.py")
    assert _rules(findings) == ["determinism"] * 2
    clean = analyze_source('''
def edf_key(item, deadline_of):
    _req, delivery = item
    deadline = deadline_of(delivery.properties.headers)
    return (delivery.tier,
            deadline if deadline is not None else float("inf"))
''', path="matchmaking_tpu/service/fixture.py")
    assert clean == []


def test_determinism_covers_lease_epoch_arithmetic():
    """ISSUE 17 satellite: lease/epoch fencing decides which host may
    write, so lease-deadline / epoch / ack-watermark / lag arithmetic
    born from time.time() would make FAILOVER (and the failover-soak's
    bit-identical transcript) a function of wall-clock jitter. The
    sanctioned shapes are a caller-passed ``now`` (time.monotonic() at
    the call site) and counter arithmetic."""
    findings = analyze_source('''
import time

class Lease:
    def renew_all(self, interval, acked):
        self.lease_deadline = time.time() + interval
        epoch = int(time.time())
        ack_seq = acked + time.time()
        lag_ms = (time.time() - self.sent_at) * 1e3
        return lag_ms
''', path="matchmaking_tpu/service/fixture.py")
    assert _rules(findings) == ["determinism"] * 4
    # The sanctioned shape (service/replication.py): every deadline is a
    # pure function of a caller-passed now; epochs/ack seqs are counters.
    clean = analyze_source('''
class Lease:
    def acquire(self, now, lease_s):
        self.lease_deadline = now + lease_s
        self.epoch += 1
        return self.epoch

    def pump(self, now, sent_at, acked):
        ack_seq = acked + 1
        lag_ms = (now - sent_at) * 1e3
        return ack_seq, lag_ms
''', path="matchmaking_tpu/service/fixture.py")
    assert clean == []


@pytest.mark.forensics
def test_determinism_covers_spine_seq_arithmetic():
    """ISSUE 18 satellite: the forensics spine's causal order IS its
    monotone counter seq — a spine/event/incident seq or capture
    schedule derived from time.time() would make the incident-soak's
    bit-identical transcript (and every postmortem timeline) a function
    of wall-clock jitter. The sanctioned clocks on a spine row are DATA
    fields (mono_ns, wall) that never feed the seq."""
    findings = analyze_source('''
import time

class Spine:
    def stamp(self, last):
        spine_seq = int(time.time() * 1e6)
        event_seq = last + time.time()
        self.next_capture = time.time() + 5.0
        mono_ns = time.time() * 1e9
        return spine_seq, event_seq, mono_ns
''', path="matchmaking_tpu/utils/fixture.py")
    assert _rules(findings) == ["determinism"] * 4
    # The sanctioned shape (utils/forensics.py): seq from a counter,
    # mono_ns from the monotonic clock, wall as plain display data.
    clean = analyze_source('''
import itertools
import time

class Spine:
    def __init__(self):
        self._seq = itertools.count(1)

    def stamp(self):
        spine_seq = next(self._seq)
        mono_ns = time.monotonic_ns()
        wall = time.time()
        return spine_seq, mono_ns, wall
''', path="matchmaking_tpu/utils/fixture.py")
    assert clean == []


@pytest.mark.net
def test_determinism_covers_retry_backoff_heartbeat_arithmetic():
    """ISSUE 20 satellite: the socket transport's reconnect schedule,
    heartbeat liveness verdict, and RTT-budgeted lease validity decide
    WHEN a peer is declared dead and WHEN a primary must fence — born
    from time.time() they make failover timing (and the soak's
    bit-identical transcript) a function of wall-clock jitter, and
    unseeded reconnect jitter makes two seeded runs dial on different
    schedules."""
    findings = analyze_source('''
import random
import time

class Conn:
    def dial_plan(self, attempt, base, rtt_samples):
        backoff = base * (2 ** attempt) * random.random()
        self.next_dial = time.time() + backoff
        self.next_heartbeat = time.time() + 0.05
        rtt_ms = (time.time() - self.sent_at) * 1e3
        valid_until = time.time() + self.lease_s
        retry_at = time.time() + 0.2
        return backoff
''', path="matchmaking_tpu/net/fixture.py")
    assert _rules(findings) == ["determinism"] * 6
    # The sanctioned shapes (net/transport.py, net/lease.py): jitter via
    # hash01(seed, "backoff", conn, attempt) — a pure function of the
    # connection identity — and every deadline from a caller-passed
    # time.monotonic() value.
    clean = analyze_source('''
from matchmaking_tpu.utils.chaos import hash01

class Conn:
    def dial_plan(self, now, attempt, base, cap, sent_at):
        d = min(cap, base * (2 ** attempt))
        backoff = d * (0.5 + 0.5 * hash01(self.seed, "backoff",
                                          self.name, attempt))
        self.next_dial = now + backoff
        self.next_heartbeat = now + 0.05
        rtt_ms = (now - sent_at) * 1e3
        valid_until = now + self.lease_s
        return backoff
''', path="matchmaking_tpu/net/fixture.py")
    assert clean == []


# ---- perf (ISSUE 8: O(pool)/O(matches) scans on the hot path) --------------

def test_perf_flags_pool_scan_in_hot_path_function():
    """A for-loop over a pool mirror column inside a hot-path-named
    function is the O(pool) wall the columnar path exists to avoid."""
    findings = analyze_source('''
class Engine:
    def _flush_window(self, now):
        total = 0.0
        for r in self.pool.m_rating:
            total += r
        return total
''', path="matchmaking_tpu/engine/fixture.py")
    assert _rules(findings) == ["perf"]
    assert "m_rating" in findings[0].message


def test_perf_flags_waiting_scan_and_full_column_asarray():
    findings = analyze_source('''
import numpy as np

class Engine:
    def _dispatch_cols(self, cols, now):
        ages = [now - r.enqueued_at for r in self.engine.waiting()]
        col = np.asarray(self.pool.m_enqueued)
        return ages, col
''', path="matchmaking_tpu/engine/fixture.py")
    assert sorted(_rules(findings)) == ["perf", "perf"]


def test_perf_flags_request_at_inside_loop():
    findings = analyze_source('''
class Engine:
    def _finalize_window(self, slots):
        return [self.pool.request_at(s) for s in slots]
''', path="matchmaking_tpu/engine/fixture.py")
    assert _rules(findings) == ["perf"]
    assert "request_at" in findings[0].message


def test_perf_accepts_vectorized_hot_path_and_cold_scans():
    """Indexed column reads (col[slots]) are the sanctioned vectorized
    form; window-sized loops are fine; and the same scan OUTSIDE a
    hot-path-named function (sweepers, eviction policy) is out of scope."""
    clean = analyze_source('''
import numpy as np

class Engine:
    def _finalize_columnar(self, qs, now):
        eff = np.maximum(0.0, now - self.pool.m_enqueued[qs])
        ids = self.pool.m_id[qs]
        return eff, ids

    def _flush_inner(self, window):
        return [req for req, _d in window]

    def _evict_policy(self):
        return sorted(self.engine.waiting(), key=lambda r: r.enqueued_at)
''', path="matchmaking_tpu/engine/fixture.py")
    assert clean == []


def test_perf_flags_per_delivery_header_parse_in_hot_loop():
    """ISSUE 9: a headers[...] subscript or headers.get(...) call inside a
    loop in a hot-path function is per-delivery wire work the
    window-granular path removed — parse once at admission, cache on the
    Delivery."""
    findings = analyze_source('''
class Runtime:
    def _flush_columnar(self, deliveries, now):
        tiers = []
        for d in deliveries:
            tiers.append(int(d.properties.headers["x-tier"]))
        return tiers

    def _handle_columnar_out(self, out, deliveries, now):
        return [d.properties.headers.get("x-deadline") for d in deliveries]
''', path="matchmaking_tpu/service/fixture.py")
    assert sorted(_rules(findings)) == ["perf", "perf"]
    assert "header parse" in findings[0].message
    # The cached read (no header touch) is the sanctioned form.
    clean = analyze_source('''
class Runtime:
    def _flush_columnar(self, deliveries, now):
        return [(d.tier, d.deadline) for d in deliveries]

    def _on_delivery(self, delivery):
        # Not hot-path-named: the once-per-delivery admission parse site.
        return delivery.properties.headers.get("x-tier")
''', path="matchmaking_tpu/service/fixture.py")
    assert clean == []


def test_perf_flags_per_element_encode_response_in_hot_loop():
    """ISSUE 9: encode_response() per element inside _flush_*/_handle_*
    is the egress hot loop the native batch encoder replaced."""
    findings = analyze_source('''
from matchmaking_tpu.service.contract import encode_response

class Runtime:
    def _handle_columnar_out(self, out, responses):
        return [encode_response(r) for r in responses]
''', path="matchmaking_tpu/service/fixture.py")
    assert _rules(findings) == ["perf"]
    assert "encode_response" in findings[0].message
    # Outside a loop (one-off response) it is fine, as is the batch call.
    clean = analyze_source('''
from matchmaking_tpu.service.contract import encode_response
from matchmaking_tpu.native import codec

class Runtime:
    def _handle_columnar_out(self, out, resp, rows):
        bodies = codec.encode_simple_batch(*rows)
        return encode_response(resp)
''', path="matchmaking_tpu/service/fixture.py")
    assert clean == []


def test_perf_inline_ignore_with_reason_suppresses():
    body = '''
class Engine:
    def _finalize_window(self, slots):
        return [self.pool.request_at(s) for s in slots]  # matchlint: ignore[perf] object path by contract
'''
    assert analyze_source(
        body, path="matchmaking_tpu/engine/fixture.py") == []


# ---- settlement (ISSUE 10: flow-sensitive exactly-once typestate) ----------

def test_settlement_credit_leak_on_exception_path_at_exact_line():
    """The flagship planted bug: an exception edge between admission.admit
    and the release handler leaks a credit — caught at the exact line of
    the statement whose raise escapes while the credit is held."""
    findings = analyze_source('''
class Runtime:
    async def handle(self, delivery):
        self.admission.admit(delivery.delivery_tag, delivery.tier)
        ctx = self.make_context(delivery)
        try:
            await self.pipeline.run(ctx)
        except BaseException:
            self.admission.release(delivery.delivery_tag)
            raise
        self.batcher.submit((None, delivery))
''', path="matchmaking_tpu/service/fixture.py")
    leaks = [f for f in findings if f.rule == "settlement"]
    assert {f.line for f in leaks} == {5, 11}, findings
    assert all("credit leak" in f.message for f in leaks)
    # line 5: make_context raising leaks; line 11: submit outside the try.


def test_settlement_accepts_fully_wrapped_admit_region():
    findings = analyze_source('''
class Runtime:
    async def handle(self, delivery):
        self.admission.admit(delivery.delivery_tag, delivery.tier)
        try:
            ctx = self.make_context(delivery)
            await self.pipeline.run(ctx)
            self.batcher.submit((None, delivery))
        except BaseException:
            self.admission.release(delivery.delivery_tag)
            raise
''', path="matchmaking_tpu/service/fixture.py")
    assert [f for f in findings if f.rule == "settlement"] == []


def test_settlement_double_ack_across_helper_call_at_exact_line():
    findings = analyze_source('''
class Runtime:
    # settles: delivery
    def _ack(self, delivery):
        self.app.broker.ack(self.consumer_tag, delivery.delivery_tag)
        self.admission.release(delivery.delivery_tag)

    # settles: delivery
    def _shed(self, delivery):
        self.respond(delivery)
        self._ack(delivery)

    def finish(self, delivery):
        self._shed(delivery)
        self._ack(delivery)
''', path="matchmaking_tpu/service/fixture.py")
    doubles = [f for f in findings if f.rule == "settlement"]
    assert len(doubles) == 1, findings
    assert doubles[0].line == 15
    assert "double-settle" in doubles[0].message
    assert doubles[0].context == "Runtime.finish"


def test_settlement_collection_contract_and_vacuous_empty_shape():
    """`# settles: *deliveries` demands settlement before a normal return;
    the `if not window: return` emptiness shape and a settling loop both
    discharge it — an unrelated early return does not."""
    clean = analyze_source('''
class Runtime:
    # settles: delivery
    def _ack(self, delivery):
        self.app.broker.ack(self.consumer_tag, delivery.delivery_tag)

    # settles: *deliveries
    def _shed_all(self, deliveries):
        metas = []
        for d in deliveries:
            tr = self.trace(d)
            metas.append((d, tr))
        if not metas:
            return
        self.publish_batch(metas)
        for d, tr in metas:
            self._ack(d)
''', path="matchmaking_tpu/service/fixture.py")
    assert [f for f in clean if f.rule == "settlement"] == []
    leak = analyze_source('''
class Runtime:
    # settles: delivery
    def _ack(self, delivery):
        self.app.broker.ack(self.consumer_tag, delivery.delivery_tag)

    # settles: *deliveries
    def _shed_all(self, deliveries):
        if self.closed:
            return
        for d in deliveries:
            self._ack(d)
''', path="matchmaking_tpu/service/fixture.py")
    leaks = [f for f in leak if f.rule == "settlement"]
    assert len(leaks) == 1 and leaks[0].line == 10, leak
    assert "window leak" in leaks[0].message


def test_settlement_escape_to_window_meta_is_a_handoff():
    """Storing the window's pairs/deliveries into inflight meta transfers
    ownership (collection settles at collection time) — no finding."""
    findings = analyze_source('''
class Runtime:
    # settles: *pairs
    async def _dispatch(self, pairs, now):
        deliveries_in = [d for _, d in pairs]
        tok = await self.to_thread(self.engine.go)
        self._inflight_meta[tok] = (dict(pairs), deliveries_in)
''', path="matchmaking_tpu/service/fixture.py")
    assert [f for f in findings if f.rule == "settlement"] == []


def test_settlement_flush_return_contract_refinement():
    """ISSUE 12: a loop over the result of ``to_thread(closure)`` where
    the closure dispatches a window and returns ``engine.flush()`` runs
    its body EXACTLY ONCE (depth-1/never-empty flush() return contract) —
    settling the window's deliveries inside it is neither a double-settle
    (no second iteration) nor conditional (no zero-iteration path). The
    exact shape the two retired ``ignore[settlement]`` comments covered
    in _flush_columnar's non-pipelined branch."""
    clean = analyze_source('''
class Runtime:
    # settles: delivery
    def _ack(self, delivery):
        self.app.broker.ack(self.consumer_tag, delivery.delivery_tag)

    # settles: *deliveries
    def _handle_out(self, out, deliveries, now):
        for d in deliveries:
            self._ack(d)

    # settles: *deliveries
    async def _flush_sync(self, cols, deliveries, now):
        def run_engine():
            self.engine.search_columns_async(cols, now)
            return self.engine.flush()

        outs = await asyncio.to_thread(run_engine)
        for tok, out in outs:
            self._handle_out(out, deliveries, now)
        return
''', path="matchmaking_tpu/service/fixture.py")
    assert [f for f in clean if f.rule == "settlement"] == [], clean
    # The refinement is value-flow-narrow: a flush() WITHOUT the dispatch
    # in the same closure (a drain — 0..depth windows) keeps both paths,
    # so the conditional settlement is still reported.
    dirty = analyze_source('''
class Runtime:
    # settles: delivery
    def _ack(self, delivery):
        self.app.broker.ack(self.consumer_tag, delivery.delivery_tag)

    # settles: *deliveries
    def _handle_out(self, out, deliveries, now):
        for d in deliveries:
            self._ack(d)

    # settles: *deliveries
    async def _drain(self, deliveries, now):
        def collect():
            return self.engine.flush()

        outs = await asyncio.to_thread(collect)
        for tok, out in outs:
            self._handle_out(out, deliveries, now)
        return
''', path="matchmaking_tpu/service/fixture.py")
    assert [f for f in dirty if f.rule == "settlement"], dirty


def test_settlement_admit_loop_without_settle_leaks_per_iteration():
    findings = analyze_source('''
class Runtime:
    def admit_all(self, deliveries):
        for d in deliveries:
            self.admission.admit(d.delivery_tag)
''', path="matchmaking_tpu/service/fixture.py")
    leaks = [f for f in findings if f.rule == "settlement"
             and "credit leak" in f.message]
    assert leaks, findings
    assert any(f.line in (3, 4) for f in leaks)


def test_settlement_length_parallel_filter_refinement():
    """ISSUE 13 satellite: two locals filtered by the SAME predicate —
    a mask-vector ``take`` on the column plane and an ``if``-filtered
    comprehension on the delivery plane — keep row-parallel residues, so
    an emptiness test on one vacuously settles the other's group too (the
    empty-residue shape that carried _flush_columnar's last inline
    ignore). The refinement is value-flow-narrow: breaking the predicate
    identity (planted bug below) keeps the window-leak finding."""
    clean = analyze_source('''
import numpy as np

class Runtime:
    # settles: *deliveries
    def _handle_out(self, out, deliveries, now):
        for d in deliveries:
            self.app.broker.ack(self.tag, d.delivery_tag)

    # settles: *deliveries
    def _flush(self, cols, deliveries, keep, now):
        drop = self._pay_debt(keep)
        if drop:
            mask = np.fromiter(
                (pid not in drop for pid in cols.ids.tolist()),
                bool, len(cols))
            cols = cols.take(mask)
            deliveries_in = [deliveries[s] for s, pid, _ in keep
                             if pid not in drop]
            if not len(cols):
                return
        out = self.engine.go(cols)
        self._handle_out(out, deliveries, now)
''', path="matchmaking_tpu/service/fixture.py")
    assert [f for f in clean if f.rule == "settlement"] == [], clean
    # Planted bug: the delivery-plane filter tests a DIFFERENT set
    # (dropped vs drop) — the residues are no longer length-parallel, so
    # `len(cols) == 0` proves nothing about the deliveries and the
    # window-leak report at the early return must survive.
    dirty = analyze_source('''
import numpy as np

class Runtime:
    # settles: *deliveries
    def _handle_out(self, out, deliveries, now):
        for d in deliveries:
            self.app.broker.ack(self.tag, d.delivery_tag)

    # settles: *deliveries
    def _flush(self, cols, deliveries, keep, now):
        drop = self._pay_debt(keep)
        dropped = self._other_set(keep)
        if drop:
            mask = np.fromiter(
                (pid not in drop for pid in cols.ids.tolist()),
                bool, len(cols))
            cols = cols.take(mask)
            deliveries_in = [deliveries[s] for s, pid, _ in keep
                             if pid not in dropped]
            if not len(cols):
                return
        out = self.engine.go(cols)
        self._handle_out(out, deliveries, now)
''', path="matchmaking_tpu/service/fixture.py")
    leaks = [f for f in dirty if f.rule == "settlement"
             and "window leak" in f.message]
    assert leaks and leaks[0].line == 22, dirty
    # Same-plane pairs never link: two plain comprehensions can share a
    # predicate TEXT while filtering different base collections (lengths
    # unrelated), so `not a` proves nothing about b — the leak report
    # must survive.
    same_plane = analyze_source('''
class Runtime:
    # settles: *deliveries
    def _shed(self, deliveries, xs, ys, drop):
        a = [pid for pid in xs if pid not in drop]
        b = [deliveries[s] for s, pid, _ in ys if pid not in drop]
        if not a:
            return
        self.publish_batch(b)
''', path="matchmaking_tpu/service/fixture.py")
    assert [f for f in same_plane if f.rule == "settlement"
            and "window leak" in f.message], same_plane


def test_settlement_flush_columnar_empty_residue_ignore_retired():
    """The last matchlint inline ignore in service/app.py (the
    empty-residue ``len(cols)``↔deliveries parallelism) is retired: the
    tree carries NO ignore[settlement] comments and the settlement rule
    is clean over the live file."""
    import pathlib

    src = pathlib.Path("matchmaking_tpu/service/app.py").read_text()
    assert "ignore[settlement]" not in src
    new, _accepted, _warnings = analyze_repo(
        dynamic=False, rules={"settlement"}, use_cache=False)
    assert [f for f in new if f.rule == "settlement"
            and "app.py" in f.path] == [], new


# ---- lock-pairing ----------------------------------------------------------

def test_lock_pairing_flags_unbalanced_paths_and_accepts_try_finally():
    findings = analyze_source('''
class Runtime:
    def bad(self):
        self._pool_lock.acquire()
        self.step()
        self._pool_lock.release()

    def good(self):
        self._pool_lock.acquire()
        try:
            self.step()
        finally:
            self._pool_lock.release()
''', path="matchmaking_tpu/service/fixture.py")
    pairs = [f for f in findings if f.rule == "lock-pairing"]
    assert len(pairs) == 1, findings
    assert pairs[0].context == "Runtime.bad"
    assert "exception path" in pairs[0].message


# ---- device (ISSUE 10: jaxpr device-path audit) ----------------------------

def test_device_flags_host_item_inside_kernel_module_at_exact_line():
    findings = analyze_source('''
import jax

class KS:
    def _search_step(self, pool, batch, now):
        cap = pool["rating"].item()
        return pool
''', path="matchmaking_tpu/engine/kernels.py")
    dev = [f for f in findings if f.rule == "device"]
    assert len(dev) == 1 and dev[0].line == 6, findings
    assert ".item()" in dev[0].message


def test_device_init_host_setup_is_exempt():
    findings = analyze_source('''
import numpy as np

class KS:
    def __init__(self, edges):
        self._edges = np.asarray(edges)
''', path="matchmaking_tpu/engine/kernels.py")
    assert [f for f in findings if f.rule == "device"] == []


def test_device_use_after_donation_flagged_and_rebind_accepted():
    bad = analyze_source('''
class Engine:
    def step(self, packed):
        pool2, out = self.kernels.search_step_packed(self._dev_pool, packed)
        stale = self._dev_pool["rating"]
        self._dev_pool = pool2
        return out, stale
''', path="matchmaking_tpu/engine/fixture.py")
    dev = [f for f in bad if f.rule == "device"]
    assert len(dev) == 1 and dev[0].line == 5, bad
    assert "DONATED" in dev[0].message
    good = analyze_source('''
class Engine:
    def step(self, packed):
        self._dev_pool, out = self.kernels.search_step_packed(
            self._dev_pool, packed)
        fresh = self._dev_pool["rating"]
        return out, fresh
''', path="matchmaking_tpu/engine/fixture.py")
    assert [f for f in good if f.rule == "device"] == []


def test_device_padded_lane_taint_catches_unmasked_accumulator():
    """The QualityAccumKernel shape: masked lanes carry the +inf dist
    sentinel; a float-mask MULTIPLY is not a sanitizer (0 x inf = NaN) —
    only a validity select is."""
    import jax.numpy as jnp

    from matchmaking_tpu.analysis import device_audit

    def bad_accum(state, out):
        dist = out[2]
        q_slot = out[0].astype(jnp.int32)
        valid = q_slot < 64
        rf = valid.astype(jnp.float32)
        return state + (rf * dist).sum()

    bad = device_audit.check_padded_lanes(
        bad_accum, (jnp.zeros(()), jnp.zeros((3, 8))), 1, "bad_accum")
    assert len(bad) == 1 and "padded-lane contamination" in bad[0].message

    def good_accum(state, out):
        dist = out[2]
        q_slot = out[0].astype(jnp.int32)
        valid = q_slot < 64
        d = jnp.where(valid, dist, 0.0)
        rf = valid.astype(jnp.float32)
        return state + (rf * d).sum()

    assert device_audit.check_padded_lanes(
        good_accum, (jnp.zeros(()), jnp.zeros((3, 8))), 1,
        "good_accum") == []


def test_device_dtype_drift_detected_via_eval_shape():
    import jax.numpy as jnp

    from matchmaking_tpu.analysis import device_audit

    def upcast_step(pool, packed):
        return dict(pool, rating=pool["rating"].astype(jnp.float16)), packed

    out = []
    device_audit._check_pool_preserved(
        upcast_step, "fixture.step", "ctx",
        {"rating": jnp.zeros(4, jnp.float32)}, (jnp.zeros(3),), out)
    assert len(out) == 1 and "dtype drift" in out[0].message

    def identity_step(pool, packed):
        return pool, packed

    clean = []
    device_audit._check_pool_preserved(
        identity_step, "fixture.id", "ctx",
        {"rating": jnp.zeros(4, jnp.float32)}, (jnp.zeros(3),), clean)
    assert clean == []


def test_device_ring_audit_rejects_split_permutation():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax
    from jax.sharding import Mesh, PartitionSpec as P

    from matchmaking_tpu.analysis import device_audit

    mesh = Mesh(np.array(jax.devices()[:4]), ("pool",))

    def bad_ring(x):
        perm = [(0, 1), (1, 0), (2, 3), (3, 2)]  # two 2-cycles, no ring
        return lax.ppermute(x, "pool", perm)

    f = jax.shard_map(bad_ring, mesh=mesh, in_specs=P("pool"),
                      out_specs=P("pool"), check_vma=False)
    closed = jax.make_jaxpr(f)(jnp.zeros(8))
    out = []
    device_audit._check_ring(closed, 4, "fixture.ring", "ctx", out)
    assert len(out) == 1 and "not a single" in out[0].message

    def good_ring(x):
        perm = [(i, (i + 1) % 4) for i in range(4)]
        return lax.ppermute(x, "pool", perm)

    g = jax.shard_map(good_ring, mesh=mesh, in_specs=P("pool"),
                      out_specs=P("pool"), check_vma=False)
    clean = []
    device_audit._check_ring(jax.make_jaxpr(g)(jnp.zeros(8)), 4,
                             "fixture.ring", "ctx", clean)
    assert clean == []


# ---- stale-ignore (suppression hygiene) ------------------------------------

def test_stale_ignore_reports_dead_suppressions_and_spares_live_ones():
    live = '''
import time

async def handler():
    # matchlint: ignore[blocking-call] admin endpoint, bounded one-shot
    time.sleep(0.1)
'''
    assert analyze_source(live,
                          path="matchmaking_tpu/service/fixture.py") == []
    dead = '''
import asyncio

async def handler():
    # matchlint: ignore[blocking-call] nothing blocking here anymore
    await asyncio.sleep(0.1)
'''
    findings = analyze_source(dead,
                              path="matchmaking_tpu/service/fixture.py")
    assert _rules(findings) == ["stale-ignore"]
    assert findings[0].line == 5
    assert "no longer suppresses" in findings[0].message


def test_stale_ignore_skips_ignore_syntax_inside_strings():
    findings = analyze_source('''
DOC = """
    # matchlint: ignore[blocking-call] this is documentation, not a comment
"""
''', path="matchmaking_tpu/service/fixture.py")
    assert findings == []


# ---- tooling: --format=json + cache ----------------------------------------

def test_cli_json_format_is_machine_readable(capsys):
    import json as _json

    from matchmaking_tpu.analysis.engine import main

    rc = main(["--static-only", "--no-cache", "--format=json"])
    out = capsys.readouterr().out
    data = _json.loads(out)
    assert set(data) == {"findings", "baselined", "warnings"}
    assert rc == (1 if data["findings"] else 0)


def test_result_cache_replays_findings_for_unchanged_files(tmp_path):
    import json as _json

    from matchmaking_tpu.analysis import engine as _engine

    root = tmp_path / "repo"
    (root / "matchmaking_tpu" / "analysis").mkdir(parents=True)
    # A tiny one-file tree with a known finding.
    (root / "matchmaking_tpu" / "service").mkdir(parents=True)
    (root / "matchmaking_tpu" / "service" / "fix.py").write_text(
        "import time\n\n\nasync def handler():\n    time.sleep(0.1)\n")
    new1, _, _ = _engine.analyze_repo(str(root), dynamic=False)
    assert [f.rule for f in new1] == ["blocking-call"]
    cache = _json.loads((root / ".matchlint_cache.json").read_text())
    assert "matchmaking_tpu/service/fix.py" in cache["files"]
    # Second run replays from cache, byte-identical findings.
    new2, _, _ = _engine.analyze_repo(str(root), dynamic=False)
    assert [(f.rule, f.path, f.line) for f in new1] == \
        [(f.rule, f.path, f.line) for f in new2]


# ---- review regressions: finally routing + suppression hygiene -------------

def test_settlement_release_in_finally_balances_every_path():
    """try/except-reraise/finally with the release in the finally is the
    canonical balanced shape: handler raises route THROUGH the finally
    (regression: the CFG once sent them past it), and the re-raise after
    an exceptionally-entered finally carries the post-release state."""
    findings = analyze_source('''
class Runtime:
    async def handle(self, delivery):
        self.admission.admit(delivery.delivery_tag)
        try:
            await self.pipeline.run(delivery)
        except BaseException:
            self.log()
            raise
        finally:
            self.admission.release(delivery.delivery_tag)
''', path="matchmaking_tpu/service/fixture.py")
    assert [f for f in findings if f.rule == "settlement"] == []


def test_lock_pairing_release_in_finally_with_typed_handler():
    findings = analyze_source('''
class Runtime:
    def locked(self):
        self._pool_lock.acquire()
        try:
            self.step()
        except ValueError:
            self.log()
            raise
        finally:
            self._pool_lock.release()
''', path="matchmaking_tpu/service/fixture.py")
    assert [f for f in findings if f.rule == "lock-pairing"] == []


def test_settlement_branch_header_gets_no_exception_edge():
    """A branch whose BODY contains calls must not leak at the header:
    evaluating `self.flag` cannot raise (regression: may_raise once
    walked the whole compound statement)."""
    findings = analyze_source('''
class Runtime:
    def handle(self, delivery):
        self.admission.admit(delivery.delivery_tag)
        if self.flag:
            self.admission.release(delivery.delivery_tag)
        else:
            self.admission.release(delivery.delivery_tag)
''', path="matchmaking_tpu/service/fixture.py")
    assert [f for f in findings if f.rule == "settlement"] == []


def test_stale_ignore_findings_are_themselves_suppressible():
    findings = analyze_source('''
import asyncio

async def handler():
    # matchlint: ignore[stale-ignore] kept for a pending revert
    # matchlint: ignore[blocking-call] nothing blocking here anymore
    await asyncio.sleep(0.1)
''', path="matchmaking_tpu/service/fixture.py")
    assert findings == []


# ---- settlement guard-flag refinement (ISSUE 11 satellite) -----------------

_RECORDED_SHAPE = '''
class Runtime:
    # settles: *extra_nack
    async def _revive_pipelined(self, now, extra_nack=None):
        for d in extra_nack or ():
            self._nack(d)

    # settles: delivery
    def _nack(self, delivery):
        self.app.broker.nack(self.tag, delivery.delivery_tag)

    # settles: *pairs
    async def dispatch(self, pairs, now):
        recorded = False
        deliveries_in = [d for _, d in pairs]
        try:
            tok = self.launch(deliveries_in)
            self._inflight_meta[tok] = (dict(pairs), deliveries_in)
            recorded = True
            self.collect(now)
        except Exception:
            await self._revive_pipelined(
                now, extra_nack=None if recorded else deliveries_in)
            return
'''


def test_settlement_guard_flag_refinement_proves_recorded_shape():
    """The PR 10 inline ignore at the `recorded` seam is retired: a bool
    flag whose ONLY True-assignment immediately follows the window-meta
    hand-off correlates exactly with the group's escape, so the
    `None if flag else group` settle argument is exactly-once on every
    path — no conditional-settlement finding."""
    findings = analyze_source(_RECORDED_SHAPE,
                              path="matchmaking_tpu/service/fixture.py")
    assert [f for f in findings if f.rule == "settlement"] == [], findings


def test_settlement_uncorrelated_guard_flag_still_flags():
    """Move `recorded = True` BEFORE the hand-off and the correlation is
    broken (an exception between flag-set and hand-off reaches the
    handler with flag True and the window NOT escaped — nothing would
    settle it): the refinement must not fire, and the possible
    double-settle report survives."""
    broken = _RECORDED_SHAPE.replace(
        "            self._inflight_meta[tok] = (dict(pairs), deliveries_in)\n"
        "            recorded = True\n",
        "            recorded = True\n"
        "            self._inflight_meta[tok] = (dict(pairs), deliveries_in)\n")
    findings = [f for f in analyze_source(
        broken, path="matchmaking_tpu/service/fixture.py")
        if f.rule == "settlement"]
    assert findings, "uncorrelated flag must still report"
    assert any("double-settle" in f.message for f in findings)


def test_settlement_refined_shape_with_leftover_ignore_reads_stale():
    """A now-redundant `# matchlint: ignore[settlement]` on the refined
    shape suppresses nothing — the stale-ignore rule reports it (this is
    how the retired app.py ignore was found and removed)."""
    with_ignore = _RECORDED_SHAPE.replace(
        "            await self._revive_pipelined(",
        "            # matchlint: ignore[settlement] retired by the "
        "guard-flag refinement\n"
        "            await self._revive_pipelined(")
    findings = analyze_source(with_ignore,
                              path="matchmaking_tpu/service/fixture.py")
    stale = [f for f in findings if f.rule == "stale-ignore"]
    assert stale, findings


def test_settlement_rule_covers_control_package():
    """ISSUE 11: control/ joined the settlement/lock-pairing scope — a
    credit-leak shape placed there must report exactly as in service/."""
    code = '''
class Executor:
    async def handle(self, delivery):
        self.admission.admit(delivery.delivery_tag, delivery.tier)
        ctx = self.make_context(delivery)
        self.batcher.submit((None, delivery))
'''
    findings = [f for f in analyze_source(
        code, path="matchmaking_tpu/control/fixture.py")
        if f.rule == "settlement"]
    assert findings and any("credit leak" in f.message for f in findings)


# ---- speculation rule (ISSUE 16) ------------------------------------------


def test_speculation_flags_commit_without_validate():
    findings = analyze_source('''
class Runtime:
    def cut(self, now):
        self.engine.spec_commit(self.engine.pool_mutations, now)
''', path="matchmaking_tpu/service/fixture.py")
    spec = [f for f in findings if f.rule == "speculation"]
    assert spec and "without a live spec_validate" in spec[0].message
    assert spec[0].context == "Runtime.cut"


def test_speculation_flags_validate_after_mutate():
    findings = analyze_source('''
class Runtime:
    def cut(self, now):
        tok = self.engine.spec_validate(now)
        self.engine.remove("p0")          # mutation between the pair
        self.engine.spec_commit(tok, now)
''', path="matchmaking_tpu/service/fixture.py")
    assert [f for f in findings if f.rule == "speculation"], findings


def test_speculation_accepts_adjacent_validate_commit():
    findings = analyze_source('''
class Runtime:
    def cut(self, now):
        tok = self.engine.spec_validate(now, max_age_s=0.5)
        if tok is not None:
            self.engine.spec_commit(tok, now)
        self.engine.rescan_async(16, now)  # AFTER the commit: fine
''', path="matchmaking_tpu/service/fixture.py")
    assert [f for f in findings if f.rule == "speculation"] == []


def test_speculation_commit_consumes_its_validation():
    findings = analyze_source('''
class Runtime:
    def cut(self, now):
        tok = self.engine.spec_validate(now)
        self.engine.spec_commit(tok, now)
        self.engine.spec_commit(tok, now)  # second commit: stale token
''', path="matchmaking_tpu/service/fixture.py")
    assert len([f for f in findings if f.rule == "speculation"]) == 1


def test_speculation_nested_def_gets_fresh_state():
    findings = analyze_source('''
class Runtime:
    def outer(self, now):
        tok = self.engine.spec_validate(now)

        def later():
            self.engine.spec_commit(tok, now)  # runs on its own schedule
''', path="matchmaking_tpu/service/fixture.py")
    assert [f for f in findings if f.rule == "speculation"], findings


# ---- protocol: fence dominance ---------------------------------------------

@pytest.mark.protocol
def test_protocol_fence_flags_unchecked_append():
    findings = analyze_source('''
class Journal:
    # protocol-effect: journal_append requires-fence fence
    def _append(self, payload):
        self.seq += 1
        return self.seq
''', path="matchmaking_tpu/utils/fixture.py")
    assert _rules(findings) == ["protocol"]
    assert "not fence-dominated" in findings[0].message
    assert findings[0].line == 5


@pytest.mark.protocol
def test_protocol_fence_accepts_checked_append():
    findings = analyze_source('''
class Journal:
    # protocol-effect: journal_append requires-fence fence
    def _append(self, payload):
        if self.fence is not None and not self.fence():
            raise RuntimeError("fenced")
        self.seq += 1
        return self.seq
''', path="matchmaking_tpu/utils/fixture.py")
    assert findings == []


@pytest.mark.protocol
def test_protocol_fence_catches_exception_path_leak():
    """A handler entered from BEFORE the fence check reaches the append
    with the pre-check state — the classic try/except bypass."""
    findings = analyze_source('''
class Journal:
    # protocol-effect: journal_append requires-fence fence
    def _append(self, payload):
        try:
            frame = self.encode(payload)
            if not self.fence():
                raise RuntimeError("fenced")
            self.seq += 1
        except ValueError:
            self.seq += 1
        return self.seq
''', path="matchmaking_tpu/utils/fixture.py")
    assert _rules(findings) == ["protocol"]
    assert findings[0].line == 11


# ---- protocol: bounded-by / requires-check ---------------------------------

@pytest.mark.protocol
def test_protocol_bounded_by_flags_foreign_watermark():
    findings = analyze_source('''
class Applier:
    # protocol-effect: standby_ack bounded-by applied_seq
    def pump(self):
        for rec in self.link.recv():
            self.apply(rec)
        self.link.ack(self.link.max_delivered)
''', path="matchmaking_tpu/service/fixture.py")
    assert _rules(findings) == ["protocol"]
    assert "not bounded by 'applied_seq'" in findings[0].message
    assert "max_delivered" in findings[0].message


@pytest.mark.protocol
def test_protocol_bounded_by_accepts_declared_watermark():
    findings = analyze_source('''
class Applier:
    # protocol-effect: standby_ack bounded-by applied_seq
    def pump(self):
        for rec in self.link.recv():
            self.apply(rec)
        self.link.ack(self.applied_seq)
''', path="matchmaking_tpu/service/fixture.py")
    assert findings == []


@pytest.mark.protocol
def test_protocol_requires_check_flags_discarded_renewal():
    findings = analyze_source('''
class Repl:
    # protocol-effect: lease_renewal requires-check renew
    def pump(self, now):
        self.authority.renew(self.queue, self.owner, self.epoch, now)
''', path="matchmaking_tpu/service/fixture.py")
    assert _rules(findings) == ["protocol"]
    assert "result discarded" in findings[0].message


@pytest.mark.protocol
def test_protocol_requires_check_accepts_tested_renewal():
    findings = analyze_source('''
class Repl:
    # protocol-effect: lease_renewal requires-check renew
    def pump(self, now):
        if not self.authority.renew(self.queue, self.owner, self.epoch,
                                    now):
            self.refuse()
''', path="matchmaking_tpu/service/fixture.py")
    assert findings == []


# ---- protocol: role state machine ------------------------------------------

@pytest.mark.protocol
def test_protocol_role_machine_flags_every_illegal_shape():
    findings = analyze_source('''
# protocol-role: primary -> fenced
class Repl:
    def __init__(self):
        self.role = "fenced"

    def fence(self):
        self.role = self.compute()

    def resume(self):
        self.role = "primary"

    def zombie(self):
        self.role = "zombie"
''', path="matchmaking_tpu/service/fixture.py")
    assert _rules(findings) == ["protocol"] * 4
    msgs = "\n".join(f.message for f in findings)
    assert "must bind the start state 'primary'" in msgs
    assert "literal state name" in msgs
    assert "role regression" in msgs
    assert "undeclared role state 'zombie'" in msgs


@pytest.mark.protocol
def test_protocol_role_machine_accepts_forward_transitions():
    findings = analyze_source('''
# protocol-role: primary -> fenced
class Repl:
    def __init__(self):
        self.role = "primary"

    def fence(self):
        self.role = "fenced"
''', path="matchmaking_tpu/service/fixture.py")
    assert findings == []


# ---- protocol: monotone watermarks -----------------------------------------

@pytest.mark.protocol
def test_protocol_monotone_flags_rewind_scale_and_unguarded():
    findings = analyze_source('''
# protocol-monotone: seq, acked_seq
class Journal:
    def __init__(self):
        self.seq = 0
        self.acked_seq = 0

    def rewind(self):
        self.seq = self.seq - 1

    def double(self):
        self.seq *= 2

    def unguarded(self, a):
        self.acked_seq = a
''', path="matchmaking_tpu/utils/fixture.py")
    assert _rules(findings) == ["protocol"] * 3
    msgs = "\n".join(f.message for f in findings)
    assert "rewound from its own value" in msgs
    assert "mutated with Mult" in msgs
    assert "non-monotone rebind of watermark 'acked_seq'" in msgs


@pytest.mark.protocol
def test_protocol_monotone_accepts_guarded_flag_and_max_advances():
    findings = analyze_source('''
# protocol-monotone: acked_seq, sent_seq, synced_seq
class Repl:
    def __init__(self):
        self.acked_seq = 0
        self.sent_seq = 0
        self.synced_seq = 0

    def guarded(self, a):
        if a > self.acked_seq:
            self.acked_seq = a

    def flagged(self, a):
        progress = a > self.acked_seq
        if progress:
            self.acked_seq = a

    def maxed(self, written):
        self.synced_seq = max(self.synced_seq, written)

    def bump(self):
        self.sent_seq += 1
''', path="matchmaking_tpu/service/fixture.py")
    assert findings == []


@pytest.mark.protocol
def test_protocol_rebase_annotation_admits_the_apply_seam():
    findings = analyze_source('''
# protocol-monotone: applied_seq
class Applier:
    def __init__(self):
        self.applied_seq = 0

    def _apply(self, seq, rec):
        # protocol-rebase: callers admit only the contiguous next seq
        self.applied_seq = seq
''', path="matchmaking_tpu/service/fixture.py")
    assert findings == []


@pytest.mark.protocol
def test_protocol_rebase_without_covered_store_reads_stale():
    findings = analyze_source('''
# protocol-monotone: applied_seq
class Applier:
    def __init__(self):
        self.applied_seq = 0

    def peek(self, seq):
        # protocol-rebase: nothing on the next line stores a watermark
        return seq
''', path="matchmaking_tpu/service/fixture.py")
    assert _rules(findings) == ["protocol"]
    assert "stale protocol-rebase" in findings[0].message


# ---- protocol: annotation hygiene ------------------------------------------

@pytest.mark.protocol
def test_protocol_annotation_hygiene_parse_unknown_and_stale():
    findings = analyze_source('''
# protocol-role: primary
class A:
    pass


# protocol-lease: primary -> fenced
class B:
    # protocol-effect: journal_append requires-fence fence
    def helper(self):
        return 1
''', path="matchmaking_tpu/service/fixture.py")
    assert _rules(findings) == ["protocol"] * 3
    msgs = "\n".join(f.message for f in findings)
    assert "wants 'state -> state" in msgs
    assert "unknown protocol annotation 'protocol-lease:'" in msgs
    assert "stale protocol-effect" in msgs


@pytest.mark.protocol
def test_protocol_undeclared_effect_sweep_pins_sibling_methods():
    """A class that declares response_publish on the funnel cannot grow
    a second publish path without its own annotation (the _respond_error
    shape this PR routed through the funnel)."""
    findings = analyze_source('''
class App:
    # protocol-effect: response_publish requires-fence may_publish
    def _publish_body(self, body):
        if self.may_publish():
            self.broker.publish(body)

    def _respond_error(self, body):
        self.broker.publish(body)
''', path="matchmaking_tpu/service/fixture.py")
    assert _rules(findings) == ["protocol"]
    assert "undeclared protocol effect" in findings[0].message
    assert "_respond_error" in findings[0].message


# ---- protocol: record-type vocabulary --------------------------------------

@pytest.mark.protocol
def test_protocol_vocab_collision_flags_both_definers():
    findings = analyze_source('''
RT_ADMIT = 1
RT_TERMINAL = 1
RT_NAMES = {RT_ADMIT: "admit", RT_TERMINAL: "terminal"}
''', path="matchmaking_tpu/utils/fixture.py")
    assert _rules(findings) == ["protocol"] * 2
    assert all("share value 1" in f.message for f in findings)


@pytest.mark.protocol
def test_protocol_vocab_rt_names_must_cover_every_type():
    findings = analyze_source('''
RT_ADMIT = 1
RT_CLEAN = 4
RT_NAMES = {RT_ADMIT: "admit"}
''', path="scripts/fixture_dump.py")
    assert _rules(findings) == ["protocol"]
    assert "RT_NAMES misses record type(s) RT_CLEAN" in findings[0].message


@pytest.mark.protocol
def test_protocol_vocab_applier_must_reference_every_streamed_type():
    findings = analyze_source('''
RT_ADMIT = 1
RT_CLEAN = 4


class StreamApplier:
    def _apply(self, seq, rtype, payload):
        if rtype == RT_ADMIT:
            self.admit(payload)
''', path="matchmaking_tpu/service/fixture.py")
    assert _rules(findings) == ["protocol"]
    assert "never references record type(s) RT_CLEAN" in findings[0].message


@pytest.mark.protocol
def test_protocol_vocab_flags_hardcoded_schema_version():
    findings = analyze_source('''
FORMAT_VERSION = 1


def header():
    return {"version": 1}
''', path="matchmaking_tpu/utils/fixture.py")
    assert _rules(findings) == ["protocol"]
    assert "schema version hardcoded" in findings[0].message


@pytest.mark.protocol
def test_protocol_vocab_accepts_constant_reference():
    findings = analyze_source('''
FORMAT_VERSION = 1


def header():
    return {"version": FORMAT_VERSION}
''', path="matchmaking_tpu/utils/fixture.py")
    assert findings == []


# ---- protocol: ignore hygiene ----------------------------------------------

@pytest.mark.protocol
def test_protocol_findings_are_suppressible_and_stale_ignores_flag():
    live = '''
class Journal:
    # protocol-effect: journal_append requires-fence fence
    def _append(self, payload):
        self.seq += 1  # matchlint: ignore[protocol] fixture: fence checked by caller
'''
    assert analyze_source(live,
                          path="matchmaking_tpu/utils/fixture.py") == []
    dead = '''
class Journal:
    # protocol-effect: journal_append requires-fence fence
    def _append(self, payload):
        if not self.fence():
            raise RuntimeError("fenced")
        self.seq += 1  # matchlint: ignore[protocol] fence checked by caller
'''
    findings = analyze_source(dead,
                              path="matchmaking_tpu/utils/fixture.py")
    assert _rules(findings) == ["stale-ignore"]
    assert "no longer suppresses" in findings[0].message
