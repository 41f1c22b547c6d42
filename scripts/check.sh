#!/usr/bin/env bash
# The repo gate, in order:
#   1. matchlint (python -m matchmaking_tpu.analysis) — fails on any
#      finding outside analysis/baseline.json. Runs FIRST because it is
#      seconds, not minutes, and a lock-discipline bug should fail fast.
#   2. tier-1 tests (the ROADMAP.md verify recipe's pytest selection).
# The benchmark (python3 -m benchmark.run) never invokes this script, so
# lint time is never in a benchmark number.
set -euo pipefail
cd "$(dirname "$0")/.."

echo "== matchlint =="
JAX_PLATFORMS=cpu python -m matchmaking_tpu.analysis

echo "== control plane =="
# ISSUE 11 gate: the settlement + lock-pairing dataflow rules armed over
# the placement control plane (matchmaking_tpu/control/ joined their
# scope) — a credit-leak or unbalanced-acquire shape in the migration
# executor/controller fails fast and by rule name, before the full lint
# above repeats it in context. --static-only: these two rules need no
# jax tracing, so this stays sub-second.
JAX_PLATFORMS=cpu python -m matchmaking_tpu.analysis \
    --rules settlement,lock-pairing --static-only
# Placement suite by marker: migration round trip / shard cycle /
# arbiter regressions fail fast and by name.
JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'placement and not slow' \
    --continue-on-collection-errors -p no:cacheprovider

echo "== codec parity =="
# ISSUE 9 gate: rebuild libmmcodec.so FROM SOURCE (force — CI must never
# gate against the checked-in binary), then fuzz the native batch codec
# vs the Python contract module: decode field-parity, encode
# BYTE-identity (tests/test_codec_fuzz.py, `codec` marker).
JAX_PLATFORMS=cpu python -c '
from matchmaking_tpu.native import codec
ok = codec.rebuild(force=True)
print("libmmcodec.so rebuilt from source:", ok)
raise SystemExit(0 if ok else 1)'
JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'codec and not slow' \
    --continue-on-collection-errors -p no:cacheprovider

echo "== ingress =="
# ISSUE 12 gate: the columnar consume_batch ingress. libmmcodec.so was
# rebuilt FROM SOURCE by the codec section above, so the concat decoder
# under test is never a stale checked-in binary. The suite runs by
# marker: broker burst-callback seam units, the consume-time decode, and
# the equivalence soaks (consume_batch on vs off, ingress shards 1 vs 4 —
# identical pairings, normalized responses, and settlement counters).
JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'ingress and not slow' \
    --continue-on-collection-errors -p no:cacheprovider

echo "== attribution smoke =="
# ISSUE 6 fast gate: a seeded 400-player soak must decompose every settled
# trace into work + wait that sums to its e2e span (telescoping identity),
# with the histogram-side p99 agreeing within one log bucket.
JAX_PLATFORMS=cpu python -m pytest tests/test_attribution.py -q \
    -k 'smoke' --continue-on-collection-errors -p no:cacheprovider

echo "== overload =="
# The overload-control suite (ISSUE 5) runs by marker first: admission /
# shed / deadline / drain regressions fail fast and by name before the
# full tier-1 sweep repeats them in context.
JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'overload and not slow' \
    --continue-on-collection-errors -p no:cacheprovider

echo "== qos =="
# Tiered-QoS suite (ISSUE 7): priority partitions / EDF ordering /
# pool-resident deadline expiry regressions fail fast and by name.
JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'qos and not slow' \
    --continue-on-collection-errors -p no:cacheprovider

echo "== quality =="
# Match-quality & fairness suite (ISSUE 8): device-vs-host accumulator
# reconciliation / disparity detection / quality-SLO burn / waited_ms
# wire contract regressions fail fast and by name.
JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'quality and not slow' \
    --continue-on-collection-errors -p no:cacheprovider

echo "== bucketed formation =="
# ISSUE 14 gate: hierarchical rating-bucketed formation. The equivalence
# suite runs by marker: bucketed↔flat bit-exactness at the kernel seam
# (traffic + rescan, banded/unbanded/hot-bucket/widening), the sharded
# per-bucket frontier vs the single-device dense kernels at D=2/4, the
# tournament-tree frontier merge vs the linear merge at D=2/4/8, the
# adaptive frontier-K ladder + audit ring, and the quality observatory's
# disparity-no-regression check under hierarchical formation.
JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'bucketed and not slow' \
    --continue-on-collection-errors -p no:cacheprovider

echo "== durability =="
# ISSUE 15 gate: crash durability. The suite runs by marker first —
# journal framing/replay (CRC frames, torn tails, clean-marker
# detection), byte-level corruption fixtures (sidecar CRC, snapshot
# fallback, compaction crash points), the service hard-crash round trip
# (zero lost waiting players, redeliveries replay the SAME match), the
# two-run bit-identical recovery transcript under seeded chaos, the
# D=2→1 device-loss failover, and the sanitizer's journal twin.
JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'durability and not slow' \
    --continue-on-collection-errors -p no:cacheprovider

echo "== replication =="
# ISSUE 17 gate: hot-standby journal replication + fenced failover. The
# suite runs by marker first — lease/epoch authority semantics, the
# at-least-once link under scripted drop/dup/delay/partition faults, the
# standby applier's ordering + baseline re-base, the service stream
# round trip, the fenced ex-primary regression (a superseded owner can
# neither append nor publish), the sanitizer's replication twin, and the
# offline journal inspector.
JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'replication and not slow' \
    --continue-on-collection-errors -p no:cacheprovider

echo "== net =="
# ISSUE 20 gate: real-transport DCN seams. The suite runs by marker
# first — frame-codec fuzz (torn frames at every byte offset, hostile
# length prefixes, CRC flips, interleaved heartbeats), the socket
# replication link end-to-end over UDS with QueueReplication +
# StandbyApplier unchanged, deterministic network-nemesis scripts, the
# remote lease client's renewal-in-flight-at-expiry refusal, and the
# sanitizer's ack-beyond-received twin over a real socket.
JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'net and not slow' \
    --continue-on-collection-errors -p no:cacheprovider

echo "== protocol =="
# ISSUE 19 gate: protocol conformance. The suite runs by marker first —
# the matchlint `protocol` rule's fixture positives/negatives (fence
# dominance incl. exception edges, watermark monotonicity, the role
# state machine, bounded-by/requires-check effects, the cross-file RT_*
# vocabulary) and the small-scope model checker's own regressions
# (explorer exhaustiveness + POR state-space preservation on a toy
# world, clean protocol scopes, the stale-epoch-resume replay, every
# seeded mutant's minimized digest-replayable counterexample).
JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'protocol and not slow' \
    --continue-on-collection-errors -p no:cacheprovider
# Then the committed scope: 2 queues x depth 6 x {expire,crash,drop,dup}
# x fault budget 2 must be EXHAUSTIVE with zero violations, and the
# seeded-mutant gate must catch all four protocol mutants with
# replay-verified counterexamples while the unmutated baseline stays
# clean. Pure host-side (no jax backend).
JAX_PLATFORMS=cpu python - <<'EOF'
import json
from matchmaking_tpu.analysis.modelcheck import (
    run_modelcheck, run_mutation_gate)
out = run_modelcheck()
print("modelcheck smoke:", json.dumps(
    {k: out[k] for k in ("modelcheck_states_explored", "modelcheck_nodes",
                         "modelcheck_exhaustive", "modelcheck_violations",
                         "modelcheck_elapsed_s")}))
assert out["modelcheck_violations"] == 0, \
    f"protocol violation: {out['modelcheck_violation']}\n" \
    + "\n".join(out["modelcheck_timeline"])
assert out["modelcheck_exhaustive"], "scope not exhausted (cap hit)"
gate = run_mutation_gate()
for name, rec in sorted(gate["mutation_gate_mutants"].items()):
    print(f"mutant {name}: caught={rec['caught']} "
          f"replay_ok={rec['replay_ok']} steps={rec['steps']} "
          f"digest={rec['digest']}")
assert gate["mutation_gate_passed"], \
    f"mutation gate failed: {json.dumps(gate, indent=2)}"
print("modelcheck smoke: OK")
EOF

echo "== forensics =="
# ISSUE 18 gate: incident forensics. The suite runs by marker first —
# the causal spine's monotone seq under concurrent worker threads, the
# deterministic transcript projection (clock fields and timing refs
# dropped), trigger/rate-limit/reentrancy capture with counted drops,
# concurrent /debug/incidents + prom scrapes after a real failover, the
# capture-during-drain non-interference check, the offline postmortem
# root chain, and the journal LSN-range slicer. The static twin is the
# matchlint determinism rule's spine-seq tokens in the full lint above.
JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'forensics and not slow' \
    --continue-on-collection-errors -p no:cacheprovider
# Every committed example bundle must validate against the current
# schema AND survive the offline analyzer's root-chain resolution — a
# schema drift that orphans the examples fails here, not in an incident.
JAX_PLATFORMS=cpu python - <<'EOF'
import glob, json, subprocess, sys
from matchmaking_tpu.utils.forensics import validate_bundle
bundles = sorted(glob.glob("examples/incidents/*.json"))
if not bundles:
    sys.exit("no committed example bundles under examples/incidents/")
for path in bundles:
    with open(path, encoding="utf-8") as f:
        problems = validate_bundle(json.load(f))
    if problems:
        sys.exit(f"{path}: {problems}")
    proc = subprocess.run(
        [sys.executable, "scripts/postmortem.py", path, "--json"],
        capture_output=True, text=True, timeout=120)
    if proc.returncode != 0:
        sys.stderr.write(proc.stderr)
        sys.exit(f"postmortem failed on {path} ({proc.returncode})")
    chain = json.loads(proc.stdout)["root_chain_kinds"]
    print(f"example bundle OK: {path} (root chain: {' -> '.join(chain)})")
EOF

echo "== speculation =="
# ISSUE 16 gate: speculative formation. The equivalence suite runs by
# name, seconds-scale on the CPU harness: commit ≡ rescan bit-exactness
# (single and chained steps, fallback after invalidation), every
# invalidation path (admit delta, expiry incl. the zero-effect sweep
# carve-out, dedup, mid-gap removal, restore, staleness), the
# validate-before-commit token discipline (commit-without-validate and
# validate-after-mutate raise), the seeded spec-on vs spec-off soak
# (bit-identical match stream, zero lost players, zero double matches
# across a drain/restore cycle), and the service spec-loop + drain
# round trips. The static twin of the token discipline is the matchlint
# `speculation` rule in the full lint above; the dynamic twin rides the
# sanitizer suite in tier-1.
JAX_PLATFORMS=cpu python -m pytest tests/test_speculation.py -q \
    -m 'not slow' --continue-on-collection-errors -p no:cacheprovider

echo "== scenario observatory =="
# ISSUE 13 gate: population-model scenario determinism (bit-identical
# arrival transcripts, steady ≡ legacy loadgen byte for byte), the
# telemetry counter-reset hardening, and the closed-loop autotuner
# acceptance (autotune-on beats static on a scripted overload, with a
# bit-identical knob-decision audit trace across two runs). The suite
# includes the seeded 2-cell mini-matrix smoke through
# loadgen.scenario_matrix in-process: artifact schema, autotuner audit
# ring non-empty, and replay identity of the scenario digests.
JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'scenario and not slow' \
    --continue-on-collection-errors -p no:cacheprovider

echo "== tier-1 =="
exec env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow' \
    --continue-on-collection-errors -p no:cacheprovider
