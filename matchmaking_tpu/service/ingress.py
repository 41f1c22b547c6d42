"""In-process sharded ingress: consume-burst decode + per-shard state.

ISSUE 12's ingress plane. PR 9 made the request→response path
window-granular on the EGRESS side; the top remaining per-delivery cost
(PR 6 attribution) was the broker consume machinery — one handler
invocation + bookkeeping per delivery — and the flush-time re-decode of
bodies the consumer had already held in its hands. This module is the
decode side of that story:

- **Consume-burst decode** — the broker's ``consume_batch`` seam hands the
  app ONE callback per drained burst; ``IngressShards.decode_burst`` packs
  the burst's bodies into a single arena + offset array (the mirror of the
  batch ENCODERS' output layout) and decodes them in one native call
  (``codec.decode_batch_concat``). Each delivery gets a ``(DecodedBurst,
  index)`` reference (``Delivery.row``), so the window flush assembles its
  columns by vectorized gather instead of re-decoding — the columns merge
  at the EDF cut, whichever bursts and shards they came from.

- **Shard workers** — rows the native decoder flags NEEDS_PYTHON (parties,
  escapes, exotica) fall back through ``contract.decode_request`` (the
  semantic source of truth), consistent-hashed by correlation id (the
  request identity available pre-decode) into
  ``BrokerConfig.ingress_shards`` worker slices. At N=1 the fallback runs
  inline (today's path, byte for byte); at N>1 each shard's slice runs on
  a worker thread — disjoint row indices, so the writes into the burst
  arrays never contend, and the workers touch NO shared mutable state
  (the dedup probe runs at the cut, on the event loop).

- **Per-shard settlement state** — ``ShardedRecent`` splits the
  at-least-once terminal-replay cache into per-shard dicts keyed by
  player id, so the cut-time probe (and any future shard-local prober)
  only ever touches one shard's dict per row. Everything else on the
  ingress path (admission credits, the batcher, trace settles) stays
  event-loop-confined and is proven settle-exactly-once by matchlint's
  settlement typestate — which is exactly why this split can stay
  lock-free.

Region/game-mode names are deliberately kept as STRINGS in the burst
columns and interned at the cut: interner codes belong to one engine
incarnation, and a crash revive or breaker swap between consume and flush
would otherwise dereference stale codes.
"""

from __future__ import annotations

import asyncio
import zlib
from typing import TYPE_CHECKING, Any  # noqa: F401  (Any: reject tuples)

import numpy as np

from matchmaking_tpu.utils.trace import stage

if TYPE_CHECKING:  # pragma: no cover - typing only
    from matchmaking_tpu.service.broker import Delivery


def shard_of(key: str, n: int) -> int:
    """Consistent request-id → shard hash. crc32, NOT ``hash()``: the
    builtin is salted per process (PYTHONHASHSEED), and the equivalence
    soaks replay shard routing bit-identically across runs."""
    if n <= 1:
        return 0
    return zlib.crc32(key.encode()) % n


class ShardedRecent:
    """The at-least-once terminal-replay cache (player id → (encoded body,
    expiry)), split into per-shard dicts by the consistent request-id hash.
    N=1 is a single dict — the pre-shard behavior exactly. All mutation
    happens on the event loop (probe at the cut, ``_remember`` at publish);
    the split means a future shard worker probing ITS shard can never
    contend with another's."""

    __slots__ = ("n", "_shards")

    def __init__(self, n: int = 1):
        self.n = max(1, int(n))
        self._shards: list[dict[str, tuple[bytes, float]]] = [
            {} for _ in range(self.n)]

    def _dict(self, pid: str) -> dict[str, tuple[bytes, float]]:
        if self.n == 1:
            return self._shards[0]
        return self._shards[zlib.crc32(pid.encode()) % self.n]

    def get(self, pid: str) -> "tuple[bytes, float] | None":
        return self._dict(pid).get(pid)

    def pop(self, pid: str) -> None:
        self._dict(pid).pop(pid, None)

    def set(self, pid: str, value: "tuple[bytes, float]") -> None:
        self._dict(pid)[pid] = value

    def __len__(self) -> int:
        if self.n == 1:
            return len(self._shards[0])
        return sum(len(d) for d in self._shards)

    def items(self):
        """Every (pid, (body, expiry)) across the shards — the journal
        compaction's carry walk (ISSUE 15): live dedup entries are
        re-appended into the fresh segment so the at-least-once horizon
        survives the truncation. Shard-major order (deterministic: the
        shard split is a pure hash of the id)."""
        for d in self._shards:
            yield from d.items()

    def prune(self, now: float) -> None:
        """Drop expired entries (the time-throttled flush-side prune)."""
        for i, d in enumerate(self._shards):
            self._shards[i] = {k: v for k, v in d.items() if v[1] > now}


class DecodedBurst:
    """One consume burst's preparsed request columns. Row i of the burst
    is valid iff ``ok[i]`` (invalid rows were settled at consume).
    Region/mode are names ("" = wildcard), interned at the EDF cut.

    The all-OK fast path ADOPTS the native decoder's output arrays
    directly (zero copies — the common shape under load); bursts with
    fallback/reject rows allocate and fill."""

    __slots__ = ("ids", "rating", "rd", "threshold", "region", "mode", "ok")

    def __init__(self, ids, rating, rd, threshold, region, mode, ok):
        self.ids = ids
        self.rating = rating
        self.rd = rd
        self.threshold = threshold
        self.region = region
        self.mode = mode
        self.ok = ok

    @classmethod
    def empty(cls, n: int) -> "DecodedBurst":
        return cls(np.empty(n, object), np.empty(n, np.float32),
                   np.empty(n, np.float32), np.empty(n, np.float32),
                   np.empty(n, object), np.empty(n, object),
                   np.zeros(n, bool))


class IngressShards:
    """N in-process ingress shard workers for one queue runtime."""

    def __init__(self, n: int = 1):
        self.n = max(1, int(n))

    # The NEEDS_PYTHON fallback for one shard's slice: decode through the
    # contract path, write fields into the burst arrays (disjoint indices
    # per shard — thread-safe by construction), collect rejects. The
    # (counter, code, reason) rows MUST keep the flush's classification
    # (ContractError → rejected_by_middleware with its own code/reason;
    # party > 1 → rejected_by_engine/party_not_supported): the caller
    # settles them through app._reject_delivery, and the on/off
    # equivalence soaks pin the mapping.
    @staticmethod
    def _fallback_slice(burst: DecodedBurst, deliveries: "list[Delivery]",
                        idxs: "list[int]") -> "list[tuple[int, str, str, str]]":
        from matchmaking_tpu.service.contract import (
            ContractError,
            decode_request,
        )

        rejects: list[tuple[int, str, str, str]] = []
        for i in idxs:
            try:
                req = decode_request(deliveries[i].body)
            except ContractError as e:
                rejects.append((i, "rejected_by_middleware", e.code,
                                e.reason))
                continue
            if req.party_size > 1:
                # 1v1 queue: parties are unservable (oracle semantics) —
                # same reject the flush's fallback path produced.
                rejects.append((i, "rejected_by_engine",
                                "party_not_supported",
                                "engine rejected request: "
                                "party_not_supported"))
                continue
            burst.ids[i] = req.id
            burst.rating[i] = req.rating
            burst.rd[i] = req.rating_deviation
            burst.threshold[i] = (np.nan if req.rating_threshold is None
                                  else req.rating_threshold)
            burst.region[i] = "" if req.region == "*" else req.region
            burst.mode[i] = "" if req.game_mode == "*" else req.game_mode
            burst.ok[i] = True
        return rejects

    async def decode_burst(
        self, deliveries: "list[Delivery]",
    ) -> "tuple[list[Delivery], list[tuple[Any, str, str, str]]]":
        """Decode one consume burst: one native call over the burst's
        concatenated bodies, per-shard contract fallback for NEEDS_PYTHON
        rows. Sets ``d.row = (burst, i)`` on every valid delivery.
        Returns (kept deliveries, rejects) — the CALLER settles rejects
        (respond + ack) so all settlement stays in the runtime."""
        from matchmaking_tpu.native import codec

        n = len(deliveries)
        # No per-body copy: Delivery.body is bytes on both transports, and
        # join() materializes the one concatenated buffer the decoder
        # reads (the mirror of the encoders' arena layout).
        bodies = [d.body if isinstance(d.body, bytes) else bytes(d.body)
                  for d in deliveries]
        offsets = np.zeros(n + 1, np.int64)
        np.cumsum(np.fromiter((len(b) for b in bodies), np.int64, n),
                  out=offsets[1:])
        with stage("decode", rows=n):
            native = codec.decode_batch_concat(b"".join(bodies), offsets)
        rejects_i: list[tuple[int, str, str, str]] = []
        fallback: list[int] = []
        if native is None:
            # Native library raced away: the whole burst takes the
            # contract path (sharded below).
            burst = DecodedBurst.empty(n)
            fallback = list(range(n))
        else:
            ids_n, rating_n, rd_n, thr_n, reg_n, mode_n, status_n = native
            if not status_n.any():  # every row OK (== codec.OK == 0)
                # The loaded-path common case: adopt the decoder's arrays
                # wholesale — no per-row status walk, no column copies.
                burst = DecodedBurst(ids_n, rating_n, rd_n, thr_n,
                                     reg_n, mode_n, np.ones(n, bool))
                for i, d in enumerate(deliveries):
                    d.row = (burst, i)
                return deliveries, []
            burst = DecodedBurst.empty(n)
            status_l = status_n.tolist()
            for i in range(n):
                st = status_l[i]
                if st == codec.OK:
                    burst.ok[i] = True
                elif st == codec.NEEDS_PYTHON:
                    fallback.append(i)
                else:
                    rejects_i.append((i, "rejected_by_middleware",
                                      codec.error_code(st),
                                      "malformed payload"))
            okm = burst.ok
            burst.ids[okm] = ids_n[okm]
            burst.rating[okm] = rating_n[okm]
            burst.rd[okm] = rd_n[okm]
            burst.threshold[okm] = thr_n[okm]
            burst.region[okm] = reg_n[okm]
            burst.mode[okm] = mode_n[okm]
        if fallback:
            if self.n > 1 and len(fallback) > 1:
                # Shard the contract-path work by request id where we have
                # one (correlation id pre-decode — stable across
                # redelivery), one worker thread per non-empty shard.
                by_shard: list[list[int]] = [[] for _ in range(self.n)]
                for i in fallback:
                    key = deliveries[i].properties.correlation_id or str(i)
                    by_shard[shard_of(key, self.n)].append(i)
                slices = [idxs for idxs in by_shard if idxs]
                results = await asyncio.gather(*(
                    asyncio.to_thread(self._fallback_slice, burst,
                                      deliveries, idxs)
                    for idxs in slices))
                for rej in results:
                    rejects_i.extend(rej)
            else:
                rejects_i.extend(
                    self._fallback_slice(burst, deliveries, fallback))
        kept: list[Delivery] = []
        ok_l = burst.ok.tolist()
        for i, d in enumerate(deliveries):
            if ok_l[i]:
                d.row = (burst, i)
                kept.append(d)
        rejects = [(deliveries[i], counter, code, reason)
                   for i, counter, code, reason in rejects_i]
        return kept, rejects


def gather_rows(refs: "list[tuple[DecodedBurst, int]]"):
    """Merge window rows from their burst columns at the EDF cut: one
    vectorized take per (burst, column) instead of a per-row Python loop.
    ``refs`` is in final window order (post EDF sort / dedup / expiry
    filtering); rows from the same burst gather together and scatter back
    into their window positions."""
    k = len(refs)
    if k and all(burst is refs[0][0] for burst, _ in refs):
        # Single-burst window (bursts ≥ windows under load): one fancy
        # index per column, no scatter bookkeeping.
        b = refs[0][0]
        idx = np.fromiter((i for _, i in refs), np.int64, k)
        return (b.ids[idx], b.rating[idx], b.rd[idx], b.threshold[idx],
                b.region[idx], b.mode[idx])
    ids = np.empty(k, object)
    rating = np.empty(k, np.float32)
    rd = np.empty(k, np.float32)
    threshold = np.empty(k, np.float32)
    region = np.empty(k, object)
    mode = np.empty(k, object)
    by_burst: dict[int, tuple[DecodedBurst, list[int], list[int]]] = {}
    for j, (burst, i) in enumerate(refs):
        entry = by_burst.get(id(burst))
        if entry is None:
            entry = by_burst[id(burst)] = (burst, [], [])
        entry[1].append(i)
        entry[2].append(j)
    for burst, src, dst in by_burst.values():
        s = np.asarray(src, np.int64)
        t = np.asarray(dst, np.int64)
        ids[t] = burst.ids[s]
        rating[t] = burst.rating[s]
        rd[t] = burst.rd[s]
        threshold[t] = burst.threshold[s]
        region[t] = burst.region[s]
        mode[t] = burst.mode[s]
    return ids, rating, rd, threshold, region, mode
