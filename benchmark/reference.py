"""The plain reference that decides ``correct`` for a 1v1 queue.

This is the default queue kind: a configuration that names no ``kind``
is judged here, and its requests carry no body fields beyond id and
rating. A configuration of another kind names a module of its own under
``benchmark/kinds/`` that defines the same names (``cells.KIND_API``) and
reads the same ``RunData``; its plain data for other kinds (the queue's
team size and role slots, each request's extra fields, each match's
teams) is ignored here.

It knows the semantics of a 1v1 rating-distance queue and nothing of the
program: the queue runs on the device engine throughout (a service that
falls back to its host oracle answers correctly, but not from the path
under test); a match is two waiting players whose rating distance, computed in
float32 as the configuration states, is within the queue's threshold; a
player is matched at most once; every request is answered ``matched`` or
``queued``; and a searcher takes the nearest partner that is free.

What it compares is every answer the run received, once the window has
closed and the drain has ended: each reply's status, each match's players
against who was waiting, and the waiting pool the program holds at the end
(its host mirror's count, and the active slots of its pool on the device)
against the arithmetic of what went in and what was matched.

The last number is the quality of each answer. A searcher is the later of
a match's two players to enter. A player is *certainly free* to it when
it entered before the searcher was sent and was never matched by the end
of the run: it sat in the pool all through the searcher's window. The
number is the count of certainly free players strictly nearer to the
searcher than the partner it got, and the run reports its largest value
over all searchers. The program picks the best free candidate of each
pool block, so under contention a searcher may fall back past a few
players of a block whose best was taken; a program that ranks candidates
in a lower precision falls back past hundreds.

The control is this reference put in the program's place in bfloat16, the
precision below the float32 the configuration states: for each searcher
it takes the first certainly free restored player at the nearest bfloat16
distance, and its number is read the same way (``control_skipped``).
"""

from __future__ import annotations

import bisect
import collections
from dataclasses import dataclass, field

import numpy as np

#: Statuses a served request may end in; any other reply is a failure.
OK_STATUSES = ("matched", "queued")

#: The numbers compared, in the order they are printed, each with its
#: limit. Counts of broken guarantees are exact: their limit is 0. The
#: limit on ``skipped_max`` lies between the readings of sound runs and of
#: the control (PERF.md gives both).
LIMITS = {
    "off_device": 0,
    "unanswered": 0,
    "bad_status": 0,
    "not_waiting": 0,
    "inconsistent": 0,
    "over_threshold": 0,
    "twice": 0,
    "pool_gap": 0,
    "device_pool_gap": 0,
    "skipped_max": 40,
}


@dataclass
class Reply:
    """One reply as the client received it."""

    to: str                    # the requester (correlation id)
    status: str
    t: float                   # receive time, seconds (client clock)
    match_id: str = ""
    players: tuple[str, ...] = ()
    #: The match's split into teams, as its body gives it.
    teams: tuple[tuple[str, ...], ...] = ()


@dataclass
class RunData:
    """Everything the reference needs, as plain data."""

    threshold: float
    #: Restored waiting players: ids and ratings.
    pool_ids: list[str]
    pool_rating: np.ndarray
    #: Requests in send order: ids, ratings and send times (client clock).
    sent_ids: list[str]
    sent_rating: np.ndarray
    sent_t: np.ndarray
    replies: list[Reply]
    #: Waiting players the program reports after the drain: its host
    #: mirror's count, and the active slots of its pool on the device.
    pool_size_end: int
    device_pool_size_end: int
    #: Times the queue left the device path for the host oracle (the
    #: service's breaker counters, the team queue's delegations, and its
    #: engine and pool at the end).
    off_device: int = 0
    #: The queue's settings, from the configuration's first queue.
    team_size: int = 1
    role_slots: tuple[str, ...] = ()
    #: The kind's extra body fields of each restored player and each sent
    #: request (in the order above), one column per body key.
    pool_fields: dict[str, np.ndarray] = field(default_factory=dict)
    sent_fields: dict[str, np.ndarray] = field(default_factory=dict)


def fields(rng: np.random.Generator, n: int, config: dict) -> dict:
    """A 1v1 request carries no body fields beyond its id and rating."""
    return {}


def f32_distance(a, b):
    """|a - b| in float32, as the configuration's ratings are held."""
    return np.abs(np.asarray(a, np.float32) - np.asarray(b, np.float32))


#: Slack around a float32 interval before the exact count inside it.
SLACK = 1e-2


def nearer_counts(free: np.ndarray, r: np.ndarray, d: np.ndarray
                  ) -> np.ndarray:
    """For each searcher rating ``r[i]`` and partner distance ``d[i]``, how
    many of the ratings ``free`` lie strictly nearer: f32 |p - r| < d.
    The candidates of each searcher come from a sorted search with some
    slack; the count inside it is exact in float32."""
    free = np.sort(free.astype(np.float32))
    keys = free.astype(np.float64)
    r64 = np.asarray(r, np.float32).astype(np.float64)
    d64 = np.asarray(d, np.float32).astype(np.float64)
    lo = np.searchsorted(keys, r64 - d64 - SLACK)
    hi = np.searchsorted(keys, r64 + d64 + SLACK, side="right")
    out = np.zeros(len(r64), np.int64)
    for i in np.nonzero((hi > lo) & (d64 > 0))[0]:
        near = f32_distance(free[lo[i]:hi[i]], r64[i]) < np.float32(d64[i])
        out[i] = int(near.sum())
    return out


def bf16_picks(free: np.ndarray, r: np.ndarray) -> np.ndarray:
    """The control's choice for each searcher rating: among the ratings
    ``free`` (in their order), the first at the nearest bfloat16 distance
    to the searcher's bfloat16 rating. Returns the picked ratings."""
    import ml_dtypes

    def level(x):
        return (np.asarray(x, np.float32).astype(ml_dtypes.bfloat16)
                .astype(np.float32).astype(np.float64))

    lv = level(free)
    order = np.lexsort((np.arange(len(free)), lv))
    first = np.r_[True, lv[order][1:] != lv[order][:-1]]
    levels, first_idx = lv[order][first], order[first]
    lr = level(r)
    k = np.searchsorted(levels, lr)
    below = np.clip(k - 1, 0, len(levels) - 1)
    above = np.clip(k, 0, len(levels) - 1)
    d_below, d_above = np.abs(levels[below] - lr), np.abs(levels[above] - lr)
    take = np.where(d_below < d_above, first_idx[below],
                    np.where(d_above < d_below, first_idx[above],
                             np.minimum(first_idx[below], first_idx[above])))
    return np.asarray(free, np.float64)[take]


def _dynamic_nearer(dynamic: list[float], r: float, d: float) -> int:
    """The count of ``nearer_counts`` over a short sorted list."""
    i = bisect.bisect_left(dynamic, r - d - SLACK)
    k = bisect.bisect_right(dynamic, r + d + SLACK)
    if k <= i or d <= 0:
        return 0
    near = f32_distance(dynamic[i:k], r) < np.float32(d)
    return int(near.sum())


def compare(data: RunData, control: bool = False) -> dict[str, int]:
    """Every number compared, from one run's answers. With ``control`` the
    result also holds ``control_skipped``: the same reading of the
    bfloat16 reference's picks for the same searchers."""
    thr = np.float32(data.threshold)
    rating: dict[str, float] = dict(zip(data.pool_ids,
                                        data.pool_rating.tolist()))
    entry: dict[str, int] = dict.fromkeys(data.pool_ids, -1)
    sent_at: dict[str, float] = {}
    for i, (pid, r, t) in enumerate(zip(data.sent_ids,
                                        data.sent_rating.tolist(),
                                        data.sent_t.tolist())):
        rating[pid] = r
        entry[pid] = i
        sent_at[pid] = t

    out = dict.fromkeys(LIMITS, 0)
    out["off_device"] = data.off_device
    answered: set[str] = set()
    matches: dict[str, tuple[str, ...]] = {}
    seen_at: dict[str, float] = {}
    for rep in data.replies:
        answered.add(rep.to)
        if rep.status not in OK_STATUSES:
            out["bad_status"] += 1
            continue
        if rep.status != "matched":
            continue
        players = tuple(rep.players)
        if rep.to not in players or len(players) != 2:
            out["inconsistent"] += 1
            continue
        prev = matches.setdefault(rep.match_id, players)
        if set(prev) != set(players):
            out["inconsistent"] += 1
        seen_at[rep.match_id] = min(rep.t, seen_at.get(rep.match_id, rep.t))
    out["unanswered"] = sum(1 for pid in data.sent_ids
                            if pid not in answered)

    in_matches: collections.Counter = collections.Counter()
    valid: list[tuple[str, str]] = []
    for mid, players in matches.items():
        in_matches.update(players)
        if any(p not in rating for p in players):
            out["not_waiting"] += 1
            continue
        # A match cannot be answered before both its players were sent.
        if any(sent_at.get(p, -np.inf) > seen_at[mid] for p in players):
            out["not_waiting"] += 1
            continue
        a, b = players
        if f32_distance(rating[a], rating[b]) > thr:
            out["over_threshold"] += 1
        valid.append((a, b))
    out["twice"] = sum(1 for n in in_matches.values() if n > 1)
    waiting = len(data.pool_ids) + len(data.sent_ids) - 2 * len(matches)
    out["pool_gap"] = abs(data.pool_size_end - waiting)
    out["device_pool_gap"] = abs(data.device_pool_size_end - waiting)

    # Quality: certainly free players nearer than the partner a searcher
    # got. Restored players never matched are free to every searcher;
    # requesters never matched are free to those sent after them.
    matched = set(in_matches)
    free_static = np.asarray(
        [r for pid, r in zip(data.pool_ids, data.pool_rating.tolist())
         if pid not in matched], np.float64)
    partner: dict[str, str] = {}
    for a, b in valid:
        s, q = (a, b) if entry[a] > entry[b] else (b, a)
        partner[s] = q
    order = [pid for pid in data.sent_ids if pid in partner]
    r = np.asarray([rating[pid] for pid in order], np.float64)
    d = f32_distance(r, [rating[partner[pid]] for pid in order])
    nearer = nearer_counts(free_static, r, d)
    if control:
        d_ctl = f32_distance(r, bf16_picks(free_static, r))
        nearer_ctl = nearer_counts(free_static, r, d_ctl)
    # The requesters never matched, added in send order.
    dynamic: list[float] = []
    j = 0
    skipped = control_skipped = 0
    for pid in data.sent_ids:
        if pid in partner:
            skipped = max(skipped, int(nearer[j])
                          + _dynamic_nearer(dynamic, r[j], d[j]))
            if control:
                control_skipped = max(control_skipped, int(nearer_ctl[j])
                                      + _dynamic_nearer(dynamic, r[j],
                                                        d_ctl[j]))
            j += 1
        if pid not in matched:
            bisect.insort(dynamic, float(np.float32(rating[pid])))
    out["skipped_max"] = skipped
    if control:
        out["control_skipped"] = control_skipped
    return out


def failures(numbers: dict[str, int]) -> list[str]:
    """The numbers over their limits, each with its limit."""
    return [f"{k}={numbers[k]} over its limit {lim}"
            for k, lim in LIMITS.items() if numbers[k] > lim]


def report(numbers: dict[str, int]) -> dict[str, list[int]]:
    """Each number compared beside its limit: ``{name: [number, limit]}``."""
    return {k: [numbers[k], lim] for k, lim in LIMITS.items()}
