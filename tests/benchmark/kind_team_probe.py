"""A queue kind for tests: a team queue whose requests pin one region and
game mode, and a comparison of each match's shape.

A test copies this file into a checkout as ``benchmark/kinds/<name>.py``
and names it in a configuration (``"kind": "<name>"``), with the fields to
pin (``"pinned": {"region": "eu", "game_mode": "ranked"}``; a
configuration without ``pinned`` sends requests with none, each a
wildcard). The comparison holds each matched reply to ``2 * team_size``
distinct players split into two teams of ``team_size``, each player
waiting and all of one region and mode, the spread of their float32
ratings within the threshold, and no player in two matches. A match of
restored players alone is answered to no one, so the waiting pool is held
to at most what went in less the matches answered, less whole matches
only, and its count on the device to the host's. It is a test's kind, not
a team queue's full reference: it reads no quality number and has no
control.
"""

from __future__ import annotations

import collections

import numpy as np

OK_STATUSES = ("matched", "queued")

LIMITS = {
    "off_device": 0,
    "unanswered": 0,
    "bad_status": 0,
    "inconsistent": 0,
    "not_waiting": 0,
    "mixed_group": 0,
    "over_threshold": 0,
    "twice": 0,
    "pool_gap": 0,
    "device_pool_gap": 0,
}


def fields(rng: np.random.Generator, n: int, config: dict) -> dict:
    return {k: np.full(n, v, object)
            for k, v in config.get("pinned", {}).items()}


def _groups(ids, cols: dict) -> dict:
    wild = np.full(len(ids), "*", object)
    keys = zip(cols.get("region", wild).tolist(),
               cols.get("game_mode", wild).tolist())
    return dict(zip(ids, keys))


def compare(data, control: bool = False) -> dict[str, int]:
    out = dict.fromkeys(LIMITS, 0)
    out["off_device"] = data.off_device
    rating = dict(zip(data.pool_ids, data.pool_rating.tolist()))
    rating.update(zip(data.sent_ids, data.sent_rating.tolist()))
    group = _groups(data.pool_ids, data.pool_fields)
    group.update(_groups(data.sent_ids, data.sent_fields))
    size = data.team_size
    answered: set[str] = set()
    matches: dict[str, frozenset] = {}
    for rep in data.replies:
        answered.add(rep.to)
        if rep.status not in OK_STATUSES:
            out["bad_status"] += 1
            continue
        if rep.status != "matched":
            continue
        players = frozenset(rep.players)
        teams = [frozenset(t) for t in rep.teams]
        if (len(rep.players) != 2 * size or len(players) != 2 * size
                or rep.to not in players
                or sorted(map(len, teams)) != [size, size]
                or frozenset().union(*teams) != players):
            out["inconsistent"] += 1
            continue
        if matches.setdefault(rep.match_id, players) != players:
            out["inconsistent"] += 1
    out["unanswered"] = sum(pid not in answered for pid in data.sent_ids)

    seen: collections.Counter = collections.Counter()
    for players in matches.values():
        seen.update(players)
        if any(p not in rating for p in players):
            out["not_waiting"] += 1
            continue
        if len({group[p] for p in players}) != 1:
            out["mixed_group"] += 1
        r = np.asarray([rating[p] for p in players], np.float32)
        if r.max() - r.min() > np.float32(data.threshold):
            out["over_threshold"] += 1
    out["twice"] = sum(n > 1 for n in seen.values())
    unseen = (len(data.pool_ids) + len(data.sent_ids)
              - 2 * size * len(matches) - data.pool_size_end)
    out["pool_gap"] = max(0, -unseen) + unseen % (2 * size)
    out["device_pool_gap"] = abs(data.device_pool_size_end
                                 - data.pool_size_end)
    return out


def failures(numbers: dict[str, int]) -> list[str]:
    return [f"{k}={numbers[k]} over its limit {lim}"
            for k, lim in LIMITS.items() if numbers[k] > lim]


def report(numbers: dict[str, int]) -> dict[str, list[int]]:
    return {k: [numbers[k], lim] for k, lim in LIMITS.items()}
