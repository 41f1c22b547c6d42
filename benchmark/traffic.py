"""The one traffic generator: every mix is a parameter file it reads.

A mix (``benchmark/traffic/<name>.json``) says::

    {"loop": "open", "arrivals": "poisson", "rate": 5000.0,
     "ratings": {"model": "iid" | "paired", "mean": 1500.0, "sd": 300.0}}

The arithmetic is the program's own load generator's
(``service/loadgen.offered_load``), copied so that a change to the program
cannot move the yardstick: Poisson arrivals at ``rate`` per second, and
ratings either drawn independently (``iid``: every arrival searches the
waiting pool) or as the loadgen's default cohort (``paired``: consecutive
arrivals share one rating, so they match each other).

One change makes runs steadier: the count of arrivals is fixed at
``round(rate * seconds)`` and only their times are drawn. The times of a
Poisson process given its count are sorted uniform draws, so every seed
offers the same amount of work in another order.

A request's rating is a whole number of hundredths, as its body carries
it: ``k / 100`` for an integer ``k``, which formats to the body's decimal
and parses back to the same double.

A configuration's queue kind (``cells.load_kind``) may give each request
more body fields (region, game mode, rating deviation, ...). They are
drawn by ``add_fields`` from streams of their own, so the ratings and
times above are drawn exactly as without them, and a request without them
has the body ``{"id":…,"rating":…}`` byte for byte.
"""

from __future__ import annotations

import json
from dataclasses import dataclass, field, replace

import numpy as np

#: Independent random streams, one per use of the seed.
STREAM_POOL, STREAM_WARMUP, STREAM_WINDOW = 1, 2, 3
#: The kind's extra fields of the pool, the warm-up and the window.
STREAM_POOL_FIELDS, STREAM_WARMUP_FIELDS, STREAM_WINDOW_FIELDS = 4, 5, 6


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """A generator for one stream of ``seed`` (any integer, however large
    or negative)."""
    return np.random.default_rng([stream, int(seed) % (1 << 64)])


def centi_ratings(rng: np.random.Generator, n: int, mean: float,
                  sd: float) -> np.ndarray:
    """``n`` normal ratings rounded to hundredths (float64 ``k / 100``)."""
    return np.rint(rng.normal(mean, sd, size=n) * 100.0) / 100.0


@dataclass(frozen=True)
class Arrivals:
    """Requests in send order: ids, ratings, due times (seconds from the
    start of their phase) and the kind's extra body fields, one column
    each under its body key."""

    ids: list[str]
    rating: np.ndarray
    due: np.ndarray
    fields: dict[str, np.ndarray] = field(default_factory=dict)

    def __len__(self) -> int:
        return len(self.ids)

    def bodies(self) -> list[bytes]:
        if not self.fields:
            return [f'{{"id":"{pid}","rating":{r:.2f}}}'.encode()
                    for pid, r in zip(self.ids, self.rating.tolist())]
        extra = zip(*([f',"{k}":{json.dumps(v)}' for v in col.tolist()]
                      for k, col in self.fields.items()))
        return [(f'{{"id":"{pid}","rating":{r:.2f}' + "".join(x)
                 + "}").encode()
                for pid, r, x in zip(self.ids, self.rating.tolist(), extra)]


def add_fields(phase: list[Arrivals], kind, config: dict, seed: int,
               stream: int) -> list[Arrivals]:
    """The requests of one phase, each with the fields the configuration's
    kind draws for it (``kind.fields(rng, n, config)``), in send order from
    one stream of ``seed``."""
    rng = rng_for(seed, stream)
    return [replace(a, fields=kind.fields(rng, len(a), config))
            for a in phase]


def mix_ratings(rng: np.random.Generator, n: int,
                ratings: dict) -> np.ndarray:
    model = ratings["model"]
    mean, sd = float(ratings["mean"]), float(ratings["sd"])
    if model == "iid":
        return centi_ratings(rng, n, mean, sd)
    if model == "paired":
        return np.repeat(centi_ratings(rng, (n + 1) // 2, mean, sd), 2)[:n]
    raise ValueError(f"unknown rating model {model!r}")


def window_arrivals(traffic: dict, seed: int, seconds: float) -> Arrivals:
    """The measured window's requests: ``round(rate * seconds)`` open-loop
    Poisson arrivals over ``[0, seconds)``."""
    if traffic.get("loop") != "open" or traffic.get("arrivals") != "poisson":
        raise ValueError("the generator drives open-loop Poisson arrivals "
                         f"only, not {traffic.get('loop')!r}/"
                         f"{traffic.get('arrivals')!r}")
    rng = rng_for(seed, STREAM_WINDOW)
    n = int(round(float(traffic["rate"]) * seconds))
    due = np.sort(rng.uniform(0.0, seconds, size=n))
    rating = mix_ratings(rng, n, traffic["ratings"])
    return Arrivals([f"r{i}" for i in range(n)], rating, due)


def waiting_pool(config: dict, seed: int) -> Arrivals:
    """The configuration's waiting players, restored before any traffic.
    They enter as columns, not as request bodies, so their ratings keep
    float32's full precision: a searcher's exact rating is then almost
    never tied with a waiting player's, and a paired arrival's twin (at
    distance 0) is its nearest partner."""
    w = config["waiting"]
    rng = rng_for(seed, STREAM_POOL)
    n = int(w["count"])
    rating = rng.normal(float(w["mean"]), float(w["sd"]), size=n)
    return Arrivals([f"w{i}" for i in range(n)],
                    rating.astype(np.float32).astype(np.float64), np.zeros(n))


def warmup_bursts(config: dict, traffic: dict, seed: int) -> list[Arrivals]:
    """One burst per batch bucket, sized to land in it (the largest twice
    over, so a full window is cut), with the mix's rating spread drawn
    independently: the served path then runs every shape the window uses
    before the window opens."""
    buckets = sorted(config["service"]["engine"]["batch_buckets"])
    sizes = [b // 2 + 1 for b in buckets[:-1]] + [2 * buckets[-1]]
    rng = rng_for(seed, STREAM_WARMUP)
    spread = dict(traffic["ratings"], model="iid")
    out, start = [], 0
    for n in sizes:
        rating = mix_ratings(rng, n, spread)
        out.append(Arrivals([f"u{start + i}" for i in range(n)], rating,
                            np.zeros(n)))
        start += n
    return out
