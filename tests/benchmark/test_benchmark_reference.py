"""The plain reference that decides ``correct``: each broken guarantee is
caught, a clean set of answers passes, and the vectorized quality count
equals a plain loop over every free player."""

import numpy as np
import pytest

from benchmark import reference as R
from benchmark.reference import Reply, RunData


def clean(n_pool=400, n_req=120, seed=0):
    """Requests that each take their nearest free player, as plain data."""
    rng = np.random.default_rng(seed)
    pool = rng.normal(1500, 300, n_pool).astype(np.float32).astype(float)
    req = np.rint(rng.normal(1500, 300, n_req) * 100) / 100
    ids = [f"w{i}" for i in range(n_pool)]
    sent = [f"r{i}" for i in range(n_req)]
    free = dict(zip(ids, pool))
    replies, matched = [], 0
    for k, (pid, r) in enumerate(zip(sent, req)):
        near = min(free, key=lambda p: abs(np.float32(free[p])
                                           - np.float32(r)))
        if abs(free[near] - r) <= 100:
            del free[near]
            replies.append(Reply(pid, "matched", 1.0 + k, f"m{k}",
                                 (pid, near)))
            matched += 1
        else:
            free[pid] = r
            replies.append(Reply(pid, "queued", 1.0 + k))
    left = n_pool + n_req - 2 * matched
    return RunData(100.0, ids, pool, sent, req, np.arange(n_req) * 1.0,
                   replies, left, left)


def test_clean_answers_pass():
    numbers = R.compare(clean())
    assert not R.failures(numbers), numbers
    assert numbers["skipped_max"] == 0


def test_a_pair_over_the_threshold_is_caught():
    d = clean()
    rep = next(r for r in d.replies if r.status == "matched")
    far = int(np.argmax(np.abs(d.pool_rating - d.sent_rating[0])))
    rep.players = (rep.to, d.pool_ids[far])
    assert R.compare(d)["over_threshold"] >= 1


def test_a_player_twice_is_caught():
    d = clean()
    m = [r for r in d.replies if r.status == "matched"]
    m[1].players = (m[1].to, m[0].players[1])
    assert R.compare(d)["twice"] == 1


def test_a_missing_reply_is_caught():
    d = clean()
    d.replies = d.replies[:-1]
    assert R.compare(d)["unanswered"] == 1


def test_a_bad_status_and_an_unknown_partner_are_caught():
    d = clean()
    d.replies[0] = Reply(d.replies[0].to, "error", 1.0)
    m = next(r for r in d.replies[1:] if r.status == "matched")
    m.players = (m.to, "nobody")
    numbers = R.compare(d)
    assert numbers["bad_status"] == 1 and numbers["not_waiting"] == 1


def test_pool_counts_off_by_one_are_caught():
    d = clean()
    d.device_pool_size_end += 1
    numbers = R.compare(d)
    assert numbers["device_pool_gap"] == 1 and numbers["pool_gap"] == 0


def test_a_fall_back_to_the_host_oracle_fails_the_run():
    d = clean()
    d.off_device = 1
    assert R.failures(R.compare(d))


def test_a_partner_past_nearer_free_players_is_counted():
    d = clean()
    m = next(r for r in d.replies if r.status == "matched")
    r = d.sent_rating[d.sent_ids.index(m.to)]
    # Swap in a worse free partner: the nearest ones it skips count.
    taken = {p for rep in d.replies for p in rep.players}
    free = [(abs(d.pool_rating[i] - r), pid)
            for i, pid in enumerate(d.pool_ids)
            if pid not in taken and abs(d.pool_rating[i] - r) <= 100]
    free.sort()
    m.players = (m.to, free[5][1])
    d.pool_size_end = d.device_pool_size_end = d.pool_size_end
    assert R.compare(d)["skipped_max"] >= 5


def _loop_nearer(free, r, d):
    return [sum(1 for p in free
                if np.abs(np.float32(p) - np.float32(ri)) < np.float32(di))
            for ri, di in zip(r, d)]


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_vectorized_count_equals_a_plain_loop(seed):
    rng = np.random.default_rng(seed)
    free = rng.normal(1500, 300, 3000)
    r = np.rint(rng.normal(1500, 300, 200) * 100) / 100
    d = np.abs(rng.normal(0, 3, 200)).astype(np.float32)
    d[:10] = 0.0
    assert R.nearer_counts(free, r, d).tolist() == _loop_nearer(free, r, d)


def test_the_bf16_control_skips_far_more_than_sound_answers():
    rng = np.random.default_rng(4)
    free = rng.normal(1500, 300, 50_000)
    r = np.rint(rng.normal(1500, 300, 500) * 100) / 100
    picks = R.bf16_picks(free, r)
    d = R.f32_distance(r, picks)
    assert R.nearer_counts(free, r, d).max() > 50
