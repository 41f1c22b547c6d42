"""Sharded merge: device time (ms) of the collective operations
(all-gather, collective-permute and the like, by XLA op kind) per window,
averaged over the chips (profiler trace of the window's last seconds)."""


def read(run):
    r = run.readings
    t, tr = r.get("trace"), r.get("traced")
    if not t or not tr or t["collective_s"] <= 0:
        return None
    windows = tr["spans"].get("windows", 0)
    if windows <= 0:
        return None
    return t["collective_s"] / windows * 1e3
