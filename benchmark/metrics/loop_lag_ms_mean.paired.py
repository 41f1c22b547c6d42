"""Event loop: how late, on average, the collector's sleep woke beyond
the time it asked for (ms per tick): the time a ready callback waited for
the one event loop. The program's ``loop_lag`` site category, over the
untraced part of the window."""


def read(run):
    cat = run.readings["window"]["categories"].get("loop_lag")
    if cat is None or cat["traces"] <= 0:
        return None
    return cat["total_s"] / cat["traces"] * 1e3
