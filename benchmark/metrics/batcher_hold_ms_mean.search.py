"""Batcher layer: mean time per settled request (ms) in the flight
recorder's batcher_hold category (the window clock, or windows queued
ahead), over the untraced part of the window."""


def read(run):
    w = run.readings["window"]
    cat = w["categories"].get("batcher_hold")
    if cat is None or w["settled"] <= 0:
        return None
    return cat["total_s"] / w["settled"] * 1e3
