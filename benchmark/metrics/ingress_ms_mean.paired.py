"""Broker and middleware layer: mean host time per settled request (ms) in
the flight recorder's broker_dwell, middleware and ingress categories,
over the untraced part of the window."""

CATEGORIES = ("broker_dwell", "middleware", "ingress")


def read(run):
    w = run.readings["window"]
    cats = [w["categories"][c] for c in CATEGORIES if c in w["categories"]]
    if not cats or w["settled"] <= 0:
        return None
    return sum(c["total_s"] for c in cats) / w["settled"] * 1e3
