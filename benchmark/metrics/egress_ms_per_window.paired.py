"""Response egress: host time per window (ms) to build its replies (and
queued acks), publish them and ack its requests: the program's ``egress``
site category over the engine's window count, over the untraced part of
the window. The journal commit between building and publishing is not
counted."""


def read(run):
    w = run.readings["window"]
    cat = w["categories"].get("egress")
    windows = w["spans"].get("windows", 0)
    if cat is None or windows <= 0:
        return None
    return cat["total_s"] / windows * 1e3
