"""Engine host side: host time per window (ms) that the engine spends
collecting results (readiness polls, device-to-host materialisation and
finalize) from its ``collect_s`` span counter, over the untraced part of
the window."""


def read(run):
    spans = run.readings["window"]["spans"]
    windows = spans.get("windows", 0)
    if "collect_s" not in spans or windows <= 0:
        return None
    return spans["collect_s"] / windows * 1e3
