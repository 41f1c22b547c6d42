"""Engine host side: mean host time per window (ms) to dispatch it (dedupe,
slot allocation, pack, host-to-device copy and the step's launch), from
the engine's span counters over the untraced part of the window."""


def read(run):
    spans = run.readings["window"]["spans"]
    if spans.get("windows", 0) <= 0:
        return None
    return spans["dispatch_s"] / spans["windows"] * 1e3
