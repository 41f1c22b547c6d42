"""Device: the share of the traced window (%) in which no operation ran
on the device, averaged over the chips used (profiler trace)."""


def read(run):
    t = run.readings.get("trace")
    if not t or t["idle_share"] is None or t["busy_s"] <= 0:
        return None
    return 100.0 * t["idle_share"]
