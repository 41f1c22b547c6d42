"""Engine host side: mean time per settled request (ms) that results
waited for their readback group and for the device-to-host transfer
(flight recorder readback_group_wait + readback_transfer), over the
untraced part of the window."""

CATEGORIES = ("readback_group_wait", "readback_transfer")


def read(run):
    w = run.readings["window"]
    cats = [w["categories"][c] for c in CATEGORIES if c in w["categories"]]
    if not cats or w["settled"] <= 0:
        return None
    return sum(c["total_s"] for c in cats) / w["settled"] * 1e3
