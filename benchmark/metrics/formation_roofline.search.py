"""Formation kernels: the share (%) of the roofline that the device
reaches over the traced window. The least time of each window is counted
from shapes (``benchmark/work.py``: the pool read and written back, the
packed window in and the result out, and the pair operations) at the
chip's published peaks, and the sum over the traced windows is divided
by the device's busy time per chip. Busy time covers every operation, so
the share is a lower bound of the formation step's own."""

from benchmark import peaks, work


def read(run):
    r = run.readings
    t, tr = r.get("trace"), r.get("traced")
    if not t or not tr or t["busy_s"] <= 0:
        return None
    windows = tr["spans"].get("windows", 0)
    if windows <= 0:
        return None
    p = peaks.peaks(r["device_kind"])
    least, _bound = work.least_seconds(
        r["slots_per_chip"], tr["lanes_padded"] / windows,
        p["hbm_bytes_per_s"], p["bf16_flops_per_s"])
    return 100.0 * least * windows / t["busy_s"]
