"""What a formation window has to move and compute, counted from shapes.

The dense 1v1 step (``engine/kernels.py``) scans the whole pool once per
window: it reads every column of every slot, writes the columns back (the
scan's stacked outputs are the updated pool), and reads and writes the
``active`` column once more to evict the matched. Besides the pool it
reads the packed window (9 float32 rows of the window's bucket) and writes
the result (3 float32 rows). Its operations are the rating distance and
the masks of every window lane against every slot.

The counts are of what the algorithm needs; the program may move more.
A share computed from them is therefore a lower bound of the real one.
"""

from __future__ import annotations

#: Bytes per pool slot: rating, rd, region, mode, threshold, enqueue time
#: (4 bytes each) and the active flag (1 byte).
POOL_SLOT_BYTES = 6 * 4 + 1
#: The active flag, read and written again by the eviction pass.
EVICT_SLOT_BYTES = 2 * 1
#: Rows of the packed window in and of the result out, float32 each.
PACKED_IN_ROWS, PACKED_OUT_ROWS = 9, 3
#: Operations per (lane, slot) pair: the difference, its absolute value,
#: the threshold minimum, the compare, three mask ands and the select.
OPS_PER_PAIR = 8


def window_bytes(slots: int, lanes: int) -> int:
    """Bytes one chip moves for one window: ``slots`` pool slots on the
    chip, a window bucket of ``lanes``."""
    pool = slots * (2 * POOL_SLOT_BYTES + EVICT_SLOT_BYTES)
    return pool + lanes * 4 * (PACKED_IN_ROWS + PACKED_OUT_ROWS)


def window_ops(slots: int, lanes: int) -> int:
    """Operations one chip computes for one window."""
    return slots * lanes * OPS_PER_PAIR


def least_seconds(slots: int, lanes: int, hbm_bytes_per_s: float,
                  ops_per_s: float) -> tuple[float, str]:
    """The least time the chip could take for one window, and which of the
    two bounds sets it."""
    t_bytes = window_bytes(slots, lanes) / hbm_bytes_per_s
    t_ops = window_ops(slots, lanes) / ops_per_s
    return (t_bytes, "hbm") if t_bytes >= t_ops else (t_ops, "compute")
