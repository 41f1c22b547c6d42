"""Per-queue write-ahead pool journal + hard-crash recovery (ISSUE 15).

The graceful path (PR 5/6/11's drain → checkpoint → restore) only fires on
SIGTERM; a hard crash (OOM, host loss, ``kill -9``) previously lost the
entire waiting pool, the held admission credits, and the at-least-once
dedup/replay cache. This module makes failure a *planned transition with
bounded, measured blackout*:

- **Framing.** A journal segment is a sequence of CRC-framed records:
  ``<IIQB`` header (crc32, payload length, record seq, record type) +
  payload bytes, crc computed over (length, seq, type, payload). The first
  record is a version-stamped SEGMENT header naming the snapshot the
  segment's records follow. A torn tail (crash mid-write) parses as "stop
  here", never as garbage records.

- **Record types.** ``ADMIT`` — one record per CUT WINDOW carrying every
  dispatched player's columns (the hot columnar path pays one buffered
  append per window, not per player); ``TERMINAL`` — one player reached a
  terminal state (matched / timeout / shed-evicted), payload = the encoded
  response body + dedup expiry, exactly what the ``_recent`` replay cache
  holds; ``ADMISSION`` — the AdmissionController decision checkpoint
  (written at compaction); ``CLEAN`` — clean-shutdown marker (its absence
  at boot IS the crash detector).

- **Write-ahead discipline.** Appends are buffered; ``commit()`` writes
  the buffer in one ``os.write`` and fsyncs per the configured policy
  (``none`` | ``interval`` | ``window``). The service commits before a
  terminal response is published and before a delivery is acked, so under
  ``fsync="window"`` a response the client saw implies a durable terminal
  record — the invariant that makes recovery yield zero double matches.

- **Compaction.** The live segment periodically compacts: the current seq
  ``S`` is captured under the engine lock with the pipeline drained, the
  pool snapshots to ``<queue>.snap.<S>.npz`` (utils/checkpoint format,
  atomic tmp+rename), the live segment rotates to ``.prev`` and a fresh
  segment opens anchored at ``S``, carrying the live dedup entries and the
  admission checkpoint forward. Replay filters by SEQ, not by file, so a
  crash at any point inside compaction recovers losslessly (the
  crash-during-compaction test pins "old snapshot still wins").

- **Recovery.** ``PoolJournal`` attaches to whatever artifacts exist at
  construction: it picks the newest snapshot that *verifies* (falling back
  to the previous good one with a speakable warning on corruption),
  replays the retained segments' records with seq > snapshot seq into a
  final (waiting, removed, recent, admission) state, and reports whether
  the shutdown was clean. The app applies that state to the engine and
  measures the whole span as ``crash_rto_ms``.
"""

from __future__ import annotations

import base64
import dataclasses
import glob
import json
import logging
import os
import re
import struct
import threading
import time
import zlib
from typing import Any

log = logging.getLogger(__name__)

FORMAT_VERSION = 1

#: Record frame header: crc32, payload length, record seq, record type.
_HEADER = struct.Struct("<IIQB")

RT_SEGMENT = 0   #: segment header (version stamp + snapshot anchor)
RT_ADMIT = 1     #: one cut window's dispatched players (columns)
RT_TERMINAL = 2  #: one player's terminal (response body + dedup expiry)
RT_ADMISSION = 3  #: AdmissionController decision checkpoint
RT_CLEAN = 4     #: clean-shutdown marker
RT_TERMINALS = 5  #: one window's terminals in ONE record (the hot path:
#                  one json+crc+lock acquire per window, not per player)


class FencedError(RuntimeError):
    """Append refused: this journal's owner was epoch-fenced (ISSUE 17).
    A superseded ex-primary must not extend the WAL — the standby's
    successor owns this queue's history now. Raised by ``_append`` when
    the installed ``fence`` check fails."""

_FSYNC_POLICIES = ("none", "interval", "window")

_SNAP_RE = re.compile(r"\.snap\.(\d+)\.npz$")


def journal_path(directory: str, queue: str) -> str:
    return os.path.join(directory, f"{queue}.journal")


def snapshot_path(directory: str, queue: str, seq: int) -> str:
    return os.path.join(directory, f"{queue}.snap.{seq:012d}.npz")


def list_snapshots(directory: str, queue: str) -> list[tuple[int, str]]:
    """(seq, path) of every compaction snapshot for ``queue``, newest
    first. ``.tmp`` leftovers from an interrupted compaction never match."""
    out: list[tuple[int, str]] = []
    for path in glob.glob(os.path.join(directory, f"{queue}.snap.*.npz")):
        m = _SNAP_RE.search(path)
        if m is not None:
            out.append((int(m.group(1)), path))
    out.sort(reverse=True)
    return out


def _frame(seq: int, rtype: int, payload: bytes) -> bytes:
    crc = zlib.crc32(struct.pack("<IQB", len(payload), seq, rtype))
    crc = zlib.crc32(payload, crc)
    return _HEADER.pack(crc, len(payload), seq, rtype) + payload


def read_segment(path: str) -> tuple[dict[str, Any], list[tuple[int, int, bytes]], bool, int]:
    """Parse one segment: (header dict, [(seq, rtype, payload)], torn,
    intact byte offset).

    Stops cleanly at the first truncated/CRC-bad frame — a torn tail is
    the normal post-crash shape, not an error; everything before it is
    intact by the per-record CRC, and ``intact`` is where a re-attaching
    writer may truncate-and-append. Raises :class:`ValueError` only when
    the SEGMENT header itself is unreadable (the file is not a journal)."""
    records: list[tuple[int, int, bytes]] = []
    torn = False
    with open(path, "rb") as f:
        data = f.read()
    off = 0
    header: dict[str, Any] | None = None
    while off + _HEADER.size <= len(data):
        crc, length, seq, rtype = _HEADER.unpack_from(data, off)
        end = off + _HEADER.size + length
        if end > len(data):
            torn = True
            break
        payload = data[off + _HEADER.size:end]
        want = zlib.crc32(struct.pack("<IQB", length, seq, rtype))
        want = zlib.crc32(payload, want)
        if want != crc:
            torn = True
            break
        if rtype == RT_SEGMENT:
            if header is None:
                header = json.loads(payload.decode("utf-8"))
            # A stray later SEGMENT record is ignored (never written).
        else:
            records.append((seq, rtype, payload))
        off = end
    if off < len(data):
        torn = True
    if header is None:
        raise ValueError(f"{path}: no valid segment header")
    if header.get("version") != FORMAT_VERSION:
        raise ValueError(
            f"{path}: unsupported journal version {header.get('version')}")
    return header, records, torn, off


def admit_payload(rows: list[list[Any]]) -> bytes:
    """One cut window's ADMIT payload. Each row:
    [id, rating, rd, region, mode, threshold|None, enqueued_at, reply_to,
    correlation_id, tier, deadline] — region/mode by NAME (codes are
    process-local), the utils/checkpoint portability rule."""
    return json.dumps({"rows": rows}, separators=(",", ":")).encode("utf-8")


def terminal_payload(pid: str, body: bytes, expiry: float) -> bytes:
    return json.dumps(
        {"id": pid, "body": base64.b64encode(body).decode("ascii"),
         "exp": expiry}, separators=(",", ":")).encode("utf-8")


def terminals_payload(entries: "list[tuple[str, bytes, float]]") -> bytes:
    """One window's terminals as a single RT_TERMINALS payload."""
    return json.dumps(
        {"t": [[pid, base64.b64encode(body).decode("ascii"), exp]
               for pid, body, exp in entries]},
        separators=(",", ":")).encode("utf-8")


def row_to_request(row: list[Any]):
    """Inverse of the ADMIT row shape → SearchRequest (the engine.restore
    payload — same fidelity as utils/checkpoint's object fallback)."""
    from matchmaking_tpu.service.contract import SearchRequest

    thr = row[5]
    return SearchRequest(
        id=str(row[0]), rating=float(row[1]), rating_deviation=float(row[2]),
        region=str(row[3]), game_mode=str(row[4]),
        rating_threshold=None if thr is None else float(thr),
        enqueued_at=float(row[6]), reply_to=str(row[7]),
        correlation_id=str(row[8]), tier=int(row[9]),
        deadline_at=float(row[10]))


@dataclasses.dataclass
class RecoveredQueue:
    """The journal's view of one queue at boot, ready to apply."""

    queue: str
    #: Clean-shutdown marker present (no crash recovery needed).
    clean: bool = True
    #: Newest snapshot that VERIFIED, or "" (start from empty).
    snapshot: str = ""
    snapshot_seq: int = 0
    #: A newer snapshot existed but failed verification (fell back).
    fallback: bool = False
    #: Speakable corruption notes (corrupt snapshots, torn tails).
    corrupt: list[str] = dataclasses.field(default_factory=list)
    #: id → admit row for journal-admitted players still waiting.
    waiting: dict[str, list[Any]] = dataclasses.field(default_factory=dict)
    #: ids that reached a terminal state and were NOT re-admitted after
    #: (applied to the snapshot with engine.remove at recovery).
    removed: set[str] = dataclasses.field(default_factory=set)
    #: id → (response body, dedup expiry): the ``_recent`` replay cache.
    recent: dict[str, tuple[bytes, float]] = dataclasses.field(
        default_factory=dict)
    #: Last AdmissionController checkpoint seen, or None.
    admission: dict[str, Any] | None = None
    last_seq: int = 0
    replayed: int = 0

    def transcript(self) -> dict[str, Any]:
        """Deterministic content summary (the two-run bit-identity pin):
        a pure function of the recovered STATE, independent of window
        framing, record grouping, AND compaction cadence — the snapshot
        name carries its anchor seq (a framing fact), so only its
        presence is recorded."""
        return {
            "queue": self.queue,
            "clean": self.clean,
            "snapshot": bool(self.snapshot),
            "fallback": self.fallback,
            "waiting": sorted(self.waiting),
            "removed": sorted(self.removed),
            "recent": sorted(self.recent),
        }


def _verify_snapshot(path: str) -> bool:
    """Fully read a pool snapshot (np.load + meta + every array) so a
    truncated/bit-flipped file is caught HERE, before recovery commits to
    replaying against it."""
    import numpy as np

    try:
        with np.load(path, allow_pickle=False) as z:
            meta = json.loads(str(z["meta"]))
            if meta.get("version") is None:
                return False
            for name in z.files:
                z[name]  # force decompression — zip CRCs check the bytes
        return True
    except Exception:
        return False


# protocol-monotone: seq, synced_seq, last_seq
class PoolJournal:
    """One queue's write-ahead journal. Thread-safe: appends come from the
    event loop (terminal settles) AND from engine-lock-holding worker
    threads (window dispatch), so every mutation runs under an internal
    ``threading.Lock``.

    Construction ATTACHES to existing artifacts (recovery parse into
    ``self.recovered``) and continues the sequence numbering past the
    newest record — it never truncates state; compaction and the clean
    marker are explicit calls."""

    def __init__(self, directory: str, queue: str, *, fsync: str = "none",
                 fsync_interval_s: float = 0.05,
                 compact_records: int = 50_000,
                 compact_bytes: int = 8 << 20,
                 keep_snapshots: int = 2):
        if fsync not in _FSYNC_POLICIES:
            raise ValueError(
                f"unknown fsync policy {fsync!r} (one of {_FSYNC_POLICIES})")
        os.makedirs(directory, exist_ok=True)
        self.directory = directory
        self.queue = queue
        self.fsync = fsync
        self.fsync_interval_s = fsync_interval_s
        self.compact_records = max(1, compact_records)
        self.compact_bytes = max(1, compact_bytes)
        self.keep_snapshots = max(1, keep_snapshots)
        self._lock = threading.Lock()
        self._buf: list[bytes] = []  # guarded-by: _lock
        self._closed = False  # guarded-by: _lock
        #: Bytes written out (os.write) but not yet fsynced — what a
        #: policy commit still owes durability for.
        self._unsynced = False  # guarded-by: _lock
        self._last_fsync = time.monotonic()  # guarded-by: _lock
        #: Monotone record sequence (recovery replay order; the matchlint
        #: determinism rule guards this against wall-clock arithmetic).
        self.seq = 0  # guarded-by: _lock
        #: Highest seq covered by an fsync — the durability watermark
        #: (seq - synced_seq = records a HOST loss could still drop;
        #: surfaced per queue in the /metrics durability report).
        self.synced_seq = 0  # guarded-by: _lock
        #: Live-segment accounting (compaction trigger).
        self.segment_records = 0  # guarded-by: _lock
        self.segment_bytes = 0  # guarded-by: _lock
        #: Lifetime write-amplification accounting: file bytes written vs
        #: logical payload bytes journaled.
        self.bytes_written = 0  # guarded-by: _lock
        self.payload_bytes = 0  # guarded-by: _lock
        self._fd: int | None = None  # guarded-by: _lock
        #: Replication stream tap (ISSUE 17, service/replication.py; None
        #: = replication off): called as ``tap(seq, rtype, payload)``
        #: inside the append lock for EVERY sealed record — appends AND
        #: the compaction carries (which consume seqs without going
        #: through ``_append``; an untapped carry would stall the
        #: standby's contiguous-apply watermark forever).
        self.tap = None
        #: Epoch fence (ISSUE 17; None = unfenced): a ``() -> bool``
        #: check run at the top of every append — False means this
        #: journal's owner was superseded and ``_append`` raises
        #: :class:`FencedError` instead of extending history.
        self.fence = None
        #: Recovery parse of whatever artifacts existed at attach (None =
        #: nothing on disk: a genuinely fresh boot).
        self.recovered: RecoveredQueue | None = self._attach()
        if self.recovered is not None:
            self.seq = self.recovered.last_seq
            self._reopen_live()
        else:
            self._open_segment(snapshot="", snapshot_seq=0)
        self.synced_seq = self.seq

    # ---- attach / recovery -------------------------------------------------

    def _reopen_live(self) -> None:
        """Re-attach the writer to the existing live segment: truncate a
        torn tail back to the last intact frame (appending after garbage
        would hide every later record from replay), then append. A live
        segment that is missing or headerless gets a fresh one."""
        live = journal_path(self.directory, self.queue)
        if not os.path.exists(live) or self._live_intact < 0:
            self._open_segment(snapshot="", snapshot_seq=0)
            return
        fd = os.open(live, os.O_WRONLY)
        if self._live_intact:
            os.ftruncate(fd, self._live_intact)
        os.lseek(fd, 0, os.SEEK_END)
        self._fd = fd
        self.segment_records = 0  # conservative: rotation decides anyway
        self.segment_bytes = os.fstat(fd).st_size

    def _attach(self) -> RecoveredQueue | None:
        #: Intact byte offset of the live segment (-1 = unreadable, 0 =
        #: intact end-to-end — ftruncate(0) is never wanted, so 0 means
        #: "no truncation needed" here).
        self._live_intact = 0
        live = journal_path(self.directory, self.queue)
        prev = live + ".prev"
        snaps = list_snapshots(self.directory, self.queue)
        if not os.path.exists(live) and not os.path.exists(prev) \
                and not snaps:
            return None
        rec = RecoveredQueue(queue=self.queue)
        # Newest VERIFIED snapshot wins; a corrupt newer one falls back to
        # the previous good generation with a speakable note instead of
        # crashing the boot (the satellite-1 contract).
        first = True
        for seq, path in snaps:
            if _verify_snapshot(path):
                rec.snapshot, rec.snapshot_seq = path, seq
                rec.fallback = not first
                break
            rec.corrupt.append(
                f"snapshot {os.path.basename(path)} failed verification "
                f"(truncated or corrupt) — falling back")
            first = False
        # Replay retained segments oldest-first; seq filtering (not file
        # filtering) makes a crash at any compaction point lossless.
        records: list[tuple[int, int, bytes]] = []
        clean = False
        torn_any = False
        any_segment = False
        for path in (prev, live):
            if not os.path.exists(path):
                continue
            try:
                _header, recs, torn, intact = read_segment(path)
            except ValueError as e:
                rec.corrupt.append(str(e))
                if path == live:
                    self._live_intact = -1  # headerless: rebuild it
                continue
            any_segment = True
            if torn:
                torn_any = True
                rec.corrupt.append(
                    f"{os.path.basename(path)}: torn tail — replay stops "
                    f"at the last intact record")
                if path == live:
                    self._live_intact = intact
            records.extend(recs)
        records.sort(key=lambda r: r[0])
        for seq, rtype, payload in records:
            rec.last_seq = max(rec.last_seq, seq)
            if rtype == RT_CLEAN:
                clean = True
                continue
            clean = False  # any later mutation reopens the journal
            if rtype == RT_ADMIT:
                if seq <= rec.snapshot_seq:
                    continue  # pool membership superseded by the snapshot
                rec.replayed += 1
                for row in json.loads(payload.decode("utf-8"))["rows"]:
                    rec.waiting[str(row[0])] = row
                    rec.removed.discard(str(row[0]))
            elif rtype in (RT_TERMINAL, RT_TERMINALS):
                # Terminals rebuild ``recent`` REGARDLESS of seq: the
                # at-least-once dedup horizon is not pool state, so a
                # pre-anchor terminal surviving in the .prev segment still
                # counts (this is what makes a crash between compaction's
                # two renames lossless — the carries may be gone, but the
                # old segment's terminals are not). Pool effects (waiting/
                # removed) stay seq-filtered: the snapshot is the pool
                # truth at the anchor.
                d = json.loads(payload.decode("utf-8"))
                entries = (d["t"] if rtype == RT_TERMINALS
                           else [[d["id"], d["body"], d["exp"]]])
                for pid, b64, exp in entries:
                    pid = str(pid)
                    rec.recent[pid] = (base64.b64decode(b64), float(exp))
                    if seq > rec.snapshot_seq:
                        rec.replayed += 1
                        rec.waiting.pop(pid, None)
                        rec.removed.add(pid)
            elif rtype == RT_ADMISSION:
                # Checkpoint, not a delta: the newest retained one wins
                # whatever its seq (records replay in seq order).
                rec.admission = json.loads(payload.decode("utf-8"))
        # No segment at all (snapshot-only dir): treat as unclean — the
        # process died between snapshot and segment creation. A torn tail
        # also voids the marker: something wrote after it.
        rec.clean = clean and not torn_any if any_segment else False
        return rec

    # ---- the append/commit hot path ----------------------------------------

    def _open_segment(self, snapshot: str, snapshot_seq: int) -> None:
        header = {"version": FORMAT_VERSION, "queue": self.queue,
                  "snapshot": os.path.basename(snapshot) if snapshot else "",
                  "snapshot_seq": snapshot_seq}
        frame = _frame(0, RT_SEGMENT,
                       json.dumps(header, separators=(",", ":")).encode())
        path = journal_path(self.directory, self.queue)
        fd = os.open(path + ".new", os.O_CREAT | os.O_TRUNC | os.O_WRONLY,
                     0o644)
        os.write(fd, frame)
        os.fsync(fd)
        os.replace(path + ".new", path)
        self._fd = fd
        self.segment_records = 0
        self.segment_bytes = len(frame)
        self.bytes_written += len(frame)

    # protocol-effect: journal_append requires-fence fence
    def _append(self, rtype: int, payload: bytes, logical: int,
                writeout: bool = False) -> int:
        """THE append seam (the sanitizer's journal twin patches exactly
        this): assign the next seq, frame, and buffer — or, with
        ``writeout``, ``os.write`` the frame directly inside the same
        lock hold (the hot-path records: the buffer is then never
        observably dirty, so a concurrent settle's acked-after-append
        audit cannot race a half-staged append; a PROCESS crash cannot
        lose written bytes, so this is also what recovers a mid-window
        crash's players as waiting). Returns the seq."""
        if self.fence is not None and not self.fence():
            # Epoch fencing (ISSUE 17): a superseded ex-primary CANNOT
            # extend the WAL — checked before the lock so a fenced
            # writer never even contends with the successor's history.
            raise FencedError(
                f"journal append for {self.queue!r} refused: owner was "
                f"epoch-fenced (a standby took over this queue)")
        with self._lock:
            if self._closed:
                raise RuntimeError(
                    f"journal for {self.queue!r} is closed (append after "
                    f"clean-shutdown marker)")
            self.seq += 1
            seq = self.seq
            frame = _frame(seq, rtype, payload)
            if writeout and self._fd is not None:
                os.write(self._fd, frame)
                self.segment_records += 1
                self.segment_bytes += len(frame)
                self.bytes_written += len(frame)
                self._unsynced = True
            else:
                self._buf.append(frame)
            self.payload_bytes += logical
            if self.tap is not None:
                # Replication stream (ISSUE 17): ship the sealed record.
                # Never let a tap failure poison the append — replication
                # loss is bounded by acks; a failed append is data loss.
                try:
                    self.tap(seq, rtype, payload)
                except Exception:
                    log.exception("journal tap failed for %r seq %d",
                                  self.queue, seq)
            return seq

    def append_admits(self, rows: list[list[Any]]) -> int:
        """One cut window's dispatched players — ONE record, written out
        in the append (host-loss durability is only promised at the
        response/ack commit points, where the policy fsync runs)."""
        payload = admit_payload(rows)
        return self._append(RT_ADMIT, payload, len(payload), writeout=True)

    def append_terminal(self, pid: str, body: bytes, expiry: float) -> int:
        return self._append(RT_TERMINAL, terminal_payload(pid, body, expiry),
                            len(body))

    def append_terminals(self,
                         entries: "list[tuple[str, bytes, float]]") -> int:
        """One cut window's terminals — ONE record (one json+crc+lock
        acquire per window), written out in the append like the admits."""
        return self._append(RT_TERMINALS, terminals_payload(entries),
                            sum(len(b) for _, b, _ in entries),
                            writeout=True)

    @property
    def dirty(self) -> bool:
        return bool(self._buf)

    # holds-lock: _lock
    def _writeout_locked(self) -> None:
        """Drain the frame buffer in one os.write (caller holds _lock)."""
        if not self._buf or self._fd is None:
            return
        data = b"".join(self._buf)
        n = len(self._buf)
        self._buf.clear()
        os.write(self._fd, data)
        self.segment_records += n
        self.segment_bytes += len(data)
        self.bytes_written += len(data)
        self._unsynced = True

    def flush_buffer(self) -> None:
        """Write the buffered frames WITHOUT any fsync, whatever the
        policy — the admit-at-dispatch point. A PROCESS crash cannot lose
        os.write'd bytes (the page cache outlives the process), so a
        mid-window crash still recovers the window's players as waiting;
        host-loss durability is only promised at the response/ack commit
        points, where ``commit()`` runs the policy fsync. Keeping the
        dispatch path fsync-free is what holds the fsync="window" steady-
        state overhead to ONE fsync per window."""
        with self._lock:
            self._writeout_locked()

    @property
    def needs_commit(self) -> bool:
        """Anything for the service's write-ahead commit point to do:
        buffered frames, or written-but-unsynced bytes a durability
        policy still owes an fsync."""
        if self._buf:
            return True
        return self._unsynced and self.fsync in ("interval", "window")

    def commit(self, force_sync: bool = False) -> None:
        """Write the buffered frames in one os.write; fsync per policy
        (covering any earlier ``flush_buffer`` writeouts too). Called by
        the service before a terminal response publishes and before a
        delivery acks — the write-ahead points."""
        with self._lock:
            self._writeout_locked()
            if self._fd is None or not (force_sync or self._unsynced):
                return
            if force_sync or self.fsync == "window":
                written = self.seq
                os.fsync(self._fd)
                self._unsynced = False
                self.synced_seq = max(self.synced_seq, written)
                self._last_fsync = time.monotonic()
            elif self.fsync == "interval":
                now = time.monotonic()
                if now - self._last_fsync >= self.fsync_interval_s:
                    written = self.seq
                    os.fsync(self._fd)
                    self._unsynced = False
                    self.synced_seq = max(self.synced_seq, written)
                    self._last_fsync = now

    def wants_compact(self) -> bool:
        return (self.segment_records >= self.compact_records
                or self.segment_bytes >= self.compact_bytes)

    # ---- compaction --------------------------------------------------------

    def compact_begin(self) -> tuple[int, str]:
        """Capture the compaction anchor. Caller MUST hold the queue's
        engine lock with the pipeline drained (so the pool cannot mutate
        between the seq capture and the snapshot write) and then write the
        pool snapshot to the returned path (utils/checkpoint.save_pool —
        atomic by construction). Returns (anchor seq, snapshot path)."""
        self.commit()
        with self._lock:
            return self.seq, snapshot_path(self.directory, self.queue,
                                           self.seq)

    # protocol-effect: journal_append requires-fence fence
    def compact_finish(self, anchor_seq: int, snap_path: str,
                       carry_terminals: list[tuple[str, bytes, float]] = (),
                       admission: dict[str, Any] | None = None) -> None:
        """Rotate to a fresh segment anchored at the (verified) snapshot,
        carrying the live dedup entries + admission checkpoint forward so
        the at-least-once horizon survives the truncation.

        Crash-atomic by construction: the successor segment is built
        COMPLETE (header + carries + admission, fsynced) in a side file
        before the two renames, so at every crash point recovery reads a
        consistent (snapshot, segments) pair — and the seq-unfiltered
        TERMINAL replay in ``_attach`` covers the one window between the
        renames where the carries are not yet the live segment (the old
        segment's terminals still are)."""
        if self.fence is not None and not self.fence():
            # A fenced ex-primary must not rewrite history either —
            # compaction rotates segments and consumes seqs.
            raise FencedError(
                f"journal compaction for {self.queue!r} refused: owner "
                f"was epoch-fenced")
        if not _verify_snapshot(snap_path):
            # Never truncate history against a snapshot that does not
            # read back: the old segment keeps covering the pool.
            raise ValueError(
                f"compaction snapshot {snap_path!r} failed verification — "
                f"keeping the current journal segment")
        live = journal_path(self.directory, self.queue)
        with self._lock:
            header = {"version": FORMAT_VERSION, "queue": self.queue,
                      "snapshot": os.path.basename(snap_path),
                      "snapshot_seq": anchor_seq}
            frames = [_frame(0, RT_SEGMENT,
                             json.dumps(header,
                                        separators=(",", ":")).encode())]
            logical = 0
            #: Compaction carries consume seqs without going through
            #: ``_append`` — tap them too (ISSUE 17), or the replication
            #: standby would stall forever waiting for the gap. Carries
            #: are re-statements of already-streamed state, so the
            #: standby's apply is idempotent over them.
            tapped: list[tuple[int, int, bytes]] = []
            for pid, body, exp in carry_terminals:
                self.seq += 1
                payload = terminal_payload(pid, body, exp)
                frames.append(_frame(self.seq, RT_TERMINAL, payload))
                tapped.append((self.seq, RT_TERMINAL, payload))
                logical += len(body)
            if admission is not None:
                self.seq += 1
                payload = json.dumps(admission,
                                     separators=(",", ":")).encode("utf-8")
                frames.append(_frame(self.seq, RT_ADMISSION, payload))
                tapped.append((self.seq, RT_ADMISSION, payload))
                logical += len(payload)
            if self.tap is not None:
                for seq, rtype, payload in tapped:
                    try:
                        self.tap(seq, rtype, payload)
                    except Exception:
                        log.exception("journal tap failed for %r seq %d",
                                      self.queue, seq)
            data = b"".join(frames)
            fd = os.open(live + ".new",
                         os.O_CREAT | os.O_TRUNC | os.O_WRONLY, 0o644)
            os.write(fd, data)
            os.fsync(fd)
            if self._fd is not None:
                os.fsync(self._fd)
                os.close(self._fd)
                self._fd = None
            if os.path.exists(live):
                os.replace(live, live + ".prev")
            os.replace(live + ".new", live)
            self._fd = fd
            self.segment_records = len(frames) - 1
            self.segment_bytes = len(data)
            self.bytes_written += len(data)
            self.payload_bytes += logical
            # The successor was fsynced before the renames and the old
            # segment before close, so everything appended so far is
            # durable — keep the watermark true.
            self._unsynced = False
            self.synced_seq = max(self.synced_seq, self.seq)
        self._gc(anchor_seq)

    def _gc(self, anchor_seq: int) -> None:
        """Drop snapshot generations beyond the retention window (the
        anchor counts as generation 1)."""
        snaps = list_snapshots(self.directory, self.queue)
        keep = {path for seq, path in snaps[:self.keep_snapshots]}
        for _seq, path in snaps:
            if path not in keep:
                try:
                    os.unlink(path)
                except OSError:
                    log.warning("could not gc old snapshot %s", path)

    # ---- lifecycle ---------------------------------------------------------

    def mark_clean(self) -> None:
        """Append the clean-shutdown marker and make it durable — boot
        sees this and skips crash recovery."""
        self._append(RT_CLEAN, b"", 0)
        self.commit(force_sync=True)

    def close(self) -> None:
        with self._lock:
            self._closed = True
            if self._fd is not None:
                if self._buf:
                    data = b"".join(self._buf)
                    self._buf.clear()
                    os.write(self._fd, data)
                    self.bytes_written += len(data)
                os.fsync(self._fd)
                os.close(self._fd)
                self._fd = None

    def abandon(self) -> None:
        """Crash-fidelity teardown (durability tests): DROP the
        uncommitted buffer (a real crash loses it) and close the fd
        without a clean marker or fsync — the on-disk state is exactly
        what a ``kill -9`` would leave."""
        with self._lock:
            self._closed = True
            self._buf.clear()
            if self._fd is not None:
                os.close(self._fd)
                self._fd = None
