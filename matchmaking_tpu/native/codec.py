"""ctypes binding for the native batch wire codec (native/codec.cc).

Ingress: one C call decodes a window of raw AMQP JSON bodies into
RequestColumns arrays (the engine's columnar fast path); rows flagged
NEEDS_PYTHON (parties, roles, string escapes) or invalid fall back to
``contract.decode_request`` — the semantic source of truth whose validation
the C++ mirrors (equivalence pinned by tests/test_native_codec.py).

Egress: one C call encodes a window of response bodies — matched pairs
(``encode_matched_batch``) and queued/timeout/shed rows
(``encode_simple_batch``) — BYTE-IDENTICAL to ``contract.encode_response``
(pinned by the seeded fuzz corpus in tests/test_codec_fuzz.py). Rows the
exact contract cannot express natively (non-ASCII ids, non-finite floats,
embedded NULs) come back as ``None`` and the caller re-encodes just those
through the Python contract module.

The library builds lazily with g++ (no deps; ~1 s once, cached next to the
source under a name that carries the source's hash). Everything degrades
to pure Python when g++ or the build is unavailable — the native layer is
an accelerator, never a requirement.
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import subprocess
import threading

import numpy as np

log = logging.getLogger(__name__)

_SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "native", "codec.cc")

# Status codes (keep in sync with codec.cc).
OK = 0
NEEDS_PYTHON = 1
_ERROR_CODES = {
    2: "bad_json",
    3: "missing_field",
    4: "bad_type",
    5: "bad_rating",
    6: "bad_threshold",
}

#: Row kinds for the simple-response encoder (keep in sync with codec.cc).
KIND_QUEUED = 0
KIND_TIMEOUT = 1
KIND_SHED = 2

_lock = threading.Lock()
_lib: ctypes.CDLL | None = None
_build_failed = False


def _lib_path() -> str:
    """The library built from the CURRENT codec.cc: its name carries a
    hash of the source, so a binary built from other source (a copy of the
    tree resets mtimes) is never the one loaded."""
    with open(_SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    return os.path.join(os.path.dirname(_SRC), f"libmmcodec-{digest}.so")


def _build_locked() -> str:
    """Compile the library for the current source unless it exists (caller
    holds ``_lock``); returns its path and removes libraries built from
    other source. CI rebuilds through here (scripts/check.sh codec
    section) so nothing ever depends on a checked-in binary."""
    lib = _lib_path()
    if not os.path.exists(lib):
        # Build beside, then rename: concurrent builders (test workers)
        # never load a half-written file.
        tmp = f"{lib}.{os.getpid()}.tmp"
        subprocess.run(["g++", "-O2", "-shared", "-fPIC", "-o", tmp, _SRC],
                       check=True, capture_output=True, timeout=120)
        os.replace(tmp, lib)
    native_dir = os.path.dirname(lib)
    for name in os.listdir(native_dir):
        if (name.startswith("libmmcodec") and name.endswith(".so")
                and os.path.join(native_dir, name) != lib):
            try:
                os.unlink(os.path.join(native_dir, name))
            except OSError:
                pass
    return lib


def _load() -> ctypes.CDLL | None:
    """Build (once) and load the shared library; None if unavailable."""
    global _lib, _build_failed
    if _lib is not None or _build_failed:
        return _lib
    with _lock:
        if _lib is not None or _build_failed:
            return _lib
        try:
            lib = ctypes.CDLL(_build_locked())
            lib.mm_decode_requests.restype = ctypes.c_int64
            lib.mm_decode_requests.argtypes = [
                ctypes.POINTER(ctypes.c_char_p),          # bufs
                np.ctypeslib.ndpointer(np.int32),         # lens
                ctypes.c_int32,                           # n
                np.ctypeslib.ndpointer(np.float32),       # rating
                np.ctypeslib.ndpointer(np.float32),       # rd
                np.ctypeslib.ndpointer(np.float32),       # threshold
                np.ctypeslib.ndpointer(np.int32),         # status
                ctypes.c_char_p,                          # arena
                ctypes.c_int64,                           # cap
                np.ctypeslib.ndpointer(np.int64),         # id_off
                np.ctypeslib.ndpointer(np.int64),         # region_off
                np.ctypeslib.ndpointer(np.int64),         # mode_off
            ]
            lib.mm_decode_requests_concat.restype = ctypes.c_int64
            lib.mm_decode_requests_concat.argtypes = [
                ctypes.c_char_p,                          # buf (concat bodies)
                ctypes.c_int64,                           # buf_len
                np.ctypeslib.ndpointer(np.int64),         # body offsets [n+1]
                ctypes.c_int32,                           # n
                np.ctypeslib.ndpointer(np.float32),       # rating
                np.ctypeslib.ndpointer(np.float32),       # rd
                np.ctypeslib.ndpointer(np.float32),       # threshold
                np.ctypeslib.ndpointer(np.int32),         # status
                ctypes.c_char_p,                          # arena
                ctypes.c_int64,                           # cap
                np.ctypeslib.ndpointer(np.int64),         # id_off
                np.ctypeslib.ndpointer(np.int64),         # region_off
                np.ctypeslib.ndpointer(np.int64),         # mode_off
            ]
            lib.mm_encode_matched.restype = ctypes.c_int64
            lib.mm_encode_matched.argtypes = [
                ctypes.POINTER(ctypes.c_char_p),          # id_a
                ctypes.POINTER(ctypes.c_char_p),          # id_b
                ctypes.POINTER(ctypes.c_char_p),          # match_id
                ctypes.c_int32,                           # n
                np.ctypeslib.ndpointer(np.float64),       # lat_a
                np.ctypeslib.ndpointer(np.float64),       # lat_b
                np.ctypeslib.ndpointer(np.float64),       # quality
                np.ctypeslib.ndpointer(np.float64),       # waited_a
                np.ctypeslib.ndpointer(np.float64),       # waited_b
                ctypes.POINTER(ctypes.c_char_p),          # trace_a (or None)
                ctypes.POINTER(ctypes.c_char_p),          # trace_b (or None)
                ctypes.c_char_p,                          # arena
                ctypes.c_int64,                           # cap
                np.ctypeslib.ndpointer(np.int64),         # off
                np.ctypeslib.ndpointer(np.int32),         # status
            ]
            lib.mm_encode_simple.restype = ctypes.c_int64
            lib.mm_encode_simple.argtypes = [
                np.ctypeslib.ndpointer(np.int32),         # kind
                ctypes.POINTER(ctypes.c_char_p),          # player_id
                np.ctypeslib.ndpointer(np.float64),       # lat_ms
                np.ctypeslib.ndpointer(np.float64),       # retry_ms
                ctypes.POINTER(ctypes.c_char_p),          # trace_id (or None)
                np.ctypeslib.ndpointer(np.int32),         # tier
                ctypes.c_int32,                           # n
                ctypes.c_char_p,                          # arena
                ctypes.c_int64,                           # cap
                np.ctypeslib.ndpointer(np.int64),         # off
                np.ctypeslib.ndpointer(np.int32),         # status
            ]
            _lib = lib
        except Exception:
            log.exception("native codec unavailable; using pure-Python codec")
            _build_failed = True
    return _lib


def available() -> bool:
    return _load() is not None


def rebuild(force: bool = False) -> bool:
    """Rebuild the library from codec.cc (the CI seam: check.sh calls
    this so the parity fuzz gate never runs against a stale checked-in
    binary). ``force`` unlinks first. Returns availability afterwards."""
    global _lib, _build_failed
    with _lock:
        lib = _lib_path()
        if force and os.path.exists(lib):
            if _lib is not None:
                return True  # already loaded in this process: can't unlink
            os.unlink(lib)
        _build_failed = False
    return _load() is not None


def decode_batch(bodies: list[bytes]):
    """Decode a window of JSON bodies natively.

    Returns (ids, rating, rd, threshold, region_names, mode_names, status)
    where string columns are object arrays ("" region/mode = wildcard) and
    ``status`` is int32 per row (OK / NEEDS_PYTHON / error codes — map via
    ``error_code``). Returns None when the native library is unavailable.
    """
    lib = _load()
    if lib is None:
        return None
    n = len(bodies)
    lens = np.fromiter((len(b) for b in bodies), np.int32, n)
    bufs = (ctypes.c_char_p * n)(*bodies)
    rating = np.empty(n, np.float32)
    rd = np.empty(n, np.float32)
    threshold = np.empty(n, np.float32)
    status = np.empty(n, np.int32)
    id_off = np.empty(n + 1, np.int64)
    region_off = np.empty(n + 1, np.int64)
    mode_off = np.empty(n + 1, np.int64)
    cap = int(lens.sum()) + 16
    arena = ctypes.create_string_buffer(cap)
    used = lib.mm_decode_requests(
        bufs, lens, n, rating, rd, threshold, status, arena, cap,
        id_off, region_off, mode_off)
    if used < 0:  # arena overflow cannot happen (strings ⊆ input), but guard
        return None
    raw = arena.raw
    ids = np.empty(n, object)
    regions = np.empty(n, object)
    modes = np.empty(n, object)
    for i in range(n):
        if status[i] == OK:
            ids[i] = raw[id_off[i]:region_off[i]].decode()
            regions[i] = raw[region_off[i]:mode_off[i]].decode()
            modes[i] = raw[mode_off[i]:id_off[i + 1]].decode()
        else:
            ids[i] = regions[i] = modes[i] = ""
    return ids, rating, rd, threshold, regions, modes, status


def decode_batch_concat(buf: bytes, offsets: "np.ndarray"):
    """Decode a consume burst's bodies natively from the CONCAT layout
    (ISSUE 12): one contiguous buffer of n bodies packed back-to-back with
    ``offsets`` ([n+1] int64; body i spans offsets[i]..offsets[i+1]) — the
    mirror of the encoders' arena+offset output, so a broker burst flows
    into the decoder without a per-row pointer table. Same return shape as
    ``decode_batch``; rows with inverted/out-of-range offsets come back as
    ``bad_json``. None when the native library is unavailable."""
    lib = _load()
    if lib is None:
        return None
    offsets = np.ascontiguousarray(offsets, np.int64)
    n = len(offsets) - 1
    rating = np.empty(n, np.float32)
    rd = np.empty(n, np.float32)
    threshold = np.empty(n, np.float32)
    status = np.empty(n, np.int32)
    id_off = np.empty(n + 1, np.int64)
    region_off = np.empty(n + 1, np.int64)
    mode_off = np.empty(n + 1, np.int64)
    cap = len(buf) + 16
    arena = ctypes.create_string_buffer(cap)
    used = lib.mm_decode_requests_concat(
        buf, len(buf), offsets, n, rating, rd, threshold, status,
        arena, cap, id_off, region_off, mode_off)
    if used < 0:  # arena overflow cannot happen (strings ⊆ input), but guard
        return None
    raw = arena.raw
    ids = np.empty(n, object)
    regions = np.empty(n, object)
    modes = np.empty(n, object)
    for i in range(n):
        if status[i] == OK:
            ids[i] = raw[id_off[i]:region_off[i]].decode()
            regions[i] = raw[region_off[i]:mode_off[i]].decode()
            modes[i] = raw[mode_off[i]:id_off[i + 1]].decode()
        else:
            ids[i] = regions[i] = modes[i] = ""
    return ids, rating, rd, threshold, regions, modes, status


def error_code(status: int) -> str:
    return _ERROR_CODES.get(int(status), "bad_json")


def _cstr_array(strings, n: int):
    """str sequence → (c_char_p array, needs_python_rows): rows with an
    embedded NUL would be silently truncated by c_char_p (corrupting the
    body AND its dedup-replay copy) — they take the Python encoder."""
    out = (ctypes.c_char_p * n)()
    bad: list[int] = []
    for i, s in enumerate(strings):
        b = s.encode()
        if b"\x00" in b:
            bad.append(i)
            b = b""
        out[i] = b
    return out, bad


def _slice_bodies(raw: bytes, off: np.ndarray, status: np.ndarray,
                  n: int) -> list[bytes | None]:
    return [raw[off[j]:off[j + 1]] if status[j] == OK else None
            for j in range(n)]


def encode_matched_batch(ids_a, ids_b, match_ids, lat_a_ms, lat_b_ms,
                         quality, waited_a_ms, waited_b_ms,
                         trace_a=None, trace_b=None):
    """Encode 2n matched-response bodies natively (a0, b0, a1, b1, ...),
    byte-identical to ``contract.encode_response`` including the
    ``waited_ms`` field and the optional per-side ``trace_id``.

    Returns a list of 2n entries, each ``bytes`` or ``None`` (NEEDS_PYTHON:
    non-ASCII id / non-finite float / embedded NUL — re-encode that row via
    the Python contract), or None when the native library is unavailable.
    """
    lib = _load()
    if lib is None:
        return None
    n = len(match_ids)
    if n == 0:
        return []
    lat_a_ms = np.ascontiguousarray(lat_a_ms, np.float64)
    lat_b_ms = np.ascontiguousarray(lat_b_ms, np.float64)
    quality = np.ascontiguousarray(quality, np.float64)
    waited_a_ms = np.ascontiguousarray(waited_a_ms, np.float64)
    waited_b_ms = np.ascontiguousarray(waited_b_ms, np.float64)
    a_ptrs, bad_a = _cstr_array(ids_a, n)
    b_ptrs, bad_b = _cstr_array(ids_b, n)
    m_ptrs, bad_m = _cstr_array(match_ids, n)
    tr_a = tr_b = None
    bad_ta: list[int] = []
    bad_tb: list[int] = []
    if trace_a is not None:
        tr_a, bad_ta = _cstr_array(trace_a, n)
    if trace_b is not None:
        tr_b, bad_tb = _cstr_array(trace_b, n)
    off = np.empty(2 * n + 1, np.int64)
    status = np.empty(2 * n, np.int32)
    # Fixed part ≈ 160 B/response + 4 id copies + match/trace ids; escapes
    # can at worst 6x a string, hence the generous bound with retry.
    cap = 320 * 2 * n + 8 * sum(
        len(a_ptrs[i] or b"") + len(b_ptrs[i] or b"") + len(m_ptrs[i] or b"")
        for i in range(n))
    if tr_a is not None:
        cap += 8 * sum(len(tr_a[i] or b"") for i in range(n))
    if tr_b is not None:
        cap += 8 * sum(len(tr_b[i] or b"") for i in range(n))
    for _ in range(2):
        arena = ctypes.create_string_buffer(cap)
        used = lib.mm_encode_matched(
            a_ptrs, b_ptrs, m_ptrs, n, lat_a_ms, lat_b_ms, quality,
            waited_a_ms, waited_b_ms, tr_a, tr_b, arena, cap, off, status)
        if used >= 0:
            bodies = _slice_bodies(arena.raw, off, status, 2 * n)
            # NUL-carrying rows were encoded from a blanked string: force
            # them to Python. A bad player/match id poisons BOTH sides
            # (each body embeds the whole pair); a bad trace id only its
            # own side.
            for i in bad_a + bad_b + bad_m:
                bodies[2 * i] = None
                bodies[2 * i + 1] = None
            for i in bad_ta:
                bodies[2 * i] = None
            for i in bad_tb:
                bodies[2 * i + 1] = None
            return bodies
        cap *= 4
    return None  # pragma: no cover - bound above cannot be exceeded twice


def encode_simple_batch(kinds, player_ids, lat_ms, retry_ms=None,
                        trace_ids=None, tiers=None):
    """Encode n queued/timeout/shed bodies natively (``kinds`` of
    KIND_QUEUED/KIND_TIMEOUT/KIND_SHED), byte-identical to
    ``contract.encode_response``. ``tiers`` entries < 0 (or None) omit the
    tier key (untiered services). Same None-row fallback contract as
    ``encode_matched_batch``; None when the library is unavailable."""
    lib = _load()
    if lib is None:
        return None
    n = len(player_ids)
    if n == 0:
        return []
    kinds = np.ascontiguousarray(kinds, np.int32)
    lat_ms = np.ascontiguousarray(lat_ms, np.float64)
    retry_ms = (np.zeros(n, np.float64) if retry_ms is None
                else np.ascontiguousarray(retry_ms, np.float64))
    tiers = (np.full(n, -1, np.int32) if tiers is None
             else np.ascontiguousarray(tiers, np.int32))
    p_ptrs, bad_p = _cstr_array(player_ids, n)
    tr = None
    bad_t: list[int] = []
    if trace_ids is not None:
        tr, bad_t = _cstr_array(trace_ids, n)
    off = np.empty(n + 1, np.int64)
    status = np.empty(n, np.int32)
    cap = 256 * n + 8 * sum(len(p_ptrs[i] or b"") for i in range(n))
    if tr is not None:
        cap += 8 * sum(len(tr[i] or b"") for i in range(n))
    for _ in range(2):
        arena = ctypes.create_string_buffer(cap)
        used = lib.mm_encode_simple(kinds, p_ptrs, lat_ms, retry_ms, tr,
                                    tiers, n, arena, cap, off, status)
        if used >= 0:
            bodies = _slice_bodies(arena.raw, off, status, n)
            for i in bad_p + bad_t:
                bodies[i] = None
            return bodies
        cap *= 4
    return None  # pragma: no cover - bound above cannot be exceeded twice
