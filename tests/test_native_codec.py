"""Native batch wire decoder (native/codec.cc) vs contract.decode_request —
the Python decoder is the semantic source of truth; every native row must
agree (value-exact for OK rows, same error class for bad rows, NEEDS_PYTHON
rows re-decoded by Python must succeed)."""

import json

import numpy as np
import pytest

from matchmaking_tpu.native import codec
from matchmaking_tpu.service.contract import ANY, ContractError, decode_request

pytestmark = pytest.mark.skipif(not codec.available(),
                                reason="native codec unavailable (no g++?)")


def _native_rows(bodies):
    out = codec.decode_batch(bodies)
    assert out is not None
    return out


class TestAgainstPythonDecoder:
    def test_plain_requests_exact(self):
        bodies = [
            b'{"id":"alice","rating":1500}',
            b'{"id":"bob","rating":1540.25,"rating_deviation":120.5}',
            b'{"id":"c","rating":-300,"region":"eu","game_mode":"ranked"}',
            b'{"id":"d","rating":0,"rating_threshold":42.5}',
            b'{"event-name":"matchmaking.search","id":"e","rating":7}',
            b'  {  "id" : "f" , "rating" : 12e2 }  ',
        ]
        ids, rating, rd, thr, regions, modes, status = _native_rows(bodies)
        for i, body in enumerate(bodies):
            py = decode_request(body)
            assert status[i] == codec.OK
            assert ids[i] == py.id
            assert rating[i] == pytest.approx(py.rating, rel=1e-6)
            assert rd[i] == pytest.approx(py.rating_deviation, rel=1e-6)
            if py.rating_threshold is None:
                assert np.isnan(thr[i])
            else:
                assert thr[i] == pytest.approx(py.rating_threshold, rel=1e-6)
            assert (regions[i] or ANY) == py.region
            assert (modes[i] or ANY) == py.game_mode

    def test_error_rows_same_code(self):
        cases = [
            b"not json at all",
            b"[1,2,3]",
            b'{"rating":1500}',                       # missing id
            b'{"id":"x"}',                           # missing rating
            b'{"id":"x","rating":"high"}',           # bad type
            b'{"id":"x","rating":true}',             # bool rating
            b'{"id":7,"rating":1500}',               # non-string id
            b'{"id":"x","rating":1e7}',              # out of range
            b'{"id":"x","rating":1500,"rating_deviation":-1}',
            b'{"id":"x","rating":1500,"rating_threshold":0}',
            b'{"id":"x","rating":1500,"party":"nope"}',
        ]
        ids, *_rest, status = _native_rows(cases)
        for i, body in enumerate(cases):
            with pytest.raises(ContractError) as err:
                decode_request(body)
            if status[i] == codec.NEEDS_PYTHON:
                continue  # fallback path reports the Python error — fine
            assert status[i] != codec.OK, body
            assert codec.error_code(status[i]) == err.value.code, body

    def test_number_grammar_agrees_with_python(self):
        """The JSON number grammar divergence (round-1 advisory): strtod
        accepts forms json.loads rejects (`+5`, `5.`, `05`, ...) and
        json.loads accepts forms strtod's caller once mapped to bad_type
        (Infinity/NaN). Native must agree with Python on every form: same
        error class, or NEEDS_PYTHON (re-decode by the source of truth)."""
        cases = [
            b'{"id":"x","rating":+5}',             # leading + → bad_json
            b'{"id":"x","rating":5.}',             # bare trailing . → bad_json
            b'{"id":"x","rating":.5}',             # bare leading . → bad_json
            b'{"id":"x","rating":5e}',             # empty exponent → bad_json
            b'{"id":"x","rating":05}',             # leading zero → bad_json
            b'{"id":"x","rating":5e+}',            # sign-only exponent
            b'{"id":"x","rating":--5}',            # double sign
            b'{"id":"x","rating":1500,"rating_deviation":+1}',
            b'{"id":"x","rating":1500,"rating_threshold":5.}',
            b'{"id":"x","rating":Infinity}',       # json.loads: inf → bad_rating
            b'{"id":"x","rating":-Infinity}',
            b'{"id":"x","rating":NaN}',            # json.loads: nan → bad_rating
            b'{"id":"x","rating":1500,"junk":+1}', # malformed in ignored key
            b'{"id":"x","rating":nulx}',           # malformed literal → bad_json
            b'{"id":"x","rating":"unclosed}',      # unterminated string
            b'{"id":"x","rating":null}',           # well-formed null → bad_type
        ]
        *_cols, status = _native_rows(cases)
        for i, body in enumerate(cases):
            with pytest.raises(ContractError) as err:
                decode_request(body)
            if status[i] == codec.NEEDS_PYTHON:
                continue  # fallback path reports the Python error — fine
            assert status[i] != codec.OK, body
            assert codec.error_code(status[i]) == err.value.code, body

    def test_number_grammar_valid_forms_still_ok(self):
        bodies = [
            b'{"id":"a","rating":0}',
            b'{"id":"b","rating":-0.5}',
            b'{"id":"c","rating":1.25e2}',
            b'{"id":"d","rating":2E+3}',
            b'{"id":"e","rating":900e-1}',
            b'{"id":"f","rating":0.0}',
            b'{"id":"g","rating":1500,"rating_threshold":Infinity}',  # py: ok
        ]
        ids, rating, *_rest, status = _native_rows(bodies)
        for i, body in enumerate(bodies):
            py = decode_request(body)  # Python accepts all of these
            if status[i] == codec.NEEDS_PYTHON:
                continue  # Infinity threshold defers to Python — fine
            assert status[i] == codec.OK, body
            assert rating[i] == pytest.approx(py.rating, rel=1e-6)

    def test_complex_rows_flagged_for_python(self):
        bodies = [
            b'{"id":"p","rating":1,"roles":["tank","dps"]}',
            b'{"id":"p","rating":1,"party":[{"id":"q","rating":2}]}',
            b'{"id":"p\\u00e9","rating":1}',          # escape in id
            b'{"id":"p","rating":1,"region":7}',       # coerced by Python
        ]
        *_cols, status = _native_rows(bodies)
        for i, body in enumerate(bodies):
            assert status[i] == codec.NEEDS_PYTHON, body
            decode_request(body)  # Python fallback must succeed

    def test_empty_roles_party_fast_path(self):
        bodies = [b'{"id":"p","rating":1,"roles":[],"party":[]}',
                  b'{"id":"q","rating":2,"roles":[ ],"party": []}']
        ids, *_rest, status = _native_rows(bodies)
        assert list(status) == [codec.OK, codec.OK]
        assert list(ids) == ["p", "q"]

    def test_fuzz_against_python(self, rng):
        """Random flat payloads: native OK rows must equal Python exactly."""
        keys = ["id", "rating", "rating_deviation", "region", "game_mode",
                "rating_threshold", "extra_junk", "nested"]
        bodies = []
        for i in range(300):
            payload = {"id": f"p{i}", "rating": float(rng.normal(1500, 400))}
            if rng.random() < 0.5:
                payload["rating_deviation"] = float(rng.uniform(0, 350))
            if rng.random() < 0.5:
                payload["region"] = rng.choice(["eu", "na", "apac"])
            if rng.random() < 0.3:
                payload["game_mode"] = "ranked"
            if rng.random() < 0.3:
                payload["rating_threshold"] = float(rng.uniform(1, 200))
            if rng.random() < 0.2:
                payload["extra_junk"] = {"nested": [1, {"a": "b"}, None]}
            if rng.random() < 0.2:
                payload["flag"] = bool(rng.random() < 0.5)
            bodies.append(json.dumps(payload).encode())
        ids, rating, rd, thr, regions, modes, status = _native_rows(bodies)
        n_ok = 0
        for i, body in enumerate(bodies):
            py = decode_request(body)
            if status[i] != codec.OK:
                continue
            n_ok += 1
            assert ids[i] == py.id
            assert rating[i] == pytest.approx(py.rating, rel=1e-6)
            assert rd[i] == pytest.approx(py.rating_deviation, rel=1e-6)
            assert (regions[i] or ANY) == py.region
            assert (modes[i] or ANY) == py.game_mode
        assert n_ok >= 250  # fast path covers the overwhelming majority


def _py_matched(pid, ids_a, ids_b, mid, lat, qual, waited, trace_id=""):
    from matchmaking_tpu.service.contract import (
        MatchResult,
        SearchResponse,
        encode_response,
    )

    return encode_response(SearchResponse(
        status="matched", player_id=pid, latency_ms=float(lat),
        waited_ms=float(waited), trace_id=trace_id,
        match=MatchResult(match_id=mid, players=(ids_a, ids_b),
                          teams=((ids_a,), (ids_b,)), quality=float(qual))))


class TestNativeEncoder:
    """Batch response encoder vs contract.encode_response: BYTE-identical
    for every row the native path claims (status OK); rows it cannot
    express exactly (non-ASCII, non-finite, NUL) come back None and the
    caller re-encodes through the Python contract."""

    def test_matched_byte_identical_varied(self):
        ids_a = ["alice", 'q"uote', "back\\slash", "ctl\x01", "tab\there"]
        ids_b = ["bob", "b2", "b3", "b4", "b5"]
        mids = [f"m{i}" for i in range(5)]
        lat_a = np.array([12.3456, 0.0, 0.00004, 1.5, 99999.999])
        lat_b = np.array([1.0, 2.25, 3.875, 0.125, 7.0])
        qual = np.array([0.987654321, 1.0, 0.0, 0.5, 0.333333333])
        wa = np.array([10.0, 0.5, 0.0, 1.25, 3e-7])
        wb = np.array([0.0, 1.0, 2.0, 3.0, 4.0])
        tr_a = ["", "t1", "", "t3", ""]
        bodies = codec.encode_matched_batch(ids_a, ids_b, mids, lat_a, lat_b,
                                            qual, wa, wb, tr_a, None)
        assert bodies is not None and len(bodies) == 10
        for i in range(5):
            assert bodies[2 * i] == _py_matched(
                ids_a[i], ids_a[i], ids_b[i], mids[i], lat_a[i], qual[i],
                wa[i], tr_a[i])
            assert bodies[2 * i + 1] == _py_matched(
                ids_b[i], ids_a[i], ids_b[i], mids[i], lat_b[i], qual[i],
                wb[i])

    def test_simple_byte_identical(self):
        import json

        from matchmaking_tpu.service.contract import (
            SearchResponse,
            encode_response,
        )

        kinds = [codec.KIND_QUEUED, codec.KIND_TIMEOUT, codec.KIND_SHED]
        pids = ["p0", "p1", ""]
        lat = np.array([0.0, 1234.5678, 0.125])
        retry = np.array([0.0, 0.0, 250.0])
        traces = ["tq", "", "ts"]
        tiers = np.array([-1, 2, 0], np.int32)
        bodies = codec.encode_simple_batch(kinds, pids, lat, retry, traces,
                                           tiers)
        assert bodies is not None
        statuses = ["queued", "timeout", "shed"]
        for i in range(3):
            py = encode_response(SearchResponse(
                status=statuses[i], player_id=pids[i],
                latency_ms=float(lat[i]), retry_after_ms=float(retry[i]),
                trace_id=traces[i],
                tier=None if tiers[i] < 0 else int(tiers[i])))
            assert bodies[i] == py
            assert json.loads(bodies[i])["status"] == statuses[i]

    def test_empty_batch(self):
        assert codec.encode_matched_batch([], [], [], [], [], [],
                                          [], []) == []
        assert codec.encode_simple_batch([], [], []) == []

    def test_exotic_rows_fall_back_per_row(self):
        # Embedded NUL: c_char_p would truncate -> that row is None.
        bodies = codec.encode_matched_batch(
            ["a\x00b", "c"], ["bob", "dan"], ["m1", "m2"],
            np.array([1.0, 2.0]), np.array([1.0, 2.0]),
            np.array([0.5, 0.5]), np.array([0.0, 0.0]),
            np.array([0.0, 0.0]))
        assert bodies is not None
        assert bodies[0] is None and bodies[1] is None  # a-side id is bad
        assert bodies[2] == _py_matched("c", "c", "dan", "m2", 2.0, 0.5, 0.0)
        assert bodies[3] == _py_matched("dan", "c", "dan", "m2", 2.0, 0.5,
                                        0.0)
        # Non-finite floats are not strict JSON -> that SIDE is None.
        bodies = codec.encode_matched_batch(
            ["a"], ["b"], ["m1"], np.array([float("nan")]),
            np.array([1.0]), np.array([0.5]), np.array([0.0]),
            np.array([0.0]))
        assert bodies[0] is None and bodies[1] is not None
        # Non-ASCII ids: json.dumps escapes over decoded text -> both
        # sides of the match carry the id, so both fall back.
        bodies = codec.encode_matched_batch(
            ["unié"], ["b"], ["m1"], np.array([1.0]), np.array([1.0]),
            np.array([0.5]), np.array([0.0]), np.array([0.0]))
        assert bodies[0] is None and bodies[1] is None


def test_library_is_keyed_on_source_hash(tmp_path, monkeypatch):
    """A library built from OTHER source is never loaded, whatever its
    mtime: a copied tree resets mtimes, so staleness is the source hash
    carried in the library's name, not a timestamp comparison."""
    import os
    import shutil

    src = tmp_path / "codec.cc"
    shutil.copy(codec._SRC, src)
    monkeypatch.setattr(codec, "_SRC", str(src))
    first = codec._build_locked()
    assert os.path.exists(first)
    # Edit the source, then make the old binary look NEWER than it.
    src.write_text(src.read_text() + "\n// edited\n")
    later = os.path.getmtime(src) + 3600
    os.utime(first, (later, later))
    # The pre-hash name, fresh mtime and all, must not be picked up either.
    legacy = tmp_path / "libmmcodec.so"
    shutil.copy(first, legacy)
    os.utime(legacy, (later, later))
    second = codec._build_locked()
    assert second != first
    assert os.path.exists(second)
    assert not os.path.exists(first) and not legacy.exists()
