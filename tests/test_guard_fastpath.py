"""The serve guard's accept path against the guard it replaced.

``validate_events`` accepts a buffer in one pass (a type-byte limit table
and the live ticks' order) and names a violation only on refusal.  Its
contract is the older one-check-at-a-time guard's, frozen below as
``_reference``: on every input the new guard returns the same canonical
words (uint32, bitwise equal) or raises the same exception class with the
same message, and it accepts every such buffer on the one-pass path,
never through the diagnosis.  The session feed's variant also returns the buffer's spike
count for the pending-spike quota; the engine tests check that count, the
quota's refusal, and that a refused feed leaves its session untouched.
"""

import jax
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # declared in requirements.txt; CI installs the real thing
    from _hypothesis_fallback import given, settings, strategies as st

from repro.core.aer import EVT_END, EVT_LABEL, EVT_SPIKE, MAX_ADDR, MAX_TICK
from repro.core.rsnn import Presets, init_params
from repro.serve import BatchedEngine
from repro.serve.guard import (
    GuardConfig,
    GuardError,
    MalformedEventError,
    QuotaExceededError,
    StreamContractError,
    _accept,
    _validate_counting,
    validate_events,
)

_KNOWN_KINDS = (0, EVT_END, EVT_LABEL, EVT_SPIKE)


def _reference(events, guard, *, min_tick=0, what="event buffer"):
    """The guard as it was before the accept path, kept verbatim."""
    arr = np.asarray(events)
    if arr.dtype == object or not (
        np.issubdtype(arr.dtype, np.integer)
        or np.issubdtype(arr.dtype, np.unsignedinteger)
    ):
        raise MalformedEventError(
            f"{what}: expected an integer array of AER words, got dtype "
            f"{arr.dtype}"
        )
    words = arr.ravel()
    if words.size > guard.max_words_per_feed:
        raise QuotaExceededError(
            f"{what}: {words.size} words exceeds the per-feed quota "
            f"({guard.max_words_per_feed})"
        )
    if words.size == 0:
        return words.astype(np.uint32)
    w64 = words.astype(np.int64)
    if (w64 < 0).any() or (w64 > 0xFFFFFFFF).any():
        bad = w64[(w64 < 0) | (w64 > 0xFFFFFFFF)][0]
        raise MalformedEventError(
            f"{what}: word value {bad} outside the 32-bit AER word range"
        )
    words = words.astype(np.uint32)
    kind = words >> 24
    known = np.isin(kind, _KNOWN_KINDS)
    if not known.all():
        i = int(np.nonzero(~known)[0][0])
        raise MalformedEventError(
            f"{what}: word {i} (0x{int(words[i]):08x}) carries unknown "
            f"type byte 0x{int(kind[i]):02x}"
        )
    pad_payload = (kind == 0) & (words != 0)
    if pad_payload.any():
        i = int(np.nonzero(pad_payload)[0][0])
        raise MalformedEventError(
            f"{what}: word {i} (0x{int(words[i]):08x}) has type byte 0 but "
            "a non-zero payload — corrupted word, not padding"
        )
    live = kind != 0
    if guard.check_addresses and guard.n_in is not None:
        addr = (words >> 12) & MAX_ADDR
        bad_addr = (kind == EVT_SPIKE) & (addr >= guard.n_in)
        if bad_addr.any():
            i = int(np.nonzero(bad_addr)[0][0])
            raise MalformedEventError(
                f"{what}: spike word {i} targets neuron {int(addr[i])}, "
                f"model has n_in={guard.n_in}"
            )
    if guard.monotone and live.any():
        tick = (words & MAX_TICK).astype(np.int64)[live]
        if int(tick[0]) < min_tick:
            raise StreamContractError(
                f"{what}: first tick {int(tick[0])} regresses behind the "
                f"stream's high-water mark {min_tick} (feeds must be "
                "tick-ordered and non-decreasing across buffers)"
            )
        steps = np.diff(tick)
        if (steps < 0).any():
            i = int(np.nonzero(steps < 0)[0][0])
            raise StreamContractError(
                f"{what}: ticks decrease within the buffer "
                f"({int(tick[i])} -> {int(tick[i + 1])} at live word {i + 1})"
            )
    return words


def _outcome(fn):
    """``("ok", words)`` or ``("raise", class, message)``."""
    try:
        return ("ok", fn())
    except GuardError as e:
        return ("raise", type(e), str(e))


def _assert_same(events, guard, min_tick=0, what="session 7 feed"):
    """The new guard, and the feed's counting variant, against the
    reference on one input; returns the reference's outcome."""
    want = _outcome(
        lambda: _reference(events, guard, min_tick=min_tick, what=what)
    )
    got = _outcome(
        lambda: validate_events(events, guard, min_tick=min_tick, what=what)
    )
    counted = _outcome(
        lambda: _validate_counting(
            events, guard, min_tick=min_tick, what=lambda: what
        )
    )
    assert got[0] == want[0] == counted[0], (want, got, counted)
    # the one-pass accept path takes every buffer the reference accepts,
    # so no accepted buffer pays for the diagnosis
    fast = _accept(events, guard, min_tick)
    assert (fast is not None) == (want[0] == "ok")
    if want[0] == "raise":
        assert got[1:] == want[1:]
        assert counted[1:] == want[1:]
        return want
    words, n_spikes = counted[1]
    for out in (got[1], words):
        assert out.dtype == np.uint32 and out.ndim == 1
        np.testing.assert_array_equal(out, want[1])
    assert n_spikes == int(np.count_nonzero(want[1] >> 24 == 0x03))
    return want


def _pack(kind, addr, tick):
    """One AER word as a Python int (``aer.pack``'s layout)."""
    return (kind << 24) | (addr << 12) | tick


def _spk(addr, tick):
    return _pack(EVT_SPIKE, addr, tick)


def _u32(*words):
    return np.array(words, np.uint32)


G12 = GuardConfig(n_in=12)
FEED = _u32(
    _pack(EVT_LABEL, 2, 0), _spk(0, 1), _spk(11, 1), _spk(5, 7),
    _pack(EVT_END, 0, 9),
)

# Each case: (id, buffer, guard, min_tick, "ok" | the exception class).
_CASES = [
    # one violation each
    ("dtype_float", np.array([1.5, 2.5]), G12, 0, MalformedEventError),
    ("dtype_bool", np.array([True, False]), G12, 0, MalformedEventError),
    ("dtype_object", np.array([None, 3], dtype=object), G12, 0,
     MalformedEventError),
    ("dtype_str", np.array(["a"]), G12, 0, MalformedEventError),
    ("dtype_complex", np.array([1 + 2j]), G12, 0, MalformedEventError),
    ("range_negative", np.array([_spk(0, 1), -1], np.int64), G12, 0,
     MalformedEventError),
    ("range_2_32", np.array([_spk(0, 1), 2**32], np.int64), G12, 0,
     MalformedEventError),
    ("range_uint64_top", np.array([2**64 - 1], np.uint64), G12, 0,
     MalformedEventError),
    # wider than 32 bits, these wrap to a pad and to a legal spike
    ("range_negative_wraps_to_pad", np.array([-(2**32)], np.int64), G12, 0,
     MalformedEventError),
    ("range_negative_wraps_to_spike",
     np.array([_spk(0, 1) - 2**32], np.int64), G12, 0, MalformedEventError),
    ("range_wraps_to_spike", np.array([_spk(0, 1) + 2**32], np.int64), G12,
     0, MalformedEventError),
    ("range_int8_negative", np.array([0, -3], np.int8), G12, 0,
     MalformedEventError),
    ("range_int32_min", np.array([-(2**31)], np.int32), G12, 0,
     MalformedEventError),
    ("unknown_type_byte", _u32(_spk(0, 1), 0x04000001), G12, 0,
     MalformedEventError),
    ("unknown_type_byte_ff", _u32(0xFFFFFFFF), G12, 0, MalformedEventError),
    ("dirty_pad", _u32(_spk(0, 1), 0x00000001), G12, 0,
     MalformedEventError),
    ("dirty_pad_top_payload", _u32(0x00FFFFFF), G12, 0, MalformedEventError),
    ("spike_address_n_in", _u32(_spk(1, 0), _spk(12, 0)), G12, 0,
     MalformedEventError),
    ("spike_address_max", _u32(_spk(MAX_ADDR, 0)), G12, 0,
     MalformedEventError),
    ("first_tick_regression", _u32(_spk(0, 5), _spk(1, 6)), G12, 6,
     StreamContractError),
    ("in_buffer_regression", _u32(_spk(0, 5), _spk(1, 6), _spk(2, 3)), G12,
     0, StreamContractError),
    ("word_quota", np.zeros(5, np.uint32),
     GuardConfig(n_in=12, max_words_per_feed=4), 0, QuotaExceededError),
    # several violations in one buffer, in both orders
    ("unknown_then_pad", _u32(0x7F000000, 0x00000001), G12, 0,
     MalformedEventError),
    ("pad_then_unknown", _u32(0x00000001, 0x7F000000), G12, 0,
     MalformedEventError),
    ("address_then_regression", _u32(_spk(12, 9), _spk(0, 3)), G12, 0,
     MalformedEventError),
    ("regression_then_address", _u32(_spk(0, 9), _spk(0, 3), _spk(12, 9)),
     G12, 0, MalformedEventError),
    ("pad_then_address", _u32(0x00000002, _spk(12, 0)), G12, 0,
     MalformedEventError),
    ("address_then_pad", _u32(_spk(12, 0), 0x00000002), G12, 0,
     MalformedEventError),
    ("first_tick_then_in_buffer", _u32(_spk(0, 2), _spk(0, 1)), G12, 5,
     StreamContractError),
    ("range_and_unknown", np.array([0x7F000000, -1], np.int64), G12, 0,
     MalformedEventError),
    ("unknown_and_range", np.array([-1, 0x7F000000], np.int64), G12, 0,
     MalformedEventError),
    ("quota_and_dtype", np.zeros(5, np.float32),
     GuardConfig(n_in=12, max_words_per_feed=4), 0, MalformedEventError),
    ("quota_and_unknown", _u32(*[0x7F000000] * 5),
     GuardConfig(n_in=12, max_words_per_feed=4), 0, QuotaExceededError),
    # shapes, containers, pads
    ("feed", FEED, G12, 0, "ok"),
    ("python_list", [int(w) for w in FEED], G12, 0, "ok"),
    ("python_list_negative", [int(FEED[0]), -5], G12, 0,
     MalformedEventError),
    ("python_list_huge", [2**70], G12, 0, MalformedEventError),
    ("python_list_empty", [], G12, 0, MalformedEventError),
    ("two_d", np.stack([FEED, FEED[::-1] * 0]), G12, 0, "ok"),
    ("two_d_bad_second_row", np.stack([FEED, FEED]), G12, 0,
     StreamContractError),
    ("strided_view", np.repeat(FEED, 2)[::2], G12, 0, "ok"),
    ("empty", np.zeros(0, np.uint32), G12, 0, "ok"),
    ("empty_past_high_water", np.zeros(0, np.uint32), G12, 99, "ok"),
    ("all_pad", np.zeros(8, np.uint32), G12, 50, "ok"),
    ("pads_in_middle", _u32(0, _spk(0, 3), 0, 0, _spk(1, 3), 0, _spk(2, 8)),
     G12, 3, "ok"),
    ("pads_hide_no_regression", _u32(_spk(0, 9), 0, _spk(1, 2)), G12, 0,
     StreamContractError),
    # label and end words carry any payload
    ("label_and_end", _u32(_pack(EVT_LABEL, MAX_ADDR, 0),
                           _pack(EVT_END, MAX_ADDR, MAX_TICK)), G12, 0,
     "ok"),
    ("label_full_payload", _u32(0x02FFFFFF), G12, 0, "ok"),
    ("end_regression", _u32(_pack(EVT_END, 0, 7),
                            _pack(EVT_LABEL, 0, 6)), G12, 0,
     StreamContractError),
    # n_in
    ("n_in_none", _u32(_spk(MAX_ADDR, 0)), GuardConfig(), 0, "ok"),
    ("n_in_4096", _u32(_spk(MAX_ADDR, 0)), GuardConfig(n_in=4096), 0, "ok"),
    ("n_in_above_4096", _u32(_spk(MAX_ADDR, 0)), GuardConfig(n_in=5000), 0,
     "ok"),
    ("n_in_4095", _u32(_spk(MAX_ADDR, 0)), GuardConfig(n_in=4095), 0,
     MalformedEventError),
    ("n_in_1", _u32(_spk(0, 0), _spk(1, 0)), GuardConfig(n_in=1), 0,
     MalformedEventError),
    ("n_in_0", _u32(_spk(0, 0)), GuardConfig(n_in=0), 0,
     MalformedEventError),
    ("n_in_0_no_spikes", _u32(_pack(EVT_LABEL, 1, 0)),
     GuardConfig(n_in=0), 0, "ok"),
    # checks switched off
    ("addresses_off", _u32(_spk(MAX_ADDR, 0)),
     GuardConfig(n_in=12, check_addresses=False), 0, "ok"),
    ("monotone_off", _u32(_spk(0, 9), _spk(0, 1)),
     GuardConfig(n_in=12, monotone=False), 50, "ok"),
    ("both_off_still_pads", _u32(0x00000001),
     GuardConfig(n_in=12, check_addresses=False, monotone=False), 0,
     MalformedEventError),
    # the high-water mark
    ("min_tick_at_first_live", _u32(0, _spk(0, 5), _spk(1, 5)), G12, 5,
     "ok"),
    ("min_tick_one_past", _u32(0, _spk(0, 5), _spk(1, 5)), G12, 6,
     StreamContractError),
    ("min_tick_numpy", _u32(_spk(0, 5)), G12, np.int64(5), "ok"),
    ("min_tick_negative", _u32(_spk(0, 0)), G12, -3, "ok"),
]


@pytest.mark.parametrize(
    "events,guard,min_tick,expect",
    [c[1:] for c in _CASES],
    ids=[c[0] for c in _CASES],
)
def test_matches_reference(events, guard, min_tick, expect):
    got = _assert_same(events, guard, min_tick)
    if expect == "ok":
        assert got[0] == "ok"
    else:
        assert got[0] == "raise" and got[1] is expect


_INT_DTYPES = [
    "int8", "int16", "int32", "int64", "uint8", "uint16", "uint32", "uint64",
]


@pytest.mark.parametrize("dtype", _INT_DTYPES)
def test_every_integer_dtype_matches_reference(dtype):
    info = np.iinfo(np.dtype(dtype))
    candidates = [
        np.zeros(6, np.int64),
        np.array([0, 1, 0], np.int64),
        np.array([info.min, 0, info.max], np.int64 if dtype != "uint64"
                 else np.uint64),
        FEED.astype(np.int64),
        np.array([0x7F000000, _spk(12, 3)], np.int64),
        np.array([-1, 2**32], np.int64),
    ]
    for vals in candidates:
        if vals.size and (int(vals.min()) < info.min
                          or int(vals.max()) > info.max):
            continue
        for guard in (G12, GuardConfig(), GuardConfig(n_in=12, monotone=False)):
            _assert_same(vals.astype(dtype), guard)


# --------------------------------------------------------------------------
# random buffers biased towards the edges of each field
# --------------------------------------------------------------------------

_EDGE_WORDS = [
    0, 1, 0x00000FFF, 0x00FFFFFF, 0x01000000, 0x01FFFFFF, 0x02000000,
    0x02FFFFFF, 0x03000000, 0x03FFFFFF, 0x04000000, 0x7F000000, 0xFF000000,
    0xFFFFFFFF, -1, 2**32, 2**63,
]


_EDGE_ADDRS = [0, 1, 11, 12, 13, 4094, MAX_ADDR]
_OTHER_KINDS = [0, EVT_END, EVT_LABEL, 4, 0xFF]


def _edge_biased_words(seed, size):
    """Python ints: edge words, spikes at edge addresses, words of other
    type bytes and raw 32-bit noise, in equal shares."""
    rng = np.random.default_rng(seed)
    words = []
    for source in rng.integers(0, 4, size):
        tick = int(rng.integers(0, MAX_TICK + 1))
        if source == 0:
            words.append(_EDGE_WORDS[rng.integers(len(_EDGE_WORDS))])
        elif source == 1:
            words.append(_spk(_EDGE_ADDRS[rng.integers(len(_EDGE_ADDRS))], tick))
        elif source == 2:
            kind = _OTHER_KINDS[rng.integers(len(_OTHER_KINDS))]
            words.append(_pack(kind, int(rng.integers(0, MAX_ADDR + 1)), tick))
        else:
            words.append(int(rng.integers(0, 2**32)))
    return words


@given(
    seed=st.integers(0, 2**31 - 1),
    size=st.integers(0, 40),
    sort_ticks=st.booleans(),
    dtype=st.sampled_from([None] + _INT_DTYPES),
    n_in=st.sampled_from([None, 0, 1, 12, 4095, 4096, 5000]),
    check_addresses=st.booleans(),
    monotone=st.booleans(),
    min_tick=st.sampled_from([0, 1, 7, MAX_TICK]),
    max_words=st.sampled_from([8, 1 << 20]),
)
@settings(max_examples=300, deadline=None)
def test_random_buffers_match_reference(
    seed, size, sort_ticks, dtype, n_in, check_addresses, monotone,
    min_tick, max_words,
):
    words = _edge_biased_words(seed, size)
    if sort_ticks:
        # in tick order, so that many buffers pass every check
        words = sorted(words, key=lambda w: w & MAX_TICK if w >= 0 else -1)
    arr = np.array(words) if words else np.zeros(0, np.uint32)
    if dtype is not None and arr.dtype != object:
        info = np.iinfo(np.dtype(dtype))
        if not arr.size or (int(arr.min()) >= info.min
                            and int(arr.max()) <= info.max):
            arr = arr.astype(dtype)
    guard = GuardConfig(
        n_in=n_in, max_words_per_feed=max_words, monotone=monotone,
        check_addresses=check_addresses,
    )
    _assert_same(arr, guard, min_tick)


# --------------------------------------------------------------------------
# the session feed's guard inside the engine
# --------------------------------------------------------------------------


def _engine(max_pending_events):
    cfg = Presets.braille(n_classes=3, num_ticks=48)
    params = init_params(jax.random.key(0), cfg)
    return BatchedEngine(
        cfg, params, backend="scan", tick_tile=8,
        guard=GuardConfig(max_pending_events=max_pending_events),
    )


def _session_state(sess):
    return (
        sess.sp_tick.copy(), sess.sp_addr.copy(), sess.sp_ptr,
        sess.max_fed_tick, sess.n_events, sess.label, sess.label_seen,
        sess.end_seen,
    )


def _assert_untouched(sess, before):
    after = _session_state(sess)
    np.testing.assert_array_equal(after[0], before[0])
    np.testing.assert_array_equal(after[1], before[1])
    assert after[2:] == before[2:]


def test_feed_quota_counts_only_spikes():
    eng = _engine(max_pending_events=6)
    h = eng.open_session()
    sess = eng._sessions[h.sid]
    lane = eng._lanes[sess.model_id]
    feed = _u32(
        _pack(EVT_LABEL, 1, 0), 0, _spk(0, 1), _spk(1, 1), 0, _spk(2, 2),
        _spk(3, 2),
    )
    # the count the engine used before: spike type bytes in the words
    assert int(np.count_nonzero(feed >> 24 == 0x03)) == 4
    out = eng._guard_feed(lane, sess, feed)
    np.testing.assert_array_equal(out, feed)
    assert h.feed(feed) == 4
    # 4 buffered + 2 incoming fills the quota exactly; one more refuses
    h.feed(_u32(_spk(4, 3), _pack(EVT_END, 0, 3), _spk(5, 3)))
    before = _session_state(sess)
    with pytest.raises(QuotaExceededError) as err:
        h.feed(_u32(_spk(6, 3)))
    assert str(err.value) == (
        f"session {h.sid}: 6 buffered + 1 incoming spikes exceeds "
        "max_pending_events=6"
    )
    _assert_untouched(sess, before)
    assert eng.stream_stats(1.0).rejected == 1


@pytest.mark.parametrize(
    "bad,exc",
    [
        (_u32(0x7F000000), MalformedEventError),
        (_u32(0x00000001), MalformedEventError),
        (_u32(_spk(12, 9)), MalformedEventError),
        (_u32(_spk(0, 2)), StreamContractError),
        (_u32(_spk(0, 9), _spk(0, 8)), StreamContractError),
        (np.array([1.5]), MalformedEventError),
    ],
    ids=["unknown", "dirty_pad", "address", "first_tick", "in_buffer",
         "dtype"],
)
def test_refused_feed_leaves_session_untouched(bad, exc):
    eng = _engine(max_pending_events=1 << 20)
    h = eng.open_session()
    sess = eng._sessions[h.sid]
    h.feed(_u32(_pack(EVT_LABEL, 1, 0), _spk(0, 3), _spk(4, 5)))
    before = _session_state(sess)
    want = _outcome(lambda: _reference(
        bad, GuardConfig(n_in=12), min_tick=max(sess.max_fed_tick, 0),
        what=f"session {h.sid} feed",
    ))
    with pytest.raises(exc) as err:
        h.feed(bad)
    assert want == ("raise", type(err.value), str(err.value))
    _assert_untouched(sess, before)
    assert eng.stream_stats(1.0).rejected == 1
    assert h.feed(_u32(_spk(1, 5))) == 1   # the stream goes on
