"""Traffic is a pure function of the seed."""

import numpy as np

from bench.cells import sessions, train
from bench.harness import load_spec, resolve


def _streams(seed):
    _, config, tr = resolve(load_spec(), "braille_q.sessions")
    rng = np.random.default_rng(seed)
    return sessions.Streams(rng, 200, 4, 16, tr["letters"],
                            config["sample_ticks"], tr["feed_ticks"])


def _same(a, b):
    return (np.array_equal(a.seq, b.seq) and np.array_equal(a.f_lo, b.f_lo)
            and np.array_equal(a.f_end, b.f_end)
            and all(np.array_equal(x, y) for x, y in zip(a.tables, b.tables)))


def test_session_streams_repeat_for_one_seed_and_differ_for_two():
    assert _same(_streams(3), _streams(3))
    assert not _same(_streams(3), _streams(4))


def test_streams_cut_feeds_in_tick_order_within_bounds():
    s = _streams(5)
    lo, hi = 16, 256
    for i in range(s.n):
        ends = s.f_end[s.feed_off[i]:s.feed_off[i + 1]]
        assert np.all(np.diff(ends) > 0)
        sizes = np.diff(np.concatenate([[0], ends]))
        assert sizes.max() <= hi and (sizes[:-1] > 0).all()
        ticks = np.concatenate([s.words(f) & 0xFFF for f in
                                range(s.feed_off[i], s.feed_off[i + 1])])
        assert np.all(np.diff(ticks.astype(np.int64)) >= 0)


def test_open_loop_schedule_repeats_and_offers_its_rate():
    _, _, tr = resolve(load_spec(), "braille_q.sessions_rate")
    tr = dict(tr, offered_events_per_s=5000)
    a = sessions.schedule(7, _streams(3), tr, 2.0)
    b = sessions.schedule(7, _streams(3), tr, 2.0)
    c = sessions.schedule(8, _streams(3), tr, 2.0)
    assert all(np.array_equal(x, y) for x, y in zip(a[:3], b[:3]))
    assert not np.array_equal(a[2], c[2])
    s = _streams(3)
    offered = (s.f_hi[a[2]] - s.f_lo[a[2]]).sum() / 2.0
    assert abs(offered / 5000 - 1) < 0.01
    assert np.all(np.diff(a[0]) >= 0) and a[0].max() < 2.0


def test_training_set_repeats_for_one_seed_and_differs_for_two():
    for name in ("braille_q.train", "cue_q.train"):
        _, config, tr = resolve(load_spec(), name)
        tr = dict(tr, dataset_samples=30)
        d = [train.dataset(np.random.default_rng(s), config, tr)["train"]["events"]
             for s in (1, 1, 2)]
        assert np.array_equal(d[0], d[1])
        assert d[0].shape == d[2].shape == (30, tr["event_words"])
        assert not np.array_equal(d[0], d[2])


def test_training_set_draws_again_a_sample_over_the_width():
    _, config, tr = resolve(load_spec(), "cue_q.train")
    tr = dict(tr, dataset_samples=30, event_words=440)
    words = train.dataset(np.random.default_rng(4), config, tr)["train"]["events"]
    assert words.shape == (30, 440) and (words[:, -1] != 0).any()
