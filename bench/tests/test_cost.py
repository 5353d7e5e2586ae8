"""The yardstick's arithmetic against hand counts."""

from bench import cost


def test_forward_macs_match_hand_counts():
    # Braille 12-38-3: 12*38 + 38*38 + 38*3 = 456 + 1444 + 114
    assert cost.forward_macs(12, 38, 3) == 2014
    # cue 40-100-2: 4000 + 10000 + 200
    assert cost.forward_macs(40, 100, 2) == 14200


def test_train_macs_add_learning_signal_and_three_gradients():
    assert cost.train_macs(12, 38, 3) == 2 * 2014 + 38 * 3 == 4142
    assert cost.train_macs(40, 100, 2) == 2 * 14200 + 200 == 28600


def test_session_roofline_and_mfu_by_hand():
    dims = (12, 38, 3)
    ops = cost.session_ops(dims, session_ticks=1000)
    assert ops == 2 * 2014 * 1000
    # one tile of 128 lanes carrying 500 events
    nbytes = cost.session_bytes(dims, lanes=128, events=500, tiles=1)
    assert nbytes == 2 * 4 * (2 * 38 + 2 * 3 + 1) * 128 + 4 * 500 + 2014
    least = cost.least_seconds(ops, nbytes, 393e12, 819e9)
    assert least == max(ops / 393e12, nbytes / 819e9)
    assert least == nbytes / 819e9          # bandwidth-bound at this size


def test_train_bytes_by_hand():
    dims = (40, 100, 2)
    got = cost.train_bytes(dims, samples=10, num_ticks=150, commits=1)
    assert got == 10 * 150 * 40 / 8 + 14200 + 4 * 14200
    assert cost.train_ops(dims, 10, 150) == 2 * 28600 * 10 * 150
