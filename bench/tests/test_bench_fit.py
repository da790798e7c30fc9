"""The cuts, the frames, and the reference's index answers and gaps."""
import numpy as np
import pytest

from bench import data, reference
from bench.tests.tiny import PREDICATES

CUTS = [(np.float32(0.3), np.float32(0.7))]


def test_quantile_cuts_reach_their_shares_on_cpu_frames():
    n, hw = 1024, 32

    def frames_of(lo, hi):
        rows = np.arange(lo, lo + 256)
        rows[hi - lo:] = hi - 1
        return data.device_frames(3, rows, hw)

    params = data.make_weights(3, PREDICATES)
    scores, sigs = reference.score_frames(frames_of, n, 256, PREDICATES,
                                          params)
    assert scores.shape == (3, n) and sigs.shape == (n, 8, 8)
    for s in scores:
        lo, hi = data.quantile_cuts(s, 0.25, 0.75)
        assert (s <= lo).mean() == pytest.approx(0.25, abs=2 / n)
        assert (s >= hi).mean() == pytest.approx(0.25, abs=2 / n)
        # halfway between two calibration scores: none sits on a cut
        assert not np.isin([lo, hi], s).any()


def test_frames_are_uint8_valued_and_seeded():
    a = np.asarray(data.device_frames(1, np.arange(4), 32))
    b = np.asarray(data.device_frames(1, np.arange(4), 32))
    c = np.asarray(data.device_frames(2, np.arange(4), 32))
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    assert np.array_equal(a * 256, np.round(a * 256))
    assert np.array_equal(data.make_frames(1, 4, 32), a)


def test_large_seeds_differ_in_the_high_bits():
    a = np.asarray(data.device_frames(5, np.arange(1), 32))
    b = np.asarray(data.device_frames(5 + 2**32, np.arange(1), 32))
    assert not np.array_equal(a, b)


def test_aliases_point_at_the_last_frame_scored():
    diffs = np.array([np.inf, 0.5, 0.001, 0.002, 0.3, 0.0, 0.4])
    assert reference.aliases(diffs, 0.008).tolist() == [0, 1, 1, 1, 4, 4, 6]


def test_index_answers_by_hand():
    alias = np.array([0, 0, 2, 3, 4])
    s = np.array([[0.2, 0.9, 0.5, 0.8, 0.32]], np.float32)
    want = reference.index_answers(alias, s, CUTS, 0.25)
    # frame 1 is a duplicate: no score, no label, no candidacy
    assert np.isnan(want["scores"][0, 1])
    assert want["decided"][0].tolist() == [0, -1, -1, 1, -1]
    # tau = 0.3 + 0.25 * 0.2 = 0.35: 0.5 clears it, 0.32 does not
    assert want["candidate"][0].tolist() == [False, False, True, True,
                                             False]


def test_answer_gaps_by_hand():
    alias = np.array([0, 1, 1, 3])
    diffs = np.array([np.inf, 0.5, 0.0079, 0.2])
    s = np.array([[0.2, 0.69, 0.5, 0.5]], np.float32)
    want = reference.index_answers(alias, s, CUTS, 0.25)
    same = {k: v.copy() for k, v in want.items()}
    assert reference.answer_gaps(same, want, diffs, 0.008, CUTS,
                                 0.25).max() == 0
    got = {k: v.copy() for k, v in want.items()}
    got["scores"][0, 0] += np.float32(1e-4)     # a score moved
    got["decided"][0, 1] = 1                    # 0.69 labelled 1
    got["alias"][2] = 2                         # a duplicate scored
    got["scores"][0, 3] = np.nan                # a score missing
    gaps = reference.answer_gaps(got, want, diffs, 0.008, CUTS, 0.25)
    assert gaps == pytest.approx([1e-4, 0.01, 1e-4, 1.0], rel=1e-3)
    got["indexed"] = np.array([True, True, True, False])
    assert reference.answer_gaps(got, want, diffs, 0.008, CUTS,
                                 0.25)[3] == 1.0


def test_high_precision_control_differs_from_highest():
    x = np.linspace(0.1, 0.9, 4 * 8 * 8 * 3, dtype=np.float32)
    x = x.reshape(4, 8, 8, 3)
    params = data.make_weights(9, [{"levels": [
        {"conv_layers": 1, "conv_nodes": 8, "dense_nodes": 8,
         "resolution": 8, "color": "rgb"}]}])[0][0]
    hi = np.asarray(reference.forward(params, x, "highest"))
    lo = np.asarray(reference.forward(params, x, "high"))
    assert 0 < np.abs(hi - lo).max() < 1e-3


@pytest.mark.parametrize("res,layers", [(30, 4), (12, 2)])
def test_reference_agrees_with_the_program_at_odd_sizes(res, layers):
    """Max-pooling a 15 px (or 3 px) map drops its last row and column
    in both."""
    from repro.models.cnn import cnn_forward

    level = {"conv_layers": layers, "conv_nodes": 8, "dense_nodes": 8,
             "resolution": res, "color": "rgb"}
    params = data.make_weights(4, [{"levels": [level]}])[0][0]
    x = np.asarray(data.device_frames(4, np.arange(3), res))
    assert np.allclose(reference.forward(params, x),
                       cnn_forward(params, x), atol=1e-5)
