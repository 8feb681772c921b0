"""The generator: ragged last chunk, one geometry under every seed, the
same gaps in another order, the one topic law."""
import numpy as np
import pytest

from harness import data

GEN = {"geometry_seed": 0, "rows_per_top": 40, "rows_per_sub": 4,
       "sub_spread": 0.5, "noise": 0.1}


def test_ragged_corpus(monkeypatch):
    monkeypatch.setattr(data, "CHUNK", 64)
    shape = data.Shape(64 * 3 + 17, 16, GEN)
    x = np.asarray(data.make_corpus(shape, 5))
    assert x.shape == (64 * 3 + 17, 16) and np.isfinite(x).all()
    # every row lies near its own (rotated) sub-centre, the last ones too
    sub = np.asarray(data.centres(shape)) @ np.asarray(data.rotation(5, 16))
    own = sub[np.arange(len(x)) % shape.n_sub]
    assert np.abs(x - own).max() < 10 * GEN["noise"]


def test_seed_rotates_one_geometry():
    shape = data.Shape(300, 16, GEN)
    a = np.asarray(data.make_corpus(shape, 1), np.float64)
    b = np.asarray(data.make_corpus(shape, 2**33 + 1), np.float64)
    assert np.abs(a - b).max() > 0.1                    # other rows
    np.testing.assert_allclose(a @ a.T, b @ b.T, atol=1e-3)  # same geometry
    again = np.asarray(data.make_corpus(shape, 1), np.float64)
    np.testing.assert_array_equal(a, again)              # same seed, same rows


def test_rotation_is_orthogonal():
    r = np.asarray(data.rotation(7, 32), np.float64)
    np.testing.assert_allclose(r @ r.T, np.eye(32), atol=1e-5)


def test_gaps_are_one_set_in_another_order():
    a = data.exp_gaps(500, 40.0, np.random.default_rng(1))
    b = data.exp_gaps(500, 40.0, np.random.default_rng(2))
    assert not np.array_equal(a, b)
    np.testing.assert_array_equal(np.sort(a), np.sort(b))
    assert abs(a.sum() - 500 / 40.0) < 0.2


def test_uniform_topics_only():
    shape = data.Shape(4000, 8, GEN)
    s = data.topic_subs(shape, {"kind": "uniform"}, 5000,
                        np.random.default_rng(0))
    assert ((0 <= s) & (s < shape.n_sub)).all()
    with pytest.raises(ValueError):
        data.topic_subs(shape, {"kind": "zipf"}, 5, np.random.default_rng(0))
