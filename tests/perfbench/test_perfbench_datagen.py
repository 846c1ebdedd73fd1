"""The generator: the same seed gives the same table whatever the
threads or the order of blocks; seeds differ; columns are what the
configuration says. NumPy only."""

import json
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "perfbench")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from harness import datagen  # noqa: E402


@pytest.fixture(scope="module")
def spec():
    with open(os.path.join(BENCH, "configs", "criteo-dp256-rank.json")) as fh:
        return json.load(fh)["data"]


@pytest.fixture()
def small_blocks(monkeypatch):
    monkeypatch.setattr(datagen, "BLOCK_ROWS", 1000)


def test_same_seed_same_table_whatever_the_threads(spec, small_blocks):
    big = 2 ** 31 + 12345
    X1, y1 = datagen.make_table(spec, 4500, big, threads=1)
    X2, y2 = datagen.make_table(spec, 4500, big, threads=5)
    assert X1.dtype == np.float32 and X1.shape == (4500, 67)
    assert X1.flags["C_CONTIGUOUS"] and y1.dtype == np.float32
    assert np.array_equal(X1, X2) and np.array_equal(y1, y2)


def test_blocks_can_be_filled_in_any_order(spec, small_blocks):
    X1, y1 = datagen.make_table(spec, 3500, 7, threads=1)
    X2 = np.empty_like(X1)
    y2 = np.empty_like(y1)
    for b in (3, 0, 2, 1):
        datagen.fill_block(spec, 7, b, X2, y2)
    assert np.array_equal(X1, X2) and np.array_equal(y1, y2)


def test_a_shorter_table_is_a_prefix_of_whole_blocks(spec, small_blocks):
    X1, _ = datagen.make_table(spec, 3000, 11, threads=2)
    X2, _ = datagen.make_table(spec, 2000, 11, threads=2)
    assert np.array_equal(X1[:2000], X2)


def test_seeds_differ_but_share_the_population(spec, small_blocks):
    X1, y1 = datagen.make_table(spec, 20000, 1)
    X2, y2 = datagen.make_table(spec, 20000, 2)
    assert not np.array_equal(X1, X2)
    assert abs(y1.mean() - y2.mean()) < 0.03
    assert 0.3 < y1.mean() < 0.7


def test_count_columns_are_integers_with_ties(spec, small_blocks):
    X, y = datagen.make_table(spec, 20000, 3)
    counts, normal = X[:, :13], X[:, 13:]
    assert np.all(counts >= 0) and np.all(counts == np.floor(counts))
    assert all(len(np.unique(counts[:, j])) < 255 for j in range(13))
    assert abs(normal.mean()) < 0.01 and abs(normal.std() - 1.0) < 0.01
    assert set(np.unique(y)) == {0.0, 1.0}


def test_unknown_column_kind_raises():
    with pytest.raises(ValueError, match="unknown column kind"):
        datagen.column_layout({"columns": [{"kind": "zipf", "n": 1}]})
