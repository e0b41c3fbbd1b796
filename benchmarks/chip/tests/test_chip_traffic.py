"""Each traffic mix: the same seed gives the same batches, another seed
other batches, in the program's mesh layout."""
import chipcells  # noqa: F401  (puts the benchmark on the path)
import numpy as np
import pytest

from chipcells import FILES, tiny_cell
from traffic import Traffic

CELLS = sorted(FILES)
BIG = 2**31 + 987_654


def small(name):
    cell = tiny_cell(name)
    return {**cell.traffic, "seq_len": 32, "batch": 4}, cell.config


@pytest.mark.parametrize("name", CELLS)
def test_same_seed_same_batches_other_seed_other(name):
    mix, cfg = small(name)
    a, b = Traffic(mix, cfg, BIG), Traffic(mix, cfg, BIG)
    c = Traffic(mix, cfg, BIG + 1)
    for r in (0, 5):
        ba, bb, bc = a.round_batch(r), b.round_batch(r), c.round_batch(r)
        for k in ba:
            assert np.array_equal(np.asarray(ba[k], np.float32),
                                  np.asarray(bb[k], np.float32))
            assert not np.array_equal(np.asarray(ba[k], np.float32),
                                      np.asarray(bc[k], np.float32))
    assert not np.array_equal(a.round_batch(0)["labels"],
                              a.round_batch(1)["labels"])


@pytest.mark.parametrize("name", CELLS)
def test_layout_and_range(name):
    mix, cfg = small(name)
    t = Traffic(mix, cfg, 3)
    batch = t.round_batch(0)
    rows = mix["batch"] * mix["clients"]
    assert batch["labels"].shape == (mix["local_steps"], rows, 32)
    assert batch["labels"].min() >= 0
    assert batch["labels"].max() < cfg["vocab_size"]
    if mix["kind"] == "lm":
        assert np.array_equal(batch["tokens"][..., 1:],
                              batch["labels"][..., :-1])
    else:
        assert batch["embeddings"].shape == (mix["local_steps"], rows, 32,
                                             cfg["d_model"])
        assert str(batch["embeddings"].dtype) == "bfloat16"
    one = t.client_batch(batch, mix["clients"] - 1)
    assert one["labels"].shape == (mix["local_steps"], mix["batch"], 32)


def test_bigram_is_planted():
    mix, cfg = small("xlstm-350m.k2-s512")
    tok = Traffic(mix, cfg, 11).round_batch(0)["tokens"]
    follow = (tok[..., :-1].astype(np.int64) * 31 + 7) % cfg["vocab_size"]
    share = np.mean(follow == tok[..., 1:])
    assert 0.4 < share < 0.65
