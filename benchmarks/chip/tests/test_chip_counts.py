"""FLOP and byte counts against hand counts at each cell's shapes."""
import chipcells  # noqa: F401  (puts the benchmark on the path)
import json
import os

import pytest

import counts
import manifest

HERE = os.path.dirname(os.path.abspath(__file__))

# xlstm-350m, by hand: an mLSTM layer multiplies a token by q, k, v, o
# (1024 x 2048 each), down (2048 x 1024) and the two gates (1024 x 4); an
# sLSTM layer by w_in (1024 x 4096), its recurrent 4 x 4 x 256 x 256 and a
# gated feed-forward of 3 x 1024 x 1408; 12 of each, and the unembedding.
XLSTM_MATMUL = 12 * (5 * 1024 * 2048 + 2 * 1024 * 4) \
    + 12 * (1024 * 4096 + 4 * 4 * 256 * 256 + 3 * 1024 * 1408) \
    + 50304 * 1024
# every leaf, padded to whole blocks of 2048 (final_norm is one block of
# 1024), and the 32 (16 for final_norm) kept entries of each block
XLSTM_ELEMENTS = 343_856_128
XLSTM_KEPT = (343_856_128 - 1024) // 2048 * 32 + 16
# hubert-xlarge at 21 layers: q, k, v, o (1280 x 1280), up and down
# (1280 x 5120), and the 504-way target projection
HUBERT_MATMUL = 21 * (4 * 1280 * 1280 + 2 * 1280 * 5120) + 504 * 1280


def cfg(name):
    return manifest.load_json(os.path.join(manifest.HERE, "configs",
                                           name + ".json"))


def mix(name):
    return manifest.load_json(os.path.join(manifest.HERE, "traffic",
                                           name + ".json"))


def test_hand_count_of_parameters():
    assert XLSTM_MATMUL == 292_257_792
    assert sum(counts.leaf_sizes(cfg("xlstm-350m"))) == XLSTM_ELEMENTS


@pytest.mark.parametrize("traffic,seq,tokens", [
    ("k2-s512", 512, 2 * 8 * 512), ("4c-k2-s64", 64, 4 * 2 * 8 * 64)])
def test_xlstm_round_flops(traffic, seq, tokens):
    quadratic = 12 * 2 * 2 * seq * 2048     # q.k and weights.v, S x S
    want = 3 * (2 * XLSTM_MATMUL + quadratic) * tokens
    assert counts.model_flops_per_round(cfg("xlstm-350m"),
                                        mix(traffic)) == want


def test_hubert_round_flops():
    c = cfg("hubert-xlarge")
    assert c["num_layers"] == 21
    want = 3 * (2 * HUBERT_MATMUL + 21 * 2 * 2 * 250 * 1280) * 4 * 250
    assert counts.model_flops_per_round(c, mix("k1-f250")) == want


def test_select_and_ingest_bytes():
    c = cfg("xlstm-350m")
    assert counts.padded_blocks(c, 1 / 64) == (XLSTM_ELEMENTS, XLSTM_KEPT)
    assert counts.select_bytes(c, 1 / 64) == \
        12 * XLSTM_ELEMENTS + 8 * XLSTM_KEPT
    assert counts.ingest_bytes(c, 1 / 64, 4) == \
        32 * XLSTM_ELEMENTS + 4 * 8 * XLSTM_KEPT


def test_peaks_table_is_keyed_by_device_kind():
    assert counts.peaks("TPU v5 lite")["peak_flops_bf16"] == 197e12
    with pytest.raises(KeyError):
        counts.peaks("cpu")
    with open(os.path.join(manifest.HERE, "peaks.json")) as f:
        assert "Google Cloud" in json.load(f)["source"]
