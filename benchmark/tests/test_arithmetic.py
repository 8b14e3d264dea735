"""The functions that count operations and bytes, against numbers worked
by hand."""

import pytest

from benchmark import loader

MLP = loader.read_json(loader.os.path.join(
    loader.HERE, "configs", "mlp-2048x10.json"))
BERT = loader.read_json(loader.os.path.join(
    loader.HERE, "configs", "bert-base.json"))


def test_mlp_flops_per_sample_is_the_references_own():
    # 2048^2 = 4,194,304; layer 0: 4x, layers 1..9: 6x each -> 58 x
    mlp = loader.load_module("families", "mlp")
    assert mlp.flops_per_item(MLP, {}) == 58 * 4_194_304 == 243_269_632


def test_mlp_parameters():
    assert MLP["parameters"] == 10 * (2048 * 2048 + 2048)


def test_bert_matmul_weights():
    # per layer 4*768^2 + 2*768*3072 = 2,359,296 + 4,718,592 = 7,077,888
    # x12 = 84,934,656; head 768^2 = 589,824; decoder 30522*768 = 23,440,896
    bert = loader.load_module("families", "bert")
    assert bert.matmul_weights(BERT) == 108_965_376


@pytest.mark.parametrize("seq,want", [
    # forward 2 x 108,965,376 = 217,930,752, attention 12*4*S*768 = 36,864 S
    (128, 3 * (217_930_752 + 4_718_592)),       # 667,948,032
    (512, 3 * (217_930_752 + 18_874_368)),      # 710,415,360
])
def test_bert_flops_per_token(seq, want):
    bert = loader.load_module("families", "bert")
    assert bert.flops_per_item(BERT, {"seq_len": seq}) == want


def test_bert_items_are_tokens():
    bert = loader.load_module("families", "bert")
    job = {"dp": 1, "batch_per_chip": 32, "seq_len": 512}
    assert bert.items_per_step(BERT, job) == 16_384


def test_codec_roundtrip_bytes():
    # per element: encode 4 + 1 + 1/16, decode 1 + 1/16 + 4 = 10.125 B
    codec = loader.load_module("metrics", "codec.hbm_roofline_pct")
    assert codec.roundtrip_bytes(41_963_520, 16) == 424_880_640


def test_peaks_are_the_published_ones_and_unknown_kinds_raise():
    v5e = loader.peaks("TPU v5 lite")
    assert v5e["bf16_flops"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["ici_bits_per_s"] == 1600e9
    with pytest.raises(KeyError):
        loader.peaks("TPU v9 imaginary")
