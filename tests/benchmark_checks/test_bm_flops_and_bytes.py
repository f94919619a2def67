"""The benchmark's own operation and byte counts, against hand-worked numbers."""

import json
import os

import pytest

from benchmark import manifest, roofline
from benchmark.families import gpt2 as family


def _config(name):
    with open(os.path.join(manifest.HERE, "configs", name + ".json")) as f:
        return json.load(f)


# matmul parameters: L * 12 E^2 + V E
#   large: 36 * 12 * 1280^2 + 50304 * 1280 = 707,788,800 + 64,389,120
#   xl:    48 * 12 * 1600^2 + 50304 * 1600 = 1,474,560,000 + 80,486,400
# per token: 6 * that + 6 * L * S * E (causal attention, S = 1024)
#   large: 6 * 772,177,920 + 6 * 36 * 1024 * 1280 = 4,633,067,520 + 283,115,520
#   xl:    6 * 1,555,046,400 + 6 * 48 * 1024 * 1600 = 9,330,278,400 + 471,859,200
@pytest.mark.parametrize("name,params,flops", [
    ("gpt2-large-774m", 772_177_920, 4_916_183_040),
    ("gpt2-xl-1558m", 1_555_046_400, 9_802_137_600),
])
def test_train_flops_per_token(name, params, flops):
    c = _config(name)
    assert roofline.dense_matmul_params(
        c["n_layer"], c["n_embd"], c["vocab_size"]) == params
    assert family.train_flops_per_token(c, 1024) == flops


def test_causal_count_is_below_the_programs_full_square_count():
    """flops_profiler.model_flops_per_token charges 12*L*S*E (the full S x S
    scores); a causal model needs half of that term."""
    c = _config("gpt2-large-774m")
    full = 6 * 772_177_920 + 12 * 36 * 1024 * 1280
    assert full - family.train_flops_per_token(c, 1024) == 283_115_520


def test_flash_attention_flops_per_step():
    # one layer, batch 8, 20 heads: 6 * 8 * 20 * 1024^2 * 64 = 64,424,509,440
    assert roofline.causal_attention_train_flops(8, 20, 1024, 64) \
        == 64_424_509_440
    c = _config("gpt2-large-774m")
    assert family.train_attention_flops_per_step(c, 8, 1024) \
        == 36 * 64_424_509_440
    # ... which is the attention term of the per-token count times the tokens
    assert 36 * 64_424_509_440 == 283_115_520 * 8 * 1024


def test_decode_kv_bytes_and_pool_bytes_per_token():
    c = _config("gpt2-large-774m")
    # K and V, 36 layers, 1280 wide, bf16: 2 * 36 * 1280 * 2 = 184,320 B/token
    assert family.kv_bytes_per_token(c) == 184_320
    assert family.decode_kv_bytes(c, [100, 300]) == 184_320 * 400
    # bf16 weights: 36 * (12*1280^2 + 13*1280) + (50304+1024)*1280 + 2*1280
    # = 774,090,240 parameters, 2 bytes each
    assert family.weight_bytes(c) == 2 * 774_090_240


def test_peaks_are_keyed_by_device_kind_and_an_unknown_kind_is_an_error():
    v5e = roofline.peaks_for("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["interconnect_bits_per_s"] == 1600e9
    with pytest.raises(KeyError, match="no peaks recorded"):
        roofline.peaks_for("cpu")


def test_roofline_share():
    # 197e12 flops in 2 s at a 197e12 peak is half the roofline
    assert roofline.share(197e12, 197e12, 2.0) == pytest.approx(50.0)
    assert roofline.share(1.0, 1.0, 0.0) is None
