"""Work counts against hand-computed numbers, at live-context lengths."""
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
from chipbench import work  # noqa: E402


def dims(name):
    return work.Dims.of(json.loads(
        (ROOT / "chipbench" / "configs" / f"{name}.json").read_text()))


def test_qwen3_8b_decode_iteration():
    d = dims("qwen3_8b")
    # 4096*(2*4096 + 2*1024) + 3*4096*12288
    assert d.layer_params == 192_937_984
    # (16 layers + 4096 x 151936 head) x 2 bytes
    assert d.weight_bytes == 7_418_675_200
    assert d.kv_bytes_per_token == 16 * 2 * 8 * 128 * 2 == 65_536
    flops, nbytes = work.decode_iteration(d, [100, 300])
    # linear 2*2*16*192937984 + attention 16*4*32*128*400 + head 2*2*4096*151936
    assert flops == 12_348_030_976 + 104_857_600 + 2_489_319_424
    # weights + live K/V read (400 tokens) + new K/V written (2 tokens)
    assert nbytes == 7_418_675_200 + 65_536 * 402


def test_mistral_nemo_12b_prefill_wave():
    d = dims("mistral_nemo_12b")
    assert d.layer_params == 272_629_760
    assert d.weight_bytes == 7_885_291_520
    assert d.kv_bytes_per_token == 49_152
    # a whole 100-token prompt, and a 50-token chunk over a 200-token prefix
    flops, nbytes = work.prefill_wave(d, [(100, 0, True), (50, 200, False)])
    # pairs: 100*101/2 + 50*200 + 50*51/2 = 16325
    assert flops == 981_467_136_000 + 3_209_625_600 + 1_342_177_280
    assert nbytes == 7_885_291_520 + 49_152 * 350


def test_roofline_takes_the_larger_bound():
    peak = work.peaks("TPU v5 lite")
    assert peak["bf16_flops_per_s"] == 197e12
    assert peak["hbm_bytes_per_s"] == 819e9
    assert work.least_time(197e12, 1.0, peak) == pytest.approx(1.0)
    assert work.least_time(1.0, 819e9, peak) == pytest.approx(1.0)


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        work.peaks("TPU v4")
