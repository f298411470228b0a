"""Operations and bytes, worked by hand for each configuration, and the
table of peaks."""
import json
from pathlib import Path

import pytest

from bench import flops, harness
from bench.reference.model import arch

CONFIGS = (Path(__file__).resolve().parents[1] / "configs",
           Path(__file__).resolve().parent / "configs")   # not yet a cell


def _arch(name):
    path = next(d / f"{name}.json" for d in CONFIGS
                if (d / f"{name}.json").is_file())
    return arch(json.loads(path.read_text()))


def test_qwen2_by_hand():
    a = _arch("qwen2-1.5b")
    # Q,K,V,O: 2*1536*(12+2*2)*128 + 2*12*128*1536 = 11,010,048
    # SwiGLU: 2*3*1536*8960 = 82,575,360; 28 layers
    assert flops.linear_flops_per_token(a) == 28 * (11_010_048 + 82_575_360)
    assert flops.head_flops(a) == 2 * 1536 * 151_936
    # one query over 100 keys: 4*12*128*100 per layer
    assert flops.attention_flops(a, 100) == 28 * 614_400
    assert flops.decode_flops(a, 100) == (2_620_391_424 + 17_203_200
                                         + 466_747_392)
    # prefill of positions 32..35 (the first 32 from the prefix index):
    # contexts 33+34+35+36 = 138
    assert flops.prefill_flops(a, 32, 4) == (4 * 2_620_391_424
                                            + 138 * 28 * 6_144
                                            + 466_747_392)
    # decode kernel, slots at lengths 100 and 17 of 16: 7 + 2 pages of
    # 16 x 2 KV heads x 128 x (K,V) x 2 bytes; q and out of 16 rows
    f, b = flops.paged_decode_call(a, [100, 17], page=16, batch=16)
    assert f == 4 * 12 * 128 * 117
    assert b == 9 * 16 * 2 * 128 * 2 * 2 + 2 * 16 * 12 * 128 * 2


def test_mixtral_by_hand():
    a = _arch("mixtral-8x7b-l1")
    # Q,K,V,O: 2*4096*(32+2*8)*128 + 2*32*128*4096 = 83,886,080
    # router 2*4096*8 = 65,536; two of eight experts 2*2*3*4096*14336
    assert flops.linear_flops_per_token(a) == (83_886_080 + 65_536
                                               + 704_643_072)
    assert flops.head_flops(a) == 2 * 4096 * 32_000
    f, b = flops.paged_decode_call(a, [1], page=16, batch=16)
    assert f == 4 * 32 * 128
    assert b == 1 * 16 * 8 * 128 * 2 * 2 + 2 * 16 * 32 * 128 * 2


def test_least_time_takes_the_binding_bound():
    assert flops.least_time(197e12, 1.0, 197e12, 819e9) == 1.0
    assert flops.least_time(1.0, 819e9, 197e12, 819e9) == 1.0


def test_peaks_table():
    p = harness.load_peak("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert p["hbm_bytes"] == 16e9
    with pytest.raises(ValueError):
        harness.load_peak("TPU v9 imaginary")
