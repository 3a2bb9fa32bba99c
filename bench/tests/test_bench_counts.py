"""Counts from shapes and the table of peaks."""
import json
from pathlib import Path

import pytest

from bench import counts, peaks

ROOT = Path(__file__).resolve().parents[2]


def test_granite_flops_per_token_hand_count():
    cfg = json.loads((ROOT / "bench" / "configs" /
                      "granite-moe-1b-a400m-L8.json").read_text())
    # per layer: q 1024x1024, k and v 1024x512 each, o 1024x1024
    attn = 1024 * 1024 + 2 * 1024 * 512 + 1024 * 1024          # 3,145,728
    # router 1024x32, eight SwiGLU experts of 3 x 1024x512
    moe = 1024 * 32 + 8 * 3 * 1024 * 512                        # 12,615,680
    head = 49155 * 1024
    matmul = 8 * (attn + moe) + head
    assert matmul == 176_425_984
    # causal attention, 2048 positions: 2*16*64*2049 forward a layer, x3
    attention = 8 * 3 * 2 * 16 * 64 * 2049
    want = 6 * matmul + attention
    assert counts.granite_flops_per_token(cfg, 2048) == want
    assert 1.15e9 < want < 1.17e9


def test_agg_decode_bytes_against_wire_accounting():
    from repro.core import wire_accounting as WA
    padded, nb = 1 << 20, 256
    for bits in (2, 4, 8, 16):
        body = WA.packed_body_bytes(padded, bits, nb)
        assert counts.agg_decode_bytes(256, padded, bits, nb) == \
            256 * body + 12 * padded


def test_peaks_known_and_unknown():
    p = peaks.peaks("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks("cpu")
