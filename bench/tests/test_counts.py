"""FLOP and byte counts against hand counts at the published widths."""
import json

from benchlib import flops, spec

from conftest import BENCH

ARCH = json.loads((BENCH / "configs" / "wan21-1.3b-480p-17f.json")
                  .read_text())["arch"]
WAN = spec.model("wan21-dit-1.3b")


def test_tokens_of_the_two_latents():
    assert flops.tokens((5, 60, 104), (1, 2, 2)) == 7800
    assert flops.tokens((21, 60, 104), (1, 2, 2)) == 32760


def test_dit_forward_flops_by_hand_17f():
    s, d, ff, ctx = 7800, 1536, 8960, 512
    block = (2 * 1536 * 6 * d                    # adaLN
             + 2 * s * d * d * 4                  # self q k v o
             + 2 * s * s * d * 2                  # self scores, values
             + 2 * s * d * d * 2                  # cross q o
             + 2 * ctx * d * d * 2                # cross k v
             + 2 * s * ctx * d * 2                # cross scores, values
             + 2 * s * d * ff * 3)                # SwiGLU
    outside = (2 * s * 64 * d + 2 * ctx * 4096 * d
               + 2 * 256 * 1536 + 2 * 1536 * 1536
               + 2 * 1536 * 2 * d + 2 * s * d * 64)
    assert WAN.forward_flops(ARCH, (5, 60, 104)) == 30 * block + outside


def test_guided_step_is_two_forwards_and_matches_the_estimates():
    f17 = WAN.step_flops(ARCH, (5, 60, 104))
    f81 = WAN.step_flops(ARCH, (21, 60, 104))
    assert f17 == 2 * WAN.forward_flops(ARCH, (5, 60, 104))
    # 7.7e13 and 6.2e14: the published-width estimates of the two cells
    assert 7.4e13 < f17 < 7.8e13
    assert 6.0e14 < f81 < 6.4e14


def test_latent_blend_bytes_by_hand():
    # H step of the 17f cell: K=2 windows of 58 rows, 5*104*16 per row
    rest = 5 * 104 * 16
    want = 4 * (2 * 58 * rest + 60 * rest + 2 * 58 + 60)
    assert flops.latent_blend_bytes(2, 58, 60, rest) == want
    # about 6 MB per stitch, 7 us at 819 GB/s
    assert 5.5e6 < want < 6.5e6


def test_peaks_known_and_unknown_devices():
    import pytest

    from benchlib.peaks import peaks

    assert peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        peaks("cpu")
