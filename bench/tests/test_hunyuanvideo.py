"""HunyuanVideo's MMDiT lands as files only: a tiny configuration of the
``hunyuanvideo-dit`` module runs through the unchanged harness, its
reference decides ``correct`` at K=2 and K=1, and
``joint_attention_roofline`` reads the kernel's events against the joint
attention's FLOPs (and nothing off an MMDiT)."""
import json

import pytest

from benchlib import spec
from benchlib.tracefile import Event, Trace

from harness_run import run_cell

TINY_ARCH = dict(double_layers=1, single_layers=2, refiner_layers=1,
                 d_model=96, num_heads=2, head_dim=48, d_ff=384,
                 rope_axes=[8, 20, 20], context_len=8, context_dim=64,
                 pooled_dim=32, latent_channels=4, dtype="float32")


def add_tiny_hyv(tree) -> None:
    conf = spec.config("hunyuanvideo-544p-33f")
    conf["arch"].update(TINY_ARCH)
    conf.update(name="tinyhyv", latent=[4, 8, 12])
    (tree / "configs" / "tinyhyv.json").write_text(json.dumps(conf))
    bench = json.loads((tree / "BENCHMARK.json").read_text())
    for cell, traffic in (("tinyhyv-lp2", "lp2-3step-closed"),
                          ("tinyhyv-k1", "k1-3step-closed")):
        bench["workloads"].append({"name": cell, "config": "tinyhyv",
                                   "traffic": traffic, "chips": 1,
                                   "why": "test"})
        (tree / "limits" / f"{cell}.json").write_text(
            (tree / "limits" / "tiny-lp2.json").read_text())
    (tree / "BENCHMARK.json").write_text(json.dumps(bench))


@pytest.mark.parametrize("cell", ["tinyhyv-lp2", "tinyhyv-k1"])
def test_tiny_hunyuan_cell_is_correct(tree, cell):
    add_tiny_hyv(tree)
    out = run_cell(tree, cell)
    assert out["correct"], out["check"]
    assert out["check"]["served_err"]["value"] < 1e-5


def _rec(arch, ops):
    host = [Event("bench.window", 0, 10 ** 9)]
    return {"arch": arch, "latent": [9, 68, 120], "peaks": {"bf16_flops":
                                                            197e12},
            "events": Trace({0: ops}, host), "trace": {"devices": [0]},
            "stitch": [{"dim": 1, "k": 2, "window": 66, "extent": 68,
                        "rest": 0}]}


def test_joint_attention_roofline_reads_the_kernel():
    read = spec.reader("joint_attention_roofline")
    arch = spec.config("hunyuanvideo-544p-33f")["arch"]
    # two windows of 9 x 33 x 60 = 17,820 video tokens, plus the text
    s = 17820 + 256
    need = 2 * 4 * 3072 * (15 * s * s + 2 * 256 ** 2)
    spent_ns = need / 197e12 * 1e9 / 0.8       # the kernel at 80% of peak
    ops = [Event("%dit_flash_attention.3 = bf16[2,1] custom-call()", 0,
                 spent_ns / 2),
           Event("%dit_flash_attention.5 = bf16[2,1] custom-call()",
                 spent_ns / 2, spent_ns),
           Event("%fusion.1 = bf16[2] fusion()", spent_ns, 2 * spent_ns)]
    assert read(_rec(arch, ops)) == pytest.approx(80.0, rel=1e-6)
    # WAN's block has no joint attention: nothing to read
    wan = spec.config("wan21-1.3b-480p-17f")["arch"]
    assert read(_rec(wan, ops)) is None
    assert read(dict(_rec(arch, ops), events=None)) is None
