"""An architecture added as new files only (a module under ``models/`` and
a configuration that names it) is run through its own module: its
reference decides ``correct`` and its FLOP count reaches ``step.mfu``."""
import json

import pytest

from benchlib import spec

from harness_run import run_cell

# WAN's program under another name, with a reference of its own: the
# conditional and unconditional forwards as two calls, and a guidance
# combine that is sound or, for the control, leaves the guidance out
MODULE = '''
from pathlib import Path

import jax
import jax.numpy as jnp

from benchlib import spec

WAN = spec.model("wan21-dit-1.3b", Path(__file__).resolve().parents[1])
make_params, context, step_extras = WAN.make_params, WAN.context, \\
    WAN.step_extras
STEP_FLOPS = 123456789


def program(conf):
    return WAN.program(dict(conf, model="wan21-dit-1.3b"))


def window_fn(arch, quant, mesh, axis):
    a = dict(arch)

    @jax.jit
    def fn(params, win, t, ctx):
        with jax.default_matmul_precision("highest"):
            cond = WAN.velocity(params, win, t, ctx, a, quant)
            uncond = WAN.velocity(params, win, t, jnp.zeros_like(ctx), a,
                                  quant)
        return jnp.stack([cond, uncond])

    return lambda params, win, t, ctx, guidance: fn(params, win, t, ctx)


def guide(pred, guidance):
    return {guided}


def step_flops(arch, latent):
    return STEP_FLOPS
'''
GUIDED = {"sound": "pred[:, 1] + guidance * (pred[:, 0] - pred[:, 1])",
          "wrong_reference": "pred[:, 0]"}

# a reader of what the harness hands the metrics as the step's FLOPs
FLOPS_READER = "def read(rec):\n    return rec['step_flops']\n"


def add_architecture(tree, guided: str) -> None:
    """The architecture ``toy-dit`` and the cell ``toy-lp2`` on it, as new
    files, plus an end-to-end reader of ``rec["step_flops"]``."""
    (tree / "models" / "toy-dit.py").write_text(
        MODULE.format(guided=guided))
    conf = json.loads((tree / "configs" / "tiny.json").read_text())
    conf.update(name="toy", model="toy-dit")
    (tree / "configs" / "toy.json").write_text(json.dumps(conf))
    (tree / "metrics" / "x.step_flops.py").write_text(FLOPS_READER)
    (tree / "limits" / "toy-lp2.json").write_text(
        (tree / "limits" / "tiny-lp2.json").read_text())
    bench = json.loads((tree / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "toy-lp2", "config": "toy",
                               "traffic": "lp2-3step-closed", "chips": 1,
                               "why": "test"})
    bench["end_to_end"].append({"name": "x.step_flops", "unit": "FLOP",
                                "better": "lower", "bound": 0.01,
                                "source": "host_clock"})
    (tree / "BENCHMARK.json").write_text(json.dumps(bench))


@pytest.mark.parametrize("case", sorted(GUIDED))
def test_added_architecture_runs_through_its_module(tree, case):
    add_architecture(tree, GUIDED[case])
    result = run_cell(tree, "toy-lp2")
    assert result["correct"] is (case == "sound"), result["check"]
    assert result["metrics"]["x.step_flops"]["value"] == 123456789
    if case == "sound":
        assert result["check"]["step_err"]["value"] < 1e-4
    else:
        assert result["check"]["step_err"]["value"] > \
            3 * result["check"]["step_err"]["limit"]


@pytest.mark.parametrize("model", ["wan21-dit-1.3b", "toy-dit"])
def test_step_mfu_reads_the_module_count(tree, model):
    """``step.mfu`` takes the step's FLOPs from the record, where the
    harness puts the module's ``step_flops``, not from the widths."""
    add_architecture(tree, GUIDED["sound"])
    conf = json.loads((tree / "configs" / "tiny.json").read_text())
    work = spec.model(model, tree).step_flops(conf["arch"], conf["latent"])
    rec = {"step_flops": work, "steps": 9, "step_s": 0.5, "chips": 1,
           "peaks": {"bf16_flops": 197e12}}
    assert spec.reader("step.mfu", tree)(rec) == pytest.approx(
        100.0 * work / 0.5 / 197e12)
