"""The reduction by program scope and program span (``benchlib.scopes``)
on hand-made events, the probe (``bench/scopes.py``) on a tiny cell, and
the existing trace reduction and metrics on the recorded chip traces,
which must read what they read before the program had scopes."""
from __future__ import annotations

import json
from pathlib import Path

import pytest

from benchlib import scopes, spec, tracefile
from benchlib.peaks import peaks
from benchlib.tracefile import Event

from conftest import DATA

MS = 1e6                                  # ns per ms


def _ops():
    """Device 0 over [0, 100] ms: step program A (0-60) holds a loop
    (5-50) with a self-attention fusion (10-30) and an FFN dot (30-45)
    inside, then an instruction no scope claims (50-55); step program B
    (70-80) runs a stitch op; an op of no step program runs at 85-90."""
    ops = [Event("%while.1 = (f32[8]) while(..)", 5 * MS, 50 * MS),
           Event("%fusion.2 = f32[8]{0} fusion(..)", 10 * MS, 30 * MS),
           Event("%dot.3 = bf16[8]{0} dot(..)", 30 * MS, 45 * MS),
           Event("%copy.4 = f32[8]{0} copy(..)", 50 * MS, 55 * MS),
           Event("%fusion.2 = f32[8]{0} fusion(..)", 70 * MS, 80 * MS),
           Event("%add.1 = f32[8]{0} add(..)", 85 * MS, 90 * MS)]
    tracefile.nest(ops)
    modules = [Event("jit_lp_step_T(111)", 0, 60 * MS),
               Event("jit_lp_step_H(222)", 70 * MS, 80 * MS),
               Event("jit_concatenate(333)", 85 * MS, 90 * MS)]
    return ops, modules


MAPS = {
    "jit_lp_step_T": [{"while.1": "dit.blocks", "fusion.2": "dit.self_attn",
                       "dot.3": "dit.ffn"}],
    "jit_lp_step_H": [{"fusion.2": "lp.stitch"}],
}


def test_device_scopes_self_time():
    ops, modules = _ops()
    got = scopes.device_scopes(ops, modules, MAPS, 0, 100 * MS)
    assert got == pytest.approx({
        "dit.blocks": 10e-3,        # the loop less the 35 ms inside it
        "dit.self_attn": 20e-3,
        "dit.ffn": 15e-3,
        "lp.stitch": 10e-3,
        # copy.4 (no scope) and the op of another program
        "unscoped": 10e-3,
    })
    # only what lies inside the window counts
    part = scopes.device_scopes(ops, modules, MAPS, 60 * MS, 100 * MS)
    assert part == pytest.approx({"lp.stitch": 10e-3, "unscoped": 5e-3})


def test_device_scopes_two_programs_of_one_name():
    ops, modules = _ops()
    # two executables named jit_lp_step_H that disagree: unscoped
    split = dict(MAPS, jit_lp_step_H=[{"fusion.2": "lp.stitch"},
                                      {"fusion.2": "dit.ffn"}])
    got = scopes.device_scopes(ops, modules, split, 60 * MS, 100 * MS)
    assert got == pytest.approx({"unscoped": 15e-3})
    agree = dict(MAPS, jit_lp_step_H=[{"fusion.2": "lp.stitch"},
                                      {"fusion.2": "lp.stitch"}])
    got = scopes.device_scopes(ops, modules, agree, 60 * MS, 100 * MS)
    assert got == pytest.approx({"lp.stitch": 10e-3, "unscoped": 5e-3})


def test_idle_by_span_splits_a_gap():
    ops, _ = _ops()
    # idle: 0-5, 55-70, 80-85, 90-100 ms
    program = [Event("batch.denoise", 0, 62 * MS),
               Event("snapshot.record", 52 * MS, 62 * MS),
               Event("denoise.run", 62 * MS, 95 * MS)]
    got = scopes.idle_by_span(ops, program, 0, 100 * MS)
    assert got == pytest.approx({
        "batch.denoise": 5e-3,          # 0-5
        "snapshot.record": 7e-3,        # 55-62: the inner span wins
        "denoise.run": 8e-3 + 5e-3 + 5e-3,   # 62-70, 80-85, 90-95
        "host": 5e-3,                   # 95-100: no program span
    })
    assert sum(got.values()) == pytest.approx(35e-3)


def test_readings_per_step():
    r = scopes.readings({"dit.self_attn": 6.0, "dit.ffn": 0.9,
                         "lp.stitch": 0.02, "lp.halo": 0.01, "unscoped": 1},
                        {"snapshot.record": 0.003, "host": 1.0}, steps=3)
    assert r == pytest.approx({"dit.self_attn_ms": 2000.0,
                               "dit.ffn_ms": 300.0, "lp.overhead_ms": 10.0,
                               "engine.snapshot_idle_ms": 1.0})
    assert scopes.readings({}, {}, steps=0) == {}


# ------------------------------------------------ recorded chip traces
RECORDED = sorted(DATA.glob("*.xplane.pb"))
METRICS = ("device.idle_share", "device.hbm_peak_gb", "step.mfu",
           "latent_blend_roofline", "lp.collective_exposed_share")


def seed_rec(trace, summary, chips: int) -> dict:
    """A result record around a recorded trace, with fixed host-side
    numbers, for the metrics' readers."""
    conf = spec.config("wan21-1.3b-480p-17f")
    latent = tuple(conf["latent"])
    return {"arch": conf["arch"], "latent": latent,
            "step_flops": spec.model(conf["model"]).step_flops(
                conf["arch"], latent),
            "chips": chips, "peaks": peaks("TPU v5 lite"), "step_s": 2.4,
            "setup_s": 25.0, "steps": 9, "requests": 3, "window_s": 21.6,
            "memory": [{"peak_bytes_in_use": 11e9,
                        "peak_bytes_reserved": 3e8}],
            "stitch": [{"dim": 0, "k": 2, "window": 256, "extent": 512,
                        "rest": 512}],
            "trace": summary, "events": trace}


@pytest.mark.parametrize("path", RECORDED, ids=[p.name for p in RECORDED])
def test_recorded_traces_read_as_before(path: Path):
    """``device_ops``, ``idle_gaps`` and the five metrics of the accepted
    benchmark read exactly what they read before this reduction existed
    (``data/seed_readings.json``); the new reduction finds the module
    line and, with no scope maps, puts every op in ``unscoped``."""
    want = json.loads((DATA / "seed_readings.json").read_text())[path.name]
    t = tracefile.read(str(path))
    ids = sorted(t.devices)
    s = tracefile.summarize(t, ids)
    assert json.loads(json.dumps(s["breakdown"])) == want["breakdown"]
    rec = seed_rec(t, s, len(ids))
    assert {m: spec.reader(m)(rec) for m in METRICS} == want["metrics"]

    modules, program = scopes.read(str(path))
    assert modules[ids[0]] and program == []
    lo, hi = t.window()
    got = scopes.device_scopes(t.devices[ids[0]], modules[ids[0]], {}, lo,
                               hi)
    assert list(got) == ["unscoped"]
    assert got["unscoped"] == pytest.approx(
        sum(s["devices"][ids[0]]["by_op"].values()))


# --------------------------------------------------------- the probe
def test_probe_on_a_tiny_cell(tree):
    import importlib.util

    here = Path(__file__).resolve().parents[1] / "scopes.py"
    sp = importlib.util.spec_from_file_location("bench_scopes_probe", here)
    probe = importlib.util.module_from_spec(sp)
    sp.loader.exec_module(probe)
    out = probe.run("tiny-lp2", 2 ** 31 + 17, 0.2, root=tree, base=tree,
                    require_tpu=False)
    assert out["compiles_in_windows"] == 0
    assert sorted(out["programs"]) == [
        "jit_lp_step_H", "jit_lp_step_T", "jit_lp_step_W"]
    assert out["step_s"]["untraced"] > 0 and out["step_s"]["traced"] > 0
    # the CPU has no TPU plane: no device time, but the program's spans
    # reached the host plane
    assert out["program_spans"] > 0
    assert set(out["readings"]) == {"dit.self_attn_ms", "dit.ffn_ms",
                                    "lp.overhead_ms",
                                    "engine.snapshot_idle_ms"}
