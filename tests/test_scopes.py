"""Device ops carry the scope of the DiT sub-block or LP stage that owns
them, and the engine's host spans reach a profile with or without a
recorder.

* ``scope_of`` / ``scope_map`` on hand-written ``op_name`` paths and HLO;
* the step programs a 2-block DiT compiles through ``LPStepCompiler`` on
  the CPU: named per rotation dim, every compute op scoped;
* a CPU ``jax.profiler`` trace of one engine request: the program's
  spans on the host plane, the same names as the recorder's Chrome trace.
"""
from __future__ import annotations

import re

import jax
import pytest

from repro.obs.scopes import SCOPES, UNSCOPED, scope_map, scope_of

PROGRAM_SPANS = ("batch.admit", "batch.draw", "batch.denoise",
                 "denoise.run", "snapshot.record", "batch.finalize")


# ------------------------------------------------------------- parser
@pytest.mark.parametrize("op_name, scope", [
    ("jit(lp_step_T)/vmap()/dit.blocks/while/body/closed_call/"
     "dit.self_attn/dot_general", "dit.self_attn"),
    # nested scopes: the innermost in the vocabulary wins
    ("jit(lp_step_H)/lp.stitch/shard_map/lp.halo/ppermute", "lp.halo"),
    ("jit(lp_step_W)/dit.cfg/dit.blocks/while/body/while/body/dit.ffn/"
     "mul", "dit.ffn"),
    ("jit(f)/dit.blocks/while/body/checkpoint/dit.adaln/add", "dit.adaln"),
    # a scope seen through a transform's wrapper
    ("jit(f)/vmap(dit.head)/dot_general", "dit.head"),
    # components outside the vocabulary
    ("jit(f)/dit.unknown/add", UNSCOPED),
    ("jit(lp_step_T)/vmap()/while/body/add", UNSCOPED),
    ("reduce_sum", UNSCOPED),
    ("", UNSCOPED),
])
def test_scope_of_op_name_paths(op_name, scope):
    assert scope_of(op_name) == scope


HLO = """\
HloModule jit_lp_step_T, is_scheduled=true

%fused_computation (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  ROOT %multiply.1 = f32[8]{0} multiply(%param_0, %param_0), metadata={op_name="jit(lp_step_T)/dit.blocks/while/body/dit.ffn/mul"}
}

%body (p: (s32[], f32[8])) -> (s32[], f32[8]) {
  %p = (s32[], f32[8]{0}) parameter(0)
  %gte = f32[8]{0} get-tuple-element(%p), index=1
  %copy-start.1 = (f32[8]{0}, f32[8]{0}, u32[]) copy-start(%gte)
  %copy-done.1 = f32[8]{0} copy-done(%copy-start.1)
  %fusion.7 = f32[8]{0} fusion(%copy-done.1), kind=kLoop, calls=%fused_computation
  %add.3 = f32[8]{0} add(%fusion.7, %fusion.7), metadata={op_name="jit(lp_step_T)/lp.update/add"}
  %other = f32[8]{0} add(%add.3, %add.3), metadata={op_name="jit(lp_step_T)/while/body/add"}
  %unused = s32[] constant(0)
  ROOT %t = (s32[], f32[8]{0}) tuple(%gte, %other)
}

ENTRY %main (x: f32[8]) -> f32[8] {
  %x = f32[8]{0} parameter(0), metadata={op_name="z"}
  ROOT %dot.2 = f32[8]{0} dot(%x, %x), metadata={op_name="jit(lp_step_T)/dit.cfg/dit.embed/dot_general"}
}
"""


def test_scope_map_fills_ops_without_metadata():
    m = scope_map(HLO)
    assert m["dot.2"] == "dit.embed"
    assert m["add.3"] == "lp.update"
    # a fusion with no metadata takes its fused root's scope
    assert m["fusion.7"] == "dit.ffn"
    # an inserted copy takes the scope of what reads it, through a chain
    assert m["copy-done.1"] == "dit.ffn"
    assert m["copy-start.1"] == "dit.ffn"
    # an op with an unscoped op_name takes its operand's scope
    assert m["other"] == "lp.update"
    assert m["t"] == "lp.update"
    # nothing scoped reads it or is read by it
    assert m["unused"] == UNSCOPED


# -------------------------------------------- compiled step programs
def _opcode(line: str) -> str:
    rest = line.partition(" = ")[2]
    depth, i = 0, 0
    for i, c in enumerate(rest):          # skip the (possibly tuple) type
        depth += (c == "(") - (c == ")")
        if depth == 0 and c == " ":
            break
    return rest[i + 1:].split("(", 1)[0].strip()


@pytest.fixture(scope="module")
def tiny():
    from repro import models
    from repro.configs import get_config
    from repro.models import frontends

    cfg = get_config("wan21-dit-1.3b").reduced()
    params = models.build(cfg).init(jax.random.PRNGKey(0))

    def request(rid):
        from repro.serving.engine import VideoRequest

        return VideoRequest(
            request_id=rid, latent_shape=(4, 8, 12), seed=rid,
            context=frontends.text_context(jax.random.PRNGKey(rid), 1, cfg))

    return cfg, params, request


def _engine(tiny, recorder=None):
    from repro.models import dit
    from repro.serving.engine import LPServingEngine

    cfg, params, _ = tiny
    return LPServingEngine(dit.forward, params, cfg, num_partitions=2,
                           overlap_ratio=0.5, num_steps=3, max_batch=1,
                           recorder=recorder)


def test_step_programs_are_named_and_scoped(tiny):
    eng = _engine(tiny)
    eng.submit(tiny[2](0))
    eng.run()
    comp = eng._compiler
    programs = comp.programs()
    assert sorted(name for name, _ in programs) == [
        "jit_lp_step_H", "jit_lp_step_T", "jit_lp_step_W"]
    seen = set()
    for name, text in programs:
        m = scope_map(text)
        for line in text.splitlines():
            d = re.match(r"^\s*(?:ROOT\s+)?%([\w.\-]+) = ", line)
            if d is None:
                continue
            if _opcode(line.strip()) in ("fusion", "dot", "convolution",
                                         "reduce", "custom-call"):
                assert m[d.group(1)] in SCOPES, (name, line.strip()[:160])
        seen |= set(m.values())
    assert {"dit.self_attn", "dit.ffn", "lp.stitch"} <= seen
    # a second request reuses every executable: nothing new to report
    eng.submit(tiny[2](1))
    eng.run()
    assert len(comp.programs()) == len(programs)


# ------------------------------------------------------ host spans
def _profiled_spans(path: str):
    """``[(name, {stat: value})]`` of the program's spans on the host
    planes of a profile."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in PROGRAM_SPANS:
                    out.append((e.name, dict(e.stats)))
    return out


def test_engine_spans_reach_a_profile_without_recorder(tiny, tmp_path):
    from repro.obs import FlightRecorder

    eng = _engine(tiny)
    eng.submit(tiny[2](0))
    eng.run()                                   # compile outside the trace
    eng.submit(tiny[2](1))
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.run()
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.rglob("*.xplane.pb")
    spans = _profiled_spans(str(path))
    names = [n for n, _ in spans]
    for required in ("batch.draw", "batch.denoise", "snapshot.record"):
        assert required in names, names
    runs = [args for n, args in spans if n == "denoise.run"]
    # the paper's rotation: each of the 3 steps is a run of its own dim
    assert sorted(int(a["dim"]) for a in runs) == [0, 1, 2]
    draw = next(args for n, args in spans if n == "batch.draw")
    assert int(draw["batch_seq"]) == 2 and "1" in str(draw["request_ids"])

    # with a recorder: the same names in the Chrome trace
    rec = FlightRecorder()
    eng = _engine(tiny, recorder=rec)
    eng.submit(tiny[2](0))
    eng.run()
    chrome = {e["name"] for e in rec.trace.events if e["ph"] == "X"}
    assert set(PROGRAM_SPANS) <= chrome
    assert set(names) <= chrome
