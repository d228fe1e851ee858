"""Observability plane: trace schema stability, metrics correctness,
and EXACT derived wire attribution vs ``core/comm_model``.

The load-bearing contract (docs/observability.md): per-step wire bytes
are *derived* by replaying the analytic byte model over the engine's
recorded geometry/codec timelines — and because ``comm_model`` matches
compiled HLO exactly, the derived attribution must equal the model
byte-for-byte, per collective, per tier, across codecs, the sharded
hybrid wire, and mid-request mesh shrinks.
"""
import json
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.core import comm_model as cm
from repro.core.schedule import rotation_dim, usable_dims
from repro.obs import (
    FlightRecorder,
    MetricsRegistry,
    TRACE_SCHEMA,
    TraceRecorder,
    attribute_denoise_steps,
    perf_s,
    step_wire_attribution,
    tier_for_group_size,
    tiered_collectives,
    validate_trace,
)
from repro.obs import metrics as obsm

CODECS = ["fp32", "bf16", "int8", "int4", "int8-residual"]
R = 0.5


def _ccfg(dims=(8, 8, 12), steps=6):
    return cm.VDMCommConfig(
        latent_dims=dims, latent_channels=16, patch_sizes=(1, 2, 2),
        d_model=96, num_blocks=2, num_steps=steps,
    )


# --------------------------------------------------------------- clock
def test_clock_monotonic_and_stamps():
    a = perf_s()
    b = perf_s()
    assert b >= a
    from repro.obs.clock import perf_us, wall_stamp_s

    assert perf_us() > 0
    # wall stamps are epoch-scale (for snapshot metadata, never durations)
    assert wall_stamp_s() > 1e9


# --------------------------------------------------------------- trace
def test_trace_span_schema_and_validation():
    tr = TraceRecorder()
    with tr.span("batch.denoise", cat="serve", size=2):
        with tr.span("denoise.run", cat="denoise", dim=1):
            pass
    tr.instant("snapshot.record", cat="serve", step=3)
    tr.counter("wire.bytes_by_tier", {"inter": 10.0, "intra": 0.0},
               cat="wire")
    doc = tr.to_json()
    assert doc["otherData"]["schema"] == TRACE_SCHEMA
    assert validate_trace(doc) == []
    phases = sorted(e["ph"] for e in doc["traceEvents"])
    assert phases == ["C", "X", "X", "i"]
    spans = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    for e in spans:
        assert e["dur"] >= 0 and e["ts"] >= 0
    # nesting: the inner run opened after and closed before the batch
    by_name = {e["name"]: e for e in spans}
    outer, inner = by_name["batch.denoise"], by_name["denoise.run"]
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1


def test_trace_validation_rejects_malformed_docs():
    assert validate_trace({"traceEvents": []})  # missing schema tag
    base = {"otherData": {"schema": TRACE_SCHEMA}}
    bad_phase = {**base, "traceEvents": [
        {"ph": "Z", "name": "x", "ts": 0, "pid": 1, "tid": 1}]}
    assert validate_trace(bad_phase)
    bad_cat = {**base, "traceEvents": [
        {"ph": "i", "name": "x", "ts": 0, "pid": 1, "tid": 1,
         "cat": "nonsense"}]}
    assert validate_trace(bad_cat)
    no_dur = {**base, "traceEvents": [
        {"ph": "X", "name": "x", "ts": 0, "pid": 1, "tid": 1,
         "cat": "serve"}]}
    assert validate_trace(no_dur)


def test_trace_args_are_json_clean():
    tr = TraceRecorder()
    tr.instant("x", cat="obs", arr=np.arange(3), f=np.float32(1.5),
               nested={"t": (1, 2)})
    doc = tr.to_json()
    json.dumps(doc)  # must not raise
    assert validate_trace(doc) == []
    args = doc["traceEvents"][0]["args"]
    assert args["arr"] == [0, 1, 2] and args["f"] == 1.5


# ------------------------------------------------------------- metrics
def test_metrics_registry_counter_gauge_histogram():
    m = MetricsRegistry()
    m.inc(obsm.REQUESTS)
    m.inc(obsm.REQUESTS, 2)
    m.set(obsm.QUEUE_DEPTH, 7)
    for v in (0.1, 0.2, 0.3, 0.4):
        m.observe(obsm.STEP_LATENCY_S, v)
    m.inc(obsm.WIRE_BYTES, 100.0, tier="inter", collective="all-gather")
    m.inc(obsm.WIRE_BYTES, 50.0, tier="inter", collective="all-gather")
    assert m.counter_value(obsm.REQUESTS) == 3.0
    assert m.gauge_value(obsm.QUEUE_DEPTH) == 7.0
    assert m.counter_value(obsm.WIRE_BYTES, tier="inter",
                           collective="all-gather") == 150.0
    assert m.hist_values(obsm.STEP_LATENCY_S) == [0.1, 0.2, 0.3, 0.4]
    rows = {(r["name"], tuple(sorted(r["labels"].items())))
            for r in m.snapshot()}
    assert (obsm.WIRE_BYTES,
            (("collective", "all-gather"), ("tier", "inter"))) in rows


def test_metrics_exporters():
    m = MetricsRegistry()
    m.inc(obsm.WIRE_BYTES, 1024.0, tier="inter", collective="all-gather")
    m.inc(obsm.WIRE_BYTES, 10.0, tier="intra", collective="all-gather")
    m.set(obsm.DEAD_GROUPS, 1)
    m.observe(obsm.STEP_LATENCY_S, 0.5)
    jsonl = m.to_jsonl()
    rows = [json.loads(l) for l in jsonl.strip().splitlines()]
    assert all("stamp_s" in r for r in rows)
    assert {r["name"] for r in rows} == {
        obsm.WIRE_BYTES, obsm.DEAD_GROUPS, obsm.STEP_LATENCY_S}
    prom = m.to_prometheus()
    assert 'repro_wire_bytes{collective="all-gather",tier="inter"} 1024.0' \
        in prom
    assert "# TYPE repro_wire_bytes counter" in prom
    # one TYPE line per metric name even with multiple label sets
    assert prom.count("# TYPE repro_wire_bytes counter") == 1
    assert "repro_denoise_step_s_count" in prom
    assert 'quantile="0.5"' in prom


def test_histogram_reservoir_keeps_exact_totals_and_counts_dropped():
    """Satellite regression: ``observe`` used to silently stop keeping
    samples at hist_cap, freezing quantiles on the warm-up window.  The
    reservoir must (a) hold exactly ``hist_cap`` samples, (b) keep
    count/sum/min/max EXACT over the whole stream, (c) export the
    dropped-sample count, and (d) keep late samples reachable so the
    quantiles track the stream, not its head."""
    cap = 64
    m = MetricsRegistry(hist_cap=cap, seed=0)
    n = 1000
    for i in range(n):
        m.observe(obsm.E2E_LATENCY_S, float(i))
    held = m.hist_values(obsm.E2E_LATENCY_S)
    assert len(held) == cap
    assert m.hist_dropped(obsm.E2E_LATENCY_S) == n - cap
    row = [r for r in m.snapshot()
           if r["name"] == obsm.E2E_LATENCY_S][0]
    assert row["count"] == n                      # exact, not cap
    assert row["sum"] == float(sum(range(n)))     # exact
    assert row["min"] == 0.0 and row["max"] == float(n - 1)
    assert row["dropped"] == n - cap
    # an all-first-cap reservoir would put p50 at ~cap/2; a uniform one
    # tracks the stream median ~n/2
    assert row["p50"] > n * 0.2
    # below cap nothing ever drops
    m2 = MetricsRegistry(hist_cap=cap)
    for v in (0.1, 0.2):
        m2.observe(obsm.QUEUE_WAIT_S, v)
    assert m2.hist_values(obsm.QUEUE_WAIT_S) == [0.1, 0.2]
    assert m2.hist_dropped(obsm.QUEUE_WAIT_S) == 0


def test_histogram_reservoir_is_seed_deterministic():
    """Same observation sequence + same registry seed -> identical held
    samples (replayable snapshots under a fixed workload seed)."""
    def fill(seed):
        m = MetricsRegistry(hist_cap=16, seed=seed)
        for i in range(500):
            m.observe(obsm.STEP_LATENCY_S, float(i) * 0.01)
        return m.hist_values(obsm.STEP_LATENCY_S)

    assert fill(0) == fill(0)
    assert fill(0) != fill(1)


def test_prometheus_exposition_format_parses():
    """Format-level lint of ``to_prometheus()``: every sample line must
    match the exposition grammar (mangled names without dots, escaped
    label values), and histograms must export quantile + _sum/_count/
    _dropped rows."""
    import re

    m = MetricsRegistry(hist_cap=4)
    m.inc(obsm.WIRE_BYTES, 7.0, tier="inter", collective="all-gather")
    m.set(obsm.QUEUE_DEPTH, 3, source='we"ird\\lab\nel')
    for v in (0.1, 0.2, 0.3, 0.4, 0.5, 0.6):
        m.observe(obsm.E2E_LATENCY_S, v, priority="interactive")
    text = m.to_prometheus()
    name_re = r"[a-zA-Z_:][a-zA-Z0-9_:]*"
    label_re = r'[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\\n]|\\["\\n])*"'
    sample_re = re.compile(
        rf"^({name_re})(\{{{label_re}(,{label_re})*\}})? (-?[0-9.einf+-]+)$")
    names = set()
    fam, kind = None, None
    for line in text.strip().splitlines():
        if line.startswith("# TYPE "):
            _, _, fam, kind = line.split(" ")
            assert kind in ("counter", "gauge", "summary")
            continue
        match = sample_re.match(line)
        assert match, f"unparseable exposition line: {line!r}"
        name = match.group(1)
        names.add(name)
        assert "." not in name                     # dots mangled away
        float(match.group(4))                      # value parses
        # family grouping: every sample must belong to the TYPE line
        # above it.  The only valid summary children are the quantile
        # / _sum / _count rows — in particular '_dropped' must NOT
        # ride inside a summary family (strict OpenMetrics parsers
        # reject it); it is its own counter family.
        if kind == "summary":
            assert name in (fam, f"{fam}_sum", f"{fam}_count"), \
                f"{name!r} is not a summary child of {fam!r}"
        else:
            assert name == fam
    e2e = "repro_serve_e2e_latency_s"
    assert {e2e, f"{e2e}_sum", f"{e2e}_count", f"{e2e}_dropped"} <= names
    assert f"# TYPE {e2e}_dropped counter" in text
    assert f'{e2e}{{priority="interactive",quantile="0.5"}}' in text
    # escaped label round-trip: backslash, quote, newline
    assert r'source="we\"ird\\lab\nel"' in text
    # histogram past cap: _count is the exact stream length, _dropped
    # the truncation
    assert f"{e2e}_count{{priority=\"interactive\"}} 6" in text
    assert f"{e2e}_dropped{{priority=\"interactive\"}} 2" in text


def test_metrics_write_format_by_extension(tmp_path):
    m = MetricsRegistry()
    m.inc(obsm.BATCHES)
    p1, p2 = tmp_path / "m.prom", tmp_path / "m.jsonl"
    m.write(str(p1))
    m.write(str(p2))
    assert p1.read_text().startswith("# TYPE")
    assert json.loads(p2.read_text().splitlines()[0])["name"] == obsm.BATCHES


# -------------------------------------------- derived wire attribution
@pytest.mark.parametrize("codec", CODECS)
def test_step_attribution_matches_comm_model_unsharded(codec):
    cfg = _ccfg()
    K = 3
    for dim in usable_dims(cfg.latent_dims, cfg.patch_sizes, K):
        got = step_wire_attribution(cfg, K, R, dim, codec)
        want = cm.lp_halo_codec_step_collectives(cfg, K, R, dim,
                                                 codec=codec)
        assert got["inter"] == {k: float(v) for k, v in want.items()}
        assert got["intra"] == {}


@pytest.mark.parametrize("codec", CODECS)
def test_step_attribution_matches_comm_model_wire_sharded(codec):
    """The hierarchy-aware hybrid wire: tier split must equal
    ``lp_halo_sharded_step_collectives`` exactly (inter cp+ag chunks,
    intra reassembly gather)."""
    cfg = _ccfg()
    M, T = 3, 2
    for dim in usable_dims(cfg.latent_dims, cfg.patch_sizes, M):
        got = step_wire_attribution(cfg, M, R, dim, codec, tp=T,
                                    wire_shard=True, lp_impl="halo_hybrid")
        want = cm.lp_halo_sharded_step_collectives(cfg, M, T, R, dim,
                                                   codec=codec)
        for tier in ("inter", "intra"):
            assert got[tier] == {k: float(v) for k, v in
                                 want[tier].items()}, (codec, dim, tier)


def test_step_attribution_psum_family_is_codec_blind():
    cfg = _ccfg()
    for impl in ("shard_map", "uniform", "gspmd"):
        got = step_wire_attribution(cfg, 2, R, 0, "int8", lp_impl=impl)
        assert got == {"inter": {"all-reduce": float(cfg.latent_bytes)},
                       "intra": {}}


@pytest.mark.parametrize("wire_shard", [False, True])
def test_attribution_sums_match_wire_profile(wire_shard):
    """Whole-denoise totals equal ``lp_halo_wire_profile`` — the same
    quantity the step-policy autotuner prices."""
    cfg = _ccfg(steps=6)
    M, T = 3, 2
    step_codecs = ["int4", "int4", "int8", "int8", "int8-residual",
                   "int8-residual"]
    recs = attribute_denoise_steps(
        cfg, R, step_codecs, [(1, M)], tp=T, wire_shard=wire_shard,
        lp_impl="halo_hybrid")
    prof = cm.lp_halo_wire_profile(cfg, M, T, R, step_codecs,
                                   wire_shard=wire_shard)
    assert sum(r["inter_bytes"] for r in recs) == float(prof["inter"])
    assert sum(r["intra_bytes"] for r in recs) == float(prof["intra"])


def test_attribution_replays_geometry_timeline():
    """A mid-denoise eviction re-derives usable dims and the rotation
    sequence at the new K — steps at or after the event are billed on
    the shrunken mesh."""
    cfg = _ccfg(steps=4)
    geometry = [(1, 3), (3, 2)]  # evicted in the hook before step 3
    recs = attribute_denoise_steps(cfg, R, ["int8"] * 4, geometry,
                                   tp=2, wire_shard=True,
                                   lp_impl="halo_hybrid")
    assert [r["K"] for r in recs] == [3, 3, 2, 2]
    assert [r["plan_epoch"] for r in recs] == [0, 0, 1, 1]
    for r in recs:
        dims = usable_dims(cfg.latent_dims, cfg.patch_sizes, r["K"])
        assert r["dim"] == rotation_dim(r["step"], dims)
        want = cm.lp_halo_sharded_step_collectives(
            cfg, r["K"], 2, R, r["dim"], codec="int8")
        assert r["inter"] == {k: float(v) for k, v in
                              want["inter"].items()}
        assert r["intra"] == {k: float(v) for k, v in
                              want["intra"].items()}


def test_attribution_rejects_gapped_timeline():
    cfg = _ccfg()
    with pytest.raises(ValueError, match="step 1"):
        attribute_denoise_steps(cfg, R, ["int8"], [(2, 3)])


def test_attribution_prices_wire_time_with_links():
    from repro.policy.autotune import DEFAULT_LINKS

    cfg = _ccfg(steps=2)
    recs = attribute_denoise_steps(cfg, R, ["fp32", "fp32"], [(1, 3)],
                                   tp=2, wire_shard=True,
                                   lp_impl="halo_hybrid",
                                   links=DEFAULT_LINKS)
    for r in recs:
        want = DEFAULT_LINKS.wire_time_ms(r["inter_bytes"],
                                          r["intra_bytes"])
        assert r["pred_wire_time_ms"] == want > 0


def test_attribution_displaced_hidden_bytes_match_wire_profile():
    """Displaced attribution: ``inter_bytes`` stays the TOTAL (HLO-
    matching) payload; ``hidden_bytes`` marks the slab-ppermute portion
    of every step that is NOT the first of its (dim x codec x K) run;
    and the exposed/hidden split sums to exactly what the autotuner
    prices via ``lp_halo_wire_profile``."""
    from repro.policy.autotune import DEFAULT_LINKS

    cfg = _ccfg(dims=(8, 2, 2), steps=4)   # single usable dim at K=3
    assert usable_dims(cfg.latent_dims, cfg.patch_sizes, 3) == (0,)
    step_codecs = ["displaced:int8-residual"] * 3 + ["int8-residual"]
    recs = attribute_denoise_steps(cfg, R, step_codecs, [(1, 3)],
                                   links=DEFAULT_LINKS)
    sync = cm.lp_halo_codec_step_collectives(cfg, 3, R, 0,
                                             codec="int8-residual")
    pp = float(sync["collective-permute"])
    # first-of-run exposed, later displaced steps hide their ppermutes,
    # and the codec-segment boundary (step 4) is first-of-run again
    assert [r["hidden_bytes"] for r in recs] == [0.0, pp, pp, 0.0]
    for r in recs:
        assert r["inter"] == {k: float(v) for k, v in sync.items()}
        assert r["pred_wire_time_ms"] == DEFAULT_LINKS.wire_time_ms(
            r["inter_bytes"] - r["hidden_bytes"], r["intra_bytes"])
    prof = cm.lp_halo_wire_profile(cfg, 3, 1, R, step_codecs)
    assert sum(r["inter_bytes"] - r["hidden_bytes"] for r in recs) == \
        float(prof["inter"])
    assert sum(r["hidden_bytes"] for r in recs) == float(prof["hidden"])
    # the HLO contract is exposed + hidden: identical to the sync total
    sync_recs = attribute_denoise_steps(cfg, R, ["int8-residual"] * 4,
                                        [(1, 3)])
    assert sum(r["inter_bytes"] for r in recs) == \
        sum(r["inter_bytes"] for r in sync_recs)


def test_attribution_displaced_hides_nothing_across_dim_rotation():
    """With >1 usable dim the rotation flushes the stale carry every
    step (each step is first-of-run), so nothing is ever hidden — the
    rule that makes ``auto_plan`` drop displaced candidates there."""
    cfg = _ccfg(steps=4)    # (8, 8, 12): three usable dims at K=3
    assert len(usable_dims(cfg.latent_dims, cfg.patch_sizes, 3)) == 3
    recs = attribute_denoise_steps(
        cfg, R, ["displaced:int8-residual"] * 4, [(1, 3)])
    assert [r["hidden_bytes"] for r in recs] == [0.0] * 4
    prof = cm.lp_halo_wire_profile(cfg, 3, 1, R,
                                   ["displaced:int8-residual"] * 4)
    assert float(prof["hidden"]) == 0.0


def test_reconcile_counts_unattributed_steps_and_trace_fails():
    """Satellite regression: a measured run whose steps have no
    attribution record (or no priced prediction) must surface a nonzero
    ``unattributed_steps`` — and a trace carrying such a reconciliation
    row must FAIL validation, never read as free wire time."""
    from repro.obs import reconcile_segments
    from repro.policy.autotune import DEFAULT_LINKS

    cfg = _ccfg(steps=4)
    recs = attribute_denoise_steps(cfg, R, ["int8"] * 2, [(1, 3)],
                                   links=DEFAULT_LINKS)
    measured = [
        {"start": 1, "stop": 2, "wall_s": 0.2, "codec": "int8"},
        {"start": 3, "stop": 4, "wall_s": 0.2, "codec": "int8"},
    ]
    rows = reconcile_segments(recs, measured)
    assert rows[0]["unattributed_steps"] == 0
    assert rows[0]["measured_over_pred"] > 0
    assert rows[1]["unattributed_steps"] == 2    # steps 3-4: no records
    assert "measured_over_pred" not in rows[1]   # never ratio'd vs a hole
    # records lacking pred_wire_time_ms (no links) count as holes too
    unpriced = attribute_denoise_steps(cfg, R, ["int8"] * 4, [(1, 3)])
    rows2 = reconcile_segments(unpriced, measured)
    assert all(r["unattributed_steps"] == 2 for r in rows2)

    rec = FlightRecorder()
    rec.record_reconciliations([rows[0]])
    assert validate_trace(rec.trace.to_json()) == []  # clean row passes
    rec.record_reconciliations([rows[1]])
    errs = validate_trace(rec.trace.to_json())
    assert errs and any("unattributed_steps=2" in e for e in errs)
    assert any("wire.reconcile" in e for e in errs)


def test_record_wire_steps_carries_hidden_bytes():
    """``hidden_bytes`` rides the wire.step instants and the by-tier
    counter sample as an attribution of inter bytes — the collective
    byte counters themselves stay HLO-exact (unchanged)."""
    cfg = _ccfg(dims=(8, 2, 2), steps=3)
    rec = FlightRecorder()
    recs = attribute_denoise_steps(cfg, R, ["displaced:int8-residual"] * 3,
                                   [(1, 3)], links=rec.links)
    rec.record_wire_steps(recs)
    steps = [e for e in rec.trace.events if e["name"] == "wire.step"]
    assert [e["args"]["hidden_bytes"] for e in steps] == \
        [r["hidden_bytes"] for r in recs]
    counter = [e for e in rec.trace.events
               if e["name"] == "wire.bytes_by_tier"][0]
    assert counter["args"]["hidden"] == sum(r["hidden_bytes"]
                                            for r in recs) > 0
    # counters (the HLO-exactness gate) bill the TOTAL inter payload
    total = sum(rec.metrics.counter_value(obsm.WIRE_BYTES, tier="inter",
                                          collective=c)
                for c in ("all-gather", "collective-permute"))
    assert total == sum(r["inter_bytes"] for r in recs)
    assert validate_trace(rec.trace.to_json()) == []


def test_tiered_collectives_unifies_dryrun_schema():
    """dryrun's ``collectives_by_group`` -> the wire-schema records,
    keyed by the same tier vocabulary the derived attribution uses."""
    rows = tiered_collectives(
        {"all-gather[3]": 600.0, "collective-permute[3]": 400.0,
         "all-gather[2]": 1000.0, "all-reduce": 8.0}, M=3, T=2)
    by = {(r["collective"], r["group_size"]): r for r in rows}
    assert by[("all-gather", 3)]["tier"] == "inter"
    assert by[("collective-permute", 3)]["tier"] == "inter"
    assert by[("all-gather", 2)]["tier"] == "intra"
    assert by[("all-reduce", 3)]["tier"] == "inter"  # ungrouped -> M
    assert tier_for_group_size(4, 4, 4) == "ambiguous"
    assert tier_for_group_size(5, 3, 2) == "unknown"


# ------------------------------------------------------ FlightRecorder
def test_flight_recorder_disabled_planes_noop():
    rec = FlightRecorder(trace=False, metrics=False)
    with rec.span("x"):
        pass
    with rec.span("y", cat="denoise"):
        pass
    rec.instant("z")
    rec.inc(obsm.REQUESTS)
    rec.gauge(obsm.QUEUE_DEPTH, 1)
    rec.observe(obsm.STEP_LATENCY_S, 0.1)
    rec.record_snapshot(1)
    rec.record_resume(1)
    assert rec.trace is None and rec.metrics is None


def test_flight_recorder_derives_step_samples_from_fused_runs():
    rec = FlightRecorder()
    rec.record_run(1, 3, wall_s=0.3, dim=1, codec="int8")
    steps = rec.metrics.hist_values(obsm.STEP_LATENCY_S)
    assert len(steps) == 3
    assert all(abs(s - 0.1) < 1e-12 for s in steps)
    assert rec.metrics.hist_values(obsm.RUN_WALL_S) == [0.3]
    assert rec.measured_runs[0]["start"] == 1


def test_flight_recorder_wire_steps_feed_counters_and_trace():
    rec = FlightRecorder()
    cfg = _ccfg(steps=3)
    recs = attribute_denoise_steps(cfg, R, ["int8"] * 3, [(1, 3)],
                                   links=rec.links)
    rec.record_wire_steps(recs)
    want_inter = sum(r["inter_bytes"] for r in recs)
    assert rec.metrics.counter_value(
        obsm.WIRE_BYTES, tier="inter", collective="all-gather") + \
        rec.metrics.counter_value(
            obsm.WIRE_BYTES, tier="inter",
            collective="collective-permute") == want_inter
    names = [e["name"] for e in rec.trace.events]
    assert names.count("wire.step") == 3
    assert "wire.bytes_by_tier" in names
    assert validate_trace(rec.trace.to_json()) == []


def test_plan_recording_via_resolve_cli_schedule():
    """The autotuner feeds the recorder its chosen plan + ranked
    candidate field; explicit schedules record without candidates."""
    from repro.diffusion.sampler import FlowMatchEuler
    from repro.policy import resolve_cli_schedule

    cfg = _ccfg(steps=6)
    rec = FlightRecorder()
    plan = resolve_cli_schedule("auto", cfg, 3, R, FlowMatchEuler(6), 6,
                                recorder=rec)
    assert plan is not None
    assert len(rec.plans) == 1
    row = rec.plans[0]
    assert row["context"] == "auto"
    assert row["schedule"] == plan.schedule.spec
    assert row["wire_bytes"] == float(plan.wire_bytes)
    cands = row["candidates"]
    assert cands and all(
        {"codec", "denoise_bytes", "floor_db"} <= set(c) for c in cands)
    assert rec.metrics.gauge_value(obsm.PLAN_WIRE_BYTES,
                                   context="auto") == plan.wire_bytes
    rec2 = FlightRecorder()
    resolve_cli_schedule("int8-residual@0.45,bf16", cfg, 3, R,
                         FlowMatchEuler(6), 6, recorder=rec2)
    assert rec2.plans[0]["context"] == "explicit"
    assert "candidates" not in rec2.plans[0]


# ----------------------------------------- engine end-to-end (1 device)
def test_engine_emits_exact_attribution_and_valid_trace():
    from repro import models
    from repro.configs import get_config
    from repro.models import dit, frontends
    from repro.serving.engine import LPServingEngine, VideoRequest

    cfg = get_config("wan21-dit-1.3b").reduced()
    model = models.build(cfg)
    params = model.init(jax.random.PRNGKey(0))

    def fwd(p, z, t, c, cfg_model):
        return dit.forward(p, z, t, c, cfg_model)

    rec = FlightRecorder()
    eng = LPServingEngine(fwd, params, cfg, num_partitions=2,
                          overlap_ratio=0.5, num_steps=3, max_batch=2,
                          lp_impl="halo", wire_codec="int8",
                          recorder=rec)
    shape = (4, 8, 12)
    for i in range(2):
        eng.submit(VideoRequest(
            request_id=i,
            context=frontends.text_context(jax.random.PRNGKey(i), 1, cfg),
            latent_shape=shape, seed=i))
    results = eng.run()
    assert len(results) == 2

    doc = rec.trace.to_json()
    assert validate_trace(doc) == []
    names = [e["name"] for e in doc["traceEvents"]]
    for required in ("request.enqueue", "batch.admit", "batch.denoise",
                     "denoise.run", "wire.step"):
        assert required in names, names

    # derived attribution == comm_model exactly, per step, per collective
    ccfg = cm.VDMCommConfig(
        latent_dims=shape, latent_channels=cfg.latent_channels,
        patch_sizes=cfg.patch_sizes, d_model=cfg.d_model,
        num_blocks=cfg.num_layers, num_steps=3)
    dims = usable_dims(shape, cfg.patch_sizes, 2)
    assert len(rec.wire_steps) == 3
    for r in rec.wire_steps:
        assert r["K"] == 2 and r["codec"] == "int8"
        assert r["dim"] == rotation_dim(r["step"], dims)
        want = cm.lp_halo_codec_step_collectives(ccfg, 2, 0.5, r["dim"],
                                                 codec="int8")
        assert r["inter"] == {k: float(v) for k, v in want.items()}
        assert r["intra"] == {}
        assert r["batch_size"] == 2

    m = rec.metrics
    assert m.counter_value(obsm.REQUESTS) == 2.0
    assert m.counter_value(obsm.BATCHES) == 1.0
    assert m.counter_value(obsm.COMPILES, epoch="0") > 0
    assert len(m.hist_values(obsm.STEP_LATENCY_S)) == 3
    assert m.hist_values(obsm.BATCH_WALL_S)
    total_wire = sum(
        row["value"] for row in m.snapshot()
        if row["name"] == obsm.WIRE_BYTES)
    assert total_wire == sum(r["inter_bytes"] + r["intra_bytes"]
                             for r in rec.wire_steps)
    # reconciliation rows: every measured run got a prediction
    assert rec.reconciliations
    for row in rec.reconciliations:
        assert row["measured_wall_ms"] > 0
        assert row["pred_wire_time_ms"] >= 0


# ---------------------------------------------------- launch CLI (fast)
ENV = {"PYTHONPATH": "src", "PATH": "/usr/bin:/bin", "HOME": "/root",
       "JAX_PLATFORMS": "cpu"}


def test_dryrun_trace_out_is_schema_valid(tmp_path):
    """Tier-1 CI gate: ``dryrun --trace-out`` must produce schema-valid
    trace JSON (the fast skip-rule cell — no compile)."""
    trace = tmp_path / "trace.json"
    metrics = tmp_path / "metrics.jsonl"
    res = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun",
         "--arch", "granite-3-2b", "--shape", "long_500k",
         "--trace-out", str(trace), "--metrics-out", str(metrics)],
        capture_output=True, text=True, cwd="/root/repo", env=ENV,
        timeout=420)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "SKIP" in res.stdout
    doc = json.load(open(trace))
    assert validate_trace(doc) == []
    assert doc["otherData"]["schema"] == TRACE_SCHEMA
    events = {e["name"] for e in doc["traceEvents"]}
    assert "dryrun.skip" in events
    assert metrics.exists()


# ----------------------------------- fault-drill attribution (multi-dev)
_DRILL_SCRIPT = r"""
import os, json
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, numpy as np
from repro import models
from repro.configs import get_config
from repro.launch.mesh import make_hybrid_mesh
from repro.models import dit, frontends
from repro.obs import FlightRecorder
from repro.serving.engine import LPServingEngine, VideoRequest

M, T, STEPS = 3, 2, 4
SHAPE = (8, 8, 12)
cfg = get_config("wan21-dit-1.3b").reduced()
model = models.build(cfg)
params = model.init(jax.random.PRNGKey(0))
def fwd(p, z, t, c, cfg_model):
    return dit.forward(p, z, t, c, cfg_model)

rec = FlightRecorder()
eng = LPServingEngine(
    fwd, params, cfg, num_partitions=M, overlap_ratio=0.5,
    num_steps=STEPS, max_batch=1, wire_codec="int8-residual",
    lp_impl="halo_hybrid", mesh=make_hybrid_mesh(M, T), elastic=True,
    inject_fault="dead:1@3", recorder=rec)
eng.submit(VideoRequest(
    request_id=0,
    context=frontends.text_context(jax.random.PRNGKey(1), 1, cfg),
    latent_shape=SHAPE, seed=0))
res = eng.run()[0]
rec.write_trace(os.environ["DRILL_TRACE"])
out = {
    "wire_steps": rec.wire_steps,
    "geometry": eng._geom_events,
    "evictions": eng.evictions,
    "K": eng.K,
    "tp": eng.tp,
    "wire_shard": eng.wire_shard,
    "restarts": res.restarts,
    "overlap": eng.r,
    "resumes": rec.metrics.counter_value("snapshot.resumes"),
    "eviction_count": rec.metrics.counter_value(
        "serve.evictions", reason="dead"),
    "faults_injected": rec.metrics.counter_value(
        "faults.injected", kind="dead"),
}
print("JSON:" + json.dumps(out))
"""


@pytest.mark.slow
def test_fault_drill_attribution_exact_across_mesh_shrink(tmp_path):
    """The acceptance drill: dead:1@3 on a (3, 2) mesh.  The recorder's
    per-step byte attribution must match ``comm_model`` exactly per
    tier both BEFORE the eviction (K=3) and AFTER the shrink (K=2),
    and the trace must carry the fault/evict/restart story."""
    trace_path = tmp_path / "drill_trace.json"
    res = subprocess.run(
        [sys.executable, "-c", _DRILL_SCRIPT],
        capture_output=True, text=True, cwd="/root/repo",
        env={**ENV, "DRILL_TRACE": str(trace_path)}, timeout=560)
    rec = None
    for line in res.stdout.splitlines():
        if line.startswith("JSON:"):
            rec = json.loads(line[len("JSON:"):])
    assert rec is not None, res.stdout + res.stderr[-2000:]

    M, T, steps, shape = 3, 2, 4, (8, 8, 12)
    assert rec["evictions"] == 1 and rec["K"] == M - 1
    assert rec["restarts"] >= 1
    assert rec["wire_shard"] is True and rec["tp"] == T

    geometry = [tuple(g) for g in rec["geometry"]]
    assert geometry[0] == (1, M)
    assert len(geometry) == 2 and geometry[1][1] == M - 1
    evict_step = geometry[1][0]

    from repro.configs import get_config

    mcfg = get_config("wan21-dit-1.3b").reduced()
    ccfg = cm.VDMCommConfig(
        latent_dims=shape, latent_channels=mcfg.latent_channels,
        patch_sizes=mcfg.patch_sizes, d_model=mcfg.d_model,
        num_blocks=mcfg.num_layers, num_steps=steps)

    ws = rec["wire_steps"]
    assert [w["step"] for w in ws] == list(range(1, steps + 1))
    saw_pre = saw_post = False
    for w in ws:
        K = M if w["step"] < evict_step else M - 1
        assert w["K"] == K, (w, evict_step)
        dims = usable_dims(shape, mcfg.patch_sizes, K)
        dim = rotation_dim(w["step"], dims)
        assert w["dim"] == dim
        want = cm.lp_halo_sharded_step_collectives(
            ccfg, K, T, rec["overlap"], dim, codec="int8-residual")
        for tier in ("inter", "intra"):
            assert w[tier] == {k: float(v) for k, v in
                               want[tier].items()}, (w["step"], tier)
        saw_pre |= K == M
        saw_post |= K == M - 1
    assert saw_pre and saw_post  # exact on both sides of the shrink

    # trace tells the drill story
    doc = json.load(open(trace_path))
    assert validate_trace(doc) == []
    names = [e["name"] for e in doc["traceEvents"]]
    assert "fault.dead" in names
    assert "elastic.evict" in names
    assert "batch.restart" in names
    assert "snapshot.resume" in names
    evict = [e for e in doc["traceEvents"]
             if e["name"] == "elastic.evict"][0]
    assert evict["args"]["step"] == evict_step
    assert evict["args"]["reason"] == "dead"
    assert evict["args"]["new_mesh_shape"] == [M - 1, T]
    assert rec["eviction_count"] == 1.0
    assert rec["faults_injected"] == 1.0
    assert rec["resumes"] >= 1.0
