"""Serving driver CLI — LP video generation service.

  PYTHONPATH=src python -m repro.launch.serve --requests 4 --steps 6 \
      --partitions 2 --overlap 0.5 [--latent 5x60x104] [--max-batch 4] \
      [--lp-impl auto] [--wire-codec int8-residual] [--reduced]

``--arch`` names the video denoiser by configuration
(``repro.models.FORWARDS``): WAN2.1-1.3B (default; 30 blocks, d 1536,
bf16) or HunyuanVideo's MMDiT (``hunyuanvideo-dit``), at its published
width with random weights from a fixed seed; ``--reduced`` swaps in the
small f32 stand-in for CPU smoke runs.  ``--latent`` is the
latent each request denoises (T x H x W; 5x60x104 is 17 frames at 480p).

Step policy (docs/step_policy.md): ``--codec-schedule auto`` lets the
cost-model autotuner pick (engine, sigma-scheduled codec) minimizing
analytic wire bytes subject to ``--psnr-floor`` (default 40 dB);
``--codec-schedule 'int8-residual@0.45,bf16'`` pins an explicit schedule.

Hierarchy-aware wire (docs/wire_sharding.md): on a ``--mesh MxT`` hybrid
mesh, ``--wire-shard`` / ``--no-wire-shard`` pins the tp-sharded halo
wire (default: on; the autotuner's two-tier link model decides when
``--codec-schedule`` is set) and ``--eager-sends`` / ``--no-eager-sends``
controls ppermute/compute overlap (default: on for hybrid meshes).
"""
from __future__ import annotations

import argparse
from typing import List, Tuple

import jax

from repro import models
from repro.comm.codecs import CODEC_NAMES
from repro.configs import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models import frontends
from repro.serving.engine import LPServingEngine, VideoRequest


def parse_latent(spec: str) -> Tuple[int, int, int]:
    """``"5x60x104"`` -> ``(5, 60, 104)`` (latent T x H x W)."""
    try:
        dims = tuple(int(d) for d in spec.lower().split("x"))
    except ValueError:
        dims = ()
    if len(dims) != 3 or min(dims) <= 0:
        raise argparse.ArgumentTypeError(
            f"--latent wants TxHxW (e.g. 5x60x104), got {spec!r}")
    return dims


def load_model(reduced: bool = False, mesh=None,
               arch: str = "wan21-dit-1.3b"):
    """``(cfg, params)``: the denoiser ``arch`` at published width (or
    its reduced stand-in), random weights from seed 0.  Initialized in
    one compiled program, straight onto ``mesh`` (replicated) when one
    is given, so the parameters never sit on one device before being
    copied out."""
    cfg = get_config(arch)
    if reduced:
        cfg = cfg.reduced()
    model = models.build(cfg)
    shardings = None
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec

        shardings = NamedSharding(mesh, PartitionSpec())
    params = jax.jit(model.init, out_shardings=shardings)(
        jax.random.PRNGKey(0))
    return cfg, params


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="wan21-dit-1.3b",
                    choices=sorted(models.FORWARDS),
                    help="the video denoiser, by configuration name")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--steps", type=int, default=6)
    ap.add_argument("--partitions", type=int, default=2)
    ap.add_argument("--overlap", type=float, default=0.5)
    ap.add_argument("--latent", type=parse_latent, default=(5, 60, 104),
                    metavar="TxHxW",
                    help="request latent (default 5x60x104: 17 frames "
                         "at 480p)")
    ap.add_argument("--max-batch", type=int, default=4,
                    help="requests of one shape denoised together; the "
                         "one-chip engine runs K x 2 (CFG) rows per "
                         "request")
    ap.add_argument("--reduced", action="store_true",
                    help="2-block, 128-wide f32 stand-in of the model "
                         "(CPU smoke runs)")
    ap.add_argument("--lp-impl", default="auto",
                    choices=["auto", "uniform", "shard_map", "halo",
                             "halo_hybrid"],
                    help="LP engine; auto = psum math at K=2, halo beyond "
                         "(hybrid halo when the mesh has a tp axis)")
    ap.add_argument("--wire-codec", default=None, choices=list(CODEC_NAMES),
                    help="compress LP halo wire payloads (fixed codec)")
    ap.add_argument("--codec-schedule", default=None,
                    help="sigma-scheduled codecs: 'auto' (cost-model "
                         "autotuner) or a spec like "
                         "'int8-residual@0.45,bf16'; excludes "
                         "--wire-codec")
    ap.add_argument("--psnr-floor", type=float, default=None,
                    help="PSNR floor (dB) the codec schedule must meet "
                         "against the conformance envelope (auto "
                         "default: 40)")
    ap.add_argument("--mesh", default=None,
                    help="MxT hybrid mesh (LP groups x intra-group TP), "
                         "e.g. 4x2; M must equal --partitions.  Needs "
                         "M*T local devices")
    ap.add_argument("--wire-shard", default=None,
                    action=argparse.BooleanOptionalAction,
                    help="shard every halo payload over the tp axis "
                         "(1/T chunks across the inter-group links + an "
                         "intra-group reassembly gather; bit-identical "
                         "values).  Default: on for hybrid meshes — the "
                         "autotuner's two-tier link cost model decides "
                         "when --codec-schedule is set")
    ap.add_argument("--eager-sends", default=None,
                    action=argparse.BooleanOptionalAction,
                    help="issue all halo ppermute rounds before any "
                         "accumulation so they can overlap the DiT "
                         "tail.  Default: on for hybrid meshes")
    ap.add_argument("--elastic", action="store_true",
                    help="mid-request re-planning: the per-step hook "
                         "evicts dead/straggler LP groups through the "
                         "health monitor (disables scan fusion)")
    ap.add_argument("--inject-fault", default=None,
                    help="scripted serving-fault drill, e.g. "
                         "'dead:1@4,slow:0x2,corrupt@2' "
                         "(docs/fault_tolerance.md); dead/slow need "
                         "--elastic to recover")
    ap.add_argument("--wire-nan-guard", default=True,
                    action=argparse.BooleanOptionalAction,
                    help="absorb NaN/Inf wire payloads by falling back "
                         "to the rank-local stale slab (bit-identical "
                         "when every message is finite)")
    ap.add_argument("--trace-out", default=None,
                    help="write a Perfetto/Chrome-trace JSON of the "
                         "request lifecycle here (docs/observability.md)")
    ap.add_argument("--metrics-out", default=None,
                    help="write a metrics snapshot here (.prom/.txt -> "
                         "Prometheus text, else JSONL)")
    return ap


def build_engine(args, recorder=None):
    """``(cfg, engine)`` for parsed CLI ``args``: the model, the mesh
    (``--mesh``) and the LP serving engine, as ``main`` serves them."""
    if args.codec_schedule and args.wire_codec:
        raise SystemExit("--codec-schedule and --wire-codec are exclusive")
    mesh = None
    if args.mesh:
        from repro.launch.mesh import make_hybrid_mesh, parse_mesh

        m, t = parse_mesh(args.mesh)
        if m != args.partitions:
            raise SystemExit(
                f"--mesh {args.mesh}: LP axis {m} != --partitions "
                f"{args.partitions}")
        mesh = make_hybrid_mesh(m, t)
    cfg, params = load_model(args.reduced, mesh, args.arch)
    engine = LPServingEngine(models.FORWARDS[args.arch], params, cfg,
                             num_partitions=args.partitions,
                             overlap_ratio=args.overlap,
                             num_steps=args.steps,
                             max_batch=args.max_batch,
                             lp_impl=args.lp_impl,
                             wire_codec=args.wire_codec,
                             codec_schedule=args.codec_schedule,
                             psnr_floor=args.psnr_floor,
                             mesh=mesh,
                             wire_shard=args.wire_shard,
                             eager_sends=args.eager_sends,
                             elastic=args.elastic,
                             inject_fault=args.inject_fault,
                             wire_nan_guard=args.wire_nan_guard,
                             recorder=recorder)
    return cfg, engine


def make_requests(args, cfg) -> List[VideoRequest]:
    """The ``--requests`` requests ``main`` submits, seeded by index."""
    return [
        VideoRequest(
            request_id=i,
            context=frontends.conditioning(jax.random.PRNGKey(i), 1, cfg),
            latent_shape=tuple(args.latent),
            seed=i,
        )
        for i in range(args.requests)
    ]


def main(argv=None):
    args = build_parser().parse_args(argv)
    enable_compile_cache()
    recorder = None
    if args.trace_out or args.metrics_out:
        from repro.obs import FlightRecorder

        recorder = FlightRecorder()
    cfg, engine = build_engine(args, recorder)
    print(f"config: {cfg.name} blocks={cfg.num_layers} d={cfg.d_model} "
          f"heads={cfg.num_heads} ffn={cfg.d_ff} dtype={cfg.dtype}")
    print(f"engine: lp_impl={engine.lp_impl} codec={engine.codec.name} "
          f"tp={engine.tp} wire_shard={engine.wire_shard} "
          f"eager_sends={engine.eager_sends}")
    if engine.plan is not None:
        print(f"step policy: {engine.plan.describe()}")
    if engine._fault_plan is not None:
        print(f"fault drill: {engine._fault_plan.describe()} "
              f"(elastic={engine.elastic}, "
              f"nan_guard={engine.wire_nan_guard})")
    for req in make_requests(args, cfg):
        engine.submit(req)
    results = engine.run()
    for r in sorted(results, key=lambda x: x.request_id):
        resumed = f" resumed_from={r.resumed_from_step}" if r.restarts else ""
        print(f"request {r.request_id}: latent {tuple(r.latent.shape)} "
              f"steps={r.num_steps} wait={r.queue_wait_s:.2f}s "
              f"e2e={r.e2e_s:.2f}s batch_wall={r.batch_wall_s:.1f}s "
              f"batch={r.batch_size} restarts={r.restarts}{resumed}")
    if engine.evictions:
        print(f"elastic: evictions={engine.evictions} K={engine.K} "
              f"steps_lost={engine.last_steps_lost}")
    if recorder is not None:
        if args.trace_out:
            recorder.write_trace(args.trace_out)
            print(f"trace: {args.trace_out} "
                  f"({len(recorder.trace.events)} events)")
        if args.metrics_out:
            recorder.write_metrics(args.metrics_out)
            print(f"metrics: {args.metrics_out}")
        m = recorder.metrics
        if m is not None:
            from repro.obs import metrics as obsm

            steps = m.hist_values(obsm.STEP_LATENCY_S)
            if steps:
                import numpy as np

                p50, p99 = np.percentile(steps, [50, 99])
                print(f"obs: step_latency p50={p50 * 1e3:.1f}ms "
                      f"p99={p99 * 1e3:.1f}ms over {len(steps)} steps")
        for rec in recorder.reconciliations:
            print(f"obs: run[{rec['start']}-{rec['stop']}] "
                  f"codec={rec['codec']} "
                  f"pred_wire={rec['pred_wire_time_ms']:.2f}ms "
                  f"measured={rec['measured_wall_ms']:.1f}ms")


if __name__ == "__main__":
    main()
