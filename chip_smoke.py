"""On-chip smoke test: the LP serving path at WAN2.1-1.3B's published width.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # four chips of one host

One process, no subprocess.  It refuses to run unless JAX's first device
is a TPU, builds the serving engine the way ``repro.launch.serve`` does
(30 blocks, d 1536, 12 heads, FFN 8960, bf16, random weights from seed 0)
and drives it through ``submit`` / ``run``:

* one chip: ``--partitions 2``, latent 5x60x104 (17 frames at 480p),
  ``max_batch=1``, three requests of three steps, so the T, H and W
  rotation windows all run.  Then, on the same device, the Pallas stitch
  kernel (``kernels/latent_blend``) against the jnp blend on one set of
  real window predictions.
* ``--chips 4``: ``--mesh 4 --partitions 4`` (halo engine) on 81 frames
  (21x60x104), one request of three steps, against the same request
  through ``--lp-impl shard_map`` on the same mesh: every step of the
  halo engine, from the shard_map engine's input, at the repo's bf16
  PSNR floor.  Every device must hold the parameters.

Earlier lines report what ran; the last line is one JSON object,
``{"ok": true, "device": {...}}``.  Any failure exits non-zero without it.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

STEPS = 3
# published WAN2.1-1.3B widths the engine must run at
PUBLISHED = dict(num_layers=30, d_model=1536, num_heads=12, d_ff=8960,
                 dtype="bfloat16")
# The kernel and the jnp blend do the same f32 multiply-adds in the same
# partition order; only the lowering of the final divide by Z(x) (Mosaic
# vs XLA) may differ, by a few f32 ulps.  1e-5 of the output's magnitude
# is ~80 ulps: far above that, far below bf16 model noise (~4e-3).
BLEND_RTOL = 1e-5


class SmokeFailure(RuntimeError):
    pass


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def log(msg):
    print(msg, flush=True)


def serve_args(extra):
    from repro.launch import serve

    return serve.build_parser().parse_args(
        ["--steps", str(STEPS), "--max-batch", "1"] + extra)


def build(argv):
    """Engine + requests exactly as ``repro.launch.serve`` builds them."""
    from repro.launch import serve

    args = serve_args(argv)
    cfg, engine = serve.build_engine(args)
    for k, v in PUBLISHED.items():
        check(getattr(cfg, k) == v, f"config {k}={getattr(cfg, k)} != {v}")
    return args, cfg, engine, serve.make_requests(args, cfg)


def serve_one(engine, req):
    """Submit one request, run it to completion, block on the latent."""
    import jax
    import numpy as np

    t0 = time.perf_counter()
    engine.submit(req)
    (res,) = engine.run()
    latent = jax.block_until_ready(res.latent)
    wall = time.perf_counter() - t0
    finite = bool(np.isfinite(np.asarray(latent, np.float32)).all())
    log(f"request {req.request_id}: latent {tuple(latent.shape)} "
        f"{latent.dtype} wall={wall:.3f}s finite={finite}")
    check(finite, f"request {req.request_id} returned non-finite values")
    return latent, wall


def peak_bytes(dev):
    stats = dev.memory_stats() or {}
    check("peak_bytes_in_use" in stats,
          f"{dev} reports no peak_bytes_in_use")
    return int(stats["peak_bytes_in_use"])


def log_memory_stats(dev):
    log(f"device {dev.id} memory_stats: "
        f"{json.dumps(dev.memory_stats(), sort_keys=True)}")


def param_bytes(params):
    import jax

    return sum(x.nbytes for x in jax.tree.leaves(params))


def one_chip(dev):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import plan_uniform
    from repro.core.spmd import blend_windows, stack_windows
    from repro.diffusion.pipeline import make_guided_step_denoiser
    from repro.diffusion.sampler import FlowMatchEuler
    from repro.models import dit

    args, cfg, engine, reqs = build(
        ["--partitions", "2", "--latent", "5x60x104", "--requests", "3"])
    log(f"config: {cfg.name} blocks={cfg.num_layers} d={cfg.d_model} "
        f"heads={cfg.num_heads} ffn={cfg.d_ff} dtype={cfg.dtype} "
        f"params={param_bytes(engine.params) / 1e9:.3f}GB")
    log(f"latent: {tuple(args.latent)} x C={cfg.latent_channels} "
        f"(17 frames at 480p); engine lp_impl={engine.lp_impl} "
        f"K={engine.K} r={engine.r} max_batch={engine.max_batch} "
        f"steps={STEPS}")
    check(engine.mesh is None and engine.K == 2, "expected the one-chip "
          "K=2 engine")

    walls = []
    for req in reqs:
        latent, wall = serve_one(engine, req)
        walls.append(wall)
    comp = engine._compiler
    check(comp.compiles == 3, f"expected 3 compiled steps (T, H, W), got "
          f"{comp.compiles}")
    warm = sorted(walls[1:])[len(walls[1:]) // 2]
    log(f"compile_s={walls[0] - warm:.3f} (first request wall minus a warm "
        f"one; {comp.compiles} step programs) "
        f"warm_step_wall_s={warm / STEPS:.4f} "
        f"(warm request {warm:.3f}s / {STEPS} steps)")
    log(f"peak_bytes_in_use={peak_bytes(dev)} after serving")

    # the stitch kernel vs the jnp blend on real predictions: the H
    # windows (58 of 60 rows, starts 0 and 2) of the last served latent
    H = args.latent[1]
    plan = plan_uniform(H, cfg.patch_sizes[1], engine.K, engine.r, 1)
    guided = make_guided_step_denoiser(dit.forward, cfg)
    req = reqs[0]
    t = np.float32(FlowMatchEuler(STEPS).timestep(2))

    @jax.jit
    def window_preds(z, params, ctx):
        windows = stack_windows(z, plan, 2)
        return jax.vmap(lambda w: guided(
            w, t, params, ctx, jnp.zeros_like(ctx), req.guidance))(windows)

    @jax.jit
    def blend_diff(preds):
        fused = blend_windows(preds, plan, 2, use_kernel=True)
        plain = blend_windows(preds, plan, 2, use_kernel=False)
        diff = jnp.abs(fused.astype(jnp.float32) - plain.astype(jnp.float32))
        return jnp.max(diff), jnp.max(jnp.abs(plain.astype(jnp.float32)))

    preds = window_preds(latent, engine.params, req.context)
    compiled = blend_diff.lower(preds).compile()
    check("tpu_custom_call" in compiled.as_text(),
          "the blend program holds no Pallas kernel")
    max_diff, scale = (float(x) for x in compiled(preds))
    tol = BLEND_RTOL * max(scale, 1.0)
    log(f"latent_blend vs jnp blend_windows: preds {tuple(preds.shape)} "
        f"{preds.dtype}, window {plan.window} starts {plan.starts}, "
        f"max_abs_diff={max_diff:.3e} tol={tol:.3e} (max|out|={scale:.3f}; "
        f"same f32 multiply-adds in the same order, only the divide's "
        f"lowering may differ by a few ulps)")
    check(max_diff <= tol, f"kernel disagrees with jnp: {max_diff} > {tol}")
    log(f"peak_bytes_in_use={peak_bytes(dev)} at end")
    log_memory_stats(dev)


def psnr_db(x, ref):
    """PSNR of ``x`` against ``ref`` (peak = max |ref|), as the repo's
    conformance suite measures it."""
    import numpy as np

    mse = float(np.mean((x - ref) ** 2))
    return 10.0 * np.log10(float(np.abs(ref).max()) ** 2 / max(mse, 1e-30))


def lp_step(engine, cfg, req, z, i):
    """Denoise step ``i`` of ``req`` from the host latent ``z`` on the
    engine's own compiled steps.  Step 1 takes its input off the mesh and
    later steps mesh-replicated, as the served request does, so every
    call reuses the programs the request compiled."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec

    from repro.core.schedule import rotation_dim, usable_dims
    from repro.diffusion.sampler import FlowMatchEuler

    sampler = FlowMatchEuler(STEPS)
    dims = usable_dims(req.latent_shape, cfg.patch_sizes, engine.K)
    sc, t = sampler.step_scalars(i), np.float32(sampler.timestep(i))
    extras = (engine._step_params(), req.context,
              jnp.zeros_like(req.context), jnp.float32(req.guidance))
    z = (jnp.asarray(z) if i == 1 else
         jax.device_put(z, NamedSharding(engine.mesh, PartitionSpec())))
    fn = engine._compiler.step_fn(rotation_dim(i, dims), z, 1, sc, extras)
    return np.asarray(fn(z, t, sc, extras), np.float32)


def four_chips(devs):
    """Serve one 81-frame request through the shard_map engine and then
    the halo engine.  A random-init bf16 DiT amplifies f32 rounding over
    steps (a 1-ulp nudge of the initial noise alone costs ~10 dB over
    three steps), so the engines are held to the bf16 floor step by step
    from the same input; the end-to-end PSNR is reported beside that
    nudge's."""
    import jax
    import numpy as np

    from repro.policy.envelope import PSNR_ENVELOPE_DB

    floor = PSNR_ENVELOPE_DB["bf16"]
    common = ["--mesh", "4", "--partitions", "4", "--latent", "21x60x104",
              "--requests", "1"]
    traj = served = None
    for impl in ("shard_map", "halo"):
        args, cfg, engine, reqs = build(common + ["--lp-impl", impl])
        req = reqs[0]
        pbytes = param_bytes(engine.params)
        log(f"config: {cfg.name} blocks={cfg.num_layers} d={cfg.d_model} "
            f"heads={cfg.num_heads} ffn={cfg.d_ff} dtype={cfg.dtype} "
            f"params={pbytes / 1e9:.3f}GB")
        log(f"latent: {tuple(args.latent)} x C={cfg.latent_channels} "
            f"(81 frames at 480p); engine lp_impl={engine.lp_impl} "
            f"K={engine.K} mesh={dict(engine.mesh.shape)} steps={STEPS}")
        check(engine.lp_impl == impl, f"asked for {impl}, engine runs "
              f"{engine.lp_impl}")
        latent, wall = serve_one(engine, req)
        latent = np.asarray(latent, np.float32)
        mesh_devs = set(engine.mesh.devices.flat)
        for x in jax.tree.leaves(engine.params):
            check(x.sharding.device_set == mesh_devs and
                  x.sharding.is_fully_replicated,
                  f"parameters not replicated over the mesh: {x.sharding}")
        # the engine's initial noise, as LPServingEngine draws it
        z_T = np.asarray(jax.random.normal(
            jax.random.PRNGKey(req.seed),
            (1, *req.latent_shape, cfg.latent_channels)))
        if traj is None:
            traj = [z_T]
            for i in range(1, STEPS + 1):
                traj.append(lp_step(engine, cfg, req, traj[-1], i))
            check(np.array_equal(traj[-1], latent), "step-by-step replay "
                  "does not reproduce the served latent")
            nudged = np.nextafter(z_T, np.float32(np.inf))
            for i in range(1, STEPS + 1):
                nudged = lp_step(engine, cfg, req, nudged, i)
            log(f"shard_map replayed step by step = served latent; initial "
                f"noise nudged by 1 ulp -> psnr={psnr_db(nudged, latent):.2f}"
                f"dB after {STEPS} steps (the model's own sensitivity)")
            served = latent
        else:
            for i in range(1, STEPS + 1):
                step = lp_step(engine, cfg, req, traj[i - 1], i)
                p = psnr_db(step, traj[i])
                log(f"step {i}: halo vs shard_map from the same input "
                    f"psnr={p:.2f}dB floor={floor}dB (bf16 envelope, "
                    f"policy/envelope.py)")
                check(p >= floor, f"step {i}: halo vs shard_map {p:.2f} dB "
                      f"< {floor} dB")
            log(f"served {STEPS}-step latents, halo vs shard_map: "
                f"psnr={psnr_db(latent, served):.2f}dB")
        # drop this engine and its parameters before the next one
        del engine
        gc.collect()
    for d in devs[:4]:
        peak = peak_bytes(d)
        log(f"device {d.id}: peak_bytes_in_use={peak}")
        log_memory_stats(d)
        check(peak >= pbytes, f"device {d.id} peaked at {peak} B, below "
              f"the {pbytes} B of parameters")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1 (default): the one-chip engine; 4: LP across "
                         "the four chips of one host")
    args = ap.parse_args(argv)

    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {devs[0].platform}",
              file=sys.stderr)
        return 1
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX found {len(devs)}", file=sys.stderr)
        return 1
    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    entries = len(os.listdir(cache)) if cache and os.path.isdir(cache) else 0
    log(f"device: {devs[0].platform} {devs[0].device_kind} x{len(devs)} "
        f"(using {args.chips}); jax {jax.__version__}; compile cache "
        f"{cache} ({entries} entries at start)")
    if args.chips == 1:
        one_chip(devs[0])
    else:
        four_chips(devs)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
