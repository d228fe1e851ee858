import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The dry-run compiles against fake CPU devices by construction; pinning
# the platform (unless the caller overrides) skips jax's TPU runtime
# probe, which hangs for minutes on hosts with libtpu but no TPU.
os.environ.setdefault("JAX_PLATFORMS", "cpu")
"""Multi-pod dry-run: lower + compile every (architecture x input shape) on
the production meshes and extract memory/cost/collective evidence.

MUST be the first import in the process (jax locks the device count on
first init) — hence the XLA_FLAGS assignment above everything else.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch granite-3-2b \
      --shape train_4k [--multi-pod] [--out results.json]
  PYTHONPATH=src python -m repro.launch.dryrun --all

Per cell this produces:
  * compiled.memory_analysis()  -> bytes/device (proves it fits)
  * compiled.cost_analysis()    -> HLO FLOPs / bytes for §Roofline
  * collective bytes parsed from the compiled HLO (all-gather,
    all-reduce, reduce-scatter, all-to-all, collective-permute)
"""
import argparse
import dataclasses
import json
import sys
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro import models
from repro.configs import get_config, get_shape, skip_reason, cells
from repro.configs.base import ArchConfig, ParallelConfig, ShapeConfig
from repro.distributed.policy import (
    active_params,
    cache_head_or_dim,
    count_params,
    plan_parallel,
)
from repro.distributed.sharding import (
    batch_specs,
    cache_specs,
    param_specs,
)
from repro.launch.mesh import make_production_mesh
from repro.obs.clock import perf_s
from repro.serving.serve_step import make_decode_step, make_prefill_step
from repro.train.loop import make_train_step


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def input_specs(cfg: ArchConfig, shape: ShapeConfig) -> Dict[str, Any]:
    """ShapeDtypeStruct stand-ins for every model input of a cell —
    weak-type-correct, shardable, no device allocation."""
    B, S = shape.global_batch, shape.seq_len
    dt = jnp.dtype(cfg.dtype)
    if shape.kind == "train":
        out = {
            "tokens": _sds((B, S), jnp.int32),
            "labels": _sds((B, S), jnp.int32),
        }
        if cfg.family == "vlm":
            out["vision_embeds"] = _sds((B, cfg.num_vision_tokens, cfg.d_model), dt)
        if cfg.family == "audio":
            out["frames"] = _sds((B, cfg.encoder_seq, cfg.d_model), dt)
        return out
    if shape.kind == "prefill":
        out = {"tokens": _sds((B, S), jnp.int32)}
        if cfg.family == "vlm":
            out["vision_embeds"] = _sds((B, cfg.num_vision_tokens, cfg.d_model), dt)
        if cfg.family == "audio":
            out["frames"] = _sds((B, cfg.encoder_seq, cfg.d_model), dt)
        return out
    if shape.kind == "decode":
        out = {
            "token": _sds((B, 1), jnp.int32),
            "position": _sds((B,), jnp.int32),
        }
        if cfg.family == "audio":
            out["enc_states"] = _sds((B, cfg.encoder_seq, cfg.d_model), dt)
        return out
    if shape.kind == "vdm_generate":
        t_lat = (shape.num_frames - 1) // 4 + 1
        h_lat, w_lat = shape.height // 8, shape.width // 8
        return {
            "latent": _sds((B, t_lat, h_lat, w_lat, cfg.latent_channels), dt),
            "t": _sds((B,), jnp.float32),
            "context": _sds((2 * B, cfg.context_len, cfg.context_dim), jnp.float32),
        }
    raise ValueError(shape.kind)


def _collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Sum output-shape bytes of every collective op in compiled HLO."""
    from repro.analysis.hlo import collective_bytes

    return collective_bytes(hlo_text)


def _mem_summary(compiled) -> Dict[str, float]:
    ma = compiled.memory_analysis()
    if ma is None:
        return {}
    out = {}
    for k in (
        "argument_size_in_bytes",
        "output_size_in_bytes",
        "temp_size_in_bytes",
        "generated_code_size_in_bytes",
        "alias_size_in_bytes",
    ):
        v = getattr(ma, k, None)
        if v is not None:
            out[k] = float(v)
    return out


def _vdm_lp_step(cfg: ArchConfig, shape: ShapeConfig, mesh, parallel,
                 lp_impl: str = "gspmd", wire_codec: Optional[str] = None,
                 wire_shard: Optional[bool] = None,
                 eager_sends: Optional[bool] = None,
                 inject_fault: Optional[str] = None,
                 nan_guard: bool = False):
    """Build the jitted LP denoising step (one forward pass, dim=height)."""
    from repro.core import plan_uniform
    from repro.core.hybrid import lp_forward_halo_hybrid
    from repro.core.spmd import (
        lp_forward_gspmd,
        lp_forward_halo,
        lp_forward_shard_map,
        select_lp_impl,
    )
    from repro.diffusion.cfg import cfg_combine
    from repro.diffusion.sampler import FlowMatchEuler
    from repro.models import dit

    K = mesh.shape["data"]
    tp = mesh.shape.get("model", 1) if hasattr(mesh.shape, "get") \
        else dict(mesh.shape).get("model", 1)
    if lp_impl == "auto":
        # comm-model break-even rule; a wire codec implies the halo
        # family (that's where the codec layer lives)
        if wire_codec not in (None, "fp32"):
            lp_impl = "halo_hybrid" if tp > 1 else "halo"
        else:
            lp_impl = select_lp_impl(K, tp)
    if wire_codec not in (None, "fp32") and lp_impl == "shard_map":
        raise ValueError(
            f"--wire-codec {wire_codec} needs the halo family (or gspmd's "
            f"value-faithful blend); got --lp-impl {lp_impl} (the measured "
            "HLO would be uncoded)"
        )
    if (wire_codec and str(wire_codec).startswith("displaced")
            and lp_impl not in ("halo", "halo_hybrid")):
        raise ValueError(
            f"--wire-codec {wire_codec} is a displaced halo codec, which "
            "needs carry-resident slab state — only the halo family keeps "
            f"one (psum/gspmd have no per-direction slab carry); got "
            f"--lp-impl {lp_impl}"
        )
    # hierarchy-aware wire defaults: eager sends + tp-sharded wire on
    # for hybrid meshes (the tp axis is what gets sharded over)
    if eager_sends is None:
        eager_sends = tp > 1
    if wire_shard is None:
        wire_shard = tp > 1 and lp_impl in ("halo", "halo_hybrid")
    if wire_shard and tp <= 1:
        raise ValueError(
            "--wire-shard shards the halo wire over the tp axis; this "
            "mesh has no tp ('model') axis of size >= 2"
        )
    if wire_shard and lp_impl not in ("halo", "halo_hybrid"):
        raise ValueError(
            f"--wire-shard needs the halo family (the sharded wire lives "
            f"there), got --lp-impl {lp_impl}"
        )
    # --inject-fault: dead/slow are runtime drills (no effect on a
    # single-step lowering); corrupt@S swaps the wire codec for its
    # NaN-poisoning wrapper so the guarded decode HLO can be inspected.
    corrupt_wire = False
    if inject_fault:
        from repro.runtime.faults import parse_fault_plan

        fplan = parse_fault_plan(inject_fault)
        if fplan.corrupt:
            if lp_impl not in ("halo", "halo_hybrid") or \
                    wire_codec in (None, "fp32"):
                raise ValueError(
                    "--inject-fault corrupt@S poisons the compressed halo "
                    "wire; it needs a halo-family --lp-impl with a "
                    "--wire-codec"
                )
            corrupt_wire = True
            # a poisoned wire is only survivable with the decode guard
            nan_guard = True
    h_lat = shape.height // 8
    plan = plan_uniform(h_lat, cfg.patch_sizes[1], K, parallel.overlap_ratio, dim=1)
    sampler = FlowMatchEuler(shape.num_steps)
    guidance = 5.0
    model = models.build(cfg)

    def step(params, batch):
        z, t, ctx = batch["latent"], batch["t"], batch["context"]
        b = z.shape[0]

        kv_chunk = int(os.environ.get("REPRO_DIT_KV_CHUNK", "4096"))
        # CFG-pair-on-pod is a GSPMD-only constraint: inside the explicit
        # shard_map/halo engines every mesh axis is manual, so bare-P
        # constraints cannot apply there.
        cfg_on_pod = "pod" in mesh.axis_names and lp_impl == "gspmd"

        def denoise(window):
            z2 = jnp.concatenate([window, window], axis=0)
            t2 = jnp.concatenate([t, t], axis=0)
            if cfg_on_pod:
                # DESIGN.md §2: the CFG pair (cond, uncond) maps onto the
                # pod axis — each pod computes one branch; only the
                # latent-sized combine crosses the slow inter-pod links
                z2 = jax.lax.with_sharding_constraint(
                    z2, P("pod", *([None] * (z2.ndim - 1))))
            pred = dit.forward(params, z2, t2, ctx, cfg, kv_chunk=kv_chunk)
            if cfg_on_pod:
                pred = jax.lax.with_sharding_constraint(
                    pred, P("pod", *([None] * (pred.ndim - 1))))
            return cfg_combine(pred[:b], pred[b:], guidance)

        def denoise_tp_cfg(window):
            # hybrid Phi_m at T=2: the two tp ranks take one CFG branch
            # each — half the DiT batch per device, pair reunited by one
            # intra-group all-gather (core/hybrid.tp_cfg_combine).  The
            # split is 2-way only, so larger T falls back to the batched
            # CFG denoiser (see the dispatch below).
            from repro.core.hybrid import tp_cfg_branch, tp_cfg_combine

            br = tp_cfg_branch("model")
            my_ctx = jax.lax.dynamic_slice_in_dim(
                ctx, br * ctx.shape[0] // 2, ctx.shape[0] // 2, 0
            )
            pred = dit.forward(params, window, t, my_ctx, cfg,
                               kv_chunk=kv_chunk)
            return tp_cfg_combine(pred, "model", guidance)

        if lp_impl == "shard_map":
            pred = lp_forward_shard_map(denoise, z, plan, 2, mesh, "data")
        elif lp_impl in ("halo", "halo_hybrid"):
            hybrid = lp_impl == "halo_hybrid"
            den = denoise_tp_cfg if (hybrid and tp == 2) else denoise
            if hybrid:
                def fwd(fn, zz, pl, ax, st=None, **kw):
                    return lp_forward_halo_hybrid(
                        fn, zz, pl, ax, mesh, "data", "model",
                        codec_state=st, eager_sends=eager_sends,
                        wire_shard=wire_shard, nan_guard=nan_guard, **kw)
            else:
                def fwd(fn, zz, pl, ax, st=None, **kw):
                    return lp_forward_halo(
                        fn, zz, pl, ax, mesh, "data",
                        codec_state=st, eager_sends=eager_sends,
                        shard_axis="model" if (wire_shard and tp > 1)
                        else None, nan_guard=nan_guard, **kw)
            if wire_codec in (None, "fp32"):
                pred = fwd(den, z, plan, 2)
            else:
                from repro.comm import get_codec, init_halo_wire_state
                from repro.distributed.collectives import halo_spec

                codec = get_codec(wire_codec)
                if corrupt_wire:
                    from repro.runtime.faults import CorruptingCodec

                    codec = CorruptingCodec.wrap(codec)
                if codec.stateful:
                    # single-step lowering: a zero carry inside the step
                    # (collective shapes are state-independent, which is
                    # what the dry run measures)
                    st = init_halo_wire_state(
                        codec, halo_spec(plan),
                        tuple(s for i, s in enumerate(z.shape) if i != 2),
                    )
                    pred, _ = fwd(den, z, plan, 2, st=st, codec=codec)
                else:
                    pred = fwd(den, z, plan, 2, codec=codec)
        else:
            pred = lp_forward_gspmd(denoise, z, plan, 2, mesh, "data",
                                    codec=wire_codec)
        return sampler.step(z, pred, 1)

    return step


def lower_cell(
    arch: str,
    shape_name: str,
    multi_pod: bool = False,
    lp_impl: str = "gspmd",
    mesh=None,
    wire_codec: Optional[str] = None,
    wire_shard: Optional[bool] = None,
    eager_sends: Optional[bool] = None,
    inject_fault: Optional[str] = None,
    wire_nan_guard: bool = False,
    recorder=None,
) -> Dict[str, Any]:
    """Lower + compile one cell; return the §Dry-run record.

    ``recorder`` (``repro.obs.FlightRecorder``, optional) gets
    ``dryrun``-category spans around lower+compile plus the cell's
    ``wire_tiers`` bytes as ``wire.bytes`` counters — the same schema
    the serving engine's derived attribution uses, so measured HLO and
    ``comm_model`` replay are machine-diffable.
    """
    cfg = get_config(arch)
    shape = get_shape(shape_name)
    reason = skip_reason(arch, shape_name)
    rec: Dict[str, Any] = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "skipped": reason,
    }
    if reason:
        if recorder is not None:
            recorder.instant("dryrun.skip", cat="dryrun", arch=arch,
                             shape=shape_name, reason=reason)
        return rec

    t0 = perf_s()
    mesh = mesh or make_production_mesh(multi_pod=multi_pod)
    # a caller-supplied --mesh overrides the production tag
    rec["mesh"] = "x".join(str(v) for v in dict(mesh.shape).values())
    model = models.build(cfg)
    n_params = count_params(cfg, model)
    parallel = plan_parallel(cfg, shape, multi_pod=multi_pod, n_params=n_params)
    rec["n_params"] = n_params
    rec["n_active_params"] = active_params(cfg, n_params)
    rec["parallel"] = {
        "fsdp": parallel.fsdp_axis, "remat": parallel.remat,
        "microbatch": parallel.microbatch, "optimizer": parallel.optimizer,
    }

    params_shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    pspecs = param_specs(params_shapes, parallel)
    psh = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs,
                       is_leaf=lambda x: isinstance(x, P))
    params_sds = jax.tree.map(
        lambda l, s: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=s),
        params_shapes, psh,
    )
    ispecs = input_specs(cfg, shape)

    from repro.distributed import actctx

    dp_for_ctx = tuple(a for a in parallel.dp_axes if a in mesh.axis_names)
    if shape.kind == "vdm_generate":
        # LP parallelizes over windows (the stacked vmap axis), not batch;
        # batch-dim constraints inside the DiT would pin the CFG pair (2)
        # to the 16-way data axis and break the shard_map manual region
        dp_for_ctx = ()
    # sequence-parallel attention when head counts don't divide TP
    tp_size = mesh.shape[parallel.tp_axis]
    attn_seq = None
    # trigger on *query* heads only: kv-head replication is handled
    # acceptably by GSPMD, but non-divisible q heads partial-shard the
    # score contraction (llama3 train regressed 616->3555s collective
    # when kv=8 triggered seq-par; q=128 divides fine — §Perf B note)
    if shape.kind in ("train", "prefill") and cfg.num_heads and             cfg.num_heads % tp_size != 0:
        attn_seq = parallel.tp_axis
    if shape.kind == "vdm_generate" and lp_impl == "gspmd" and             cfg.num_heads % tp_size:
        attn_seq = parallel.tp_axis
    from contextlib import nullcontext

    def _span(name, **kw):
        if recorder is None:
            return nullcontext()
        return recorder.span(name, cat="dryrun", arch=arch,
                             shape=shape_name, **kw)

    with jax.set_mesh(mesh), actctx.batch_axes(dp_for_ctx, attn_seq=attn_seq), \
            _span("dryrun.cell", mesh=rec["mesh"]):
        if shape.kind == "train":
            train_step = make_train_step(model, parallel)
            opt_shapes = jax.eval_shape(train_step.opt_init, params_shapes)
            # optimizer states inherit their params' sharding
            def opt_spec(path_leaf):
                return None
            opt_specs = jax.tree.map(
                lambda l: NamedSharding(mesh, P(*([None] * l.ndim))), opt_shapes
            )
            # match param-shaped leaves to param specs: m/v/acc mirror params
            def mirror(tree):
                return jax.tree.map(
                    lambda l, s: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=s),
                    tree, psh,
                )
            if parallel.optimizer == "adamw":
                opt_sds = {
                    "m": mirror(opt_shapes["m"]),
                    "v": mirror(opt_shapes["v"]),
                    "step": jax.ShapeDtypeStruct(
                        (), jnp.int32, sharding=NamedSharding(mesh, P())
                    ),
                }
            else:
                opt_sds = jax.tree.map(
                    lambda l: jax.ShapeDtypeStruct(
                        l.shape, l.dtype,
                        sharding=NamedSharding(mesh, P(*([None] * l.ndim))),
                    ),
                    opt_shapes,
                )
            bspec = batch_specs("train", parallel, mesh, cfg)
            batch_sds = jax.tree.map(
                lambda l, s: jax.ShapeDtypeStruct(
                    l.shape, l.dtype, sharding=NamedSharding(mesh, s)
                ),
                ispecs, bspec,
            )
            step_sds = jax.ShapeDtypeStruct((), jnp.int32,
                                            sharding=NamedSharding(mesh, P()))
            fn = jax.jit(train_step, donate_argnums=(0, 1))
            lowered = fn.lower(params_sds, opt_sds, batch_sds, step_sds)
        elif shape.kind == "prefill":
            prefill = make_prefill_step(model, cfg)
            bspec = batch_specs("prefill", parallel, mesh, cfg)
            batch_sds = jax.tree.map(
                lambda l, s: jax.ShapeDtypeStruct(
                    l.shape, l.dtype, sharding=NamedSharding(mesh, s)
                ),
                ispecs, bspec,
            )
            fn = jax.jit(prefill)
            lowered = fn.lower(params_sds, batch_sds)
        elif shape.kind == "decode":
            decode = make_decode_step(model, cfg)
            cache_shapes = jax.eval_shape(
                lambda: model.init_cache(shape.global_batch, shape.seq_len)
            )
            kv_mode = cache_head_or_dim(cfg, mesh.shape[parallel.tp_axis])
            cache_parallel = parallel
            if shape.global_batch == 1:
                # batch=1 cannot shard over dp; the data axis instead
                # shards the cache *sequence* (sequence-parallel decode)
                cache_parallel = dataclasses.replace(
                    parallel, dp_axes=(),
                    seq_axis=parallel.seq_axis or "data",
                )
            cspecs = cache_specs(cfg, cache_parallel, mesh,
                                 seq_axis=cache_parallel.seq_axis,
                                 kv_mode=kv_mode)
            cache_sds = jax.tree.map(
                lambda l, s: jax.ShapeDtypeStruct(
                    l.shape, l.dtype, sharding=NamedSharding(mesh, s)
                ),
                cache_shapes, cspecs,
                is_leaf=lambda x: hasattr(x, "shape") or isinstance(x, P),
            )
            bspec = batch_specs("decode", parallel, mesh, cfg)
            if cfg.family == "audio":
                bspec["enc_states"] = P(None, None, None)
            dp = tuple(a for a in parallel.dp_axes if a in mesh.axis_names)
            if shape.global_batch == 1:
                # batch=1 can't shard over dp — replicate token/position
                bspec = jax.tree.map(
                    lambda s: P(*([None] * len(s))), bspec,
                    is_leaf=lambda x: isinstance(x, P),
                )
            batch_sds = jax.tree.map(
                lambda l, s: jax.ShapeDtypeStruct(
                    l.shape, l.dtype, sharding=NamedSharding(mesh, s)
                ),
                ispecs, bspec,
            )
            fn = jax.jit(decode, donate_argnums=(2,))
            lowered = fn.lower(params_sds, batch_sds, cache_sds)
        elif shape.kind == "vdm_generate":
            if inject_fault:
                from repro.runtime.faults import parse_fault_plan

                fplan = parse_fault_plan(inject_fault)
                if fplan is not None:
                    rec["fault_drill"] = fplan.describe()
                    rec["wire_nan_guard"] = bool(
                        wire_nan_guard or fplan.corrupt)
            step = _vdm_lp_step(cfg, shape, mesh, parallel, lp_impl,
                                wire_codec=wire_codec,
                                wire_shard=wire_shard,
                                eager_sends=eager_sends,
                                inject_fault=inject_fault,
                                nan_guard=wire_nan_guard)
            batch_sds = jax.tree.map(
                lambda l: jax.ShapeDtypeStruct(
                    l.shape, l.dtype, sharding=NamedSharding(mesh, P())
                ),
                ispecs,
            )
            fn = jax.jit(step)
            lowered = fn.lower(params_sds, batch_sds)
        else:
            raise ValueError(shape.kind)

        with _span("dryrun.compile", kind=shape.kind):
            compiled = lowered.compile()

    rec["lower_compile_s"] = round(perf_s() - t0, 1)
    from repro.compat import cost_analysis as _cost_analysis

    ca = _cost_analysis(compiled)
    # raw XLA numbers (while bodies counted ONCE — kept for reference only)
    rec["xla_flops_body"] = float(ca.get("flops", 0.0))
    rec["memory"] = _mem_summary(compiled)
    hlo = compiled.as_text()
    # trip-count-aware accounting (analysis/hlo_analyzer.py): per-device
    # MXU flops, HBM traffic at fusion boundaries, collective payloads
    from repro.analysis.hlo_analyzer import analyze as hlo_analyze

    anal = hlo_analyze(hlo)
    rec["flops"] = anal.flops
    rec["hbm_bytes"] = anal.hbm_bytes
    rec["collectives"] = {k: float(v) for k, v in anal.collective_bytes.items()}
    rec["collective_counts"] = {
        k: float(v) for k, v in anal.collective_counts.items()
    }
    # replica-group-size breakdown ("all-gather[4]" vs "all-gather[2]"):
    # the inter- vs intra-group split on hybrid meshes
    rec["collectives_by_group"] = {
        k: float(v) for k, v in anal.collective_group_bytes.items()
    }
    # the same vocabulary the serving recorder's derived attribution
    # uses ({"collective", "group_size", "tier", "bytes"}) — one schema,
    # machine-diffable against obs.account.step_wire_attribution
    from repro.obs.account import tiered_collectives

    mesh_axes = dict(mesh.shape)
    M = mesh_axes.get("data", 1)
    T = mesh_axes.get("model", 1)
    rec["wire_tiers"] = tiered_collectives(rec["collectives_by_group"], M, T)
    if recorder is not None:
        recorder.instant("dryrun.wire_tiers", cat="dryrun", arch=arch,
                         shape=shape_name, tiers=rec["wire_tiers"])
        from repro.obs import metrics as obsm

        for row in rec["wire_tiers"]:
            recorder.inc(obsm.WIRE_BYTES, row["bytes"], tier=row["tier"],
                         collective=row["collective"])
    return rec


def _resolve_dryrun_schedule(shape_name: str, mesh,
                             spec: str, psnr_floor: Optional[float],
                             wire_shard: Optional[bool] = None,
                             recorder=None):
    """Resolve ``--codec-schedule`` for one vdm cell against its real
    geometry, sampler trajectory, and the mesh's lp-axis size."""
    from repro.core.comm_model import wan21_comm_config
    from repro.diffusion.sampler import FlowMatchEuler
    from repro.policy import resolve_cli_schedule

    shape = get_shape(shape_name)
    K = mesh.shape["data"]
    tp = dict(mesh.shape).get("model", 1)
    ccfg = wan21_comm_config(shape.num_frames, shape.height, shape.width,
                             num_steps=shape.num_steps)
    return resolve_cli_schedule(
        spec, ccfg, K, ParallelConfig().overlap_ratio,
        FlowMatchEuler(shape.num_steps), shape.num_steps,
        psnr_floor_db=psnr_floor, tp=tp, wire_shard=wire_shard,
        recorder=recorder,
    )


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--lp-impl", default="gspmd",
                    choices=["auto", "gspmd", "shard_map", "halo",
                             "halo_hybrid"])
    from repro.comm.codecs import CODEC_NAMES

    ap.add_argument("--wire-codec", default=None, choices=list(CODEC_NAMES),
                    help="compress LP halo payloads (halo/auto impls; "
                         "gspmd takes stateless codecs value-faithfully)")
    ap.add_argument("--codec-schedule", default=None,
                    help="sigma-scheduled codecs for vdm cells: 'auto' "
                         "(cost-model autotuner, docs/step_policy.md) or "
                         "an explicit spec like 'int8-residual@0.45,"
                         "bf16'.  The dry run lowers one cell per "
                         "schedule segment (collective shapes are "
                         "per-segment static) with the PLAN's engine "
                         "(--lp-impl is ignored for those cells) and "
                         "tags each record with its segment.  Excludes "
                         "--wire-codec")
    ap.add_argument("--psnr-floor", type=float, default=None,
                    help="PSNR floor (dB) for --codec-schedule "
                         "resolution (auto default: 40)")
    ap.add_argument("--mesh", default=None,
                    help="MxT hybrid mesh (LP groups x intra-group TP), "
                         "e.g. 4x2 — replaces the production mesh")
    ap.add_argument("--wire-shard", default=None,
                    action=argparse.BooleanOptionalAction,
                    help="shard halo wire payloads over the tp axis "
                         "(hybrid meshes; default on there — the "
                         "two-tier autotuner decides for "
                         "--codec-schedule cells).  The record's "
                         "collectives_by_group shows the inter/intra "
                         "split")
    ap.add_argument("--eager-sends", default=None,
                    action=argparse.BooleanOptionalAction,
                    help="issue halo ppermutes before any accumulation "
                         "(default: on for hybrid meshes)")
    ap.add_argument("--inject-fault", default=None,
                    help="serving-fault drill spec "
                         "(docs/fault_tolerance.md).  dead:G@S / "
                         "slow:GxF are runtime-only (recorded, no "
                         "lowering effect); corrupt@S lowers the vdm "
                         "cell with the NaN-poisoning wire wrapper and "
                         "the decode guard armed so the guarded HLO can "
                         "be inspected")
    ap.add_argument("--wire-nan-guard", default=False,
                    action=argparse.BooleanOptionalAction,
                    help="lower the halo wire decode with the NaN/Inf "
                         "guard (stale-slab fallback); auto-armed by "
                         "--inject-fault corrupt@S")
    ap.add_argument("--out", default=None)
    ap.add_argument("--trace-out", default=None,
                    help="write a Chrome-trace JSON of the dry run "
                         "(dryrun-category spans + wire_tiers instants; "
                         "docs/observability.md)")
    ap.add_argument("--metrics-out", default=None,
                    help="write a metrics snapshot (.prom/.txt -> "
                         "Prometheus text, else JSONL)")
    args = ap.parse_args(argv)
    if args.codec_schedule and args.wire_codec:
        ap.error("--codec-schedule and --wire-codec are exclusive")

    todo = []
    if args.all:
        for arch, shape, _ in cells():
            todo.append((arch, shape))
    else:
        if not args.arch or not args.shape:
            ap.error("--arch and --shape required unless --all")
        todo.append((args.arch, args.shape))

    recorder = None
    if args.trace_out or args.metrics_out:
        from repro.obs import FlightRecorder

        recorder = FlightRecorder()

    meshes = [args.multi_pod] if not args.both_meshes else [False, True]
    if args.mesh:
        meshes = [False]  # --mesh overrides; one iteration, one mesh
    results = []
    failures = 0
    for multi_pod in meshes:
        if args.mesh:
            from repro.launch.mesh import make_hybrid_mesh, parse_mesh

            m, t = parse_mesh(args.mesh)
            mesh = make_hybrid_mesh(m, t)
            mesh_tag = f"{m}x{t}"
        else:
            mesh = make_production_mesh(multi_pod=multi_pod)
            mesh_tag = "2x16x16" if multi_pod else "16x16"
        for arch, shape in todo:
            tag = f"{arch} x {shape} [{mesh_tag}]"
            try:
                # --codec-schedule: one lowering per schedule segment (a
                # segment's collective shapes are static; only the codec
                # changes at segment boundaries), each record tagged.
                # The PLAN's engine is what gets lowered — the argparse
                # --lp-impl default (gspmd) has no stateful-codec layer
                # and must not leak into schedule cells.
                cells_to_lower = [
                    (args.wire_codec, args.lp_impl, args.wire_shard, None)
                ]
                if args.codec_schedule and \
                        get_shape(shape).kind == "vdm_generate":
                    plan = _resolve_dryrun_schedule(
                        shape, mesh, args.codec_schedule, args.psnr_floor,
                        wire_shard=args.wire_shard, recorder=recorder)
                    print(f"PLAN {tag}: {plan.describe()}", flush=True)
                    cells_to_lower = [
                        (seg.codec, plan.lp_impl, plan.wire_shard, {
                            "codec": seg.codec, "steps": [seg.start,
                                                          seg.stop],
                            "schedule": plan.schedule.spec,
                            "lp_impl": plan.lp_impl,
                            "wire_shard": plan.wire_shard,
                        })
                        for seg in plan.segments
                    ]
                for wire_codec, lp_impl, wire_shard, seg_info in \
                        cells_to_lower:
                    rec = lower_cell(arch, shape, multi_pod, lp_impl,
                                     mesh=mesh, wire_codec=wire_codec,
                                     wire_shard=wire_shard,
                                     eager_sends=args.eager_sends,
                                     inject_fault=args.inject_fault,
                                     wire_nan_guard=args.wire_nan_guard,
                                     recorder=recorder)
                    if seg_info is not None:
                        rec["schedule_segment"] = seg_info
                    if rec.get("skipped"):
                        print(f"SKIP {tag}: {rec['skipped']}", flush=True)
                    else:
                        seg_tag = ("" if seg_info is None else
                                   f" seg={seg_info['codec']}"
                                   f"[{seg_info['steps'][0]}.."
                                   f"{seg_info['steps'][1]}]")
                        print(
                            f"OK   {tag}{seg_tag}: "
                            f"{rec['lower_compile_s']}s "
                            f"flops={rec['flops']:.3e} "
                            f"coll={sum(rec['collectives'].values())/1e9:.2f}GB",
                            flush=True,
                        )
                    results.append(rec)
            except Exception as e:
                failures += 1
                print(f"FAIL {tag}: {type(e).__name__}: {e}", flush=True)
                traceback.print_exc()
                results.append({"arch": arch, "shape": shape,
                                "mesh": "2x16x16" if multi_pod else "16x16",
                                "error": f"{type(e).__name__}: {e}"})
    if args.out:
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)
        print(f"wrote {args.out}")
    if recorder is not None:
        if args.trace_out:
            recorder.write_trace(args.trace_out)
            print(f"wrote {args.trace_out} "
                  f"({len(recorder.trace.events)} events)")
        if args.metrics_out:
            recorder.write_metrics(args.metrics_out)
            print(f"wrote {args.metrics_out}")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
