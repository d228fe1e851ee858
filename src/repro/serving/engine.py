"""LP video-generation serving engine: request queue -> shape-batched LP
denoising -> latents out.

Production behaviours implemented (scaled to the container):
  * request batching by latent geometry (same (frames, res) denoise
    together — LP partitions are geometry-static, so batching avoids
    re-planning / recompiles);
  * compiled-step reuse ACROSS batches: the guided denoiser takes the
    text context / CFG scale as traced arguments and is built once per
    engine (not per batch), and one ``LPStepCompiler`` owns the jitted
    step cache — the second batch of a given geometry runs with zero
    retraces;
  * bounded-latency admission: a batch launches when a geometry bucket is
    full OR when the oldest request has waited ``max_wait_requests``
    queue polls (before this, ``max_wait`` was stored but never read);
  * group health: per-LP-group step times feed a
    ``runtime/health.GroupHealthMonitor`` (heartbeat deadlines with
    bounded retry-backoff on top of the straggler EMA) — a group that is
    merely *slow* gets EMA-driven rebalancing / eventual eviction, a
    group that stops reporting is declared *dead* after its retry budget
    and proposed for immediate eviction;
  * failure handling: a denoise step that raises a *recoverable* fault
    (``runtime/ft.DeviceFailure``, ``runtime/faults.ServingFault``)
    retries the batch from its last **boundary snapshot** — there is no
    ``ckpt_every_steps`` wall-clock checkpoint; instead ``lp_denoise``
    records ``(z, step)`` into a per-batch ``core.DenoiseSnapshot`` at
    every dim-rotation / codec-segment boundary (exactly where residual
    codec state re-zeroes, so (z, step) IS the whole restartable state),
    and a retry resumes there instead of from ``z_T``, losing at most
    one dim-run of steps.  Any other exception surfaces immediately;
  * fault injection: ``inject_fault="dead:G@S,slow:GxF,corrupt@S"``
    (``runtime/faults.ServingFaultPlan``, CLI ``--inject-fault``)
    scripts group death, synthetic stragglers and one-step wire
    corruption against the per-step hook for drills and the
    ``benchmarks/fault_recovery.py`` gate; ``wire_nan_guard`` (default
    on) arms the halo-wire decode guard that absorbs a NaN/Inf payload
    by falling back to the rank-local stale slab (bit-identical when
    every wire message is finite);
  * engine auto-selection + wire codecs: ``lp_impl="auto"`` picks the
    psum engine at K=2 and the halo engine beyond (the comm-model
    break-even, ``core/spmd.select_lp_impl``); ``wire_codec`` squeezes
    the halo payloads through ``comm/`` codecs (bf16/int8/int4, or
    int8-residual temporal-delta with error feedback).  Residual codec
    state is zeroed at the start of every same-dim scan run inside
    ``lp_denoise``, so state can never leak across batches/requests;
  * step policy: ``codec_schedule`` replaces the frozen per-request
    codec with a sigma-scheduled one (``policy/`` subsystem) — ``auto``
    lets the cost-model autotuner pick (engine, schedule) minimizing
    analytic wire bytes subject to ``psnr_floor`` against the
    conformance PSNR envelope; an explicit spec (e.g.
    ``int8-residual@0.45,bf16``) is taken as-is.  Scheduled segments
    run as segmented scans through the shared ``LPStepCompiler``
    (segment codec in the cache key, <= 3 x num_segments compiles);
  * hierarchy-aware wire on hybrid meshes: ``wire_shard`` (default on
    when the mesh has a tp axis; the autotuner's two-tier link model
    decides when a schedule is planned) ships each halo payload as 1/T
    chunks across the inter-group links + an intra-group reassembly
    gather — T-fold fewer inter-group bytes, bit-identical values
    (docs/wire_sharding.md); ``eager_sends`` (default on for hybrid
    meshes) issues the ppermute rounds before any accumulation so they
    overlap the Phi_m tail;
  * mid-request re-planning: with ``elastic=True`` the per-step hook
    consults ``GroupHealthMonitor.propose`` (dead groups first, then the
    EMA slow test) and applies a proposed eviction through
    ``runtime.elastic.replan_lp_compiler`` WHILE a batch is denoising —
    the compiled-step cache can never serve a stale-geometry entry and
    codec state resets exactly once.  Mesh-bound engines shrink too:
    ``launch/mesh.shrink_hybrid_mesh`` rebuilds the ``(M-1, T)`` mesh
    from the survivors and :meth:`LPServingEngine._build_forward` hands
    ``replan_lp_compiler`` forward hooks re-bound to it, so the hybrid
    halo engine evicts mid-request instead of limping to the batch
    boundary.  A resolved codec schedule is re-derived for the shrunken
    K (the analytic byte model changed), taking effect next batch.
    The engine cannot time remote LP groups itself: an external
    monitor must feed per-group step times through
    :meth:`LPServingEngine.observe_group_times` (from another thread,
    mid-batch, is fine — the hook reads the EMA at the next step
    boundary).  Note ``elastic=True`` installs a per-step hook, which
    disables scan fusion; leave it off when no monitor is attached;
  * request-lifecycle observability: every request is stamped
    submit/admit/denoise-start/done on the engine ``clock`` (injectable
    — the load harness passes a ``serving/loadgen.VirtualClock`` so
    open-loop arrivals and measured service times share one replayable
    timeline), carries a ``priority`` SLO class, and lands per-request
    ``queue_wait_s`` / ``e2e_s`` on its :class:`VideoResult` plus —
    with a recorder — a ``request.lifecycle`` trace span and
    per-priority latency histograms (``serve.queue_wait_s`` /
    ``serve.e2e_latency_s``).  An optional ``slo`` spec (``obs/slo.py``
    grammar) counts deadline violations live
    (``serve.slo_violations``); the offline evaluator recomputes the
    same per-class report from the ``--trace-out`` artifact.
"""
from __future__ import annotations

import dataclasses
from collections import defaultdict
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.comm.codecs import get_codec
from repro.configs.base import ArchConfig
from repro.core import DenoiseSnapshot, LPStepCompiler, lp_denoise
from repro.core.spmd import select_lp_impl
from repro.diffusion.pipeline import make_guided_step_denoiser
from repro.diffusion.sampler import FlowMatchEuler
from repro.obs import metrics as obsm
from repro.obs.clock import perf_s
from repro.obs.trace import span
from repro.runtime.faults import CorruptingCodec, ReplicaDeath, \
    ServingFault, parse_fault_plan
from repro.runtime.ft import DeviceFailure
from repro.runtime.health import GroupHealthMonitor


class QueueFull(RuntimeError):
    """``submit`` rejected a request because the engine queue is at its
    ``max_queue`` bound.  Backpressure, made explicit: an overload burst
    must surface to the caller (the load harness records it; the replica
    router's requeue path sheds or re-routes) instead of growing engine
    memory without limit.  The request was NOT enqueued and acquired no
    lifecycle state."""

    def __init__(self, msg: str, request_id: Optional[int] = None,
                 depth: Optional[int] = None):
        super().__init__(msg)
        self.request_id = request_id
        self.depth = depth


@dataclasses.dataclass
class VideoRequest:
    request_id: int
    # the encoded prompt, with a batch dim of 1: WAN's (1, L_ctx, ctx_dim)
    # text context, or any pytree the denoiser reads (HunyuanVideo:
    # {"text": (1, L, ctx_dim), "pooled": (1, pooled_dim)})
    context: Any
    latent_shape: Tuple[int, int, int]   # (T_lat, H_lat, W_lat)
    seed: int = 0
    guidance: float = 5.0
    # SLA metadata (obs/slo.py, serving/loadgen.py): ``priority`` names
    # the request's SLO class (deadline via an SLOSpec) and labels its
    # lifecycle metrics; ``psnr_floor`` is the per-request quality
    # floor the class maps to — carried through the lifecycle records
    # today, consumed by per-request plan selection when the replica
    # router lands (docs/step_policy.md).  Neither enters the batch
    # bucketing key: requests of different classes share a compiled
    # denoise.
    priority: str = "standard"
    psnr_floor: Optional[float] = None


@dataclasses.dataclass
class VideoResult:
    request_id: int
    latent: jnp.ndarray
    num_steps: int
    # the denoise is batched, so a request's wall time is the batch's:
    # report it as such (with the batch size) instead of pretending the
    # whole-batch wall belongs to each request individually
    batch_wall_s: float
    batch_size: int
    restarts: int = 0
    # denoise step the last retry resumed from (0 = from z_T / no retry):
    # together with ``restarts`` this quantifies the work a fault cost
    resumed_from_step: int = 0
    # per-request lifecycle latencies on the engine clock (virtual time
    # under the load harness): submit -> batch admission, and submit ->
    # batch done.  Unlike ``batch_wall_s`` these ARE per-request — two
    # riders of one batch differ by their queue waits.
    queue_wait_s: float = 0.0
    e2e_s: float = 0.0


class LPServingEngine:
    def __init__(
        self,
        dit_forward: Callable,
        params: Any,
        cfg: ArchConfig,
        num_partitions: int,
        overlap_ratio: float = 0.5,
        num_steps: int = 20,
        max_batch: int = 4,
        max_wait_requests: int = 8,
        max_queue: Optional[int] = None,
        replica_id: Optional[int] = None,
        uniform: bool = True,
        lp_impl: str = "auto",
        wire_codec: Optional[str] = None,
        codec_schedule: Optional[str] = None,
        psnr_floor: Optional[float] = None,
        plan_geometry: Tuple[int, int, int] = (13, 60, 104),
        elastic: bool = False,
        mesh=None,
        lp_axis: str = "data",
        tp_axis: str = "model",
        wire_shard: Optional[bool] = None,
        eager_sends: Optional[bool] = None,
        inject_fault=None,
        wire_nan_guard: bool = True,
        snapshots: bool = True,
        recorder=None,
        clock: Optional[Callable[[], float]] = None,
        slo=None,
    ):
        self.dit_forward = dit_forward
        self.params = params
        self._params_mesh = None        # mesh self.params is placed on
        self.cfg = cfg
        self.K = num_partitions
        self.r = overlap_ratio
        self.num_steps = num_steps
        self.max_batch = max_batch
        self.max_wait = max_wait_requests
        # bounded admission: ``submit`` raises ``QueueFull`` beyond this
        # many queued requests (None = unbounded, the historical
        # behaviour).  The bound is on the QUEUE, not in-flight work —
        # a router that dispatches at most max_batch at a time never
        # trips it, while an unrouted overload burst fails loudly.
        if max_queue is not None and max_queue < max_batch:
            raise ValueError(
                f"max_queue={max_queue} < max_batch={max_batch}: the "
                f"queue could never fill a batch")
        self.max_queue = max_queue
        # fleet identity: set by the replica router (or the operator) so
        # lifecycle rows and serve.* metrics carry a per-replica label;
        # None (a bare engine) emits the exact historical schema.
        self.replica_id = replica_id
        self.uniform = uniform
        # ``recorder`` (repro.obs.FlightRecorder) is the optional
        # observability plane: request/batch spans, serve metrics, and
        # derived per-step wire attribution.  Host state only — it is
        # never traced and never enters the step-cache key, so enabling
        # it cannot cause a recompile (benchmarks/obs_overhead.py).
        self.recorder = recorder
        # ``clock`` is the request-lifecycle time source (submit/admit/
        # done stamps).  Default: the shared monotonic perf clock.  The
        # load harness passes a ``serving.loadgen.VirtualClock`` so
        # open-loop arrival times and measured service times share one
        # replayable timeline; the engine advances a virtual clock by
        # each batch's measured wall (see ``_denoise_batch``).  The
        # clock is host state only — never traced, never in a cache key.
        self.clock: Callable[[], float] = clock if clock is not None \
            else perf_s
        # optional SLO spec (obs/slo.py, or its string grammar): when
        # set, completed requests are checked against their priority
        # class's deadline and ``serve.slo_violations`` counts live.
        # The offline evaluator recomputes violations from stamps, so
        # serving without a spec loses nothing but the live counter.
        if slo is not None:
            from repro.obs.slo import SLOSpec
            slo = SLOSpec.parse(slo)
        self.slo = slo
        self._lifecycle: Dict[int, dict] = {}   # request_id -> stamps
        self._batch_seq = 0
        self.health = GroupHealthMonitor(
            num_partitions,
            metrics=None if recorder is None else recorder.metrics)
        # back-compat alias: external monitors (and the elastic tests)
        # that fed the EMA directly keep working — the health monitor
        # wraps the very same StragglerState
        self.straggler = self.health.straggler
        self.elastic = elastic
        self.evictions = 0
        self._queue: List[VideoRequest] = []
        self._polls = 0
        self._enqueued_at: Dict[int, int] = {}       # request_id -> poll no.
        self._step_fault: Optional[Callable[[int], None]] = None  # test hook
        self._fault_plan = parse_fault_plan(inject_fault)
        if self._fault_plan is not None and \
                self._fault_plan.has_replica_targets:
            raise ValueError(
                f"fault plan {self._fault_plan.describe()!r} carries "
                "replica:-scoped targets, which a bare engine cannot "
                "interpret (it does not know which replica it is) — "
                "route it through serving.router.ReplicaRouter, which "
                "splits per-replica sub-plans"
            )
        # in-flight batch (set by run() while a batch is denoising,
        # cleared on success/terminal failure): the replica router reads
        # this to requeue a batch lost to a whole-replica death
        self._inflight: List[VideoRequest] = []
        self.wire_nan_guard = bool(wire_nan_guard)
        self.snapshots = bool(snapshots)
        self.last_steps_lost: Optional[int] = None
        self._corrupt_active = False
        self._saved_codec = None
        self._plan_resolver: Optional[Callable] = None
        self._sampler = FlowMatchEuler(num_steps)
        tp = 1
        if mesh is not None and tp_axis in mesh.axis_names:
            tp = mesh.shape[tp_axis]
        # Hierarchy-aware wire knobs.  ``eager_sends=None`` resolves to
        # on for hybrid meshes running a halo-family engine (the
        # ppermute rounds can overlap the Phi_m tail there) and off
        # otherwise; ``wire_shard=None`` lets the autotuner's two-tier
        # link model decide when a schedule is being planned, and
        # otherwise defaults to on for hybrid meshes (T-fold fewer
        # inter-group bytes; bit-identical values).  BOTH tri-states
        # resolve AFTER plan resolution + engine selection below — the
        # autotuner may flip the engine family (e.g. a fp32-only
        # schedule to psum), and resolving from ``tp`` alone here would
        # bake wire knobs for an engine the plan then discards.
        eager_sends_pinned = eager_sends is not None
        if wire_shard and tp <= 1:
            raise ValueError(
                "wire_shard shards the halo wire over the tp axis; the "
                "mesh has no tp axis (need --mesh MxT with T >= 2)"
            )
        wire_shard_pinned = wire_shard is True  # explicit operator pin
        # Step policy: a codec schedule (explicit spec or cost-model
        # "auto") subsumes the fixed wire_codec — they are exclusive.
        self.codec = get_codec(wire_codec)
        codec_active = self.codec.name not in ("fp32", "identity")
        self.plan = None
        schedule = None
        # mutable quality floor: the replica router's graceful-
        # degradation path relaxes it under overload (set_psnr_floor),
        # re-resolving the autotuner plan toward cheaper codec
        # schedules, and restores it on recovery.  Meaningful only with
        # codec_schedule="auto"; None otherwise.
        self.psnr_floor = psnr_floor
        if codec_schedule is not None:
            from repro.core.comm_model import VDMCommConfig
            from repro.policy import resolve_cli_schedule

            if codec_active:
                raise ValueError(
                    "pass wire_codec= (fixed) or codec_schedule= "
                    "(sigma-scheduled), not both"
                )
            # the plan geometry only anchors the byte model; the chosen
            # schedule depends on codec bit-widths and the sigma
            # trajectory, both geometry-robust
            ccfg = VDMCommConfig(
                latent_dims=tuple(plan_geometry),
                latent_channels=cfg.latent_channels,
                patch_sizes=cfg.patch_sizes,
                d_model=cfg.d_model,
                num_blocks=cfg.num_layers,
                num_steps=num_steps,
            )
            # kept re-invocable: an elastic eviction shrinks K, which
            # changes the analytic byte model the schedule was tuned
            # against, so _maybe_evict_straggler re-resolves the plan
            # (closing over the ORIGINAL cli wire_shard tri-state, not
            # the value the first resolution pinned)
            wire_shard_cli = wire_shard

            def _resolve_plan(k):
                return resolve_cli_schedule(
                    codec_schedule, ccfg, k, self.r, self._sampler,
                    num_steps, psnr_floor_db=self.psnr_floor, tp=tp,
                    wire_shard=wire_shard_cli, recorder=self.recorder,
                )

            self._plan_resolver = _resolve_plan
            self.plan = self._plan_resolver(self.K)
            if lp_impl == "auto":
                lp_impl = self.plan.lp_impl
            if set(self.plan.step_codecs) != {"fp32"}:
                schedule = self.plan.schedule
            wire_shard = self.plan.wire_shard
        elif psnr_floor is not None:
            raise ValueError("psnr_floor needs codec_schedule")
        # Engine selection: "auto" follows the comm model (psum at K=2,
        # halo family beyond — select_lp_impl); a non-trivial wire codec
        # or schedule implies the halo family, which is where the codec
        # layer lives.  On a 2D (lp, tp) mesh the halo family is the
        # hybrid engine: the group-axis halo schedule with the TP DiT
        # forward as the black-box intra-group Phi_m.
        explicit_halo = lp_impl in ("halo", "halo_hybrid")
        if lp_impl == "auto":
            if codec_active:
                lp_impl = "halo_hybrid" if tp > 1 else "halo"
            else:
                lp_impl = select_lp_impl(self.K, tp)
        if (codec_active or schedule is not None) and \
                lp_impl not in ("halo", "halo_hybrid"):
            what = (f"wire_codec={self.codec.name!r}" if codec_active
                    else f"codec_schedule={schedule.spec!r}")
            names = (list(self.plan.step_codecs) if self.plan is not None
                     else [self.codec.name])
            if any(str(n).startswith("displaced") for n in names):
                raise ValueError(
                    f"{what} uses a displaced halo codec, which needs "
                    "carry-resident slab state — only the halo family "
                    "keeps one (the psum/gspmd engines have no "
                    f"per-direction slab carry); got lp_impl={lp_impl!r}"
                )
            raise ValueError(
                f"{what} needs the halo family (the codec layer lives "
                f"there), got lp_impl={lp_impl!r}"
            )
        self.lp_impl = lp_impl
        self.mesh = mesh
        self.tp = tp
        # tri-state resolution, now that the engine family is final
        # (satellite fix: was previously derived from ``tp`` alone,
        # before the plan could flip the family)
        halo_family = self.lp_impl in ("halo", "halo_hybrid")
        self.eager_sends = bool(eager_sends) if eager_sends_pinned else \
            (tp > 1 and halo_family)
        self.wire_shard = (tp > 1 and halo_family) if wire_shard is None \
            else bool(wire_shard)
        if self.lp_impl not in ("halo", "halo_hybrid") or tp <= 1 or \
                mesh is None:
            # sharding is a property of the mesh-bound halo wire; the
            # psum engine and the off-mesh simulate mirror have no tp
            # wire to split (simulate is bit-identical either way).  An
            # EXPLICIT pin that cannot be honored is a config error
            # (dryrun raises for the same combination), not a silent
            # downgrade.
            if wire_shard_pinned:
                raise ValueError(
                    f"wire_shard=True needs the mesh-bound halo family, "
                    f"got lp_impl={self.lp_impl!r} "
                    f"(mesh={'yes' if mesh is not None else 'no'}, tp={tp})"
                )
            self.wire_shard = False
        self._lp_axis = lp_axis
        self._tp_axis = tp_axis
        self._schedule = schedule
        # off-mesh halo family runs the single-process simulate mirror
        # (comm.wire.simulate_halo_forward — LPStepCompiler's codec
        # default), bit-faithful incl. the codec round-trips.  Only when
        # a codec is active or halo was asked for by name: with fp32
        # wires an auto-selected halo has nothing to simulate and the
        # uniform vmapped engine is the same math for a fraction of the
        # dispatch work.  A schedule needs no compiler codec — the
        # per-segment codecs route every step through the same mirror.
        self._simulate_codec = (
            self.lp_impl in ("halo", "halo_hybrid")
            and (codec_active or explicit_halo) and schedule is None
        )
        forward, forward_factory, compiler_codec = self._build_forward(mesh)
        if self._fault_plan is not None and self._fault_plan.corrupt:
            # the corrupt fault swaps the live wire codec for one step;
            # that only means something on an engine with a fixed wire
            if schedule is not None:
                raise ValueError(
                    "corrupt@S faults need a fixed wire codec — "
                    "sigma-scheduled segments own their codecs"
                )
            if compiler_codec is None:
                raise ValueError(
                    "corrupt@S faults poison the halo wire, but this "
                    f"engine has none (lp_impl={self.lp_impl!r}); use "
                    "the halo family with a wire codec"
                )
            if compiler_codec.stateful:
                raise ValueError(
                    "corrupt@S faults need a stateless wire codec: the "
                    "residual EF protocol is symmetric (sender and "
                    "receiver decode the same base payload), so a "
                    "poisoned decode would desync the sender's own EF "
                    "state, not just the wire"
                )
        # Hoisted out of the batch loop: conditioning is traced, so this
        # closure (and every step it compiles) is batch-independent.
        self._guided = make_guided_step_denoiser(dit_forward, cfg)
        self._compiler = LPStepCompiler(
            denoise_fn=self._guided,
            update_fn=self._sampler.update,
            num_partitions=self.K,
            overlap_ratio=self.r,
            patch_sizes=cfg.patch_sizes,
            spatial_axes=(1, 2, 3),
            uniform=uniform,
            forward=forward,
            forward_factory=forward_factory,
            codec=compiler_codec,
            schedule=schedule,
            mesh_shape=None if mesh is None else (self.K, tp),
            wire_shard=self.wire_shard,
            nan_guard=self.wire_nan_guard,
        )
        # Wire-attribution timelines (repro.obs.account): one geometry
        # entry per (from_step, K) epoch and one codec entry per
        # (from_step, step_codec_names) epoch; reset per batch, appended
        # to by mid-request evictions / schedule re-plans.
        self._cur_step = 1
        self._geom_events: List[Tuple[int, int]] = [(1, self.K)]
        self._codec_events: List[Tuple[int, List[str]]] = []
        self._batch_codecs: List[str] = []
        self._runs_mark = 0

    # ----------------------------------------------------------- forward
    def _build_forward(self, mesh):
        """(Re-)build the engine's forward hook family for ``mesh``.

        Returns ``(forward, forward_factory, compiler_codec)`` in
        ``LPStepCompiler`` terms.  Factored out of ``__init__`` so
        elastic mesh-shrink recovery can re-invoke it: after
        ``launch.mesh.shrink_hybrid_mesh`` drops a dead LP group, the
        rebuilt ``(M-1, T)`` mesh needs hooks closing over IT, and
        ``runtime.elastic.replan_lp_compiler`` refuses to change K on a
        mesh-bound compiler without them.

        Fixed-codec hooks read ``self._compiler.codec`` at trace time
        (late-bound, not captured) so the one-step ``corrupt@S`` codec
        swap reaches the mesh-bound wire — the codec name is in the
        step-cache key, so the swap always keys a distinct entry.
        """
        forward = None
        forward_factory = None
        compiler_codec = None
        schedule = self._schedule
        if mesh is not None:
            from repro.core.hybrid import lp_forward_halo_hybrid
            from repro.core.spmd import lp_forward_halo, lp_forward_shard_map

            lp_axis, tp_axis = self._lp_axis, self._tp_axis
            if self.lp_impl in ("halo", "halo_hybrid"):
                if self.lp_impl == "halo_hybrid":
                    def halo_fwd(fn, z, plan, axis, **kw):
                        return lp_forward_halo_hybrid(
                            fn, z, plan, axis, mesh, lp_axis, tp_axis,
                            eager_sends=self.eager_sends,
                            wire_shard=self.wire_shard,
                            nan_guard=self.wire_nan_guard, **kw)
                else:
                    # the plain halo engine composes with extra mesh
                    # axes; slabs are replicated over tp there too, so
                    # the wire can still be sharded over it
                    halo_shard = tp_axis if (self.wire_shard and
                                             self.tp > 1) else None

                    def halo_fwd(fn, z, plan, axis, **kw):
                        return lp_forward_halo(
                            fn, z, plan, axis, mesh, lp_axis,
                            eager_sends=self.eager_sends,
                            shard_axis=halo_shard,
                            nan_guard=self.wire_nan_guard, **kw)
                if schedule is not None:
                    # scheduled: LPStepCompiler asks for a hook per
                    # segment codec; each bound hook is the same halo
                    # collective, just encoding with that segment's codec
                    def forward_factory(seg_codec):
                        if seg_codec.stateful:
                            return (lambda fn, z, plan, axis, st:
                                    halo_fwd(fn, z, plan, axis,
                                             codec=seg_codec,
                                             codec_state=st))
                        return (lambda fn, z, plan, axis:
                                halo_fwd(fn, z, plan, axis,
                                         codec=seg_codec))
                elif self.codec.stateful:
                    forward = (lambda fn, z, plan, axis, st:
                               halo_fwd(fn, z, plan, axis,
                                        codec=self._compiler.codec,
                                        codec_state=st))
                else:
                    forward = (lambda fn, z, plan, axis:
                               halo_fwd(fn, z, plan, axis,
                                        codec=self._compiler.codec))
                if schedule is None:
                    compiler_codec = self.codec
            else:
                forward = (lambda fn, z, plan, axis:
                           lp_forward_shard_map(fn, z, plan, axis, mesh,
                                                lp_axis))
        elif self._simulate_codec:
            compiler_codec = self.codec
        # else: uniform vmapped engine (psum-equivalent math, no wire)
        return forward, forward_factory, compiler_codec

    def _step_params(self):
        """The parameters as the compiled step takes them: as given
        without a mesh, replicated over the mesh otherwise.  Placed once
        per mesh (again after an eviction shrinks it), so no step
        re-sends them, and the unplaced copy is dropped so a device
        never holds the parameters twice."""
        if self.mesh is not None and self._params_mesh is not self.mesh:
            from jax.sharding import NamedSharding, PartitionSpec

            self.params = jax.device_put(
                self.params, NamedSharding(self.mesh, PartitionSpec()))
            self._params_mesh = self.mesh
        return self.params

    # ------------------------------------------------------------- queue
    def _rlabels(self) -> Dict[str, str]:
        """Per-replica metric labels: ``{}`` for a bare engine (the
        exact historical metric schema), ``{"replica": "<id>"}`` when a
        router assigned this engine a fleet identity.  Read live (not
        cached) because the router sets ``replica_id`` after
        construction."""
        if self.replica_id is None:
            return {}
        return {"replica": str(self.replica_id)}

    def submit(self, req: VideoRequest,
               submit_s: Optional[float] = None) -> None:
        if self.max_queue is not None and \
                len(self._queue) >= self.max_queue:
            rec = self.recorder
            if rec is not None:
                rec.instant("request.rejected", cat="serve",
                            request_id=req.request_id,
                            priority=req.priority,
                            depth=len(self._queue), **self._rlabels())
                rec.inc(obsm.REQUESTS_REJECTED, **self._rlabels())
            raise QueueFull(
                f"engine queue full ({len(self._queue)} >= "
                f"max_queue={self.max_queue}); request "
                f"{req.request_id} not enqueued",
                request_id=req.request_id, depth=len(self._queue))
        self._queue.append(req)
        self._enqueued_at[req.request_id] = self._polls
        # lifecycle stamps are kept engine-side (not only recorder-side)
        # so VideoResult.queue_wait_s/e2e_s work without a recorder.
        # ``submit_s`` lets an open-loop replay stamp the request's
        # ARRIVAL time instead of the call time: a synchronous driver
        # can only submit a mid-batch arrival after that batch returns,
        # and stamping the call would under-report its queue wait (and
        # e2e) by up to a full batch wall.
        self._lifecycle[req.request_id] = {
            "request_id": req.request_id,
            "priority": str(req.priority),
            "latent_shape": list(req.latent_shape),
            "guidance": float(req.guidance),
            "psnr_floor": req.psnr_floor,
            "submit_s": (float(self.clock()) if submit_s is None
                         else float(submit_s)),
        }
        if self.replica_id is not None:
            self._lifecycle[req.request_id]["replica"] = self.replica_id
        rec = self.recorder
        if rec is not None:
            rec.instant("request.enqueue", cat="serve",
                        request_id=req.request_id,
                        latent_shape=req.latent_shape,
                        guidance=req.guidance,
                        priority=req.priority, **self._rlabels())
            rec.inc(obsm.REQUESTS, **self._rlabels())
            rec.gauge(obsm.QUEUE_DEPTH, len(self._queue),
                      **self._rlabels())

    @staticmethod
    def _bucket_key(req: VideoRequest) -> Tuple:
        """Batching key: geometry AND guidance.  A batch shares one
        compiled denoise with ONE traced guidance scalar, so bucketing
        by shape alone would silently apply the first request's
        guidance to every rider."""
        return (req.latent_shape, float(req.guidance))

    def _next_batch(self, force: bool = False) -> List[VideoRequest]:
        """Admission: full bucket, aged-out oldest bucket, or
        (``force``, used when draining) the oldest bucket regardless."""
        if not self._queue:
            return []
        self._polls += 1
        by_key: Dict[Tuple, List[VideoRequest]] = defaultdict(list)
        for r in self._queue:
            by_key[self._bucket_key(r)].append(r)
        batch: List[VideoRequest] = []
        for bucket in by_key.values():
            if len(bucket) >= self.max_batch:
                batch = bucket[: self.max_batch]
                break
        if not batch:
            oldest = self._queue[0]
            age = self._polls - self._enqueued_at.get(
                oldest.request_id, self._polls
            )
            if force or age >= self.max_wait:
                batch = by_key[self._bucket_key(oldest)][: self.max_batch]
            else:
                return []
        self._batch_seq += 1
        with self._span("batch.admit", batch, size=len(batch),
                        latent_shape=batch[0].latent_shape,
                        guidance=batch[0].guidance):
            chosen = {id(r) for r in batch}
            self._queue = [r for r in self._queue if id(r) not in chosen]
            admit_s = float(self.clock())
            for r in batch:
                self._enqueued_at.pop(r.request_id, None)
                life = self._lifecycle.get(r.request_id)
                if life is not None:
                    life["admit_s"] = admit_s
                    life["batch_seq"] = self._batch_seq
                    life["batch_size"] = len(batch)
            rec = self.recorder
            if rec is not None:
                rec.observe(obsm.BATCH_SIZE, len(batch), **self._rlabels())
                rec.observe(obsm.BATCH_OCCUPANCY,
                            len(batch) / max(1, self.max_batch),
                            **self._rlabels())
                rec.gauge(obsm.QUEUE_DEPTH, len(self._queue),
                          **self._rlabels())
        return batch

    def _span(self, name: str, batch: List[VideoRequest], **args):
        """A ``serve`` span of the current batch (``repro.obs.trace.span``:
        on the profiler's clock always, in the recorder's trace when one
        is attached), identified by ``batch_seq`` and its request ids."""
        return span(name, getattr(self.recorder, "trace", None),
                    cat="serve", batch_seq=self._batch_seq,
                    request_ids=[r.request_id for r in batch], **args)

    # ------------------------------------------------------------ serving
    def observe_group_times(self, step_times) -> None:
        """Feed per-LP-group step times (seconds) into the health
        monitor (heartbeat deadlines + the straggler EMA).  This is the
        ``elastic=True`` data source: the engine runs single-process and
        cannot time remote groups, so an external monitor (per-host
        heartbeats, profiler stream) calls this — any thread, any time;
        the elastic step hook consumes the verdicts at the next step
        boundary.  Pass ``None``/``inf`` for a group that failed to
        report: enough missed rounds declare it dead."""
        self.health.observe(step_times)

    def set_psnr_floor(self, floor: Optional[float]) -> bool:
        """Move the per-engine quality floor (dB) and re-resolve the
        codec schedule against it — the replica router's graceful-
        degradation lever: a LOWER floor admits cheaper (fewer-bit)
        codec schedules, trading conformance PSNR for wire bytes and
        wall.  No-op (returns False) when the engine has no autotuned
        schedule (``codec_schedule`` unset or explicit) or the floor is
        unchanged.  Takes effect at the next batch, like every other
        re-plan — the in-flight denoise keeps its resolved segments."""
        if self._plan_resolver is None or floor == self.psnr_floor:
            return False
        self.psnr_floor = floor
        self._replan_schedule()
        return True

    def _replan_schedule(self) -> None:
        """Post-eviction: re-resolve the codec schedule at the new K.

        The schedule was tuned against the analytic byte model of the
        OLD partition count; keeping it would mis-price every remaining
        segment (the stale-plan bug this fixes: ``self.K`` shrank but
        ``self.plan`` never followed).  The re-resolved schedule is
        installed on the shared compiler and takes effect at the next
        batch — the in-flight denoise keeps its resolved segment layout,
        which stays valid because hooks bind per segment codec."""
        if self._plan_resolver is None:
            return
        self.plan = self._plan_resolver(self.K)
        new_sched = self.plan.schedule
        if new_sched is not None and \
                set(self.plan.step_codecs) != {"fp32"}:
            from repro.policy.schedule import parse_schedule

            self._schedule = parse_schedule(new_sched)
            self._compiler.schedule = self._schedule
            if self.recorder is not None:
                # codec timeline entry: a resumed retry re-resolves its
                # runs from the compiler's NEW schedule, so steps from
                # the current one onward are attributed under it
                self._codec_events.append(
                    (self._cur_step, self._step_codec_names()))

    def _maybe_evict_straggler(self) -> None:
        """Per-step elastic hook: apply a group-eviction proposal (dead
        group first, straggler EMA second) WHILE a batch is denoising.

        ``GroupHealthMonitor.propose`` fires when a group exhausted its
        heartbeat retry budget (dead) or its step-time EMA is far beyond
        the median (slow: dying host, broken link);
        ``replan_lp_compiler`` retargets the live compiler — full
        geometry in the step-cache key, codec state reset exactly once —
        and the in-flight ``lp_denoise`` loop picks up the new plan at
        the next step boundary.  Mesh-bound compilers shrink too:
        ``shrink_hybrid_mesh`` rebuilds the ``(M-1, T)`` mesh from the
        survivors and :meth:`_build_forward` supplies hooks re-bound to
        it, which ``replan_lp_compiler`` requires before changing K on a
        mesh-bound compiler.  A resolved codec schedule is re-derived
        for the shrunken K (:meth:`_replan_schedule`)."""
        proposal = self.health.propose((self.K, self.tp))
        if proposal is None:
            return
        from repro.runtime.elastic import replan_lp_compiler

        evicted, new_shape = proposal.group, proposal.new_mesh_shape
        forward = forward_factory = None
        new_mesh = self.mesh
        if self.mesh is not None:
            from repro.launch.mesh import shrink_hybrid_mesh

            new_mesh = shrink_hybrid_mesh(self.mesh, evicted, self.tp)
            forward, forward_factory, _ = self._build_forward(new_mesh)
        if replan_lp_compiler(self._compiler, new_shape, forward=forward,
                              forward_factory=forward_factory,
                              recorder=self.recorder):
            self.health.evict(evicted)
            self.K = new_shape[0]
            self.mesh = new_mesh
            self.evictions += 1
            if self._fault_plan is not None:
                # the dead hardware left the ring: its scripted faults
                # stop firing and the survivors re-index
                self._fault_plan.mark_recovered(evicted)
            rec = self.recorder
            if rec is not None:
                # geometry timeline entry: the eviction applies in the
                # step hook BEFORE step ``_cur_step`` executes, so that
                # step (and everything after) runs — and is attributed —
                # at the new K
                self._geom_events.append((self._cur_step, self.K))
                rec.instant("elastic.evict", cat="elastic",
                            group=evicted, reason=proposal.reason,
                            step=self._cur_step,
                            new_mesh_shape=list(new_shape))
                rec.inc(obsm.EVICTIONS, reason=proposal.reason,
                        **self._rlabels())
            self._replan_schedule()

    def _conditioning(self, ctx) -> Tuple:
        """The batch's conditioning as the guided step takes it, between
        the parameters and the guidance scale: the conditioning alone for
        a network that embeds the guidance, else the CFG pair's
        conditional and all-zero unconditional contexts."""
        if self.cfg.guidance_embed:
            return (ctx,)
        return (ctx, jnp.zeros_like(ctx))

    # ------------------------------------------------------ fault drills
    def _activate_corrupt(self) -> None:
        """Swap the live wire codec for its NaN-decoding twin for ONE
        step.  The codec name is part of the step-cache key, so this
        keys (and compiles) a distinct entry — the healthy executable is
        never poisoned and is re-hit verbatim after the restore."""
        comp = self._compiler
        self._saved_codec = comp.codec
        comp.codec = CorruptingCodec.wrap(comp.codec)
        self._corrupt_active = True

    def _restore_codec(self) -> None:
        if self._corrupt_active:
            self._compiler.codec = self._saved_codec
            self._corrupt_active = False

    def _step_hook(self) -> Optional[Callable[[int], None]]:
        """Compose the per-step hooks.  A hook disables scan fusion, so
        return None (fused fast path) unless a fault injector is
        registered or elastic mid-request re-planning is on.

        Hook order is load-bearing for recovery: scripted heartbeats
        feed the health monitor FIRST, the eviction attempt runs SECOND,
        and the dead-group raise comes LAST — so the step on which the
        monitor finally declares the group dead evicts it (marking the
        fault recovered) instead of burning another restart."""
        if self._step_fault is None and not self.elastic and \
                self._fault_plan is None:
            return None

        def hook(i: int) -> None:
            # the hook fires before step ``i`` executes, so an eviction
            # applied here changes the geometry step ``i`` runs under —
            # the wire-attribution timeline depends on this ordering
            self._cur_step = i
            rec = self.recorder
            plan = self._fault_plan
            if plan is not None and plan.die_fires(i):
                # whole-replica death: NOT a ServingFault — the dead
                # replica cannot retry itself, so run() must not catch
                # this; it propagates to the replica router, which
                # requeues ``self._inflight`` on a survivor
                if rec is not None:
                    for ev in plan.drain_events():
                        rec.instant("fault." + ev["kind"], cat="fault",
                                    **ev)
                        rec.inc(obsm.FAULTS_INJECTED, kind=ev["kind"],
                                **self._rlabels())
                raise ReplicaDeath(
                    f"replica {plan.die_replica} died (denoise step "
                    f"{i})", replica=plan.die_replica, step=i)
            if plan is not None:
                if self._corrupt_active:
                    # the corrupt step is behind us: restore the wire
                    self._restore_codec()
                if plan.touches_health:
                    self.health.observe(plan.heartbeats(i, self.K))
                if plan.corrupt_fires(i):
                    self._activate_corrupt()
            if self._step_fault is not None:
                self._step_fault(i)
            if self.elastic:
                self._maybe_evict_straggler()
            if plan is not None:
                dead = plan.active_dead(i)
                if rec is not None:
                    # scripted drill events fired at this step (corrupt
                    # swaps, first-time group deaths) — NaN-guard trips
                    # happen inside compiled code, so the host-side
                    # count is the injected corrupt steps forcing them
                    for ev in plan.drain_events():
                        rec.instant("fault." + ev["kind"], cat="fault",
                                    **ev)
                        rec.inc(obsm.FAULTS_INJECTED, kind=ev["kind"],
                                **self._rlabels())
                if dead is not None:
                    # the group is gone and not (yet) evicted: the halo
                    # collective would hang on it — surface a
                    # recoverable fault so run() retries from the last
                    # boundary snapshot
                    raise ServingFault(
                        f"LP group {dead} stopped heartbeating "
                        f"(denoise step {i})", step=i)

        return hook

    def _denoise_batch(
        self, reqs: List[VideoRequest],
        snapshot: Optional[DenoiseSnapshot] = None,
    ) -> List[VideoResult]:
        t0 = perf_s()
        rec = self.recorder
        shape = reqs[0].latent_shape
        # service start on the lifecycle clock; setdefault so a
        # snapshot-resumed retry keeps the FIRST dispatch stamp (the
        # retry cost is visible as done - denoise_start growing)
        start_s = float(self.clock())
        for r in reqs:
            life = self._lifecycle.get(r.request_id)
            if life is not None:
                life.setdefault("denoise_start_s", start_s)
        with self._span("batch.draw", reqs):
            ctx = jax.tree.map(lambda *c: jnp.concatenate(c, axis=0),
                               *[r.context for r in reqs])
            cond = self._conditioning(ctx)
            guidance = jnp.float32(reqs[0].guidance)
            keys = [jax.random.PRNGKey(r.seed) for r in reqs]
            z_T = jnp.concatenate([
                jax.random.normal(k, (1, *shape, self.cfg.latent_channels))
                for k in keys
            ], axis=0)

        compiles0 = self._compiler.compiles
        try:
            with self._span("batch.denoise", reqs, size=len(reqs),
                            latent_shape=shape, steps=self.num_steps,
                            K=self.K, lp_impl=self.lp_impl):
                z0 = lp_denoise(
                    None, z_T, self._sampler, self.num_steps, self.K,
                    self.r, self.cfg.patch_sizes, (1, 2, 3),
                    uniform=self.uniform,
                    # read per dispatch: an eviction in the step hook
                    # moves the mesh, and the parameters with it
                    extras=lambda: (self._step_params(), *cond, guidance),
                    compiler=self._compiler,
                    step_hook=self._step_hook(), snapshot=snapshot,
                    recorder=rec,
                )
        finally:
            # a corrupt-wire drill must never outlive its batch (the
            # swap is one-step; a fault between swap and restore would
            # otherwise leak the corrupting codec into the next batch)
            self._restore_codec()
        wall = perf_s() - t0
        # a virtual lifecycle clock (load harness) advances by the
        # batch's MEASURED wall: arrivals follow the offered-load
        # process, service times are real — the standard open-loop
        # replay for a synchronous engine.  The perf clock (default)
        # has already advanced by exactly this much on its own.
        advance = getattr(self.clock, "advance", None)
        if advance is not None:
            advance(wall)
        if rec is not None:
            rec.observe(obsm.BATCH_WALL_S, wall, **self._rlabels())
            rec.inc(obsm.COMPILES, self._compiler.compiles - compiles0,
                    epoch=self._compiler.plan_epoch, **self._rlabels())
        return [
            VideoResult(r.request_id, z0[i : i + 1], self.num_steps,
                        batch_wall_s=wall, batch_size=len(reqs))
            for i, r in enumerate(reqs)
        ]

    # ------------------------------------------------- wire attribution
    def _step_codec_names(self) -> List[str]:
        """The codec name each forward pass runs under, resolved the
        same way ``lp_denoise`` resolves its runs (schedule against the
        sampler's sigma trajectory, else the fixed wire codec)."""
        if self._schedule is not None:
            from repro.policy.schedule import trajectory_sigmas

            sigmas = trajectory_sigmas(self._sampler, self.num_steps)
            return list(self._schedule.step_codecs(sigmas))
        return [self.codec.name] * self.num_steps

    def _record_batch_wire(self, shape: Tuple[int, int, int],
                           batch_size: int) -> None:
        """Derive the completed batch's per-step wire bytes by replaying
        ``comm_model`` over the recorded geometry/codec timelines
        (``repro.obs.account`` — exact per collective per tier, the
        repo-wide byte-model invariant).  Steps duplicated by
        snapshot-resumed retries are billed once, under the geometry
        their surviving execution used; the duplicated work shows up in
        ``serve.restarts``, not here."""
        rec = self.recorder
        if rec is None:
            return
        from repro.core.comm_model import VDMCommConfig
        from repro.obs.account import attribute_denoise_steps

        ccfg = VDMCommConfig(
            latent_dims=tuple(shape),
            latent_channels=self.cfg.latent_channels,
            patch_sizes=self.cfg.patch_sizes,
            d_model=self.cfg.d_model,
            num_blocks=self.cfg.num_layers,
            num_steps=self.num_steps,
        )
        # merge the codec timeline: latest event at or before each step
        codecs = list(self._batch_codecs)
        for from_step, names in self._codec_events:
            for i in range(from_step, self.num_steps + 1):
                codecs[i - 1] = names[i - 1]
        records = attribute_denoise_steps(
            ccfg, self.r, codecs, self._geom_events, tp=self.tp,
            wire_shard=self.wire_shard, lp_impl=self.lp_impl,
            links=rec.links, batch_size=batch_size,
        )
        rec.record_wire_steps(records)
        runs = rec.measured_runs[self._runs_mark:]
        if runs:
            from repro.obs.account import reconcile_segments

            rec.record_reconciliations(reconcile_segments(records, runs))

    # ------------------------------------------------ request lifecycle
    def _finalize_requests(self, results: List[VideoResult]) -> None:
        """Close each request's lifecycle: stamp ``done_s``, derive
        ``queue_wait_s`` / ``e2e_s`` (onto the :class:`VideoResult` and
        the lifecycle row), check the SLO deadline for the request's
        priority class, and hand the row to the recorder — which emits
        it as a ``request.lifecycle`` trace span and feeds the
        per-priority latency histograms.  All stamps share the engine
        clock, so under the load harness the row lives entirely on the
        workload's virtual timeline."""
        done_s = float(self.clock())
        rec = self.recorder
        for res in results:
            life = self._lifecycle.pop(res.request_id, None)
            if life is None:
                continue
            life["done_s"] = done_s
            life["queue_wait_s"] = life["admit_s"] - life["submit_s"]
            life["e2e_s"] = done_s - life["submit_s"]
            life["restarts"] = res.restarts
            res.queue_wait_s = life["queue_wait_s"]
            res.e2e_s = life["e2e_s"]
            if self.slo is not None:
                deadline = self.slo.deadline_for(life["priority"])
                life["deadline_s"] = (deadline
                                      if deadline != float("inf") else None)
                life["violated"] = bool(life["e2e_s"] > deadline)
            if rec is not None:
                rec.record_request(life)

    def run(self, max_batches: Optional[int] = None,
            max_restarts_per_batch: int = 2) -> List[VideoResult]:
        """Drain the queue.  A batch that fails with a *recoverable*
        fault (``DeviceFailure`` — lost hardware; ``ServingFault`` —
        group death / injected wire fault) retries from its last
        boundary snapshot, bounded by ``max_restarts_per_batch``.  Any
        other exception is a programming/XLA error and surfaces
        immediately instead of burning restarts on a deterministic
        failure."""
        out: List[VideoResult] = []
        batches = 0
        while self._queue and (max_batches is None or batches < max_batches):
            # draining: don't wait out the admission age, force-launch
            reqs = self._next_batch(force=True)
            if not reqs:
                break
            # visible to the replica router: if this batch dies with the
            # replica (ReplicaDeath propagates — it is deliberately not
            # a ServingFault, a dead replica cannot retry itself) the
            # router requeues these requests elsewhere.  Cleared only on
            # success, so a terminal ServingFault leaves them readable
            # too (the router may still redispatch them).
            self._inflight = list(reqs)
            restarts = 0
            resumed_from = 0
            snapshot = DenoiseSnapshot() if self.snapshots else None
            rec = self.recorder
            # fresh attribution timelines for this batch (retries keep
            # appending to them: the timeline describes the geometry of
            # each logical step's SURVIVING execution)
            self._geom_events = [(1, self.K)]
            self._codec_events = []
            self._batch_codecs = self._step_codec_names()
            self._runs_mark = 0 if rec is None else len(rec.measured_runs)
            while True:
                try:
                    results = self._denoise_batch(reqs, snapshot)
                    with self._span("batch.finalize", reqs):
                        for res in results:
                            res.restarts = restarts
                            res.resumed_from_step = resumed_from
                        self._finalize_requests(results)
                        self._inflight = []
                        out.extend(results)
                        self._record_batch_wire(reqs[0].latent_shape,
                                                len(reqs))
                        if rec is not None:
                            rec.inc(obsm.BATCHES, **self._rlabels())
                    break
                except (DeviceFailure, ServingFault) as e:
                    restarts += 1
                    step = getattr(e, "step", None)
                    if snapshot is not None and step is not None:
                        self.last_steps_lost = max(
                            0, int(step) - 1 - snapshot.step)
                    resumed_from = 0 if snapshot is None else snapshot.step
                    if rec is not None:
                        rec.instant("batch.restart", cat="serve",
                                    restarts=restarts,
                                    fault=str(e),
                                    resume_from=resumed_from,
                                    **self._rlabels())
                        rec.inc(obsm.RESTARTS, **self._rlabels())
                    if restarts > max_restarts_per_batch:
                        # terminal: this batch will never be finalized
                        # — drop its lifecycle rows (a later reused
                        # request_id must not inherit stale stamps)
                        # with a failed-lifecycle marker in the trace
                        failed_s = float(self.clock())
                        for r in reqs:
                            life = self._lifecycle.pop(
                                r.request_id, None)
                            if rec is not None and life is not None:
                                rec.instant(
                                    "request.failed", cat="serve",
                                    request_id=r.request_id,
                                    priority=life["priority"],
                                    submit_s=life["submit_s"],
                                    failed_s=failed_s,
                                    restarts=restarts, fault=str(e))
                        raise
            batches += 1
        return out
