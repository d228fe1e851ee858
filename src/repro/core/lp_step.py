"""LP denoising engines: reference loop + the compiled fast path.

One LP forward pass = dynamic rotating partition -> parallel denoising ->
position-aware latent reconstruction (paper §3.2 workflow, Fig. 3).

Two loop drivers live here:

* :func:`lp_denoise_reference` — the original eager loop.  The denoiser
  for step ``i`` is a fresh Python closure with the timestep baked in, so
  nothing is (or can be) cached across steps.  Kept as the semantics
  oracle and the benchmark baseline.
* :func:`lp_denoise` + :class:`LPStepCompiler` — the production path.
  Timestep, scheduler scalars, and conditioning are **traced arguments**,
  so one jitted step function serves every timestep that shares a rotation
  dim; the compiled-step cache is keyed on (latent geometry, rotation dim,
  K, r, uniform, arg signatures) and ``z`` is donated.  Consecutive
  same-dim steps fuse into one ``lax.scan``.  A T-step denoise compiles at
  most once per rotation dim (<= 3 traces) instead of T times.

The production SPMD engines (``core/spmd.py``) plug in via the
``forward`` hook; both are cross-checked in tests.
"""
from __future__ import annotations

import dataclasses
import time
from collections import OrderedDict
from typing import Any, Callable, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs.trace import span

from .partition import PartitionPlan, extract, plan_partition
from .reconstruct import reconstruct
from .schedule import rotation_dim, usable_dims
from .uniform import UniformPlan, plan_uniform

# Reference-engine denoiser: maps a sub-latent (same rank as the latent)
# to its noise prediction of identical shape, timestep baked in.
DenoiseFn = Callable[[jnp.ndarray], jnp.ndarray]

# Fast-path denoiser: (window, t, *extras) -> pred, where ``t`` is a traced
# f32 scalar and ``extras`` carry traced conditioning (text context, CFG
# scale, ...).  CFG lives *inside* the fn (paper Eq. 4).
DenoiseStepFn = Callable[..., jnp.ndarray]


@dataclasses.dataclass
class DenoiseSnapshot:
    """Mid-denoise recovery point, recorded at dim-rotation / codec-
    segment boundaries.

    Pass one to :func:`lp_denoise` (the serving engine keeps one per
    batch attempt): after every completed scan run — a maximal stretch
    of same-dim, same-codec-segment steps — the latent and the step
    index are recorded here, and a later :func:`lp_denoise` call with
    the same snapshot resumes from that boundary instead of ``z_T``,
    bounding lost work to at most one dim-run.

    Why ``(z, step)`` is the WHOLE state: residual-codec wire state is
    re-zeroed at exactly these boundaries (dim switch, segment switch,
    re-plan — see ``LPStepCompiler.init_codec_state``), so the codec
    state to resume with is definitionally the fresh init the resumed
    run performs anyway — a boundary resume replays the fault-free
    arithmetic bit-for-bit.  ``z`` is kept as a HOST copy: it must
    survive both buffer donation by the next compiled step and the loss
    of the device that failed.
    """

    step: int = 0                       # last completed denoise step
    z: Optional[np.ndarray] = None      # host-resident latent at ``step``
    plan_epoch: int = 0                 # compiler epoch when recorded
    boundaries: int = 0                 # records taken (monitoring)
    resumes: int = 0                    # times a denoise resumed from here

    def record(self, step: int, z, plan_epoch: int = 0) -> None:
        self.step = int(step)
        self.z = np.asarray(z)
        self.plan_epoch = int(plan_epoch)
        self.boundaries += 1

    def clear(self) -> None:
        self.step, self.z, self.plan_epoch = 0, None, 0


def lp_forward(
    denoise_fn: DenoiseFn,
    z: jnp.ndarray,
    plan: PartitionPlan,
    axis: int,
) -> jnp.ndarray:
    """One LP forward pass with a prebuilt (paper-exact) partition plan."""
    preds = []
    for k in range(plan.num_partitions):
        with jax.named_scope("lp.window"):
            sub = extract(z, plan, k, axis)
        pred = denoise_fn(sub)
        if pred.shape != sub.shape:
            raise ValueError(
                f"denoise_fn changed the sub-latent shape: {sub.shape} -> {pred.shape}"
            )
        preds.append(pred)
    return reconstruct(preds, plan, axis)


def lp_forward_uniform(
    denoise_fn: DenoiseFn,
    z: jnp.ndarray,
    plan: UniformPlan,
    axis: int,
    use_kernel: Optional[bool] = None,
) -> jnp.ndarray:
    """One LP forward pass on uniform windows, batched with vmap.

    This mirrors what every SPMD rank does: slice a fixed-size window,
    denoise, weight, scatter-add; here the K ranks are a vmapped leading
    axis and the reduction runs through ``spmd.blend_windows`` (which on
    TPU dispatches the fused Pallas stitch kernel — ``use_kernel``
    overrides the backend default).
    """
    from .spmd import blend_windows, stack_windows

    windows = stack_windows(z, plan, axis)
    preds = jax.vmap(denoise_fn)(windows)
    return blend_windows(preds, plan, axis, use_kernel=use_kernel).astype(z.dtype)


# ------------------------------------------------------------ compiled path
def _abstract_sig(tree: Any) -> Tuple:
    """Hashable (treedef, shapes/dtypes) signature of a pytree of arrays."""
    leaves, treedef = jax.tree.flatten(tree)
    return (
        treedef,
        tuple((jnp.shape(l), jnp.result_type(l).name) for l in leaves),
    )


def _abstract(x) -> jax.ShapeDtypeStruct:
    """An argument as ``jit`` saw it: shape, dtype and, for an array
    committed to its devices, its sharding.  Readable from a donated
    (deleted) array too."""
    committed = isinstance(x, jax.Array) and x.committed
    return jax.ShapeDtypeStruct(np.shape(x), jnp.result_type(x),
                                sharding=x.sharding if committed else None)


# rotation dim -> the name of its step program (``jit_lp_step_T`` in HLO
# and in a profile's ``XLA Modules`` line)
DIM_NAMES = "THW"


class LPStepCompiler:
    """LRU cache of jitted LP step functions.

    One entry per ``(z geometry, rotation dim, scan length, K, r, uniform,
    scalars/extras signature)``.  The built step takes ``(z, t, scalars,
    extras)`` with everything but the static partition geometry traced, and
    donates ``z`` so the latent updates in place across the T-step loop.

    ``forward`` overrides the per-step LP engine, e.g.
    ``lambda fn, z, plan, axis: lp_forward_halo(fn, z, plan, axis, mesh)``
    to run the halo-exchange collective inside the compiled step.

    ``codec`` (a ``comm.codecs`` name or instance) compresses the LP
    wire payloads.  Stateless codecs (bf16/int8/int4) only change the
    per-step forward; residual codecs carry state (previous decoded
    slabs + error-feedback carries) which this cache threads through the
    ``lax.scan`` carry — never through re-traced closures — so a T-step
    denoise still compiles at most once per rotation dim.  With a codec
    and no custom ``forward``, steps run through
    ``comm.wire.simulate_halo_forward`` (the single-process mirror of
    the halo collective; pass a mesh-bound ``forward`` for real SPMD,
    stateful hooks take/return ``(pred, state)``).

    ``mesh_shape`` records the ``(lp, tp)`` mesh the ``forward`` hook is
    bound to (e.g. ``(M, T)`` for the hybrid engine).  It is part of the
    cache key together with the full partition geometry ``(K, r)``, so a
    mid-request :meth:`replan` — straggler eviction, elastic mesh change
    — can NEVER be served a stale entry compiled for the old mesh shape.

    ``schedule`` (a ``policy.CodecSchedule`` or spec string) varies the
    wire codec over the denoise: ``lp_denoise`` resolves the sigma
    thresholds against the sampler's trajectory and runs each (dim-run x
    codec-segment) as its own ``lax.scan``, passing the segment codec to
    :meth:`step_fn` per call.  The segment codec is part of the cache
    key, residual state is created fresh per segment (reset exactly once
    at every boundary), and compiles stay <= 3 x num_segments per
    denoise.  ``forward_factory`` is the scheduled twin of ``forward``:
    called with each segment's codec, it returns the mesh-bound hook for
    that codec (stateless hooks take ``(fn, z, plan, axis)``, stateful
    ones ``(fn, z, plan, axis, state)`` and return ``(pred, state)``).
    """

    def __init__(
        self,
        denoise_fn: DenoiseStepFn,
        update_fn: Callable[[jnp.ndarray, jnp.ndarray, Any], jnp.ndarray],
        num_partitions: int,
        overlap_ratio: float,
        patch_sizes: Sequence[int],
        spatial_axes: Sequence[int] = (1, 2, 3),
        uniform: bool = False,
        forward: Optional[Callable] = None,
        use_kernel: Optional[bool] = None,
        donate: bool = True,
        maxsize: int = 32,
        codec=None,
        mesh_shape: Optional[Tuple[int, ...]] = None,
        schedule=None,
        forward_factory: Optional[Callable] = None,
        wire_shard: bool = False,
        nan_guard: bool = False,
    ):
        self.denoise_fn = denoise_fn
        self.update_fn = update_fn
        self.num_partitions = num_partitions
        self.overlap_ratio = overlap_ratio
        self.patch_sizes = tuple(patch_sizes)
        self.spatial_axes = tuple(spatial_axes)
        self.uniform = uniform
        self.forward = forward
        self.use_kernel = use_kernel
        self.donate = donate
        self.maxsize = maxsize
        self.mesh_shape = None if mesh_shape is None else tuple(mesh_shape)
        self.forward_factory = forward_factory
        # records whether the bound forward hooks run the tp-sharded
        # wire (core/hybrid.lp_forward_halo_hybrid(wire_shard=True));
        # part of the cache key so a replan that swaps the hook for a
        # differently-wired one can never be served a stale entry
        self.wire_shard = bool(wire_shard)
        # arm the wire decode NaN/Inf guard on the simulate mirror
        # (mesh-bound hooks carry their own flag).  Fixed for the
        # compiler's lifetime — identity on finite wires, so it is NOT
        # part of the cache key
        self.nan_guard = bool(nan_guard)
        if schedule is not None:
            from repro.policy.schedule import parse_schedule

            schedule = parse_schedule(schedule)
            if codec is not None:
                raise ValueError(
                    "pass codec= (fixed) or schedule= (sigma-varying), "
                    "not both"
                )
            if forward is not None and forward_factory is None:
                raise ValueError(
                    "a codec schedule cannot run through a fixed "
                    "forward= hook (it is bound to one codec and would "
                    "silently ignore the segments) — pass a "
                    "forward_factory that binds the hook per segment "
                    "codec"
                )
            if not uniform and forward_factory is None:
                raise ValueError(
                    "codec schedules need the uniform-window halo "
                    "geometry (uniform=True) or a forward_factory hook"
                )
        self.schedule = schedule
        if codec is not None:
            from repro.comm.codecs import get_codec

            codec = get_codec(codec)
            if not uniform and forward is None:
                raise ValueError(
                    "wire codecs need the uniform-window halo geometry "
                    "(uniform=True) or a custom forward hook"
                )
        self.codec = codec
        self._cache: "OrderedDict[Tuple, Callable]" = OrderedDict()
        # id(step program) -> (program, abstract args of each executable
        # it compiled), for :meth:`programs`
        self._ran: dict = {}
        self.compiles = 0
        self.hits = 0
        # re-planning bookkeeping: the epoch bumps on every geometry
        # change so in-flight loops (lp_denoise) reset codec residual
        # state exactly once at the next step boundary; state_inits
        # counts init_codec_state calls (regression-tested).
        self.plan_epoch = 0
        self.state_inits = 0

    def replan(
        self,
        num_partitions: Optional[int] = None,
        overlap_ratio: Optional[float] = None,
        mesh_shape: Optional[Tuple[int, ...]] = None,
        forward: Optional[Callable] = None,
        forward_factory: Optional[Callable] = None,
        wire_shard: Optional[bool] = None,
    ) -> bool:
        """Mid-request re-plan: swap the partition geometry / mesh shape.

        Safe to call from a ``lp_denoise`` ``step_hook`` (straggler- or
        elasticity-triggered): the full geometry ``(K, r, mesh_shape)``
        is part of the step-cache key, so entries compiled for the old
        plan can never be hit again (they age out of the LRU), and the
        ``plan_epoch`` bump makes the in-flight denoise loop re-derive
        its rotation dims and re-zero codec residual state exactly once
        — old-geometry state shapes would be garbage on the new plan.
        Returns True when anything actually changed.
        """
        if wire_shard is not None and bool(wire_shard) != self.wire_shard:
            # a mesh-bound hook closes over its wire layout: flipping
            # the flag without re-binding would key (and report) the new
            # wire while executing the old one — same stale-hook hazard
            # replan_lp_compiler raises for on a K change.  Checked
            # before any mutation so a raise leaves the plan untouched.
            if (self.forward is not None and forward is None) or \
                    (self.forward_factory is not None and
                     forward_factory is None):
                raise ValueError(
                    "changing wire_shard on a compiler with a bound "
                    "forward hook needs a re-bound forward= / "
                    "forward_factory= in the same replan call"
                )
        changed = False
        if num_partitions is not None and num_partitions != self.num_partitions:
            self.num_partitions = num_partitions
            changed = True
        if overlap_ratio is not None and overlap_ratio != self.overlap_ratio:
            self.overlap_ratio = overlap_ratio
            changed = True
        if mesh_shape is not None and tuple(mesh_shape) != self.mesh_shape:
            self.mesh_shape = tuple(mesh_shape)
            changed = True
        if forward is not None and forward is not self.forward:
            # a new mesh needs a re-bound collective hook
            self.forward = forward
            changed = True
        if forward_factory is not None and \
                forward_factory is not self.forward_factory:
            self.forward_factory = forward_factory
            changed = True
        if wire_shard is not None and bool(wire_shard) != self.wire_shard:
            self.wire_shard = bool(wire_shard)
            changed = True
        if changed:
            self.plan_epoch += 1
        return changed

    @property
    def stateful(self) -> bool:
        return self.codec is not None and self.codec.stateful

    def _codec_for(self, codec):
        """Per-call codec resolution: ``None`` means the compiler's own
        fixed codec (legacy behaviour); segment codecs come in as Codec
        instances (or names) from the schedule-resolved denoise loop."""
        if codec is None:
            return self.codec
        from repro.comm.codecs import get_codec

        return get_codec(codec)

    # ------------------------------------------------------------- plans
    def _plan(self, dim: int, extent: int):
        if self.uniform:
            return plan_uniform(
                extent, self.patch_sizes[dim], self.num_partitions,
                self.overlap_ratio, dim,
            )
        return plan_partition(
            extent, self.patch_sizes[dim], self.num_partitions,
            self.overlap_ratio, dim,
        )

    def _forward(self, fn: DenoiseFn, z, plan, axis, codec=None):
        codec = self._codec_for(codec)
        if self.forward_factory is not None and codec is not None:
            return self.forward_factory(codec)(fn, z, plan, axis)
        if self.forward is not None:
            return self.forward(fn, z, plan, axis)
        if codec is not None:
            from repro.comm.wire import simulate_halo_forward

            return simulate_halo_forward(fn, z, plan, axis, codec,
                                         nan_guard=self.nan_guard)
        if self.uniform:
            return lp_forward_uniform(fn, z, plan, axis, use_kernel=self.use_kernel)
        return lp_forward(fn, z, plan, axis)

    def _forward_stateful(self, fn: DenoiseFn, z, plan, axis, state,
                          codec=None):
        """Codec-state-threading forward: returns (pred, new_state)."""
        codec = self._codec_for(codec)
        if self.forward_factory is not None:
            return self.forward_factory(codec)(fn, z, plan, axis, state)
        if self.forward is not None:
            return self.forward(fn, z, plan, axis, state)
        from repro.comm.wire import simulate_halo_forward

        return simulate_halo_forward(fn, z, plan, axis, codec, state,
                                     nan_guard=self.nan_guard)

    def init_codec_state(self, dim: int, z: jnp.ndarray, codec=None):
        """Zeroed residual-codec state for (rotation dim, latent geometry).

        ``lp_denoise`` creates this fresh at the start of every same-dim,
        same-codec-segment scan run (temporal deltas are only meaningful
        between consecutive steps along one rotation dim, and a segment
        boundary switches the wire protocol) — which also guarantees no
        codec state leaks across serving requests."""
        codec = self._codec_for(codec)
        if codec is None or not codec.stateful:
            return None
        from repro.comm.wire import init_halo_wire_state
        from repro.distributed.collectives import halo_spec

        self.state_inits += 1
        axis = self.spatial_axes[dim]
        plan = self._plan(dim, z.shape[axis])
        rest = tuple(s for i, s in enumerate(z.shape) if i != axis)
        return init_halo_wire_state(codec, halo_spec(plan), rest)

    # ------------------------------------------------------------- build
    def step_fn(
        self, dim: int, z: jnp.ndarray, n: int, scalars: Any, extras: Tuple,
        codec=None,
    ) -> Callable:
        codec = self._codec_for(codec)
        key = (
            dim, n, tuple(z.shape), jnp.result_type(z).name,
            _abstract_sig(scalars), _abstract_sig(extras),
            None if codec is None else codec.name,
            # full plan geometry + epoch: a mid-request replan (new K/r,
            # new mesh shape, re-bound forward hook) can never be served
            # an entry compiled for the old plan
            self.num_partitions, self.overlap_ratio, self.mesh_shape,
            self.wire_shard, self.plan_epoch,
        )
        cached = self._cache.get(key)
        if cached is not None:
            self._cache.move_to_end(key)
            self.hits += 1
            return cached
        axis = self.spatial_axes[dim]
        plan = self._plan(dim, z.shape[axis])
        den = self.denoise_fn

        def upd(z, pred, sc):
            with jax.named_scope("lp.update"):
                return self.update_fn(z, pred, sc)

        if codec is not None and codec.stateful:
            # codec state rides the scan carry next to z — the step stays
            # one compiled function per (rotation dim, codec segment)
            if n == 1:
                def step(zc, st, t, sc, extras):
                    pred, st = self._forward_stateful(
                        lambda w: den(w, t, *extras), zc, plan, axis, st,
                        codec,
                    )
                    return upd(zc, pred, sc), st
            else:
                def step(zc, st, ts, scs, extras):
                    def body(carry, x):
                        zb, s = carry
                        t, sc = x
                        pred, s = self._forward_stateful(
                            lambda w: den(w, t, *extras), zb, plan, axis, s,
                            codec,
                        )
                        return (upd(zb, pred, sc), s), None
                    (out, st), _ = jax.lax.scan(body, (zc, st), (ts, scs))
                    return out, st
        elif n == 1:
            def step(zc, t, sc, extras):
                pred = self._forward(
                    lambda w: den(w, t, *extras), zc, plan, axis, codec
                )
                return upd(zc, pred, sc)
        else:
            def step(zc, ts, scs, extras):
                def body(zb, x):
                    t, sc = x
                    pred = self._forward(
                        lambda w: den(w, t, *extras), zb, plan, axis, codec
                    )
                    return upd(zb, pred, sc), None
                out, _ = jax.lax.scan(body, zc, (ts, scs))
                return out

        step.__name__ = step.__qualname__ = f"lp_step_{DIM_NAMES[dim]}"
        fn = jax.jit(step, donate_argnums=(0,) if self.donate else ())
        self._cache[key] = fn
        if len(self._cache) > self.maxsize:
            _, old = self._cache.popitem(last=False)
            self._ran.pop(id(old), None)
        self.compiles += 1
        return fn

    def dispatch(self, fn: Callable, *args):
        """Run a step program from :meth:`step_fn`.  When the call
        compiles a new executable (a new program, or jit retracing one
        for arguments placed differently), its abstract arguments are
        kept for :meth:`programs`; a warm call only compares two counts.
        """
        n = fn._cache_size()
        out = fn(*args)
        if fn._cache_size() != n:
            ran = self._ran.setdefault(id(fn), (fn, []))
            ran[1].append(jax.tree.map(_abstract, args))
        return out

    def programs(self) -> list:
        """``[(module name, optimized HLO text)]`` of every executable
        the step programs in the cache compiled, for mapping a profile's
        device ops to their source scopes (``repro.obs.scopes``).

        Lowers and compiles each one again from its abstract arguments:
        a load from JAX's in-memory or persistent compilation cache
        where it holds the program, a full compile otherwise.  Call it
        outside any timed window.
        """
        out = []
        for fn, sigs in self._ran.values():
            for sig in sigs:
                text = fn.lower(*sig).compile().as_text()
                out.append((text.split(None, 2)[1].rstrip(","), text))
        return out


def lp_denoise(
    denoise_fn: Optional[DenoiseStepFn],
    z_T: jnp.ndarray,
    sampler,
    num_steps: int,
    num_partitions: int,
    overlap_ratio: float,
    patch_sizes: Sequence[int],
    spatial_axes: Sequence[int],
    uniform: bool = False,
    extras: Union[Tuple, Callable[[], Tuple]] = (),
    compiler: Optional[LPStepCompiler] = None,
    fuse_scan: bool = True,
    step_hook: Optional[Callable[[int], None]] = None,
    codec=None,
    schedule=None,
    snapshot: Optional[DenoiseSnapshot] = None,
    recorder=None,
) -> jnp.ndarray:
    """Full T-step LP denoising on the compiled fast path.

    ``denoise_fn(window, t, *extras)`` takes the timestep (and any
    conditioning in ``extras``) as traced arguments.  ``extras`` may also
    be a zero-argument callable returning that tuple, read at every
    dispatch: a ``step_hook`` that moves the mesh can then hand the next
    step arguments placed on the new one.  ``sampler`` provides
    ``timestep(i)`` / ``step_scalars(i)`` / ``update(z, pred, scalars)``
    (see ``diffusion/sampler.py``).  Pass a prebuilt ``compiler`` to reuse
    compiled steps across calls (the serving engine does, across batches);
    otherwise one is created for this call — either way a run traces at
    most once per rotation dim.  ``step_hook(i)`` fires outside the
    compiled region (fault injection, straggler accounting); setting it
    disables scan fusion so the hook really does run between steps.

    ``codec`` compresses LP wire payloads; ``schedule`` (a
    ``policy.CodecSchedule`` or spec string, mutually exclusive with
    ``codec``) varies the codec over sigma — both are ignored when
    ``compiler`` is given (the compiler owns the policy then).  A
    schedule is resolved against the sampler's sigma trajectory and
    executed as **segmented scans**: every (rotation-dim run x codec
    segment) is one compiled step / one ``lax.scan``, so a T-step
    denoise compiles at most ``3 x num_segments`` times.  Residual-codec
    state is zeroed at every rotation-dim switch, at every codec-segment
    boundary (exactly once per boundary), and at every mid-request
    re-plan (exactly once), and discarded at the end of the call:
    temporal deltas only span consecutive same-dim, same-segment steps —
    whether fused into one scan or stepped through a hook — and state
    can never leak across calls (or serving requests).  A ``step_hook``
    may call ``compiler.replan(...)`` (straggler / elastic re-planning):
    the next step re-derives its rotation dims and compiles against the
    new geometry; stale cache entries for the old plan are unreachable.

    ``snapshot`` (a :class:`DenoiseSnapshot`) arms boundary
    checkpointing: the latent is recorded (host copy) after every
    completed run — dim switch, codec-segment switch, or re-plan — and
    a call whose snapshot already holds a recorded step resumes from it
    (skipping steps ``<= snapshot.step``) instead of starting at
    ``z_T``.  The serving engine's failed-batch retry rides this: lost
    work is bounded by one dim-run, and because boundaries are exactly
    where residual codec state is re-zeroed, a boundary resume replays
    the fault-free arithmetic bit-for-bit.  A resume after a re-plan is
    fine too — the snapshot holds the full (geometry-independent)
    latent, and the resumed steps re-derive dims from the compiler's
    current K.

    Every compiled dispatch runs inside a ``denoise.run`` span
    (``denoise.step`` on the unfused path; ``dim`` and the step range in
    its args) and every boundary copy inside a ``snapshot.record`` span,
    through ``repro.obs.trace.span``: on the profiler's clock always, so
    a device profile shows what the host did between steps.

    ``recorder`` (a ``repro.obs.FlightRecorder``) adds the spans to its
    Chrome trace and feeds the run/step latency histograms.  It is pure
    host state — NEVER passed into the jitted step and never part of the
    compile cache key — so enabling it can change neither compile counts
    nor numerics (``benchmarks/obs_overhead.py`` gates both).  Per-step
    wire bytes are NOT probed here: the serving engine derives them by
    replaying ``comm_model`` (``repro.obs.account``) against the
    executed geometry.  With a recorder, and only then, each dispatch
    blocks on its value before its span closes, so the run wall that
    ``record_run`` keeps (``denoise.run_s``, and the measured side of
    ``wire.reconcile``) is device time and not the enqueue.
    """
    if step_hook is not None:
        fuse_scan = False
    get_extras = extras if callable(extras) else (lambda: extras)
    trace = getattr(recorder, "trace", None)
    comp = compiler
    if comp is None:
        if denoise_fn is None:
            raise ValueError("need denoise_fn when no compiler is given")
        comp = LPStepCompiler(
            denoise_fn, sampler.update, num_partitions, overlap_ratio,
            patch_sizes, spatial_axes, uniform=uniform, codec=codec,
            schedule=schedule,
        )

    # Resolve the (possibly absent) codec schedule to one codec per
    # forward pass.  ``None`` entries mean "the compiler's fixed codec"
    # — the legacy path, bit-identical to pre-schedule behaviour.
    active_schedule = comp.schedule
    if active_schedule is not None:
        from repro.comm.codecs import get_codec as _get_codec
        from repro.policy.schedule import trajectory_sigmas

        _sigmas = trajectory_sigmas(sampler, num_steps)
        step_codecs = [
            _get_codec(n) for n in active_schedule.step_codecs(_sigmas)
        ]
    else:
        step_codecs = [None] * num_steps

    def _codec_key(c):
        return None if c is None else c.name

    def _stateful(c):
        return comp.stateful if c is None else c.stateful

    def _dims():
        # from the compiler's CURRENT geometry: a step_hook may replan K
        # mid-request (runtime/straggler + runtime/elastic)
        dims = usable_dims(
            [z_T.shape[comp.spatial_axes[d]] for d in range(3)],
            comp.patch_sizes,
            comp.num_partitions,
        )
        if not dims:
            raise ValueError(
                f"no latent dim has >= {comp.num_partitions} patches; reduce K"
            )
        return dims

    def _snapshot(step: int, z, epoch: int) -> None:
        # the host copy of the latent: a sync with the device
        with span("snapshot.record", trace, step=step, epoch=epoch):
            snapshot.record(step, z, epoch)
        if recorder is not None:
            recorder.record_snapshot(step)

    dims = _dims()
    start = 0
    if snapshot is not None and snapshot.z is not None and snapshot.step > 0:
        # resume from the last boundary: fresh device buffer from the
        # host copy (donation-safe; the snapshot itself is untouched, so
        # a second resume from the same boundary also works)
        start = min(int(snapshot.step), num_steps)
        snapshot.resumes += 1
        if recorder is not None:
            recorder.record_resume(start)
        z = jnp.asarray(snapshot.z).astype(z_T.dtype)
    else:
        # private copy: the first step donates its input buffer, and the
        # caller's z_T must survive the call
        z = jnp.array(z_T, copy=True) if comp.donate else jnp.asarray(z_T)

    if fuse_scan:
        # group consecutive same-dim, same-codec-segment steps into
        # scan-fused runs; codec state is zeroed per run (consecutive
        # runs switch dims or cross a segment boundary, and neither
        # dim-foreign nor protocol-foreign state may carry over)
        runs: list = []
        for i in range(1, num_steps + 1):
            dim = rotation_dim(i, dims)
            ck = _codec_key(step_codecs[i - 1])
            if runs and runs[-1][0] == (dim, ck):
                runs[-1][1].append(i)
            else:
                runs.append(((dim, ck), [i]))
        for (dim, ck), idxs in runs:
            # resume support: runs at or before the snapshot boundary are
            # already done.  (A run can straddle ``start`` only when the
            # snapshot was taken under a different geometry — e.g. an
            # eviction changed the usable dims — the leftover steps run
            # as a sub-run with fresh state, which error feedback
            # absorbs.)
            idxs = [i for i in idxs if i > start]
            if not idxs:
                continue
            seg_codec = step_codecs[idxs[0] - 1]
            stateful = _stateful(seg_codec)
            ts = [np.float32(sampler.timestep(i)) for i in idxs]
            scs = [sampler.step_scalars(i) for i in idxs]
            st = comp.init_codec_state(dim, z, seg_codec) if stateful else None
            ck_name = ck or getattr(comp.codec, "name", "none")
            t0 = time.perf_counter()
            with span("denoise.run", trace, cat="denoise", dim=dim,
                      codec=ck_name, start=idxs[0], stop=idxs[-1],
                      n=len(idxs), epoch=comp.plan_epoch):
                extras = get_extras()
                if len(idxs) == 1:
                    t, sc = ts[0], scs[0]
                else:
                    t = jnp.asarray(np.stack(ts))
                    sc = jax.tree.map(
                        lambda *xs: jnp.asarray(np.stack(xs)), *scs
                    )
                fn = comp.step_fn(dim, z, len(idxs), sc, extras,
                                  codec=seg_codec)
                if stateful:
                    z, _ = comp.dispatch(fn, z, st, t, sc, extras)
                else:
                    z = comp.dispatch(fn, z, t, sc, extras)
                if recorder is not None:
                    jax.block_until_ready(z)
            if recorder is not None:
                recorder.record_run(idxs[0], idxs[-1],
                                    time.perf_counter() - t0,
                                    dim=dim, codec=ck_name,
                                    epoch=comp.plan_epoch)
            if snapshot is not None and idxs[-1] < num_steps:
                _snapshot(idxs[-1], z, comp.plan_epoch)
        return z

    # Unfused (step_hook) path: one compiled step per call, codec state
    # carried across consecutive same-dim, same-segment steps (temporal
    # deltas stay meaningful between steps) and reset on a dim switch, a
    # codec-segment boundary, or a re-plan.  The hook may call
    # ``comp.replan(...)``: the epoch bump re-derives the rotation dims
    # and resets residual state exactly once — old state shapes would be
    # garbage on the new plan.
    cur_state = None
    cur_dim = None
    cur_codec_key = None
    cur_epoch = comp.plan_epoch
    for i in range(start + 1, num_steps + 1):
        if step_hook is not None:
            step_hook(i)
        if comp.plan_epoch != cur_epoch:      # mid-request re-plan
            cur_epoch = comp.plan_epoch
            dims = _dims()
            cur_state, cur_dim = None, None
            if recorder is not None:
                recorder.record_replan(i, comp.num_partitions, cur_epoch)
            if snapshot is not None and i - 1 >= max(start, 1):
                # a re-plan is a boundary too (state re-zeroes here):
                # record the pre-replan latent so a failure during the
                # first post-replan step resumes right before it.  The
                # ``i == start + 1`` case (a replan firing on the FIRST
                # resumed step) must re-record too: ``z`` equals the
                # snapshot's latent then, but the record re-stamps the
                # boundary with the NEW epoch — a second fault resumes
                # from a boundary whose epoch matches the geometry its
                # replay will re-derive, never a pre-replan stamp.
                _snapshot(i - 1, z, cur_epoch)
        dim = rotation_dim(i, dims)
        seg_codec = step_codecs[i - 1]
        ck = _codec_key(seg_codec)
        stateful = _stateful(seg_codec)
        t = np.float32(sampler.timestep(i))
        sc = sampler.step_scalars(i)
        if stateful and (cur_state is None or dim != cur_dim
                         or ck != cur_codec_key):
            cur_state = comp.init_codec_state(dim, z, seg_codec)
        cur_dim, cur_codec_key = dim, ck
        ck_name = ck or getattr(comp.codec, "name", "none")
        t0 = time.perf_counter()
        with span("denoise.step", trace, cat="denoise", dim=dim, step=i,
                  codec=ck_name, epoch=comp.plan_epoch):
            extras = get_extras()
            fn = comp.step_fn(dim, z, 1, sc, extras, codec=seg_codec)
            if stateful:
                z, cur_state = comp.dispatch(fn, z, cur_state, t, sc,
                                             extras)
            else:
                z = comp.dispatch(fn, z, t, sc, extras)
            if recorder is not None:
                jax.block_until_ready(z)
        if recorder is not None:
            recorder.record_run(i, i, time.perf_counter() - t0,
                                dim=dim, codec=ck_name,
                                epoch=comp.plan_epoch)
        if snapshot is not None and i < num_steps:
            nxt = rotation_dim(i + 1, dims)
            nxt_ck = _codec_key(step_codecs[i])
            if nxt != dim or nxt_ck != ck:    # step i ends a run
                _snapshot(i, z, comp.plan_epoch)
    return z


# ---------------------------------------------------------- reference loop
def lp_denoise_reference(
    denoise_fn_for_step: Callable[[int, int], DenoiseFn],
    z_T: jnp.ndarray,
    scheduler_update: Callable[[jnp.ndarray, jnp.ndarray, int], jnp.ndarray],
    num_steps: int,
    num_partitions: int,
    overlap_ratio: float,
    patch_sizes: Sequence[int],
    spatial_axes: Sequence[int],
    uniform: bool = False,
) -> jnp.ndarray:
    """The original eager T-step loop (paper Fig. 3, Eqs. 3-6).

    ``denoise_fn_for_step(i, dim)`` returns the guided denoiser for forward
    pass ``i`` (1-indexed) with the timestep baked into the closure;
    ``scheduler_update(z, pred, i)`` is S(.) of Eq. 6.  Every step builds a
    fresh closure, so nothing caches — this is the semantics oracle the
    compiled path is tested against, and the benchmark baseline.
    """
    dims = usable_dims(
        [z_T.shape[spatial_axes[d]] for d in range(3)],
        patch_sizes,
        num_partitions,
    )
    if not dims:
        raise ValueError(
            f"no latent dim has >= {num_partitions} patches; reduce K"
        )
    z = z_T
    for i in range(1, num_steps + 1):
        dim = rotation_dim(i, dims)
        axis = spatial_axes[dim]
        fn = denoise_fn_for_step(i, dim)
        if uniform:
            plan = plan_uniform(
                z.shape[axis], patch_sizes[dim], num_partitions, overlap_ratio, dim
            )
            pred = lp_forward_uniform(fn, z, plan, axis)
        else:
            plan = plan_partition(
                z.shape[axis], patch_sizes[dim], num_partitions, overlap_ratio, dim
            )
            pred = lp_forward(fn, z, plan, axis)
        z = scheduler_update(z, pred, i)
    return z
