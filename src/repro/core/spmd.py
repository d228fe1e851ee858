"""SPMD LP engine — Latent Parallelism on a TPU mesh axis.

TPU adaptation of the paper's master/scatter-gather workflow (DESIGN.md §2):

* the latent is **replicated** along the lp mesh axis, so the "dynamic
  rotating partition" (scatter) is a *local slice* — zero communication;
* each rank denoises its uniform window (paper Eq. 4), weights it with its
  trapezoid mask (Eq. 12), and scatters it into a zero global buffer;
* "latent reconstruction" (Eqs. 15-17) is a single ``psum`` over the lp
  axis followed by a local divide with the analytically known normalizer
  (Eq. 16 needs no communication — weights depend on geometry only).

Two formulations compute identical math:

* :func:`stack_windows` / :func:`blend_windows` — pure functions used with
  GSPMD: stack the K windows on a leading axis sharded over the lp axis and
  let the partitioner place the slice / reduce.  Composes transparently
  with tensor-parallel sharding constraints inside the denoiser.
* :func:`lp_forward_shard_map` — explicit shard_map: guarantees the
  collective schedule (one psum of latent size per step) independent of
  partitioner heuristics.  Used by the serving engine and the dry-run.
"""
from __future__ import annotations

from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from .uniform import UniformPlan

DenoiseFn = Callable[[jnp.ndarray], jnp.ndarray]


# --------------------------------------------------------------- pure math
@jax.named_scope("lp.window")
def stack_windows(z: jnp.ndarray, plan: UniformPlan, axis: int) -> jnp.ndarray:
    """(K, ..., window, ...) stack of the K uniform windows of ``z``."""
    return jnp.stack(
        [
            jax.lax.dynamic_slice_in_dim(z, plan.starts[k], plan.window, axis)
            for k in range(plan.num_partitions)
        ]
    )


def window_weights(plan: UniformPlan) -> np.ndarray:
    """(K, window) trapezoid masks, float32."""
    return np.stack([plan.weight_1d(k) for k in range(plan.num_partitions)])


@jax.named_scope("lp.stitch")
def blend_windows(
    preds: jnp.ndarray, plan: UniformPlan, axis: int,
    use_kernel: bool | None = None,
) -> jnp.ndarray:
    """Position-aware reconstruction of stacked window predictions.

    ``preds``: (K, ...) with the partition dim at ``axis`` of each element
    (i.e. ``axis + 1`` of the stacked tensor).  The sum over the leading K
    axis is what GSPMD lowers to a reduce over the lp mesh axis.

    ``use_kernel=None`` auto-selects the fused Pallas stitch kernel
    (``kernels/latent_blend``) on TPU — one pass over the output instead
    of the K+2 latent-sized HBM round trips of the jnp scatter-add below.
    Off-TPU the kernel only runs in (slow, Python) interpret mode, so it
    stays opt-in there (tests force it on small shapes); ``kernels.ops``
    decides compiled vs interpreted from the backend.
    """
    K = plan.num_partitions
    if use_kernel is None:
        use_kernel = jax.default_backend() == "tpu"
    if use_kernel:
        from repro.kernels import ops

        p = jnp.moveaxis(preds, axis + 1, 1)        # (K, W, rest...)
        rest = p.shape[2:]
        flat = int(np.prod(rest)) if rest else 1
        out = ops.latent_blend(
            p.reshape(K, plan.window, flat),
            jnp.asarray(window_weights(plan)),
            jnp.asarray(plan.normalizer()),
            plan.starts, plan.window, plan.extent,
        )
        return jnp.moveaxis(out.reshape((plan.extent,) + rest), 0, axis)
    w = jnp.asarray(window_weights(plan))  # (K, window)
    wshape = [1] * (preds.ndim - 1)
    wshape[axis] = plan.window
    weighted = preds.astype(jnp.float32) * w.reshape((K, *wshape))
    out_shape = list(preds.shape[1:])
    out_shape[axis] = plan.extent
    zero = jnp.zeros(out_shape, jnp.float32)
    starts = jnp.asarray(plan.starts)

    def scatter(buf, pred_k, start_k):
        return jax.lax.dynamic_update_slice_in_dim(buf, pred_k, start_k, axis)

    scattered = jax.vmap(scatter, in_axes=(None, 0, 0))(zero, weighted, starts)
    acc = scattered.sum(axis=0)
    norm_shape = [1] * acc.ndim
    norm_shape[axis] = plan.extent
    norm = jnp.asarray(plan.normalizer()).reshape(norm_shape)
    return (acc / norm).astype(preds.dtype)


@jax.named_scope("lp.stitch")
def blend_windows_coded(
    preds: jnp.ndarray, plan: UniformPlan, axis: int,
    codec="int8", use_kernel: bool | None = None,
) -> jnp.ndarray:
    """Blend stacked window predictions that crossed a quantized wire.

    Each of the K window predictions is round-tripped through the codec
    exactly as the stacked engine would ship it (one per-slab scale per
    window).  For int8 the round trip is fully fused on TPU: a two-phase
    Pallas quantize (``kernels/wire_codec.int8_quantize``) and a
    dequantize+blend kernel (``dequant_blend``) that never materializes
    the dequantized f32 windows in HBM.  Other codecs decode and reuse
    :func:`blend_windows`.
    """
    from repro.comm.codecs import get_codec

    codec = get_codec(codec)
    K = plan.num_partitions
    if use_kernel is None:
        use_kernel = jax.default_backend() == "tpu"
    if codec.name == "int8" and use_kernel:
        from repro.kernels import ops

        p = jnp.moveaxis(preds, axis + 1, 1)         # (K, W, rest...)
        rest = p.shape[2:]
        flat = int(np.prod(rest)) if rest else 1
        p = p.reshape(K, plan.window, flat)
        wires, scales = [], []
        for k in range(K):
            wire, scale = ops.int8_quantize(p[k])
            wires.append(wire)
            scales.append(scale[0, 0])
        out = ops.dequant_blend(
            jnp.stack(wires), jnp.stack(scales),
            jnp.asarray(window_weights(plan)),
            jnp.asarray(plan.normalizer()),
            plan.starts, plan.window, plan.extent,
            out_dtype=preds.dtype,
        )
        return jnp.moveaxis(out.reshape((plan.extent,) + rest), 0, axis)
    # vmapped over the stacked axis (one per-slab scale per window): under
    # GSPMD this keeps the axis sharded over the lp axis — a per-k Python
    # loop of dynamic slices would force an all-gather of the stack
    roundtripped = jax.vmap(
        lambda p: codec.decode(*codec.encode(p), p.shape)
    )(preds).astype(preds.dtype)
    return blend_windows(roundtripped, plan, axis, use_kernel=use_kernel)


def lp_forward_stacked(
    denoise_fn: DenoiseFn, z: jnp.ndarray, plan: UniformPlan, axis: int
) -> jnp.ndarray:
    """Full LP forward in stacked form: slice -> vmap(denoise) -> blend.

    Under jit with the stacked axis sharded over the lp mesh axis, each
    device runs exactly one window; without a mesh this is the vmapped
    reference (tested against ``lp_forward_uniform``).
    """
    windows = stack_windows(z, plan, axis)
    preds = jax.vmap(denoise_fn)(windows)
    # jnp form: this function's point is GSPMD composability (stacked axis
    # sharded over the lp mesh axis) — the partitioner needs the visible
    # scatter-sum, not an opaque kernel
    return blend_windows(preds, plan, axis, use_kernel=False)


# ------------------------------------------------------------- GSPMD engine
def lp_forward_gspmd(
    denoise_fn: DenoiseFn,
    z: jnp.ndarray,
    plan: UniformPlan,
    axis: int,
    mesh: Mesh,
    lp_axis: str = "data",
    codec=None,
) -> jnp.ndarray:
    """LP forward with GSPMD sharding constraints on the stacked axis.

    ``codec`` routes the stacked reduce through
    :func:`blend_windows_coded`: every window prediction is round-tripped
    through the wire codec (vmapped over the sharded stacked axis, one
    per-slab scale per window) before the scatter-sum, so the engine's
    output is bit-faithful to what a codec'd wire would deliver instead
    of silently shipping f32 values.  Note the *transfer* the partitioner
    emits still carries f32 (a psum must reduce decoded values — GSPMD
    offers no hook to reduce-then-decode), which is exactly why the halo
    family, not GSPMD, is the production codec path; see
    ``comm_model.comm_lp_gspmd_codec``.  Stateless codecs only (residual
    state needs the explicit halo schedule).
    """
    if codec is not None:
        from repro.comm.codecs import get_codec

        codec = get_codec(codec)
        if codec.stateful:
            raise ValueError(
                f"codec {codec.name!r} is stateful; the GSPMD engine only "
                "supports stateless codecs (use the halo engines)"
            )
        if codec.name == "fp32":
            codec = None
    windows = stack_windows(z, plan, axis)
    spec = [None] * windows.ndim
    spec[0] = lp_axis
    windows = jax.lax.with_sharding_constraint(
        windows, NamedSharding(mesh, P(*spec))
    )
    preds = jax.vmap(denoise_fn)(windows)
    preds = jax.lax.with_sharding_constraint(
        preds, NamedSharding(mesh, P(*spec))
    )
    # jnp form always: the partitioner must see the scatter-sum to lower
    # it to a reduce over the lp axis (an opaque kernel would force an
    # all-gather of the stacked windows instead)
    if codec is not None:
        out = blend_windows_coded(preds, plan, axis, codec=codec,
                                  use_kernel=False)
    else:
        out = blend_windows(preds, plan, axis, use_kernel=False)
    return jax.lax.with_sharding_constraint(out, NamedSharding(mesh, P()))


# --------------------------------------------------------- shard_map engine
def lp_forward_shard_map(
    denoise_fn: DenoiseFn,
    z: jnp.ndarray,
    plan: UniformPlan,
    axis: int,
    mesh: Mesh,
    lp_axis: str = "data",
) -> jnp.ndarray:
    """Explicit per-device LP forward: slice local -> denoise -> psum.

    ``z`` replicated along ``lp_axis``; the only collective is one psum of
    the global-latent-sized buffer (comm_model.comm_lp_spmd's 2(K-1)/K S_z
    wire bytes per device).  The lp axis size must equal K.
    """
    K = plan.num_partitions
    if mesh.shape[lp_axis] != K:
        raise ValueError(
            f"lp axis {lp_axis!r} has size {mesh.shape[lp_axis]}, plan has K={K}"
        )
    starts = jnp.asarray(plan.starts)
    weights = jnp.asarray(window_weights(plan))  # (K, window)
    norm = jnp.asarray(plan.normalizer())

    other_axes = tuple(n for n in mesh.axis_names if n != lp_axis)

    @jax.named_scope("lp.stitch")
    def per_device(z_rep: jnp.ndarray) -> jnp.ndarray:
        k = jax.lax.axis_index(lp_axis)
        start = starts[k]
        with jax.named_scope("lp.window"):
            window = jax.lax.dynamic_slice_in_dim(z_rep, start, plan.window,
                                                  axis)
        pred = denoise_fn(window).astype(jnp.float32)
        wshape = [1] * pred.ndim
        wshape[axis] = plan.window
        pred = pred * weights[k].reshape(wshape)
        out_shape = list(z_rep.shape)
        buf = jnp.zeros(out_shape, jnp.float32)
        buf = jax.lax.dynamic_update_slice_in_dim(buf, pred, start, axis)
        with jax.named_scope("lp.halo"):
            buf = jax.lax.psum(buf, lp_axis)  # reconstruction (Eq. 15)
        nshape = [1] * buf.ndim
        nshape[axis] = plan.extent
        return (buf / norm.reshape(nshape)).astype(z_rep.dtype)

    # Replicated in/out along every axis; the denoiser may use other axes
    # (e.g. tensor parallelism over "model") internally.
    fn = jax.shard_map(
        per_device,
        mesh=mesh,
        in_specs=P(),
        out_specs=P(),
        check_vma=False,
    )
    return fn(z)


# ------------------------------------------------------- engine selection
LP_IMPLS = ("auto", "gspmd", "shard_map", "halo", "halo_hybrid")


def select_lp_impl(num_partitions: int, tp: int = 1) -> str:
    """Resolve ``lp_impl="auto"`` to a concrete SPMD engine.

    The halo schedule's wire bytes are ``K(K-1) core_pad row + Σ_t
    |perm_t| len_t row`` vs the psum's ``2(K-1) S_z``
    (``comm_model.comm_lp_halo`` vs ``comm_lp_spmd``): at K=2 the
    edge-clamped windows span nearly the whole extent and halo is
    break-even, so keep the psum engine there; from K>=3 the overlap
    slabs shrink like r·D/K and halo wins at any r<=1 (ROADMAP, PR 1
    measurements — strictly better for K>=4 on every benchmark config).

    ``tp`` is the intra-group tensor-parallel degree: on a 2D ``(lp,
    tp)`` mesh the break-even is unchanged (both engines' per-device
    wire bytes are T-independent — each tp rank runs the lp collective
    on its own ring), but the halo family must be the *hybrid* engine
    (``core/hybrid.lp_forward_halo_hybrid``), whose eager-send ordering
    lets the halo rounds overlap the tail of the intra-group forward.
    """
    if num_partitions <= 2:
        return "shard_map"
    return "halo_hybrid" if tp > 1 else "halo"


# ---------------------------------------------------------- halo-exchange
def lp_forward_halo(
    denoise_fn: DenoiseFn,
    z: jnp.ndarray,
    plan: UniformPlan,
    axis: int,
    mesh: Mesh,
    lp_axis: str = "data",
    codec=None,
    codec_state=None,
    eager_sends: bool = False,
    shard_axis: Optional[str] = None,
    nan_guard: bool = False,
):
    """Halo-exchange LP forward: the fast-path collective schedule.

    Same math as :func:`lp_forward_shard_map`, but reconstruction never
    materializes (or psums) a global-latent-sized buffer.  Each rank:

    1. slices + denoises its window and applies its trapezoid weights;
    2. exchanges only the **overlap slabs** with the ranks whose cores its
       window touches (``distributed.collectives.halo_exchange`` —
       ppermute rounds of O(overlap) bytes);
    3. normalizes its own core slice with the analytic ``Z(x)``;
    4. all-gathers the core slices (disjoint cover of the latent) and
       reassembles the replicated output locally.

    Wire bytes per device ~ (K-1)/K * S_z + halo slabs, vs the psum's
    2 (K-1)/K * S_z (``comm_model.comm_lp_halo`` vs ``comm_lp_spmd``);
    there is no all-reduce in the compiled HLO at all.

    ``codec`` (a ``comm.codecs`` name or instance) additionally squeezes
    every wire payload — ppermute slabs and the core all-gather — through
    a wire codec (``comm_model.comm_lp_halo_codec`` for the byte model).
    Residual codecs are stateful: pass ``codec_state`` from
    ``comm.wire.init_halo_wire_state`` (leading lp-axis dim) and this
    returns ``(latent, new_state)`` instead of just the latent — the
    compiled-step cache threads it through the ``lax.scan`` carry.

    All collectives name only ``lp_axis``, so the engine composes with
    extra mesh axes for free: the denoiser may use them internally (the
    hybrid LP×TP engine, ``core/hybrid.lp_forward_halo_hybrid``, is this
    function behind a validated 2D-mesh contract).  ``eager_sends``
    issues every ppermute round before any accumulation (see
    ``distributed.collectives.halo_exchange``) so async collective
    scheduling can overlap the rounds with the tail of the denoiser.

    ``shard_axis`` (hybrid meshes: the tp axis) shards every wire
    payload — halo slabs and core-gather contributions — over that
    axis: each tp rank ships only its 1/T chunk across the (slow)
    inter-group lp links and the full message is reassembled with a
    cheap intra-group all-gather.  Requires the denoiser output (and
    hence every slab) to be replicated along ``shard_axis``, which the
    hybrid Phi_m contract guarantees; the result is bit-identical to
    the unsharded engine (``comm_model.comm_lp_halo_sharded`` for the
    two-tier byte model).

    ``nan_guard`` arms the codec decode guard (``comm.wire._finite_or``):
    a corrupted wire message (NaN/Inf after decode) is replaced by the
    rank-local stale slab (residual codecs) or dropped to zeros
    (stateless) instead of poisoning the latent — elementwise selects
    only, so wire bytes and healthy-path values are unchanged.  A no-op
    without a codec (there is no decode to guard).
    """
    from repro.distributed.collectives import (
        halo_exchange,
        halo_spec,
        sharded_all_gather,
    )

    K = plan.num_partitions
    if mesh.shape[lp_axis] != K:
        raise ValueError(
            f"lp axis {lp_axis!r} has size {mesh.shape[lp_axis]}, plan has K={K}"
        )
    shard_size = 1
    if shard_axis is not None:
        if shard_axis not in mesh.axis_names:
            raise ValueError(
                f"shard axis {shard_axis!r} not on mesh: {mesh.axis_names}"
            )
        if shard_axis == lp_axis:
            # sharding over the transfer axis itself would reassemble
            # chunks of DIFFERENT senders' slabs — shapes all line up,
            # values silently wrong
            raise ValueError(
                f"shard axis must differ from the lp axis ({lp_axis!r}): "
                "wire chunks are reassembled across the shard axis after "
                "the lp transfer"
            )
        shard_size = mesh.shape[shard_axis]
        if shard_size == 1:
            shard_axis = None  # degenerate: nothing to shard over
    spec = halo_spec(plan)
    core_len = spec.core_len
    starts = jnp.asarray(plan.starts)
    weights = jnp.asarray(window_weights(plan))  # (K, window)
    # Per-rank core slice of the analytic normalizer, padded with ones so
    # the division is a no-op on the garbage rows beyond core_len[k].
    norm = plan.normalizer()
    norm_core = np.ones((K, spec.core_pad), np.float32)
    for k in range(K):
        norm_core[k, : core_len[k]] = norm[plan.core_start[k] : plan.core_end[k]]
    norm_core = jnp.asarray(norm_core)

    if codec is not None:
        from repro.comm.codecs import get_codec

        codec = get_codec(codec)
        if codec.stateful and codec_state is None:
            raise ValueError(
                f"codec {codec.name!r} is stateful: pass codec_state from "
                "comm.wire.init_halo_wire_state"
            )

    # every per-device body below runs under lp.stitch; the window slice
    # (lp.window), the exchange and the core gather (lp.halo) and the
    # denoiser (dit.*) name their own ops
    def _weighted_window(z_rep, k):
        with jax.named_scope("lp.window"):
            window = jax.lax.dynamic_slice_in_dim(z_rep, starts[k],
                                                  plan.window, axis)
        pred = denoise_fn(window).astype(jnp.float32)
        wshape = [1] * pred.ndim
        wshape[axis] = plan.window
        wpred = pred * weights[k].reshape(wshape)
        wpred = jnp.moveaxis(wpred, axis, 0)
        return jnp.pad(wpred, [(0, spec.pad)] + [(0, 0)] * (wpred.ndim - 1))

    def _reassemble(gathered, dtype):
        out = jnp.zeros((plan.extent,) + gathered.shape[2:], gathered.dtype)
        for j in range(K):  # cores tile [0, extent): static local reassembly
            out = jax.lax.dynamic_update_slice_in_dim(
                out, gathered[j, : core_len[j]], plan.core_start[j], 0
            )
        return jnp.moveaxis(out, 0, axis).astype(dtype)

    @jax.named_scope("lp.halo")
    def _core_gather_raw(core: jnp.ndarray) -> jnp.ndarray:
        """Uncoded core all-gather, wire-sharded when shard_axis is set:
        each tp rank gathers only its 1/T chunk over the lp ring, then
        one intra-group all-gather reassembles the (K, core_pad) table."""
        if shard_axis is None:
            return jax.lax.all_gather(core, lp_axis, axis=0, tiled=False)
        return sharded_all_gather(core, lp_axis, shard_axis, shard_size)

    if codec is None:
        @jax.named_scope("lp.stitch")
        def per_device(z_rep: jnp.ndarray) -> jnp.ndarray:
            k = jax.lax.axis_index(lp_axis)
            wpred = _weighted_window(z_rep, k)
            acc = halo_exchange(wpred, spec, k, lp_axis,
                                eager_sends=eager_sends,
                                shard_axis=shard_axis,
                                shard_size=shard_size)
            nshape = (spec.core_pad,) + (1,) * (acc.ndim - 1)
            core = (acc[: spec.core_pad] / norm_core[k].reshape(nshape)).astype(
                z_rep.dtype
            )
            return _reassemble(_core_gather_raw(core), z_rep.dtype)

        fn = jax.shard_map(
            per_device,
            mesh=mesh,
            in_specs=P(),
            out_specs=P(),
            check_vma=False,
        )
        return fn(z)

    from repro.comm.wire import (
        compressed_core_gather,
        compressed_halo_exchange,
    )

    if not codec.stateful:
        @jax.named_scope("lp.stitch")
        def per_device_codec(z_rep: jnp.ndarray) -> jnp.ndarray:
            k = jax.lax.axis_index(lp_axis)
            wpred = _weighted_window(z_rep, k)
            acc, _ = compressed_halo_exchange(wpred, spec, k, lp_axis,
                                              codec, {},
                                              eager_sends=eager_sends,
                                              shard_axis=shard_axis,
                                              shard_size=shard_size,
                                              nan_guard=nan_guard)
            nshape = (spec.core_pad,) + (1,) * (acc.ndim - 1)
            core = acc[: spec.core_pad] / norm_core[k].reshape(nshape)
            gathered, _ = compressed_core_gather(core, k, lp_axis, codec, {},
                                                 K, shard_axis=shard_axis,
                                                 shard_size=shard_size,
                                                 nan_guard=nan_guard)
            return _reassemble(gathered, z_rep.dtype)

        fn = jax.shard_map(
            per_device_codec,
            mesh=mesh,
            in_specs=P(),
            out_specs=P(),
            check_vma=False,
        )
        return fn(z)

    @jax.named_scope("lp.stitch")
    def per_device_stateful(z_rep: jnp.ndarray, state):
        k = jax.lax.axis_index(lp_axis)
        st = jax.tree.map(lambda s: s[0], state)  # drop the lp-axis dim
        wpred = _weighted_window(z_rep, k)
        acc, st = compressed_halo_exchange(wpred, spec, k, lp_axis, codec, st,
                                           eager_sends=eager_sends,
                                           shard_axis=shard_axis,
                                           shard_size=shard_size,
                                           nan_guard=nan_guard)
        nshape = (spec.core_pad,) + (1,) * (acc.ndim - 1)
        core = acc[: spec.core_pad] / norm_core[k].reshape(nshape)
        gathered, st = compressed_core_gather(core, k, lp_axis, codec, st, K,
                                              shard_axis=shard_axis,
                                              shard_size=shard_size,
                                              nan_guard=nan_guard)
        out = _reassemble(gathered, z_rep.dtype)
        return out, jax.tree.map(lambda s: s[None], st)

    fn = jax.shard_map(
        per_device_stateful,
        mesh=mesh,
        in_specs=(P(), P(lp_axis)),
        out_specs=(P(), P(lp_axis)),
        check_vma=False,
    )
    return fn(z, codec_state)
