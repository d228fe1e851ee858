"""Codec-aware LP collectives + a bit-faithful single-process mirror.

Two SPMD building blocks (called per-device, inside shard_map):

  * :func:`compressed_halo_exchange` — wraps
    ``distributed/collectives.halo_exchange``: same transfer schedule,
    but each slab crosses the wire through a :class:`~.codecs.Codec`
    (wire payload + per-slab scale meta per ppermute round).
  * :func:`compressed_core_gather` — the core-slice all-gather with the
    same codec (each rank quantizes its normalized core; wire + scales
    are gathered and decoded locally).

Residual codecs thread explicit state (previous decoded slabs + error
carries, see :mod:`.residual`); the state is created by
:func:`init_halo_wire_state` with a leading lp-axis dim so shard_map can
slice it per rank, and it rides the caller's ``lax.scan`` carry.

:func:`simulate_halo_forward` replays the exact same arithmetic on a
single device (static Python loop over ranks): used by the serving
engine when no mesh is attached, by quality/PSNR benchmarks, and by
tests as the oracle for the SPMD path.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.distributed.collectives import (
    HaloSpec,
    halo_spec,
    sharded_all_gather,
    sharded_ppermute,
)

from .codecs import Codec, get_codec
from .residual import ResidualCodec, residual_decode, residual_encode

WireState = Dict[str, Any]


def _dir_key(t) -> str:
    """Stable per-direction state key for one halo transfer round.

    ``halo_spec`` emits exactly one transfer per nonzero window offset,
    so the signed offset identifies the direction (``"+1"`` = slab from
    the left neighbor, ``"-1"`` = from the right, ...).  Keying the
    send/err/recv state by direction — instead of positional round
    index — makes it structurally impossible for one direction's stale
    slab to be read back for the other (the directional-mixing bug
    class), and survives any reordering of ``spec.transfers``.
    """
    return f"{t.offset:+d}"


def init_halo_wire_state(codec, spec: HaloSpec,
                         rest_shape: Tuple[int, ...]) -> WireState:
    """Zeroed codec state for one halo-LP geometry.

    Every leaf has a leading ``K`` dim (the lp axis) so shard_map slices
    one rank's state with ``P(lp_axis)``; ``simulate_halo_forward``
    indexes the same leaves with Python rank ints.  ``ag_prev`` is the
    decoded gathered-core table — identical on every rank by
    construction, kept per-rank (K, K, ...) so the layout is uniform.
    The ``pp_*`` leaves are dicts keyed per direction
    (:func:`_dir_key`), one entry per ppermute round.  Stateless codecs
    get an empty dict (still scan-carry compatible).

    Displaced codecs additionally carry a per-rank ``fresh`` flag,
    initialized to ones: the first exchange after ANY state init (start
    of a scan run, dim rotation, codec-segment boundary, replan, resume)
    deposits the freshly decoded slabs — i.e. runs synchronous — and
    zeroes the flag, so later steps in the run deposit the one-step-stale
    carry instead.  This is the dim-rotation flush rule: a rotation
    re-inits state, which re-arms the flag.
    """
    codec = get_codec(codec)
    if not codec.stateful:
        return {}
    K = spec.num_partitions
    rest = tuple(rest_shape)

    def z(shape):
        return jnp.zeros(shape, jnp.float32)

    state = {
        "pp_send": {_dir_key(t): z((K, t.length) + rest)
                    for t in spec.transfers},
        "pp_err": {_dir_key(t): z((K, t.length) + rest)
                   for t in spec.transfers},
        "pp_recv": {_dir_key(t): z((K, t.length) + rest)
                    for t in spec.transfers},
        "ag_prev": z((K, K, spec.core_pad) + rest),
        "ag_err": z((K, spec.core_pad) + rest),
    }
    if getattr(codec, "displaced", False):
        state["fresh"] = jnp.ones((K,), jnp.float32)
    return state


def _pin(x):
    """Keep the encoded dtype ON the wire.

    XLA's algebraic simplifier happily commutes converts across
    collectives (``convert_f32(ppermute(bf16 x))`` becomes
    ``ppermute(f32 x)`` + a fused round-trip), which preserves values
    but silently restores full-width transfers.  An optimization
    barrier on both sides of every collective pins the compact dtype to
    the collective op — this is what makes the analytic byte model
    (``comm_model.comm_lp_halo_codec``) match the compiled HLO.
    """
    return jax.lax.optimization_barrier(x)


def _finite_or(decoded: jnp.ndarray, fallback) -> jnp.ndarray:
    """NaN/Inf decode guard: the whole message or its fallback.

    A corrupted wire payload (flipped bits, truncated DMA, a garbage
    scale) decodes to NaN/Inf; letting even one such element into the
    accumulator poisons the entire latent within a step.  The guard is
    all-or-nothing per message — one non-finite element means the
    payload can't be trusted at all — and falls back to the *stale*
    reference where one exists (residual codecs carry the previous
    decoded slab: DistriFusion's one-step-stale boundary activations,
    absorbed by the same error-feedback machinery) or to zeros for
    stateless codecs (the contribution is skipped; every rank computes
    the same zero, so replication invariants hold).

    Elementwise select only — no new collectives, so the analytic wire
    byte model still matches the compiled HLO exactly; when the wire is
    healthy the select is the identity and values are bit-equal to the
    unguarded path.
    """
    ok = jnp.isfinite(decoded).all()
    fb = jnp.zeros_like(decoded) if fallback is None else fallback
    return jnp.where(ok, decoded, fb)


def _finite_rows_or(decoded: jnp.ndarray, fallback) -> jnp.ndarray:
    """Per-row (leading-axis) variant of :func:`_finite_or` for gathered
    (K, ...) tables: each sender's message is guarded independently."""
    axes = tuple(range(1, decoded.ndim))
    ok = jnp.isfinite(decoded).all(axis=axes, keepdims=True)
    fb = jnp.zeros_like(decoded) if fallback is None else fallback
    return jnp.where(ok, decoded, fb)


def _ppermute_msg(wire, meta, axis_name, perm, shard_axis=None,
                  shard_size=1):
    """Ship (payload, scales) through one ppermute round.

    With ``shard_axis`` (the hybrid mesh's tp axis, size ``shard_size``)
    the payload crosses the group boundary **sharded**: each tp rank
    ppermutes only its 1/T chunk of the coded wire, then the full wire
    is reassembled with one intra-group all-gather.  The meta scales are
    tiny and every tp rank of the source group encodes the identical
    slab, so each rank ships the full meta and no tp gather of it is
    needed.  Both collectives are dtype-pinned so the compact wire (and
    the T-fold inter-group saving) survives XLA's simplifier.
    """
    wire, meta = _pin((wire, meta))
    if shard_axis is not None and shard_size > 1:
        got_wire = sharded_ppermute(wire, axis_name, perm, shard_axis,
                                    shard_size, pin=_pin)
    else:
        got_wire = jax.lax.ppermute(wire, axis_name, perm)
    got_meta = tuple(jax.lax.ppermute(m, axis_name, perm) for m in meta)
    return _pin((got_wire, got_meta))


def _gather_msg(wire, meta, axis_name, shard_axis=None, shard_size=1):
    """All-gather (payload, scales) with the wire dtype pinned.

    Sharded (``shard_axis``): each tp rank contributes only its 1/T
    chunk of the coded payload to the **inter-group** ring all-gather,
    then one intra-group all-gather collects the T chunk columns and
    each device reassembles the full (K, ...) wire table locally.  Meta
    leaves stay on the inter-group gather (K tiny scales are needed in
    full on every device either way).
    """
    wire, meta = _pin((wire, meta))
    if shard_axis is not None and shard_size > 1:
        wires = sharded_all_gather(wire, axis_name, shard_axis, shard_size,
                                   pin=_pin)
    else:
        wires = jax.lax.all_gather(wire, axis_name, axis=0, tiled=False)
    metas = tuple(
        jax.lax.all_gather(m, axis_name, axis=0, tiled=False) for m in meta
    )
    return _pin((wires, metas))


# ----------------------------------------------------------- SPMD pieces
@jax.named_scope("lp.halo")
def compressed_halo_exchange(
    wpred: jnp.ndarray,
    spec: HaloSpec,
    rank: jnp.ndarray,
    axis_name: str,
    codec: Codec,
    state: WireState,
    eager_sends: bool = False,
    shard_axis: Optional[str] = None,
    shard_size: int = 1,
    nan_guard: bool = False,
) -> Tuple[jnp.ndarray, WireState]:
    """Codec twin of ``collectives.halo_exchange`` (same contract: padded
    window-first ``wpred`` in, ``(core_pad + max_transfer, ...)`` f32
    accumulator out), plus the updated per-rank codec state.

    Each transfer round sends ``codec.encode`` of the (masked) slab —
    for residual codecs, of the temporal delta with the EF carry — and
    accumulates the decoded slab.  Ranks without a peer at an offset
    send a zero slab and decode ppermute's implicit zeros to exactly
    zero (codecs map 0 -> 0), so the schedule semantics are unchanged.

    ``eager_sends`` mirrors ``halo_exchange``: every round is encoded and
    its ppermute issued before any decode/accumulate, so the wires are
    mutually independent and can overlap the local work (and each other)
    under XLA's async collective scheduling.  Values are identical either
    way — only the op ordering changes.

    ``shard_axis`` / ``shard_size`` shard every coded payload over the
    hybrid mesh's tp axis (see ``_ppermute_msg``).  Encoding always
    happens on the FULL slab — identical on every tp rank, so per-slab
    scales, quantized values, and residual/EF state are bit-equal to the
    unsharded engine and the state stays rank-local on the lp axis —
    only the wire transport is split.

    ``nan_guard`` wraps every decode in :func:`_finite_or`: a corrupted
    payload is replaced by the rank-local stale slab (the SAME
    direction's residual ``pp_recv`` reference — which is then also NOT
    advanced, so the reference stays the last healthy decode) or by
    zeros (stateless).

    Displaced codecs (``codec.displaced``) deposit the *previous* step's
    decoded slab (the ``pp_recv`` carry as of entry) into the
    accumulator while this step's ppermute lands in the carry for the
    next step — one-step-stale boundary activations, DistriFusion-style,
    with the EF delta protocol re-injecting the staleness error into the
    next residual.  The first exchange after a state init runs
    synchronous (``fresh`` flag).  The collectives issued are IDENTICAL
    to the synchronous path (elementwise select only), so wire bytes per
    collective per tier still match ``comm_model`` exactly.
    """
    stateful = isinstance(codec, ResidualCodec)
    base = codec.base if stateful else codec
    displaced = stateful and getattr(codec, "displaced", False)
    acc_len = spec.core_pad + spec.max_transfer
    trail = (1,) * (wpred.ndim - 1)
    acc = jnp.zeros((acc_len,) + wpred.shape[1:], jnp.float32)
    K = spec.num_partitions
    new_state = dict(state) if stateful else {}
    if stateful:
        new_state["pp_send"] = dict(state["pp_send"])
        new_state["pp_err"] = dict(state["pp_err"])
        new_state["pp_recv"] = dict(state["pp_recv"])
    if displaced:
        # per-rank scalar inside shard_map (the lp-axis dim is dropped
        # by the caller); ones right after init_halo_wire_state
        fresh = state["fresh"].reshape(()) > 0.5
        new_state["fresh"] = jnp.zeros_like(state["fresh"])

    def send(t) -> Tuple:
        """Encode + issue one round; returns (wire, meta, slab_shape)."""
        dk = _dir_key(t)
        slab = jax.lax.dynamic_slice_in_dim(
            wpred, jnp.asarray(t.src_start)[rank], t.length, 0
        )
        valid = jnp.arange(t.length) < jnp.asarray(t.src_len)[rank]
        slab = slab * valid.reshape((t.length,) + trail).astype(slab.dtype)
        if stateful:
            wire, meta, n_send, n_err = residual_encode(
                base, slab, state["pp_send"][dk], state["pp_err"][dk]
            )
            new_state["pp_send"][dk] = n_send
            new_state["pp_err"][dk] = n_err
        else:
            wire, meta = codec.encode(slab)
        got_wire, got_meta = _ppermute_msg(
            wire, meta, axis_name, t.perm,
            shard_axis=shard_axis, shard_size=shard_size,
        )
        return got_wire, got_meta, slab.shape

    def deposit(acc, t, msg) -> jnp.ndarray:
        got_wire, got_meta, slab_shape = msg
        if stateful:
            dk = _dir_key(t)
            prev = state["pp_recv"][dk]      # this direction's stale slab
            got, n_recv = residual_decode(
                base, got_wire, got_meta, prev, slab_shape
            )
            if nan_guard:
                got = _finite_or(got, prev)
                n_recv = _finite_or(n_recv, prev)
            new_state["pp_recv"][dk] = n_recv
            if displaced:
                # blend the step-(t-1) slab; the fresh decode only feeds
                # the carry (consumed at step t+1).  First step of a run
                # is synchronous: prev is zeros there, and zeros are NOT
                # a valid boundary activation.
                got = jnp.where(fresh, got, prev)
        else:
            got = codec.decode(got_wire, got_meta, slab_shape)
            if nan_guard:
                got = _finite_or(got, None)
        dst = jnp.asarray(t.dst_start)[rank]
        cur = jax.lax.dynamic_slice_in_dim(acc, dst, t.length, 0)
        return jax.lax.dynamic_update_slice_in_dim(acc, cur + got, dst, 0)

    msgs = ([send(t) for t in spec.transfers] if eager_sends else None)
    # own window -> own core (local, never coded)
    own_off = jnp.asarray([spec.core_start[k] - spec.starts[k] for k in range(K)])
    own = jax.lax.dynamic_slice_in_dim(wpred, own_off[rank], spec.core_pad, 0)
    acc = jax.lax.dynamic_update_slice_in_dim(
        acc, own.astype(jnp.float32), 0, 0
    )
    for ti, t in enumerate(spec.transfers):
        msg = msgs[ti] if eager_sends else send(t)
        acc = deposit(acc, t, msg)
    return acc, new_state


@jax.named_scope("lp.halo")
def compressed_core_gather(
    core: jnp.ndarray,
    rank: jnp.ndarray,
    axis_name: str,
    codec: Codec,
    state: WireState,
    num_partitions: int,
    shard_axis: Optional[str] = None,
    shard_size: int = 1,
    nan_guard: bool = False,
) -> Tuple[jnp.ndarray, WireState]:
    """All-gather of the normalized core slices through the codec.

    ``core``: (core_pad, ...) f32.  Returns the decoded (K, core_pad,
    ...) stack plus updated state.  Residual codecs delta-code against
    ``ag_prev`` (the previous gathered table — identical on all ranks,
    so each rank's own row doubles as its sender reference) with an EF
    carry on the rank's own core.  ``shard_axis`` / ``shard_size``
    shard the coded payload over the tp axis (see ``_gather_msg``);
    encode/decode and all state arithmetic stay on full values, so the
    result is bit-equal to the unsharded gather.
    """
    stateful = isinstance(codec, ResidualCodec)
    base = codec.base if stateful else codec
    K = num_partitions
    if not stateful:
        wire, meta = codec.encode(core)
        wires, metas = _gather_msg(wire, meta, axis_name,
                                   shard_axis=shard_axis,
                                   shard_size=shard_size)
        out = codec.decode(wires, metas, (K,) + core.shape)
        if nan_guard:
            out = _finite_rows_or(out, None)
        return out, {}
    corrected = core - state["ag_prev"][rank] + state["ag_err"]
    wire, meta = base.encode(corrected)
    wires, metas = _gather_msg(wire, meta, axis_name,
                               shard_axis=shard_axis,
                               shard_size=shard_size)
    d_all = base.decode(wires, metas, (K,) + core.shape)
    if nan_guard:
        # a corrupted sender's delta is dropped (row -> 0): its gathered
        # core stays the stale ``ag_prev`` slab, identical on every rank
        # (replication-safe), and the sender's own EF carry keeps the
        # full corrected value for the next healthy step
        d_all = _finite_rows_or(d_all, None)
    gathered = state["ag_prev"] + d_all
    new_err = corrected - d_all[rank]
    out_state = dict(state)
    out_state["ag_prev"] = gathered
    out_state["ag_err"] = new_err
    return gathered, out_state


# ---------------------------------------------------- single-process mirror
@jax.named_scope("lp.halo")
def simulate_halo_forward(
    denoise_fn,
    z: jnp.ndarray,
    plan,
    axis: int,
    codec=None,
    state: Optional[WireState] = None,
    nan_guard: bool = False,
):
    """Single-device replay of the codec'd halo-LP forward pass.

    Bit-faithful to ``core/spmd.lp_forward_halo(..., codec=...)``: every
    rank's slab is encoded with its own per-slab scale and state slice,
    delivery follows ``halo_spec``'s exact schedule, cores are
    normalized then round-tripped through the gather codec.  Stateless
    codecs return just the latent; stateful ones return
    ``(latent, new_state)`` (global-layout state, see
    :func:`init_halo_wire_state`).  ``nan_guard`` mirrors the SPMD
    decode guard (:func:`_finite_or`) per rank, so guarded-path quality
    tests can run single-process.
    """
    from repro.core.spmd import stack_windows, window_weights

    codec = get_codec(codec)
    stateful = isinstance(codec, ResidualCodec)
    base = codec.base if stateful else codec
    spec = halo_spec(plan)
    K = plan.num_partitions
    windows = stack_windows(z, plan, axis)
    preds = jax.vmap(denoise_fn)(windows).astype(jnp.float32)
    w = jnp.asarray(window_weights(plan))
    wshape = [1] * preds.ndim
    wshape[0] = K
    wshape[axis + 1] = plan.window
    wp = jnp.moveaxis(preds * w.reshape(wshape), axis + 1, 1)  # (K, W, rest)
    wp = jnp.pad(wp, [(0, 0), (0, spec.pad)] + [(0, 0)] * (wp.ndim - 2))
    rest = wp.shape[2:]
    trail = (1,) * len(rest)
    if stateful and state is None:
        raise ValueError(f"codec {codec.name!r} needs init_halo_wire_state")

    acc_len = spec.core_pad + spec.max_transfer
    accs = []
    for k in range(K):
        a = jnp.zeros((acc_len,) + rest, jnp.float32)
        off = spec.core_start[k] - spec.starts[k]
        accs.append(a.at[: spec.core_pad].set(wp[k, off : off + spec.core_pad]))

    displaced = stateful and getattr(codec, "displaced", False)
    new_state: WireState = {}
    if stateful:
        new_state = {
            "pp_send": {d: list(jnp.split(s, K))
                        for d, s in state["pp_send"].items()},
            "pp_err": {d: list(jnp.split(s, K))
                       for d, s in state["pp_err"].items()},
            "pp_recv": {d: list(jnp.split(s, K))
                        for d, s in state["pp_recv"].items()},
        }
    if displaced:
        new_state["fresh"] = jnp.zeros_like(state["fresh"])
    for t in spec.transfers:
        dk = _dir_key(t)
        msgs = []
        for j in range(K):  # every rank encodes (state advances SPMD-like)
            slab = wp[j, t.src_start[j] : t.src_start[j] + t.length]
            valid = jnp.arange(t.length) < t.src_len[j]
            slab = slab * valid.reshape((t.length,) + trail)
            if stateful:
                wire, meta, n_send, n_err = residual_encode(
                    base, slab,
                    state["pp_send"][dk][j], state["pp_err"][dk][j],
                )
                new_state["pp_send"][dk][j] = n_send[None]
                new_state["pp_err"][dk][j] = n_err[None]
            else:
                wire, meta = codec.encode(slab)
            msgs.append((wire, meta))
        delivered = {k: msgs[j] for j, k in t.perm}
        for k in range(K):
            if k in delivered:
                wire, meta = delivered[k]
            else:  # ppermute's implicit zeros for peerless ranks
                wire = jnp.zeros_like(msgs[0][0])
                meta = tuple(jnp.zeros_like(m) for m in msgs[0][1])
            shape = (t.length,) + rest
            if stateful:
                prev = state["pp_recv"][dk][k]  # same-direction stale slab
                got, n_recv = residual_decode(base, wire, meta, prev, shape)
                if nan_guard:
                    got = _finite_or(got, prev)
                    n_recv = _finite_or(n_recv, prev)
                new_state["pp_recv"][dk][k] = n_recv[None]
                if displaced:
                    # deposit the step-(t-1) slab; the fresh decode only
                    # advances the carry (mirrors the SPMD deposit)
                    got = jnp.where(state["fresh"][k] > 0.5, got, prev)
            else:
                got = codec.decode(wire, meta, shape)
                if nan_guard:
                    got = _finite_or(got, None)
            dst = t.dst_start[k]
            accs[k] = accs[k].at[dst : dst + t.length].add(got)

    # normalize own cores (ones-padded normalizer rows, as the SPMD path)
    norm = plan.normalizer()
    cores = []
    for k in range(K):
        nc = np.ones(spec.core_pad, np.float32)
        cl = spec.core_len[k]
        nc[:cl] = norm[spec.core_start[k] : spec.core_end[k]]
        cores.append(
            accs[k][: spec.core_pad] / jnp.asarray(nc).reshape((-1,) + trail)
        )

    core_shape = (spec.core_pad,) + rest
    if stateful:
        correcteds, wires, metas = [], [], []
        for k in range(K):
            c = cores[k] - state["ag_prev"][k][k] + state["ag_err"][k]
            wire, meta = base.encode(c)
            correcteds.append(c)
            wires.append(wire)
            metas.append(meta)
        wires_st = jnp.stack(wires)
        metas_st = tuple(
            jnp.stack([m[i] for m in metas]) for i in range(len(metas[0]))
        )
        d_all = base.decode(wires_st, metas_st, (K,) + core_shape)
        if nan_guard:
            d_all = _finite_rows_or(d_all, None)
        gathered = state["ag_prev"][0] + d_all  # replicas are identical
        new_state["ag_prev"] = jnp.broadcast_to(
            gathered[None], (K,) + gathered.shape
        )
        new_state["ag_err"] = jnp.stack(
            [correcteds[k] - d_all[k] for k in range(K)]
        )
    else:
        wires, metas = [], []
        for k in range(K):
            wire, meta = codec.encode(cores[k])
            wires.append(wire)
            metas.append(meta)
        metas_st = tuple(
            jnp.stack([m[i] for m in metas]) for i in range(len(metas[0]))
        )
        gathered = codec.decode(jnp.stack(wires), metas_st, (K,) + core_shape)
        if nan_guard:
            gathered = _finite_rows_or(gathered, None)

    out = jnp.zeros((plan.extent,) + rest, jnp.float32)
    for j in range(K):
        out = out.at[spec.core_start[j] : spec.core_end[j]].set(
            gathered[j, : spec.core_len[j]]
        )
    out = jnp.moveaxis(out, 0, axis).astype(z.dtype)
    if not stateful:
        return out
    for key in ("pp_send", "pp_err", "pp_recv"):
        new_state[key] = {
            d: jnp.concatenate(s) for d, s in new_state[key].items()
        }
    return out, new_state
