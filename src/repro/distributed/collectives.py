"""Explicit collective patterns the partitioner can't be trusted to find.

``seq_parallel_decode_attention``: flash-decode for batch=1 long-context —
the KV cache is sharded over a mesh axis along *sequence*; each shard
computes a partial softmax (max, sum, weighted values) and the combine is
two tiny psums.  This converts an idle data axis into K-fold attention
parallelism for the 500k-token cells (§Perf optimization for zamba2 /
h2o-danube long_500k).

``halo_spec`` / ``halo_exchange``: the LP fast-path collective.  Instead of
psumming a full global-latent-sized buffer per denoising step (every
position is owned by exactly one rank's core, yet the psum ships all of
them K ways), each rank sends its neighbors only the **overlap slabs** of
its weighted prediction via ``ppermute``, accumulates received slabs into
its core slice, and the replicated latent is reassembled from an
all-gather of core slices.  Wire bytes drop from 2(K-1)/K * S_z per device
to ~(K-1)/K * S_z + halo slabs (see ``core/comm_model.comm_lp_halo``).

All halo geometry is static Python derived from the uniform partition
plan, including the edge-clamped windows that can reach cores at offset
|d| >= 2 when the overlap ratio is large — the transfer schedule is exact,
not a nearest-neighbor approximation.

``wire_shard_slice`` / ``wire_unshard``: the hierarchy-aware wire split.
On a 2D ``(lp, tp)`` mesh every tp rank holds a replica of each slab, so
shipping the full slab on all T parallel lp rings moves T identical
copies across the (slow) inter-group links.  Sharding the wire over the
tp axis — each tp rank ppermutes only its 1/T chunk, receivers reassemble
with one intra-group all-gather — cuts inter-group bytes T-fold at the
price of a cheap intra-group collective.  The split is a pure transport
rearrangement (flatten, zero-pad to T equal chunks, concatenate back),
so sharded and unsharded engines are bit-identical.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

NEG_INF = -1.0e30


def _local_partial(q, k, v, kv_pos, position, window: int):
    """Per-shard partial attention.  q: (B,1,H,D); k/v: (B,S_loc,KV,D);
    kv_pos: (B, S_loc) global positions of this shard's slots.
    Returns (m (B,KV,G), l (B,KV,G), acc (B,KV,G,D))."""
    B, _, H, D = q.shape
    KV = k.shape[2]
    G = H // KV
    qg = q.reshape(B, KV, G, D).astype(jnp.float32)
    s = jnp.einsum("bkgd,bskd->bkgs", qg, k.astype(jnp.float32))
    s = s / jnp.sqrt(float(D))
    ok = kv_pos <= position[:, None]
    if window > 0:
        ok &= kv_pos > (position[:, None] - window)
    s = jnp.where(ok[:, None, None, :], s, NEG_INF)
    m = s.max(axis=-1)
    p = jnp.exp(s - m[..., None])
    p = jnp.where(ok[:, None, None, :], p, 0.0)
    l = p.sum(axis=-1)
    acc = jnp.einsum("bkgs,bskd->bkgd", p, v.astype(jnp.float32))
    return m, l, acc


def seq_parallel_decode_attention(
    q: jnp.ndarray,            # (B, 1, H, D) current-token query (RoPE'd)
    k_local: jnp.ndarray,      # (B, S_local, KV, D) this shard's KV slice
    v_local: jnp.ndarray,
    kv_pos_local: jnp.ndarray, # (B, S_local) global positions (incl. new tok)
    position: jnp.ndarray,     # (B,) current decode index
    axis_name: str,
    window: int = 0,
) -> jnp.ndarray:
    """Flash-decode combine across a sequence-sharded cache.

    Communication: 2 psums of (B, KV, G) + one of (B, KV, G, D) —
    O(B*H*D) bytes, independent of context length."""
    B, _, H, D = q.shape
    m, l, acc = _local_partial(q, k_local, v_local, kv_pos_local, position, window)
    m_glob = jax.lax.pmax(m, axis_name)
    corr = jnp.exp(m - m_glob)
    l_glob = jax.lax.psum(l * corr, axis_name)
    acc_glob = jax.lax.psum(acc * corr[..., None], axis_name)
    out = acc_glob / jnp.maximum(l_glob, 1e-37)[..., None]
    return out.reshape(B, 1, H, D).astype(q.dtype)


# ------------------------------------------------------- wire sharding
def wire_shard_len(n_elems: int, shard_size: int) -> int:
    """Per-rank chunk length of an ``n_elems`` flat wire split
    ``shard_size`` ways (last chunk zero-padded)."""
    return -(-n_elems // shard_size)


def wire_shard_slice(x: jnp.ndarray, shard_rank: jnp.ndarray,
                     shard_size: int) -> jnp.ndarray:
    """This rank's 1/T chunk of a flat view of ``x``.

    ``shard_rank`` is the traced tp-axis index; the chunk length is the
    static ``wire_shard_len`` so every rank ships a uniform shape (the
    tail chunk carries zero padding).  Flattening keeps the split exact
    for any slab shape and any wire dtype, including int4's packed last
    axis.
    """
    flat = x.reshape(-1)
    n = flat.shape[0]
    s = wire_shard_len(n, shard_size)
    if s * shard_size != n:
        flat = jnp.pad(flat, (0, s * shard_size - n))
    return jax.lax.dynamic_slice_in_dim(flat, shard_rank * s, s, 0)


def wire_unshard(chunks: jnp.ndarray, shape: Tuple[int, ...]) -> jnp.ndarray:
    """Reassemble a ``(T, s)`` stack of gathered chunks into the logical
    wire of ``shape`` (drops the tail padding).  Exact inverse of T
    ``wire_shard_slice`` calls."""
    n = 1
    for d in shape:
        n *= d
    return chunks.reshape(-1)[:n].reshape(shape)


def wire_unshard_rows(chunks: jnp.ndarray,
                      shape: Tuple[int, ...]) -> jnp.ndarray:
    """Reassemble a ``(T, K, s)`` stack of gathered chunk *columns* (one
    tp gather of a K-row lp gather) into the ``(K,) + shape`` wire
    table, dropping each row's tail padding — the batched
    :func:`wire_unshard`."""
    K = chunks.shape[1]
    n = 1
    for d in shape:
        n *= d
    return jnp.swapaxes(chunks, 0, 1).reshape(K, -1)[:, :n].reshape(
        (K,) + tuple(shape)
    )


def _id(x):
    return x


def sharded_ppermute(
    x: jnp.ndarray,
    axis_name: str,
    perm,
    shard_axis: str,
    shard_size: int,
    pin=_id,
) -> jnp.ndarray:
    """One ppermute with the payload sharded over ``shard_axis``: each
    shard rank ships its 1/T chunk across ``axis_name``, then an
    intra-group all-gather reassembles the full message at the
    receiver.  ``pin`` (the codec layer's optimization barrier) wraps
    every tensor entering/leaving a collective so compact wire dtypes
    survive XLA's simplifier.  This is THE sharded point-to-point
    transport — every engine routes through here so the byte model and
    the compiled HLO can never diverge per call site."""
    chunk = wire_shard_slice(x, jax.lax.axis_index(shard_axis), shard_size)
    got = jax.lax.ppermute(pin(chunk), axis_name, perm)
    chunks = jax.lax.all_gather(pin(got), shard_axis, axis=0, tiled=False)
    return wire_unshard(pin(chunks), x.shape)


def sharded_all_gather(
    x: jnp.ndarray,
    axis_name: str,
    shard_axis: str,
    shard_size: int,
    pin=_id,
) -> jnp.ndarray:
    """Ring all-gather over ``axis_name`` with each contribution sharded
    over ``shard_axis``: the slow-tier gather moves ``(K, 1/T chunk)``,
    one intra-group all-gather collects the chunk columns, and every
    device reassembles the full ``(K,) + x.shape`` table locally.  The
    sharded twin of ``jax.lax.all_gather(x, axis_name)``."""
    chunk = wire_shard_slice(x, jax.lax.axis_index(shard_axis), shard_size)
    lp = jax.lax.all_gather(pin(chunk), axis_name, axis=0, tiled=False)
    tp = jax.lax.all_gather(pin(lp), shard_axis, axis=0, tiled=False)
    return wire_unshard_rows(pin(tp), x.shape)


# ------------------------------------------------------------ halo exchange
@dataclasses.dataclass(frozen=True)
class HaloTransfer:
    """One ``ppermute`` round: every rank ``j`` with a nonempty overlap
    between its window and the core of rank ``j + offset`` sends that slab.

    Slabs are padded to ``length`` (the max over senders) because ppermute
    requires a uniform shape; ``src_len`` masks the padding to zero before
    the send.  All positions are *latent units* — ``src_start`` in the
    sender's window coordinates, ``dst_start`` in the receiver's core
    coordinates.  Ranks without a peer at this offset send a zero slab that
    no one receives and receive ppermute's implicit zeros.
    """

    offset: int
    length: int
    perm: Tuple[Tuple[int, int], ...]
    src_start: Tuple[int, ...]
    src_len: Tuple[int, ...]
    dst_start: Tuple[int, ...]


@dataclasses.dataclass(frozen=True)
class HaloSpec:
    """Static transfer schedule for halo-exchange LP reconstruction."""

    num_partitions: int
    window: int
    extent: int
    starts: Tuple[int, ...]
    core_start: Tuple[int, ...]
    core_end: Tuple[int, ...]
    core_pad: int                      # max core length (all-gather shard)
    transfers: Tuple[HaloTransfer, ...]

    @property
    def core_len(self) -> Tuple[int, ...]:
        return tuple(e - s for s, e in zip(self.core_start, self.core_end))

    @property
    def max_transfer(self) -> int:
        return max((t.length for t in self.transfers), default=0)

    @property
    def pad(self) -> int:
        """Zero-padding a window buffer needs so every slab slice is
        in-bounds (dynamic_slice clamping would silently corrupt data)."""
        return max(self.core_pad, self.max_transfer)


def halo_spec(plan) -> HaloSpec:
    """Build the exact transfer schedule from a uniform-window plan.

    ``plan`` needs ``num_partitions``, ``window``, ``extent``, ``starts``,
    ``core_start``, ``core_end`` (``core/uniform.UniformPlan``).  For every
    rank pair (j, k) the slab is ``window_j ∩ core_k``; pairs are grouped
    by offset ``k - j`` so each group is one ppermute.  Interior ranks only
    talk to +-1 neighbors; clamped edge windows at large overlap ratios
    produce the occasional |offset| >= 2 round, which stays exact here.
    """
    K = plan.num_partitions
    core_len = [plan.core_end[k] - plan.core_start[k] for k in range(K)]
    transfers = []
    for d in [x for x in range(-(K - 1), K) if x != 0]:
        pairs = []
        for j in range(K):
            k = j + d
            if not 0 <= k < K:
                continue
            lo = max(plan.starts[j], plan.core_start[k])
            hi = min(plan.starts[j] + plan.window, plan.core_end[k])
            if hi > lo:
                pairs.append((j, k, lo, hi))
        if not pairs:
            continue
        length = max(hi - lo for (_, _, lo, hi) in pairs)
        src_start, src_len, dst_start = [0] * K, [0] * K, [0] * K
        perm = []
        for j, k, lo, hi in pairs:
            perm.append((j, k))
            src_start[j] = lo - plan.starts[j]
            src_len[j] = hi - lo
            dst_start[k] = lo - plan.core_start[k]
        transfers.append(HaloTransfer(
            offset=d, length=length, perm=tuple(perm),
            src_start=tuple(src_start), src_len=tuple(src_len),
            dst_start=tuple(dst_start),
        ))
    return HaloSpec(
        num_partitions=K,
        window=plan.window,
        extent=plan.extent,
        starts=tuple(plan.starts),
        core_start=tuple(plan.core_start),
        core_end=tuple(plan.core_end),
        core_pad=max(core_len),
        transfers=tuple(transfers),
    )


@jax.named_scope("lp.halo")
def halo_exchange(
    wpred: jnp.ndarray,
    spec: HaloSpec,
    rank: jnp.ndarray,
    axis_name: str,
    eager_sends: bool = False,
    shard_axis: Optional[str] = None,
    shard_size: int = 1,
) -> jnp.ndarray:
    """Cross-rank reduction of overlapping window predictions, halo-only.

    ``wpred``: this rank's *weighted* prediction with the partition dim
    first, zero-padded at the end by at least ``spec.pad`` rows.  ``rank``
    is the traced lp-axis index.  Returns a ``(core_pad + max_transfer,
    ...)`` accumulator whose first ``core_len[rank]`` rows hold the full
    sum over every rank's contribution to this rank's core positions
    (unnormalized); rows beyond that are garbage by construction.

    Communication: one ppermute of slab size per transfer round — O(halo)
    bytes instead of the O(S_z) psum of the naive reconstruction.

    ``eager_sends`` issues every ppermute round up front, before any
    accumulation: the rounds carry no data dependence on each other, so
    XLA's async collective scheduler can start them all while the local
    own-core copy (and, on the hybrid mesh, the tail of the intra-group
    Phi_m forward that produces late rows of ``wpred``) is still in
    flight.  The default ordering interleaves send/accumulate per round,
    which serializes the rounds through the accumulator chain.

    ``shard_axis`` / ``shard_size`` (the hybrid mesh's tp axis and size)
    shard every slab over the tp axis: each tp rank ppermutes only its
    1/T chunk across the group boundary and the receiver reassembles
    the slab with one intra-group all-gather before depositing.  Slab
    values are tp-replicated on the hybrid mesh, so the result is
    bit-identical to the unsharded exchange — only the wire layout
    changes (inter-group bytes drop T-fold).
    """
    K = spec.num_partitions
    acc_len = spec.core_pad + spec.max_transfer
    trail = (1,) * (wpred.ndim - 1)
    acc = jnp.zeros((acc_len,) + wpred.shape[1:], wpred.dtype)
    sharded = shard_axis is not None and shard_size > 1

    def send(t: HaloTransfer) -> jnp.ndarray:
        slab = jax.lax.dynamic_slice_in_dim(
            wpred, jnp.asarray(t.src_start)[rank], t.length, 0
        )
        valid = jnp.arange(t.length) < jnp.asarray(t.src_len)[rank]
        slab = slab * valid.reshape((t.length,) + trail).astype(slab.dtype)
        if sharded:
            return sharded_ppermute(slab, axis_name, t.perm, shard_axis,
                                    shard_size)
        return jax.lax.ppermute(slab, axis_name, t.perm)

    def deposit(acc, t: HaloTransfer, got: jnp.ndarray) -> jnp.ndarray:
        dst = jnp.asarray(t.dst_start)[rank]
        cur = jax.lax.dynamic_slice_in_dim(acc, dst, t.length, 0)
        return jax.lax.dynamic_update_slice_in_dim(acc, cur + got, dst, 0)

    received = [send(t) for t in spec.transfers] if eager_sends else None
    # own window -> own core (no communication)
    own_off = jnp.asarray([spec.core_start[k] - spec.starts[k] for k in range(K)])
    own = jax.lax.dynamic_slice_in_dim(wpred, own_off[rank], spec.core_pad, 0)
    acc = jax.lax.dynamic_update_slice_in_dim(acc, own, 0, 0)
    for ti, t in enumerate(spec.transfers):
        got = received[ti] if eager_sends else send(t)
        acc = deposit(acc, t, got)
    return acc
