"""Pallas kernel validation: shape/dtype sweeps vs pure-jnp oracles,
interpret mode (CPU container; TPU is the lowering target)."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st

from repro.kernels import ops, ref
from repro.kernels.dit_attention import dit_attention
from repro.models.attention import attention_dense


def _mk_qkv(rng, B, Sq, Skv, H, KV, D, dtype):
    q = jnp.asarray(rng.normal(size=(B, Sq, H, D)), dtype)
    k = jnp.asarray(rng.normal(size=(B, Skv, KV, D)), dtype)
    v = jnp.asarray(rng.normal(size=(B, Skv, KV, D)), dtype)
    qp = jnp.broadcast_to(jnp.arange(Skv - Sq, Skv)[None], (B, Sq)).astype(jnp.int32)
    kp = jnp.broadcast_to(jnp.arange(Skv)[None], (B, Skv)).astype(jnp.int32)
    return q, k, v, qp, kp


# ------------------------------------------------------------------ flash
@pytest.mark.parametrize("B,Sq,Skv,H,KV,D", [
    (1, 16, 16, 4, 4, 32),       # MHA square
    (2, 33, 65, 8, 2, 64),       # GQA, ragged (padding path)
    (1, 128, 256, 4, 4, 128),    # MXU-aligned
    (2, 8, 200, 8, 8, 32),       # short q, long kv
])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_shapes(B, Sq, Skv, H, KV, D, causal):
    rng = np.random.default_rng(B * 100 + Sq)
    q, k, v, qp, kp = _mk_qkv(rng, B, Sq, Skv, H, KV, D, jnp.float32)
    out = ops.flash_attention(q, k, v, qp, kp, causal=causal,
                              blk_q=32, blk_k=64)
    want = ref.flash_attention_ref(q, k, v, qp, kp, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_flash_sliding_window():
    rng = np.random.default_rng(7)
    q, k, v, qp, kp = _mk_qkv(rng, 2, 64, 64, 4, 4, 32, jnp.float32)
    out = ops.flash_attention(q, k, v, qp, kp, causal=True, window=16,
                              blk_q=16, blk_k=16)
    want = ref.flash_attention_ref(q, k, v, qp, kp, causal=True, window=16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_flash_bf16():
    rng = np.random.default_rng(3)
    q, k, v, qp, kp = _mk_qkv(rng, 1, 32, 64, 4, 2, 64, jnp.bfloat16)
    out = ops.flash_attention(q, k, v, qp, kp, causal=True, blk_q=16, blk_k=32)
    want = ref.flash_attention_ref(q, k, v, qp, kp, causal=True)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32),
        atol=3e-2, rtol=3e-2)


def test_flash_kv_len_mask():
    """decode-style valid-length masking via ops wrapper."""
    rng = np.random.default_rng(9)
    q, k, v, qp, kp = _mk_qkv(rng, 2, 4, 64, 4, 4, 32, jnp.float32)
    kv_len = jnp.array([40, 17], jnp.int32)
    out = ops.flash_attention(q, k, v, qp, kp, causal=False, kv_len=kv_len,
                              blk_q=4, blk_k=16)
    from repro.models.attention import attention_dense

    want = attention_dense(q, k, v, qp, kp, causal=False, kv_len=kv_len)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_flash_skip_upper_matches():
    """the causal block-skip fast path must not change results."""
    rng = np.random.default_rng(11)
    q, k, v, qp, kp = _mk_qkv(rng, 1, 128, 128, 2, 2, 32, jnp.float32)
    a = ops.flash_attention(q, k, v, qp, kp, causal=True, blk_q=32,
                            blk_k=32, skip_upper=True)
    b = ops.flash_attention(q, k, v, qp, kp, causal=True, blk_q=32,
                            blk_k=32, skip_upper=False)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


@given(
    sq=st.integers(4, 96), skv=st.integers(4, 96),
    h=st.sampled_from([2, 4]), g=st.sampled_from([1, 2]),
    d=st.sampled_from([16, 32]), causal=st.booleans(),
)
@settings(max_examples=25, deadline=None)
def test_flash_property(sq, skv, h, g, d, causal):
    if causal and skv < sq:
        skv = sq
    kv = h // g
    rng = np.random.default_rng(sq * 97 + skv)
    q, k, v, qp, kp = _mk_qkv(rng, 1, sq, skv, h, kv, d, jnp.float32)
    out = ops.flash_attention(q, k, v, qp, kp, causal=causal,
                              blk_q=16, blk_k=16)
    want = ref.flash_attention_ref(q, k, v, qp, kp, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=3e-5, rtol=3e-5)


# ------------------------------------------------------------------ blend
def _mk_blend(rng, K, extent, patch, r, F, dtype=jnp.float32):
    from repro.core import plan_uniform
    from repro.core.spmd import window_weights

    plan = plan_uniform(extent, patch, K, r)
    preds = jnp.asarray(rng.normal(size=(K, plan.window, F)), dtype)
    w = jnp.asarray(window_weights(plan))
    z = jnp.asarray(plan.normalizer())
    return plan, preds, w, z


@pytest.mark.parametrize("K,extent,patch,r,F", [
    (4, 26, 2, 1.0, 48),
    (2, 16, 1, 0.5, 130),     # F not a multiple of blk
    (8, 64, 2, 0.25, 64),
    (3, 21, 1, 0.0, 96),      # no overlap
])
def test_latent_blend_shapes(K, extent, patch, r, F):
    rng = np.random.default_rng(K * 7 + extent)
    plan, preds, w, z = _mk_blend(rng, K, extent, patch, r, F)
    out = ops.latent_blend(preds, w, z, plan.starts, plan.window,
                           plan.extent, blk_f=32)
    want = ref.latent_blend_ref(preds, w, z, plan.starts, plan.window,
                                plan.extent)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_latent_blend_is_partition_of_unity():
    """identical predictions in every window -> exact passthrough."""
    rng = np.random.default_rng(0)
    from repro.core import plan_uniform
    from repro.core.spmd import window_weights

    plan = plan_uniform(24, 2, 4, 1.0)
    truth = jnp.asarray(rng.normal(size=(24, 33)).astype(np.float32))
    preds = jnp.stack([
        truth[plan.starts[k]:plan.starts[k] + plan.window] for k in range(4)
    ])
    w = jnp.asarray(window_weights(plan))
    z = jnp.asarray(plan.normalizer())
    out = ops.latent_blend(preds, w, z, plan.starts, plan.window, plan.extent,
                           blk_f=16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(truth), atol=1e-5)


@given(
    K=st.integers(2, 6), n_patches=st.integers(6, 40),
    patch=st.sampled_from([1, 2]), r=st.floats(0.0, 1.0),
    F=st.sampled_from([8, 33]),
)
@settings(max_examples=20, deadline=None)
def test_latent_blend_property(K, n_patches, patch, r, F):
    if n_patches < K:
        return
    rng = np.random.default_rng(K * 31 + n_patches)
    plan, preds, w, z = _mk_blend(rng, K, n_patches * patch, patch, r, F)
    out = ops.latent_blend(preds, w, z, plan.starts, plan.window,
                           plan.extent, blk_f=16)
    want = ref.latent_blend_ref(preds, w, z, plan.starts, plan.window,
                                plan.extent)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=3e-5, rtol=3e-5)


@pytest.mark.parametrize("K,extent,patch,r,F,blk_f", [
    (2, 60, 2, 0.5, 1000, 512),     # the one-chip H window, F padded
    (4, 104, 2, 0.5, 700, 512),     # unaligned starts (0, 14, 40, 54)
    (4, 21, 1, 0.5, 96, 32),        # 81-frame T windows, several F blocks
])
def test_latent_blend_bit_identical_to_oracle(K, extent, patch, r, F, blk_f):
    """Interpret mode reproduces the scatter-add oracle bit for bit: the
    kernel's f32 multiply-adds run in the same partition order."""
    rng = np.random.default_rng(K * 13 + extent)
    plan, preds, w, z = _mk_blend(rng, K, extent, patch, r, F)
    out = ops.latent_blend(preds, w, z, plan.starts, plan.window,
                           plan.extent, blk_f=blk_f)
    want = ref.latent_blend_ref(preds, w, z, plan.starts, plan.window,
                                plan.extent)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))


# ----------------------------------------------------------- DiT attention
def _rotate_f32(x, grid, origin):
    """x (B, S, H, D) rotated in f32 by the axial angles of ``grid`` at
    ``origin``: (x1 cos - x2 sin, x2 cos + x1 sin) over the two halves."""
    from repro.models import dit

    cos, sin = (t[:, None, :] for t in dit.rope_tables(grid, origin,
                                                       x.shape[-1]))
    half = x.shape[-1] // 2
    c, s = cos[..., :half], sin[..., half:]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def test_rope_tables_are_the_axial_angles():
    """``dit.rope_tables`` in rotate-half form: token (t, h, w) at origin
    (ot, oh, ow) turns pair i of the t part (the first
    ``(D // 3) & ~1`` dims' halves) by (t + ot) theta^(-2i/d_t), then the
    h part, then w; cos over both halves, sin as [-sin, +sin]."""
    from repro.models import dit

    grid, origin, D = (2, 3, 4), (1, 0, 2), 32
    cos, sin = (np.asarray(t) for t in dit.rope_tables(grid, origin, D))
    d_t = d_h = (D // 3) & ~1
    parts = [(g, o, d) for g, o, d in zip(grid, origin,
                                          (d_t, d_h, D - d_t - d_h))]
    t, h, w = np.meshgrid(*[np.arange(g) + o for g, o, _ in parts],
                          indexing="ij")
    ang = np.concatenate(
        [pos.reshape(-1, 1) * 10_000.0 ** (-np.arange(0, d, 2) / d)
         for pos, (_, _, d) in zip((t, h, w), parts)], axis=1)
    assert cos.shape == sin.shape == (np.prod(grid), D)
    np.testing.assert_allclose(cos, np.cos(np.tile(ang, 2)), atol=1e-6)
    np.testing.assert_allclose(
        sin, np.concatenate([-np.sin(ang), np.sin(ang)], 1), atol=1e-6)


# (rows, B, Sq, Skv, H, D, blocks, rope): ``blocks`` None takes the
# kernel's own (v5e-swept) sizes through ``ops``; rows > 0 vmaps a leading
# axis the way the one-chip engine maps LP windows over the step; ``rope``
# (grid, one origin per row) passes the tables of ``dit.rope_tables``
@pytest.mark.parametrize("rows,B,Sq,Skv,H,D,blocks,rope", [
    (0, 2, 300, 300, 2, 128, (128, 256, 128), None),    # ragged tails
    (0, 1, 1000, 1000, 2, 128, (256, 256, 128), None),  # a tail chunk skipped
    (0, 1, 300, 512, 2, 128, (128, 1024, 512), None),   # cross: S x 512
    (0, 1, 260, 260, 12, 128, (128, 128, 128), None),   # WAN's 12 x 128
    (2, 2, 300, 300, 2, 128, (128, 256, 128), None),    # vmapped rows
    (0, 1, 200, 260, 3, 32, None, None),                # head padded to 128
    (0, 2, 300, 300, 2, 128, (128, 256, 128),
     ((3, 10, 10), [(0, 0, 0)])),
    (0, 1, 1000, 1000, 2, 128, (256, 256, 128),
     ((10, 10, 10), [(0, 0, 0)])),
    (2, 2, 300, 300, 2, 128, (128, 256, 128),
     ((3, 10, 10), [(0, 0, 0), (2, 5, 4)])),            # LP windows' origins
    (0, 1, 260, 260, 3, 32, None, ((1, 13, 20), [(0, 0, 0)])),
], ids=["self300", "self1000", "cross512", "h12d128", "vmap_rows", "d32",
        "self300_rope", "self1000_rope", "vmap_origins_rope", "d32_rope"])
def test_dit_attention(rows, B, Sq, Skv, H, D, blocks, rope):
    """The bf16 DiT kernel against f32 dense attention on the same bf16
    inputs, with q and k rotated in f32 where ``rope`` is given.
    Tolerance: three bf16 roundings (the 1/sqrt(D)-scaled q, rotated
    first where RoPE applies, the probabilities as the PV operand, the
    output), each at most the unit roundoff 2^-9 relative, on an output
    that is a convex combination of the values; so the error stays under
    3 * 2^-9 * max|v|.  The rotated k's one rounding moves the scores
    as the scaled q's does, and the same bound holds for it."""
    lead = (rows,) if rows else ()
    rng = np.random.default_rng(Sq + Skv + H)
    q, k, v = (jnp.asarray(rng.normal(size=lead + (B, S, H, D)), jnp.bfloat16)
               for S in (Sq, Skv, Skv))
    if blocks is None:
        fn = ops.dit_attention
    else:
        bq, bkv, bkc = blocks
        fn = functools.partial(dit_attention, block_q=bq, block_kv=bkv,
                               block_kv_compute=bkc, interpret=True)
    f32 = [x.astype(jnp.float32) for x in (q, k, v)]
    if rope is None:
        out = jax.vmap(fn)(q, k, v) if rows else fn(q, k, v)
    else:
        from repro.models import dit

        grid, origins = rope
        tables = [dit.rope_tables(grid, o, D) for o in origins]
        if rows:
            cos, sin = (jnp.stack(t) for t in zip(*tables))
            out = jax.vmap(lambda q, k, v, c, s: fn(q, k, v, rope=(c, s)))(
                q, k, v, cos, sin)
            f32[:2] = [jnp.stack([_rotate_f32(x[r], grid, o)
                                  for r, o in enumerate(origins)])
                       for x in f32[:2]]
        else:
            out = fn(q, k, v, rope=tables[0])
            f32[:2] = [_rotate_f32(x, grid, origins[0]) for x in f32[:2]]
    f32 = [x.reshape((-1,) + x.shape[-3:]) for x in f32]
    n = f32[0].shape[0]
    want = attention_dense(*f32, jnp.zeros((n, Sq), jnp.int32),
                           jnp.zeros((n, Skv), jnp.int32), causal=False)
    assert out.shape == q.shape and out.dtype == jnp.bfloat16
    tol = 3 * 2.0 ** -9 * float(jnp.abs(v.astype(jnp.float32)).max())
    np.testing.assert_allclose(
        np.asarray(out, np.float32).reshape(want.shape), np.asarray(want),
        atol=tol, rtol=0)


@pytest.mark.parametrize("tpu,sharded,kernel", [
    (False, False, False),   # CPU: the chunked scan
    (True, False, True),     # TPU: the kernel
    (True, True, False),     # GSPMD context (dry-run): the chunked scan
], ids=["cpu", "tpu", "tpu_gspmd"])
def test_dit_attention_path(monkeypatch, tpu, sharded, kernel):
    """``dit._attn`` runs the kernel exactly where the backend compiles
    Pallas and no activation-sharding context is active."""
    import contextlib

    from repro.configs import get_config
    from repro.distributed import actctx
    from repro.models import dit

    cfg = get_config("wan21-dit-1.3b").reduced()
    monkeypatch.setattr(ops, "default_interpret", lambda: not tpu)
    params = dit.init_params(jax.random.PRNGKey(0), cfg)
    blk = jax.tree.map(lambda x: x[0], params["blocks"])["self_attn"]
    x = jnp.zeros((1, 16, cfg.d_model), jnp.dtype(cfg.dtype))
    ctx = actctx.batch_axes(("data",)) if sharded else contextlib.nullcontext()
    rope = dit.rope_tables((1, 4, 4), (0, 0, 0), cfg.head_dim)
    with ctx:
        jaxpr = jax.make_jaxpr(
            lambda x: dit._attn(blk, x, cfg, rope))(x)
    assert ("pallas_call" in str(jaxpr)) == kernel


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_dit_forward_kernel_path_matches_scan(monkeypatch, dtype):
    """``dit.forward`` at a reduced config predicts the same on both
    attention paths: the kernel (interpret mode) with RoPE from the
    tables in its ``dit_rope`` pass and the scale folded into q's, and
    the chunked scan after :func:`dit.apply_rope` in jnp.  A sign or a
    half swapped in either RoPE would move the prediction by O(1); the
    bf16 tolerance (a few units of 2^-8 of the output's scale) admits
    only rounding, so the two apply one formula."""
    import dataclasses

    from repro.configs import get_config
    from repro.models import dit

    cfg = dataclasses.replace(get_config("wan21-dit-1.3b").reduced(),
                              dtype=dtype)
    params = dit.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(3)
    z = jnp.asarray(rng.normal(size=(2, 3, 8, 12, cfg.latent_channels)),
                    jnp.float32)
    t = jnp.asarray([700.0, 20.0])
    ctx = jnp.asarray(rng.normal(size=(2, cfg.context_len, cfg.context_dim)),
                      jnp.float32)

    def predict(kernel):
        monkeypatch.setattr(ops, "default_interpret", lambda: not kernel)
        return jax.jit(lambda z: dit.forward(params, z, t, ctx, cfg,
                                             origin=(1, 2, 4)))(z)

    kernel = functools.partial(ops.dit_attention, interpret=True)
    monkeypatch.setattr(ops, "dit_attention", kernel)
    got, want = (np.asarray(predict(k), np.float32) for k in (True, False))
    monkeypatch.undo()
    scale = float(np.abs(want).max())
    assert scale > 0.1
    np.testing.assert_allclose(got, want, atol=4 * 2.0 ** -8 * scale, rtol=0)


def test_kernels_interpret_only_off_tpu():
    """One place decides interpret mode, from the backend."""
    assert ops.default_interpret() == (jax.default_backend() != "tpu")


# --------------------------------------------------------------- guidance
@pytest.mark.parametrize("shape", [(4, 8, 8, 4), (1, 13, 60, 104, 16)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_guidance_update(shape, dtype):
    rng = np.random.default_rng(1)
    z = jnp.asarray(rng.normal(size=shape), dtype)
    c = jnp.asarray(rng.normal(size=shape), dtype)
    u = jnp.asarray(rng.normal(size=shape), dtype)
    out = ops.guidance_update(z, c, u, w=5.0, dt=-0.02, blk=4096)
    want = ref.guidance_update_ref(z, c, u, 5.0, -0.02)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               atol=1e-2 if dtype == jnp.bfloat16 else 1e-6)


# -------------------------------------------------------------- mamba ssd
@pytest.mark.parametrize("b,s,h,p,n,chunk,hb", [
    (2, 100, 16, 32, 16, 32, 8),
    (1, 64, 8, 16, 8, 16, 8),     # hb == h
    (2, 37, 4, 8, 4, 16, 2),      # ragged seq (padding path)
])
def test_mamba_ssd_kernel(b, s, h, p, n, chunk, hb):
    rng = np.random.default_rng(s * 7 + h)
    x = jnp.asarray(rng.normal(size=(b, s, h, p)).astype(np.float32))
    dt = jnp.asarray(rng.uniform(0.01, 0.2, size=(b, s, h)).astype(np.float32))
    A = -jnp.asarray(rng.uniform(0.5, 8.0, size=(h,)).astype(np.float32))
    B = jnp.asarray(rng.normal(size=(b, s, n)).astype(np.float32))
    C = jnp.asarray(rng.normal(size=(b, s, n)).astype(np.float32))
    out = ops.mamba_ssd(x, dt * A[None, None, :], dt, B, C,
                        chunk=chunk, head_block=hb)
    want = ref.mamba_ssd_ref(x, dt * A[None, None, :], dt, B, C)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=5e-4, rtol=5e-4)


@given(
    s=st.integers(8, 80), h=st.sampled_from([4, 8]),
    p=st.sampled_from([8, 16]), chunk=st.sampled_from([8, 16, 32]),
)
@settings(max_examples=15, deadline=None)
def test_mamba_ssd_property(s, h, p, chunk):
    rng = np.random.default_rng(s * 13 + h)
    b, n = 1, 8
    x = jnp.asarray(rng.normal(size=(b, s, h, p)).astype(np.float32))
    dt = jnp.asarray(rng.uniform(0.01, 0.15, size=(b, s, h)).astype(np.float32))
    A = -jnp.asarray(rng.uniform(0.5, 4.0, size=(h,)).astype(np.float32))
    B = jnp.asarray(rng.normal(size=(b, s, n)).astype(np.float32))
    C = jnp.asarray(rng.normal(size=(b, s, n)).astype(np.float32))
    out = ops.mamba_ssd(x, dt * A[None, None, :], dt, B, C,
                        chunk=chunk, head_block=h)
    want = ref.mamba_ssd_ref(x, dt * A[None, None, :], dt, B, C)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=5e-4, rtol=5e-4)
