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
# (rows, B, Sq, Skv, H, D, blocks): ``blocks`` None takes the kernel's own
# (v5e-swept) sizes through ``ops``; rows > 0 vmaps a leading axis the way
# the one-chip engine maps LP windows over the step
@pytest.mark.parametrize("rows,B,Sq,Skv,H,D,blocks", [
    (0, 2, 300, 300, 2, 128, (128, 256, 128)),    # padded tail, 1 kv block
    (0, 1, 1000, 1000, 2, 128, (256, 256, 128)),  # tail chunk masked, one skipped
    (0, 1, 300, 512, 2, 128, (128, 1024, 512)),   # cross-attention: S x 512
    (0, 1, 260, 260, 12, 128, (128, 128, 128)),   # WAN2.1's 12 heads of 128
    (2, 2, 300, 300, 2, 128, (128, 256, 128)),    # vmapped rows
    (0, 1, 200, 260, 3, 32, None),                # head size padded to 128
], ids=["self300", "self1000", "cross512", "h12d128", "vmap_rows", "d32"])
def test_dit_attention(rows, B, Sq, Skv, H, D, blocks):
    """The bf16 DiT kernel against f32 dense attention on the same bf16
    inputs.  Tolerance: three bf16 roundings (the 1/sqrt(D)-scaled q, the
    probabilities as the PV operand, the output), each at most the unit
    roundoff 2^-9 relative, on an output that is a convex combination of
    the values; so the error stays under 3 * 2^-9 * max|v|."""
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
    out = jax.vmap(fn)(q, k, v) if rows else fn(q, k, v)
    f32 = [x.reshape((-1,) + x.shape[-3:]).astype(jnp.float32)
           for x in (q, k, v)]
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
    with ctx:
        jaxpr = jax.make_jaxpr(
            lambda x: dit._attn(blk, x, cfg, grid=(1, 4, 4)))(x)
    assert ("pallas_call" in str(jaxpr)) == kernel


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
