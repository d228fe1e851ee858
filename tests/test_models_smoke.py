"""Per-architecture smoke tests: reduced config, one forward + one decode
step on CPU, asserting output shapes and no NaNs.  Full configs are only
exercised via the dry-run (ShapeDtypeStruct, no allocation)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import models
from repro.configs import ALL_ARCHS, VDM_ARCHS, get_config
from repro.models import frontends

B, S = 2, 24


def _batch(key, cfg):
    kt, kv, kf = jax.random.split(key, 3)
    batch = {
        "tokens": jax.random.randint(kt, (B, S), 0, cfg.vocab_size or 1),
        "labels": jax.random.randint(kt, (B, S), 0, cfg.vocab_size or 1),
    }
    if cfg.family == "vlm":
        batch["vision_embeds"] = frontends.vision_patches(kv, B, cfg)
    if cfg.family == "audio":
        batch["frames"] = frontends.audio_frames(kf, B, cfg)
    if cfg.family == "vdm":
        kz, kc = jax.random.split(kv)
        batch = {
            "latent": jax.random.normal(kz, (B, 4, 8, 8, cfg.latent_channels)),
            "t": jnp.full((B,), 500.0),
            "context": frontends.conditioning(kc, B, cfg),
            "guidance": jnp.full((B,), 5.0),
        }
    return batch


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_forward_smoke(arch):
    cfg = get_config(arch).reduced()
    model = models.build(cfg)
    key = jax.random.PRNGKey(0)
    params = model.init(key)
    batch = _batch(jax.random.PRNGKey(1), cfg)
    out, aux = jax.jit(lambda p, b: model.forward(p, b))(params, batch)
    if cfg.family == "vdm":
        assert out.shape == batch["latent"].shape
    else:
        assert out.shape == (B, S, cfg.d_model)
    assert not np.isnan(np.asarray(out, np.float32)).any(), f"{arch}: NaNs"


@pytest.mark.parametrize("arch", [a for a in ALL_ARCHS if a not in VDM_ARCHS])
def test_train_step_smoke(arch):
    """One loss+grad step: finite loss, finite grads, params update."""
    cfg = get_config(arch).reduced()
    model = models.build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = _batch(jax.random.PRNGKey(1), cfg)

    loss, grads = jax.jit(jax.value_and_grad(model.loss))(params, batch)
    assert np.isfinite(float(loss)), f"{arch}: loss={loss}"
    leaves = jax.tree.leaves(grads)
    assert leaves, "no grads"
    for g in leaves:
        assert np.isfinite(np.asarray(g, np.float32)).all(), f"{arch}: NaN grad"
    # a plain SGD step changes the params
    new = jax.tree.map(lambda p, g: p - 0.1 * g.astype(p.dtype), params, grads)
    changed = any(
        not np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(new))
    )
    assert changed


@pytest.mark.parametrize(
    "arch",
    [a for a in ALL_ARCHS if a not in (*VDM_ARCHS, "whisper-small")],
)
def test_decode_step_smoke(arch):
    cfg = get_config(arch).reduced()
    model = models.build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    cache = model.init_cache(B, 32)
    tok = jnp.zeros((B, 1), jnp.int32)
    pos = jnp.zeros((B,), jnp.int32)
    logits, cache2 = jax.jit(model.decode)(params, tok, cache, pos)
    assert logits.shape == (B, 1, cfg.vocab_size)
    assert np.isfinite(np.asarray(logits)).all(), f"{arch}: NaN logits"
    # second step with updated position works on the new cache
    logits2, _ = jax.jit(model.decode)(params, tok, cache2, pos + 1)
    assert np.isfinite(np.asarray(logits2)).all()


def test_whisper_decode_smoke():
    cfg = get_config("whisper-small").reduced()
    model = models.build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    from repro.models import encdec

    frames = frontends.audio_frames(jax.random.PRNGKey(2), B, cfg)
    enc = encdec.encode(params, frames, cfg)
    cache = model.init_cache(B, 32)
    tok = jnp.zeros((B, 1), jnp.int32)
    pos = jnp.zeros((B,), jnp.int32)
    logits, cache2 = jax.jit(model.decode)(params, tok, cache, pos, enc)
    assert logits.shape == (B, 1, cfg.vocab_size)
    assert np.isfinite(np.asarray(logits)).all()


def test_prefill_decode_consistency_dense():
    """Teacher-forced forward logits == step-by-step decode logits."""
    cfg = get_config("granite-3-2b").reduced()
    model = models.build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 8), 0, cfg.vocab_size)
    hidden, _ = model.forward(params, {"tokens": tokens})
    from repro.models.transformer import logits_fn

    full = logits_fn(params, hidden, cfg)
    cache = model.init_cache(1, 8)
    outs = []
    for t in range(8):
        lg, cache = model.decode(
            params, tokens[:, t : t + 1], cache, jnp.array([t], jnp.int32)
        )
        outs.append(lg)
    stepped = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(
        np.asarray(full), np.asarray(stepped), rtol=2e-2, atol=2e-2
    )


def test_prefill_decode_consistency_hybrid():
    cfg = get_config("zamba2-2.7b").reduced()
    model = models.build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 8), 0, cfg.vocab_size)
    hidden, _ = model.forward(params, {"tokens": tokens})
    from repro.models.transformer import logits_fn

    full = logits_fn(params, hidden, cfg)
    cache = model.init_cache(1, 8)
    outs = []
    for t in range(8):
        lg, cache = model.decode(
            params, tokens[:, t : t + 1], cache, jnp.array([t], jnp.int32)
        )
        outs.append(lg)
    stepped = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(
        np.asarray(full), np.asarray(stepped), rtol=3e-2, atol=3e-2
    )


def test_full_configs_match_assignment():
    """The exact assigned numbers are what the configs carry."""
    spec = {
        "zamba2-2.7b": (54, 2560, 32, 32, 10240, 32000),
        "xlstm-1.3b": (48, 2048, 4, 4, 0, 50304),
        "granite-3-2b": (40, 2048, 32, 8, 8192, 49155),
        "llama3-405b": (126, 16384, 128, 8, 53248, 128256),
        "h2o-danube-1.8b": (24, 2560, 32, 8, 6912, 32000),
        "minitron-4b": (32, 3072, 24, 8, 9216, 256000),
        "internvl2-26b": (48, 6144, 48, 8, 16384, 92553),
        "whisper-small": (12, 768, 12, 12, 3072, 51865),
        "granite-moe-3b-a800m": (32, 1536, 24, 8, 0, 49155),
        "llama4-maverick-400b-a17b": (48, 5120, 40, 8, 0, 202048),
    }
    for arch, (L, d, H, KV, ff, V) in spec.items():
        cfg = get_config(arch)
        assert cfg.num_layers == L and cfg.d_model == d
        assert cfg.num_heads == H and cfg.num_kv_heads == KV
        assert cfg.d_ff == ff and cfg.vocab_size == V, arch
    assert get_config("zamba2-2.7b").ssm_state == 64
    assert get_config("granite-moe-3b-a800m").num_experts == 40
    assert get_config("granite-moe-3b-a800m").experts_top_k == 8
    assert get_config("granite-moe-3b-a800m").d_ff_expert == 512
    assert get_config("llama4-maverick-400b-a17b").num_experts == 128
    assert get_config("llama4-maverick-400b-a17b").experts_top_k == 1
    assert get_config("llama4-maverick-400b-a17b").d_ff_expert == 8192


def test_swa_windowed_decode_matches_full_scan():
    """The sliding-window cache-slice fast path must equal full-cache
    attention with window masking (h2o-danube decode)."""
    import dataclasses

    from repro.models.attention import decode_attention, gqa_init

    cfg = get_config("h2o-danube-1.8b").reduced()
    key = jax.random.PRNGKey(0)
    params = gqa_init(key, cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
                      cfg.head_dim, jnp.float32)
    Bx, S_max, win = 2, 64, 8
    rng = np.random.default_rng(0)
    ck = jnp.asarray(rng.normal(
        size=(Bx, S_max, cfg.num_kv_heads, cfg.head_dim)).astype(np.float32))
    cv = jnp.asarray(rng.normal(
        size=(Bx, S_max, cfg.num_kv_heads, cfg.head_dim)).astype(np.float32))
    x_t = jnp.asarray(rng.normal(size=(Bx, 1, cfg.d_model)).astype(np.float32))
    pos = jnp.array([40, 5], jnp.int32)   # one deep, one shallower than win

    fast, _, _ = decode_attention(
        params, x_t, ck, cv, pos, cfg.rope_theta, cfg.num_heads,
        cfg.num_kv_heads, cfg.head_dim, window=win)
    # reference: window = 0 fast path disabled, mask manually via window
    slow, _, _ = decode_attention(
        params, x_t, ck, cv, pos, cfg.rope_theta, cfg.num_heads,
        cfg.num_kv_heads, cfg.head_dim, window=S_max)  # no slicing branch
    # recompute slow with true window masking using the full-cache branch:
    from repro.models.attention import attention_chunked, decode_attention as _
    # simplest oracle: call decode_attention with window >= S_max disabled
    # then compare against itself is meaningless; instead compare fast vs
    # a manual full-cache masked attention:
    from repro.models.layers import apply_rope
    from repro.models.attention import dense as _dense  # noqa
    # Build oracle via private path
    import repro.models.attention as A

    q = A.dense(params["q"], x_t).reshape(Bx, 1, cfg.num_heads, cfg.head_dim)
    k_new = A.dense(params["k"], x_t).reshape(Bx, 1, cfg.num_kv_heads, cfg.head_dim)
    v_new = A.dense(params["v"], x_t).reshape(Bx, 1, cfg.num_kv_heads, cfg.head_dim)
    q = apply_rope(q, pos[:, None], cfg.rope_theta)
    k_new = apply_rope(k_new, pos[:, None], cfg.rope_theta)
    ck2 = jax.vmap(lambda cb, nb, p: jax.lax.dynamic_update_slice_in_dim(
        cb, nb, p, 0))(ck, k_new, pos)
    cv2 = jax.vmap(lambda cb, nb, p: jax.lax.dynamic_update_slice_in_dim(
        cb, nb, p, 0))(cv, v_new, pos)
    kv_pos = jnp.broadcast_to(jnp.arange(S_max)[None], (Bx, S_max))
    oracle_attn = A.attention_dense(
        q, ck2, cv2, pos[:, None], kv_pos, causal=False, window=win,
        kv_len=pos + 1)
    oracle = A.dense(params["o"], oracle_attn.reshape(Bx, 1, -1))
    np.testing.assert_allclose(np.asarray(fast), np.asarray(oracle),
                               atol=2e-5, rtol=2e-5)
