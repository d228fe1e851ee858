"""HunyuanVideo's MMDiT (``models/hunyuan_video``) against the plain
float32 reference of the benchmark's architecture module
(``bench/models/hunyuanvideo-dit.py``, loaded by path), on the CPU at a
small width: d 96, 2 heads of 48, RoPE axes (8, 20, 20), seeded weights.

* the forward against the reference's velocity, for each kind of block
  alone and together, with and without the refiner;
* ``submit``/``run`` at K=2 and K=1 against the reference LP trajectory;
* embedded guidance traces one forward over B rows, CFG one over 2B, and
  WAN's guided step still takes ``(params, ctx, null_ctx, guidance)``;
* two requests batched with pytree conditioning give what each gives
  alone;
* every compute op of the compiled step carries an ``mmdit.*`` scope;
* the step's FLOP count is one forward;
* the benchmark's own weights fill the program initializer's tree, and
  every one of them moves the program's prediction.

Program and reference both run in float32 here, so they differ only by
summation order (the chunked attention's online softmax, the fused
projections) and by RoPE angles taken in float32: about 1e-7 of a
quantity's norm.  Each tolerance below is 1e-5 of it: a hundred times
that rounding, and far below what any term of the block moves (a bias
of 0.02 or a norm scale of 1 + 0.1 x noise left out moves the output by
more than 1e-3 of its norm).
"""
from __future__ import annotations

import dataclasses
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import hunyuan_video as hv

BENCH = Path(__file__).resolve().parents[1] / "bench"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

from benchlib import reference, spec  # noqa: E402

MODULE = spec.model("hunyuanvideo-dit", BENCH)
TOL = 1e-5          # relative: see the module docstring
LATENT = (4, 8, 12)

# the benchmark configuration's ``arch`` block at the test's width
ARCH = dict(
    double_layers=1, single_layers=2, refiner_layers=2, d_model=96,
    num_heads=2, head_dim=48, d_ff=384, patch_sizes=[1, 2, 2],
    latent_channels=4, context_len=8, context_dim=64, pooled_dim=32,
    rope_axes=[8, 20, 20], rope_theta=256.0, guidance_embed=True,
    dtype="float32")


def _rel(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def _setup(**arch):
    a = dict(ARCH, **arch)
    cfg = MODULE._cfg(a)
    params = MODULE.make_params(jax.random.PRNGKey(0), a)
    return a, cfg, params


def _velocity(a, params, z, t, cond, g):
    fn = MODULE.window_fn(spec.freeze(a), None, None, None)
    return np.asarray(fn(params, z, np.float32(t),
                         jax.tree.map(lambda c: c[0], cond), np.float32(g)))


def test_config_is_the_published_model():
    cfg = get_config("hunyuanvideo-dit")
    assert (cfg.d_model, cfg.num_heads, cfg.head_dim, cfg.d_ff) == \
        (3072, 24, 128, 12288)
    assert (cfg.double_layers, cfg.single_layers, cfg.refiner_layers) == \
        (20, 40, 2)
    assert cfg.rope_axes == (16, 56, 56) and sum(cfg.rope_axes) == 128
    assert cfg.guidance_embed and cfg.pooled_dim == 768
    assert (cfg.context_len, cfg.context_dim) == (256, 4096)
    # the benchmark file's widths are the published ones; only depth cut
    import json

    conf = json.loads((BENCH / "configs" /
                       "hunyuanvideo-544p-33f.json").read_text())
    bench_cfg = MODULE.program(conf)[1]
    assert dataclasses.replace(
        bench_cfg, double_layers=20, single_layers=40,
        num_layers=60) == cfg
    assert sorted(conf["reduced"]) == ["double_layers", "single_layers"]


@pytest.mark.parametrize("double, single, refiner", [
    (1, 0, 2), (0, 2, 2), (1, 2, 0), (1, 2, 2)],
    ids=["double", "single", "no_refiner", "all"])
def test_forward_matches_reference(double, single, refiner):
    a, cfg, params = _setup(double_layers=double, single_layers=single,
                            refiner_layers=refiner)
    cond = MODULE.context(a, jax.random.PRNGKey(1))
    z = jax.random.normal(jax.random.PRNGKey(2), (1, *LATENT, 4))
    g = 5.0
    out = jax.jit(lambda p, z, c: hv.forward(
        p, z, jnp.full((1,), 700.0), c, cfg, jnp.full((1,), g)))(
        params, z, cond)
    ref = _velocity(a, params, z[0], 700.0, cond, g)
    assert _rel(out[0], ref) < TOL
    # the guidance scale, the text and the pooled vector all reach the
    # video tokens' prediction
    other = {"text": cond["text"] * 0.5, "pooled": cond["pooled"]}
    assert _rel(_velocity(a, params, z[0], 700.0, other, g), ref) > 1e-3
    other = {"text": cond["text"], "pooled": -cond["pooled"]}
    assert _rel(_velocity(a, params, z[0], 700.0, other, g), ref) > 1e-3
    assert _rel(_velocity(a, params, z[0], 700.0, cond, 6.0), ref) > 1e-3


def test_benchmark_weights_fill_the_program_tree_and_all_are_read():
    """``make_params`` draws the weights in benchmark code: the tree,
    shapes and dtypes of ``hunyuan_video.init_params``, and no leaf that
    the forward leaves unread (its gradient would be exactly 0)."""
    a, cfg, params = _setup()
    program = jax.eval_shape(lambda k: hv.init_params(k, cfg),
                             jax.random.PRNGKey(0))
    assert jax.tree.structure(program) == jax.tree.structure(params)
    for want, got in zip(jax.tree.leaves(program), jax.tree.leaves(params)):
        assert (want.shape, want.dtype) == (got.shape, got.dtype)
    cond = MODULE.context(a, jax.random.PRNGKey(1))
    z = jax.random.normal(jax.random.PRNGKey(2), (1, *LATENT, 4))
    probe = jax.random.normal(jax.random.PRNGKey(3), z.shape)
    grads = jax.jit(jax.grad(lambda p: jnp.sum(probe * hv.forward(
        p, z, jnp.full((1,), 700.0), cond, cfg, jnp.full((1,), 5.0)))))(
        params)
    for path, g in jax.tree_util.tree_leaves_with_path(grads):
        assert np.abs(np.asarray(g)).max() > 0, jax.tree_util.keystr(path)


def _engine(cfg, params, k, max_batch=1, steps=3):
    from repro.models import FORWARDS
    from repro.serving.engine import LPServingEngine

    return LPServingEngine(FORWARDS["hunyuanvideo-dit"], params, cfg,
                           num_partitions=k, overlap_ratio=0.5 if k > 1
                           else 0.0, num_steps=steps, max_batch=max_batch)


def _request(a, rid, guidance=5.0):
    from repro.serving.engine import VideoRequest

    return VideoRequest(request_id=rid, latent_shape=LATENT, seed=rid + 7,
                        guidance=guidance,
                        context=MODULE.context(a, jax.random.PRNGKey(rid)))


@pytest.mark.parametrize("k", [2, 1])
def test_serving_matches_reference_trajectory(k):
    """The engine's served latent against the reference's LP windows,
    stitch and Euler steps around the module's prediction."""
    a, cfg, params = _setup()
    eng = _engine(cfg, params, k)
    req = _request(a, 3)
    eng.submit(req)
    (res,) = eng.run()
    z_T = np.asarray(jax.random.normal(jax.random.PRNGKey(req.seed),
                                       (1, *LATENT, 4)), np.float64)[0]
    ref = reference.Reference(MODULE, a, params)
    traj = ref.trajectory(z_T, 3, k, 0.5 if k > 1 else 0.0,
                          jax.tree.map(lambda c: np.asarray(c)[0],
                                       req.context), req.guidance)
    served = np.asarray(res.latent, np.float64)[0]
    # as a share of the reference's whole move, as the benchmark reads it
    err = np.linalg.norm(served - traj[-1]) / np.linalg.norm(traj[-1] - z_T)
    assert err < TOL


def _recording(log):
    def fwd(params, z, t, cond, cfg, *rest):
        log.append((z.shape[0], t.shape, len(rest)))
        return z
    return fwd


def test_embedded_guidance_is_one_forward_over_b_rows():
    from repro.diffusion.pipeline import make_guided_step_denoiser

    _, cfg, _ = _setup()
    log = []
    guided = make_guided_step_denoiser(_recording(log), cfg)
    window = jnp.zeros((3, *LATENT, 4))
    cond = {"text": jnp.zeros((3, 8, 64)), "pooled": jnp.zeros((3, 32))}
    jax.eval_shape(lambda w, g: guided(w, jnp.float32(1.0), None, cond, g),
                   window, jnp.float32(5.0))
    # one forward of B = 3 rows, its guidance an input of the network
    assert log == [(3, (3,), 1)]


def test_cfg_guidance_is_one_forward_over_2b_rows_and_keeps_its_extras():
    """WAN's guided step: ``(window, t, params, ctx, null_ctx,
    guidance)``, the conditional and unconditional rows in one forward,
    and the engine hands it the CFG pair's contexts."""
    from repro.diffusion.pipeline import make_guided_step_denoiser
    from repro.models import dit
    from repro.serving.engine import LPServingEngine

    cfg = get_config("wan21-dit-1.3b").reduced()
    assert not cfg.guidance_embed
    log = []
    guided = make_guided_step_denoiser(_recording(log), cfg)
    window = jnp.zeros((3, *LATENT, 4))
    ctx = jnp.ones((3, 16, 128))
    jax.eval_shape(lambda w, g: guided(w, jnp.float32(1.0), None, ctx,
                                       jnp.zeros_like(ctx), g),
                   window, jnp.float32(5.0))
    assert log == [(6, (6,), 0)]
    eng = LPServingEngine(dit.forward, None, cfg, num_partitions=2,
                          num_steps=3)
    pair = eng._conditioning(ctx)
    assert len(pair) == 2 and pair[0] is ctx
    assert not np.asarray(pair[1]).any()
    _, hcfg, _ = _setup()
    heng = _engine(hcfg, None, 2)
    cond = {"text": ctx, "pooled": ctx[:, 0]}
    assert heng._conditioning(cond) == (cond,)


def test_batched_pytree_conditioning_matches_one_at_a_time():
    a, cfg, params = _setup()
    alone = []
    for rid in (0, 1):
        eng = _engine(cfg, params, 2)
        eng.submit(_request(a, rid))
        alone.append(np.asarray(eng.run()[0].latent))
    eng = _engine(cfg, params, 2, max_batch=2)
    for rid in (0, 1):
        eng.submit(_request(a, rid))
    results = sorted(eng.run(), key=lambda r: r.request_id)
    assert [r.batch_size for r in results] == [2, 2]
    for r, want in zip(results, alone):
        assert _rel(np.asarray(r.latent), want) < TOL
    # the two requests' conditioning differs, and so do their latents
    assert _rel(alone[0], alone[1]) > 0.1


def test_step_ops_carry_mmdit_scopes():
    """Every compute op of the compiled LP step owns a scope, and the six
    MMDiT sub-block scopes are all there."""
    import re

    from repro.obs.scopes import SCOPES, scope_map

    a, cfg, params = _setup()
    eng = _engine(cfg, params, 2)
    eng.submit(_request(a, 0))
    eng.run()
    seen = set()
    for name, text in eng._compiler.programs():
        m = scope_map(text)
        for line in text.splitlines():
            d = re.match(r"^\s*(?:ROOT\s+)?%([\w.\-]+) = \S+ "
                         r"(fusion|dot|convolution)\(", line)
            if d is not None:
                assert m[d.group(1)] in SCOPES, (name, line.strip()[:160])
        seen |= set(m.values())
    assert {"mmdit.embed", "mmdit.refiner", "mmdit.double_attn",
            "mmdit.double_mlp", "mmdit.single", "mmdit.head",
            "lp.stitch"} <= seen
    assert "dit.cfg" not in seen


def test_step_flops_count_one_forward():
    import json

    conf = json.loads((BENCH / "configs" /
                       "hunyuanvideo-544p-33f.json").read_text())
    a, latent = conf["arch"], conf["latent"]
    step = MODULE.step_flops(a, latent)
    assert step == MODULE.forward_flops(a, latent)
    # a block of either kind does 12 d^2 multiply-adds per token in its
    # projections and MLP, and attends over all 18,616 tokens
    d, n = 3072, 18360 + 256
    blocks = 24 * n * d * d + 4 * n * n * d
    assert abs(step - 15 * blocks) / step < 0.005
    assert 1.26e14 < step < 1.28e14
    attn = MODULE.joint_attention_flops(a, latent)
    assert attn == 4 * d * (15 * n * n + 2 * 256 ** 2)
