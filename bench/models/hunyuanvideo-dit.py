"""HunyuanVideo's MMDiT (arXiv:2412.03603; release ``tencent/HunyuanVideo``,
config ``HYVideo-T/2-cfgdistill``) with embedded guidance: the program's
forward, the seeded weights, the conditioning stand-in, the replayed
step's extras, the plain reference's per-window prediction, and the FLOP
counts of a step and of its joint attention.

A configuration whose ``model`` is ``hunyuanvideo-dit`` runs through this
file (``benchlib.spec.model``); the harness, the reference's LP windows,
stitch and Euler step hold nothing of it.

* Conditioning: ``{"text": (L, 4096), "pooled": (768,)}`` per request,
  LLM hidden states and a pooled CLIP vector (seeded stand-ins here).
* ``vec = time_in(t) + vector_in(pooled) + guidance_in(1000 g)``; each
  embedder is Linear -> SiLU -> Linear, ``time_in`` and ``guidance_in``
  over the 256-wide sinusoidal code ``[cos, sin]``.
* Token refiner: ``c = t_emb(t) + c_emb(mean(text))``, ``x = Linear(text)``;
  per block ``x += g_msa Attn(LN_aff(x))``, ``x += g_mlp MLP_silu(LN_aff(x))``
  with ``(g_msa, g_mlp) = Linear(SiLU(c))``.
* Double-stream blocks, video and text with their own weights:
  ``(shift1, scale1, gate1, shift2, scale2, gate2) = Linear(SiLU(vec))``;
  q, k, v of ``LN(x) (1 + scale1) + shift1``; q/k RMSNorm over each head
  with a learned scale; RoPE on the video tokens' q and k; one attention
  over [video; text], split back; ``x += gate1 proj(attn)``;
  ``x += gate2 MLP_gelu_tanh(LN(x) (1 + scale2) + shift2)``.
* Single-stream blocks over [video; text]: ``(shift, scale, gate)``;
  ``linear1`` of the modulated LN gives q, k, v (3 d) and the MLP input
  (4 d); q/k RMSNorm, RoPE on the video part, attention;
  ``x += gate linear2([attn; GELU_tanh(mlp)])``.
* Final: ``LN(x) (1 + scale) + shift`` from ``Linear(SiLU(vec))``, a linear
  head, unpatchify.  LayerNorms and RMSNorms take eps 1e-6; every linear
  has a bias.
* 3D RoPE, theta 256: head part ``a`` of width ``rope_axes[a]`` rotates
  by the token's patch position along axis ``a`` of (t, h, w), as
  interleaved pairs ``x cos + rotate_half(x) sin`` with each frequency
  repeated twice.  Each LP window is denoised as a latent of its own:
  positions start at 0 in every window.
* Guidance is embedded: one forward per step, no CFG pair; ``guide``
  passes the prediction through.

The reference's forward is float32 ``jax.numpy`` with every matrix
product at ``Precision.HIGHEST``, attention exact in blocks of 256
queries; ``quant="fp8"`` is the control: every linear layer's operands
rounded to float8 e4m3 (one absmax scale per tensor) before the product.
It imports nothing of the program.

Weights: drawn here, at the tree layout the program reads, and nothing
of the program's initializer: every matrix a fan-in scaled truncated
normal in the configuration's dtype, every bias 0.02 x normal and every
norm scale 1 + 0.1 x normal in float32 (the modulations and the head
too, which the release zero-initializes), made on the device in one
jitted call.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchlib import flops
from benchlib.reference import HIGHEST, linear
from benchlib.spec import freeze

MODEL = "hunyuanvideo-dit"
FREQ_DIM = 256                  # sinusoidal code of the timestep / guidance
EPS = 1e-6
Q_CHUNK = 256                   # queries per block of the exact attention
_TRUNC_STD = 0.87962566         # std of a unit normal truncated to [-2, 2]


# ------------------------------------------------------------ program
def _cfg(a: dict):
    """The program's ArchConfig with every number of ``a`` (a
    configuration's ``arch`` block): the file's widths are what runs."""
    from repro.configs import get_config

    return dataclasses.replace(
        get_config(MODEL),
        double_layers=a["double_layers"], single_layers=a["single_layers"],
        num_layers=a["double_layers"] + a["single_layers"],
        refiner_layers=a["refiner_layers"], d_model=a["d_model"],
        num_heads=a["num_heads"], num_kv_heads=a["num_heads"],
        head_dim=a["head_dim"], d_ff=a["d_ff"],
        patch_sizes=tuple(a["patch_sizes"]),
        latent_channels=a["latent_channels"],
        context_len=a["context_len"], context_dim=a["context_dim"],
        pooled_dim=a["pooled_dim"], rope_axes=tuple(a["rope_axes"]),
        rope_theta=float(a["rope_theta"]),
        guidance_embed=bool(a["guidance_embed"]), dtype=a["dtype"])


def program(conf: dict):
    """``(forward, cfg)``: the program's denoiser for the configuration's
    ``model`` (``repro.models.FORWARDS``) and its config."""
    from repro.models import FORWARDS

    return FORWARDS[conf["model"]], _cfg(conf["arch"])




@functools.partial(jax.jit, static_argnums=(0, 1))
def _context(text_shape, pooled_shape, key):
    # stand-ins for the encoders' outputs: unit-scale noise, the scale of
    # a normalized hidden state
    kt, kp = jax.random.split(key)
    return {"text": jax.random.normal(kt, text_shape, jnp.float32),
            "pooled": jax.random.normal(kp, pooled_shape, jnp.float32)}


def context(arch: dict, key):
    """A request's conditioning stand-in: ``{"text": (1, context_len,
    context_dim), "pooled": (1, pooled_dim)}``."""
    return _context((1, arch["context_len"], arch["context_dim"]),
                    (1, arch["pooled_dim"]), key)


def step_extras(engine, req) -> tuple:
    """What the engine's compiled step takes besides the latent and the
    step scalars: the weights, the conditioning and the guidance scale
    (one forward, no unconditional pair)."""
    return (engine._step_params(), req.context, jnp.float32(req.guidance))


# ------------------------------------------------------------ reference
def _lin(x, p, quant):
    return linear(x, p["w"], quant) + p["b"]


def _embed(p, x, quant):
    return _lin(jax.nn.silu(_lin(x, p["l1"], quant)), p["l2"], quant)


def _ln(x, p=None):
    mu = jnp.mean(x, -1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(jnp.mean((x - mu) ** 2, -1, keepdims=True)
                                 + EPS)
    return y if p is None else y * p["scale"] + p["bias"]


def _rms(x, scale):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + EPS) * scale


def _timestep(t):
    half = FREQ_DIM // 2
    freqs = jnp.exp(-math.log(10000.0) * jnp.arange(half) / half)
    arg = t * freqs
    return jnp.concatenate([jnp.cos(arg), jnp.sin(arg)])


def _rope_tables(grid, a):
    """cos, sin of each token's angles, (tokens, head_dim), each
    frequency repeated for its pair."""
    parts = []
    for ax, (n, dd) in enumerate(zip(grid, a["rope_axes"])):
        freqs = 1.0 / a["rope_theta"] ** (np.arange(0, dd, 2) / dd)
        ang = np.repeat(np.arange(n)[:, None] * freqs, 2, axis=1)  # (n, dd)
        shape = [1, 1, 1, dd]
        shape[ax] = n
        parts.append(np.broadcast_to(ang.reshape(shape), (*grid, dd)))
    ang = np.concatenate(parts, -1).reshape(-1, a["head_dim"])
    return (jnp.asarray(np.cos(ang), jnp.float32),
            jnp.asarray(np.sin(ang), jnp.float32))


def _rotate_half(x):
    pairs = x.reshape(*x.shape[:-1], -1, 2)
    return jnp.stack([-pairs[..., 1], pairs[..., 0]], -1).reshape(x.shape)


def _rope(x, cos, sin):
    """x (S, H, D) of the video tokens."""
    return x * cos[:, None] + _rotate_half(x) * sin[:, None]


def _attention(q, k, v):
    """Exact softmax attention, (S, H, D) x (Skv, H, D), in query blocks."""
    s_len, heads, dim = q.shape
    n = -(-s_len // Q_CHUNK)
    qp = jnp.pad(q, ((0, n * Q_CHUNK - s_len), (0, 0), (0, 0)))

    def block(qc):
        sc = jnp.einsum("qhd,khd->hqk", qc, k, precision=HIGHEST)
        p = jax.nn.softmax(sc / math.sqrt(dim), axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST)

    out = jax.lax.map(block, qp.reshape(n, Q_CHUNK, heads, dim))
    return out.reshape(n * Q_CHUNK, heads, dim)[:s_len]


def _split_heads(x, a):
    return x.reshape(x.shape[0], a["num_heads"], a["head_dim"])


def velocity(params, z, t, cond, guidance, a: dict,
             quant: Optional[str] = None):
    """The MMDiT's prediction for one latent ``z`` (T, H, W, C) at
    timestep ``t``, guidance scale ``guidance``, under ``cond``
    (``{"text": (L, context_dim), "pooled": (pooled_dim,)}``); float32."""
    f32 = jnp.float32
    lin = functools.partial(_lin, quant=quant)
    embed = functools.partial(_embed, quant=quant)
    gelu = functools.partial(jax.nn.gelu, approximate=True)
    t_len, h_len, w_len, ch = z.shape
    pt, ph, pw = a["patch_sizes"]
    grid = (t_len // pt, h_len // ph, w_len // pw)
    d = a["d_model"]
    tok = z.reshape(grid[0], pt, grid[1], ph, grid[2], pw, ch)
    tok = tok.transpose(0, 2, 4, 1, 3, 5, 6).reshape(-1, pt * ph * pw * ch)
    img = lin(tok, params["img_in"])
    n_img = img.shape[0]
    vec = (embed(params["time_in"], _timestep(t))
           + embed(params["vector_in"], cond["pooled"]))
    if a["guidance_embed"]:
        vec = vec + embed(params["guidance_in"], _timestep(1000.0 * guidance))
    sv = jax.nn.silu(vec)
    cos, sin = _rope_tables(grid, a)

    # token refiner
    p = params["txt_in"]
    text = cond["text"].astype(f32)
    sc = jax.nn.silu(embed(p["t_emb"], _timestep(t))
                     + embed(p["c_emb"], jnp.mean(text, 0)))
    txt = lin(text, p["input"])

    def refiner(x, b):
        g_msa, g_mlp = lin(sc, b["ada"]).reshape(2, d)
        qkv = lin(_ln(x, b["norm1"]), b["qkv"]).reshape(-1, 3, d)
        q, k, v = (_split_heads(qkv[:, i], a) for i in range(3))
        x = x + g_msa * lin(_attention(q, k, v).reshape(-1, d), b["proj"])
        mlp = lin(jax.nn.silu(lin(_ln(x, b["norm2"]), b["fc1"])), b["fc2"])
        return x + g_mlp * mlp, None

    txt, _ = jax.lax.scan(refiner, txt, p["blocks"])

    def qkv_of(x, p, rope):
        qkv = x.reshape(-1, 3, d)
        q, k, v = (_split_heads(qkv[:, i], a) for i in range(3))
        q, k = _rms(q, p["q_norm"]), _rms(k, p["k_norm"])
        if rope:
            q, k = _rope(q, cos, sin), _rope(k, cos, sin)
        return q, k, v

    def double(carry, b):
        img, txt = carry
        mi = lin(sv, b["img"]["mod"]).reshape(6, d)
        mt = lin(sv, b["txt"]["mod"]).reshape(6, d)
        qi, ki, vi = qkv_of(lin(_ln(img) * (1 + mi[1]) + mi[0],
                                b["img"]["qkv"]), b["img"], True)
        qt, kt, vt = qkv_of(lin(_ln(txt) * (1 + mt[1]) + mt[0],
                                b["txt"]["qkv"]), b["txt"], False)
        attn = _attention(jnp.concatenate([qi, qt]), jnp.concatenate([ki, kt]),
                          jnp.concatenate([vi, vt])).reshape(-1, d)
        img = img + mi[2] * lin(attn[:n_img], b["img"]["proj"])
        txt = txt + mt[2] * lin(attn[n_img:], b["txt"]["proj"])

        def mlp(x, m, p):
            h = _ln(x) * (1 + m[4]) + m[3]
            return x + m[5] * lin(gelu(lin(h, p["fc1"])), p["fc2"])

        return (mlp(img, mi, b["img"]), mlp(txt, mt, b["txt"])), None

    (img, txt), _ = jax.lax.scan(double, (img, txt), params["double"])

    def single(x, b):
        shift, scale, gate = lin(sv, b["mod"]).reshape(3, d)
        h = lin(_ln(x) * (1 + scale) + shift, b["linear1"])
        qkv, mlp = h[:, :3 * d], h[:, 3 * d:]
        q, k, v = qkv_of(qkv, b, False)
        q = jnp.concatenate([_rope(q[:n_img], cos, sin), q[n_img:]])
        k = jnp.concatenate([_rope(k[:n_img], cos, sin), k[n_img:]])
        attn = _attention(q, k, v).reshape(-1, d)
        out = lin(jnp.concatenate([attn, gelu(mlp)], -1), b["linear2"])
        return x + gate * out, None

    x, _ = jax.lax.scan(single, jnp.concatenate([img, txt]),
                        params["single"])
    shift, scale = lin(sv, params["final"]["ada"]).reshape(2, d)
    out = lin(_ln(x[:n_img]) * (1 + scale) + shift, params["final"]["linear"])
    out = out.reshape(*grid, pt, ph, pw, ch).transpose(0, 3, 1, 4, 2, 5, 6)
    return out.reshape(z.shape)


@functools.lru_cache(maxsize=None)
def window_fn(arch: Tuple, quant: Optional[str], mesh, axis: Optional[str]):
    """Jitted prediction of stacked windows, called as ``fn(params,
    windows, t, ctx, guidance)``; the guidance scale enters the
    guidance embedding.  On a mesh each device takes one window of
    ``windows`` (K, T, H, W, C); otherwise ``windows`` is one window."""
    a = dict(arch)

    def one(params, win, t, ctx, g):
        with jax.default_matmul_precision("highest"):
            return velocity(params, win, t, ctx, g, a, quant)

    if mesh is None:
        return jax.jit(one)
    from jax.sharding import PartitionSpec as P

    def per_device(params, wins, t, ctx, g):
        return one(params, wins[0], t, ctx, g)[None]

    return jax.jit(jax.shard_map(
        per_device, mesh=mesh, in_specs=(P(), P(axis), P(), P(), P()),
        out_specs=P(axis), check_vma=False))


def guide(pred: np.ndarray, guidance: float) -> np.ndarray:
    """The windows' predictions (K, ...) as they are: the guidance was
    embedded in the forward."""
    return pred


# ------------------------------------------------------------ FLOPs
def joint_attention_flops(a: dict, latent: Sequence[int]) -> int:
    """FLOPs of the attention products (scores and values, 2 FLOP per
    multiply-add) of one forward over ``latent`` (T, H, W): every double-
    and single-stream block attends over the video tokens plus the text,
    every refiner block over the text alone."""
    s = flops.tokens(latent, a["patch_sizes"]) + a["context_len"]
    inner = a["num_heads"] * a["head_dim"]
    blocks = a["double_layers"] + a["single_layers"]
    return 4 * inner * (blocks * s * s
                        + a["refiner_layers"] * a["context_len"] ** 2)


def forward_flops(a: dict, latent: Sequence[int]) -> int:
    """FLOPs of one forward (one row, one timestep) over ``latent``
    (T, H, W): the multiply-adds (2 FLOP each) of its matrix products;
    elementwise work (norms, RoPE, softmax, gating, biases) is not
    counted, as is usual for a model FLOP count."""
    s = flops.tokens(latent, a["patch_sizes"])
    L, d, ff = a["context_len"], a["d_model"], a["d_ff"]
    n = s + L
    pt, ph, pw = a["patch_sizes"]
    patch = pt * ph * pw * a["latent_channels"]
    embedder = 2 * FREQ_DIM * d + 2 * d * d
    # modulations, then q, k, v, proj and the MLP of each stream's tokens
    double = 2 * 2 * d * 6 * d + 2 * n * d * (3 * d + d + 2 * ff)
    single = 2 * d * 3 * d + 2 * n * d * (3 * d + ff) + 2 * n * (d + ff) * d
    refiner = 2 * d * 2 * d + 2 * L * d * (3 * d + d + 2 * ff)
    outside = (
        2 * s * patch * d                                   # patch embed
        + embedder * (2 if a["guidance_embed"] else 1)      # time, guidance
        + 2 * a["pooled_dim"] * d + 2 * d * d               # vector_in
        + 2 * L * a["context_dim"] * d                      # refiner input
        + embedder + 2 * a["context_dim"] * d + 2 * d * d   # refiner c
        + 2 * d * 2 * d + 2 * s * d * patch                 # final layer
    )
    return (a["double_layers"] * double + a["single_layers"] * single
            + a["refiner_layers"] * refiner + outside
            + joint_attention_flops(a, latent))


def step_flops(a: dict, latent: Sequence[int]) -> int:
    """One full-latent denoise step: one forward, the guidance being
    embedded.  Independent of K and r, so LP's overlap counts as
    overhead."""
    return forward_flops(a, latent)


# ------------------------------------------------------------ weights
def _dense(key, fan_in, fan_out, dtype):
    kw, kb = jax.random.split(key)
    std = math.sqrt(1.0 / fan_in) / _TRUNC_STD
    w = jax.random.truncated_normal(kw, -2.0, 2.0, (fan_in, fan_out)) * std
    return {"w": w.astype(dtype),
            "b": 0.02 * jax.random.normal(kb, (fan_out,), jnp.float32)}


def _scale(key, n):
    return 1.0 + 0.1 * jax.random.normal(key, (n,), jnp.float32)


def _two_layer(key, fan_in, d, dtype):
    k1, k2 = jax.random.split(key)
    return {"l1": _dense(k1, fan_in, d, dtype), "l2": _dense(k2, d, d, dtype)}


def _stream(key, a, dtype):
    """One stream of a double-stream block."""
    d, ff, hd = a["d_model"], a["d_ff"], a["head_dim"]
    ks = jax.random.split(key, 7)
    return {"mod": _dense(ks[0], d, 6 * d, dtype),
            "qkv": _dense(ks[1], d, 3 * d, dtype),
            "q_norm": _scale(ks[2], hd), "k_norm": _scale(ks[3], hd),
            "proj": _dense(ks[4], d, d, dtype),
            "fc1": _dense(ks[5], d, ff, dtype),
            "fc2": _dense(ks[6], ff, d, dtype)}


def _single(key, a, dtype):
    d, ff, hd = a["d_model"], a["d_ff"], a["head_dim"]
    ks = jax.random.split(key, 5)
    return {"mod": _dense(ks[0], d, 3 * d, dtype),
            "linear1": _dense(ks[1], d, 3 * d + ff, dtype),
            "q_norm": _scale(ks[2], hd), "k_norm": _scale(ks[3], hd),
            "linear2": _dense(ks[4], d + ff, d, dtype)}


def _refiner(key, a, dtype):
    d, ff = a["d_model"], a["d_ff"]
    ks = jax.random.split(key, 9)

    def norm(k1, k2):
        return {"scale": _scale(k1, d),
                "bias": 0.02 * jax.random.normal(k2, (d,), jnp.float32)}

    return {"norm1": norm(ks[0], ks[1]), "norm2": norm(ks[2], ks[3]),
            "qkv": _dense(ks[4], d, 3 * d, dtype),
            "proj": _dense(ks[5], d, d, dtype),
            "fc1": _dense(ks[6], d, ff, dtype),
            "fc2": _dense(ks[7], ff, d, dtype),
            "ada": _dense(ks[8], d, 2 * d, dtype)}


def _stacked(key, n, draw):
    return jax.vmap(draw)(jax.random.split(key, n))


def _init(key, a):
    dtype = jnp.dtype(a["dtype"])
    d = a["d_model"]
    pt, ph, pw = a["patch_sizes"]
    patch = pt * ph * pw * a["latent_channels"]
    ks = jax.random.split(key, 12)
    params = {
        "img_in": _dense(ks[0], patch, d, dtype),
        "time_in": _two_layer(ks[1], FREQ_DIM, d, dtype),
        "vector_in": _two_layer(ks[2], a["pooled_dim"], d, dtype),
        "txt_in": {
            "input": _dense(ks[3], a["context_dim"], d, dtype),
            "t_emb": _two_layer(ks[4], FREQ_DIM, d, dtype),
            "c_emb": _two_layer(ks[5], a["context_dim"], d, dtype),
            "blocks": _stacked(ks[6], a["refiner_layers"],
                               lambda k: _refiner(k, a, dtype)),
        },
        "double": _stacked(ks[7], a["double_layers"], lambda k: {
            "img": _stream(jax.random.fold_in(k, 0), a, dtype),
            "txt": _stream(jax.random.fold_in(k, 1), a, dtype)}),
        "single": _stacked(ks[8], a["single_layers"],
                           lambda k: _single(k, a, dtype)),
        "final": {"ada": _dense(ks[9], d, 2 * d, dtype),
                  "linear": _dense(ks[10], d, patch, dtype)},
    }
    if a["guidance_embed"]:
        params["guidance_in"] = _two_layer(ks[11], FREQ_DIM, d, dtype)
    return params


def _init_frozen(key, frozen):
    return _init(key, dict(frozen))


def make_params(key, arch: dict, sharding=None):
    """The weights for ``arch`` (a configuration's ``arch`` block) from
    ``key``, in the dtype they are served in, made in one compiled call
    straight onto ``sharding``."""
    fn = jax.jit(functools.partial(_init_frozen, frozen=freeze(arch)),
                 out_shardings=sharding)
    return fn(key)
