"""Seeded DiT weights, made on the device in one jitted call.

The benchmark owns the weights: the served engine and the plain reference
both take the pytree made here, so the reference never reads anything the
program made.  The tree has the layout the program's DiT forward reads
(``patch_embed``, ``text_proj``, ``time_mlp``, stacked ``blocks``,
``final_norm``, ``final_ada``, ``head``); every matrix is a fan-in scaled
truncated normal, as the program's own initializer draws them.

One departure from the program's initializer: its adaLN projections
(``ada``, ``final_ada``) start at zero, which would make the timestep
embedding dead weight.  Here they are drawn like every other matrix, so
the modulation varies with the timestep and the check covers adaLN and
the time MLP.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from .spec import freeze

# std correction of a normal truncated to [-2, 2]
_TRUNC_STD = 0.87962566
FREQ_DIM = 256                  # sinusoidal timestep embedding width


def _dense(key, fan_in, fan_out, dtype):
    std = math.sqrt(1.0 / fan_in) / _TRUNC_STD
    w = jax.random.truncated_normal(key, -2.0, 2.0, (fan_in, fan_out)) * std
    return {"w": w.astype(dtype)}


def _block(key, a, dtype):
    d, inner = a["d_model"], a["num_heads"] * a["head_dim"]
    ks = jax.random.split(key, 12)

    def attn(k4):
        return {"q": _dense(k4[0], d, inner, dtype),
                "k": _dense(k4[1], d, inner, dtype),
                "v": _dense(k4[2], d, inner, dtype),
                "o": _dense(k4[3], inner, d, dtype)}

    return {
        "self_attn": attn(ks[0:4]),
        "cross_attn": attn(ks[4:8]),
        "cross_norm": {"scale": jnp.ones((d,), jnp.float32),
                       "bias": jnp.zeros((d,), jnp.float32)},
        "mlp": {"wi": _dense(ks[8], d, a["d_ff"], dtype),
                "wg": _dense(ks[9], d, a["d_ff"], dtype),
                "wo": _dense(ks[10], a["d_ff"], d, dtype)},
        "ada": _dense(ks[11], a["time_embed_dim"], 6 * d, dtype),
        # gates (rows 2 and 5) start at 1, shifts and scales at 0
        "ada_b": jnp.zeros((6, d), jnp.float32).at[2].set(1.0).at[5].set(1.0),
    }


def _init(key, a):
    dtype = jnp.dtype(a["dtype"])
    d = a["d_model"]
    pt, ph, pw = a["patch_sizes"]
    patch = pt * ph * pw * a["latent_channels"]
    ks = jax.random.split(key, 7)
    return {
        "patch_embed": _dense(ks[0], patch, d, dtype),
        "text_proj": _dense(ks[1], a["context_dim"], d, dtype),
        "time_mlp": {
            "w1": _dense(ks[2], FREQ_DIM, a["time_embed_dim"], jnp.float32),
            "w2": _dense(ks[3], a["time_embed_dim"], a["time_embed_dim"],
                         jnp.float32),
        },
        "blocks": jax.vmap(lambda k: _block(k, a, dtype))(
            jax.random.split(ks[4], a["num_layers"])),
        "final_norm": {"scale": jnp.ones((d,), jnp.float32),
                       "bias": jnp.zeros((d,), jnp.float32)},
        "final_ada": _dense(ks[5], a["time_embed_dim"], 2 * d, dtype),
        "head": _dense(ks[6], d, patch, dtype),
    }


def make_params(key, arch: dict, sharding=None):
    """The weights for ``arch`` (a configuration's ``arch`` block) from
    ``key``, in the dtype they are served in, made in one compiled call
    straight onto ``sharding`` (replicated over a mesh, or one device)."""
    fn = jax.jit(partial(_init_frozen, frozen=freeze(arch)),
                 out_shardings=sharding)
    return fn(key)


def _init_frozen(key, frozen):
    return _init(key, dict(frozen))
