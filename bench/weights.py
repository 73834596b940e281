"""Weights of a run, made on the device from the seed in one jitted call.

A configuration file names the module that builds its tree
(``"weights": {"module": ...}``): a dotted path under ``bench/`` whose
``build(model, twin_scale, key)`` returns the tree in the layout the
serving program takes.  This module is the dense GQA family's builder
(stacked per-layer leaves under ``layers``); the plain reference reads the
same arrays.  The values come from here and not from the program, so the
reference takes nothing that the program has made.
"""
from __future__ import annotations

import functools
from pathlib import Path

import jax
import jax.numpy as jnp

from bench import plug
from bench.traffic import rng_for

ROOT = Path(__file__).resolve().parents[1]


def padded_vocab(model: dict) -> int:
    """Vocabulary rows held: the program pads to a multiple of 256; the
    rows past ``vocab`` are never read back."""
    return (model["vocab"] + 255) // 256 * 256


def jax_key(seed: int) -> jax.Array:
    """A JAX key from a seed of any size."""
    state = rng_for(seed, "weights").integers(0, 2**32, size=2,
                                              dtype="uint64")
    return jax.random.wrap_key_data(state.astype("uint32"))


def twins(base: jax.Array, delta: jax.Array, twin_scale: float) -> jax.Array:
    """An lm_head whose columns come in twins: column 2j is ``base[:, j]``
    and column 2j+1 is ``base[:, j] + twin_scale * delta[:, j]``."""
    return jnp.stack([base, base + twin_scale * delta], -1).reshape(
        base.shape[0], -1)


def build(model: dict, twin_scale: float, key: jax.Array) -> dict:
    """float32 weights from a key: matrices N(0, 1/fan_in), embedding
    N(0, 1), norm gains U(0.8, 1.2).

    The lm_head's columns come in twins: column 2j+1 is column 2j plus
    ``twin_scale`` times a fresh N(0, 1/fan_in) column, so the logits of
    tokens 2j and 2j+1 differ by about ``twin_scale`` (the final norm's
    output has unit RMS).  That is within float32-grade rounding of the
    logits, so which of the two a path serves shows the precision it
    computed in."""
    d, L = model["d_model"], model["n_layers"]
    h, hk = model["n_heads"], model["n_kv_heads"]
    dh = model.get("head_dim") or d // h
    ff, vp = model["d_ff"], padded_vocab(model)
    f32 = jnp.float32
    ks = iter(jax.random.split(key, 16))

    def mat(shape):
        return jax.random.normal(next(ks), shape, f32) * shape[-2] ** -0.5

    def gain(shape):
        return jax.random.uniform(next(ks), shape, f32, 0.8, 1.2)

    return {
        "embed": {"table": jax.random.normal(next(ks), (vp, d), f32)},
        "layers": {
            "attn": {"wq": mat((L, d, h * dh)), "wk": mat((L, d, hk * dh)),
                     "wv": mat((L, d, hk * dh)), "wo": mat((L, h * dh, d))},
            "mlp": {"w_gate": mat((L, d, ff)), "w_up": mat((L, d, ff)),
                    "w_down": mat((L, ff, d))},
            "ln1": {"w": gain((L, d))},
            "ln2": {"w": gain((L, d))},
        },
        "ln_final": {"w": gain((d,))},
        "lm_head": {"w": twins(mat((d, vp // 2)), mat((d, vp // 2)),
                               twin_scale)},
    }


def make_params(conf: dict, seed: int, root: Path = ROOT) -> dict:
    """The run's weights, made on the device in one call by the ``build``
    of the module that the configuration file ``conf`` names, read from
    the checkout at ``root``."""
    w = conf["weights"]
    build_fn = plug.load(w["module"], root).build
    params = jax.jit(functools.partial(build_fn, conf["model"],
                                       w["lm_head_twin_scale"]))(
        jax_key(seed))
    return jax.block_until_ready(params)
