"""Weights of the DeepSeek-V2 family (latent attention, routed experts), made
on the device from the seed in the tree the serving program takes.

The configuration file names this module (``"weights": {"module":
"bench.weights_deepseek_v2"}``).  The tree is the program's MoE layout:
``dense_layers`` (the leading dense layers) and ``layers`` (the MoE layers),
each a stack with a leading layer dim; every layer has its latent attention
under ``mla`` and the held experts under ``moe`` as ``(n_held, K, N)``
stacks.  The plain reference (``bench/reference/deepseek_v2.py``) reads the
same arrays.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.weights import padded_vocab, twins


def build(model: dict, twin_scale: float, key: jax.Array) -> dict:
    """float32 weights from a key: matrices N(0, 1/fan_in) (the router
    N(0, 1/d_model)), embedding N(0, 1), norm gains U(0.8, 1.2); the
    lm_head's columns come in twins ``twin_scale`` apart
    (``bench.weights.twins``)."""
    d, L, n_dense = model["d_model"], model["n_layers"], model["first_k_dense"]
    mla, moe = model["mla"], model["moe"]
    H, lora = mla["n_heads"], mla["kv_lora"]
    dn, dr, dv = mla["qk_nope_dim"], mla["qk_rope_dim"], mla["v_head_dim"]
    held, ff = moe["n_held"] or moe["n_experts"], moe["expert_ff"]
    shared_ff = moe.get("shared_ff") or moe["n_shared"] * ff
    vp = padded_vocab(model)
    f32 = jnp.float32
    ks = iter(jax.random.split(key, 64))

    def mat(shape):
        return jax.random.normal(next(ks), shape, f32) * shape[-2] ** -0.5

    def gain(shape):
        return jax.random.uniform(next(ks), shape, f32, 0.8, 1.2)

    def attention(n):
        return {"w_q": mat((n, d, H * (dn + dr))), "w_dkv": mat((n, d, lora)),
                "w_kr": mat((n, d, dr)), "kv_norm": gain((n, lora)),
                "w_uk": mat((n, lora, H * dn)), "w_uv": mat((n, lora, H * dv)),
                "w_o": mat((n, H * dv, d))}

    def swiglu(lead, width):
        return {"w_gate": mat(lead + (d, width)), "w_up": mat(lead + (d, width)),
                "w_down": mat(lead + (width, d))}

    n_moe = L - n_dense
    tree = {
        "embed": {"table": jax.random.normal(next(ks), (vp, d), f32)},
        "layers": {
            "mla": attention(n_moe),
            "moe": {"router": mat((n_moe, d, moe["n_experts"])),
                    **swiglu((n_moe, held), ff),
                    "shared": swiglu((n_moe,), shared_ff)},
            "ln1": {"w": gain((n_moe, d))},
            "ln2": {"w": gain((n_moe, d))},
        },
        "ln_final": {"w": gain((d,))},
        "lm_head": {"w": twins(mat((d, vp // 2)), mat((d, vp // 2)),
                               twin_scale)},
    }
    if n_dense:
        tree["dense_layers"] = {
            "mla": attention(n_dense),
            "mlp": swiglu((n_dense,), model["dense_ff"]),
            "ln1": {"w": gain((n_dense, d))},
            "ln2": {"w": gain((n_dense, d))},
        }
    return tree
