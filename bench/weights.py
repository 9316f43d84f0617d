"""Random weights made from the seed on the device, each model in one
jitted call, in the dtype it is served in. The trees have the layout the
program's models read (layers stacked on a leading axis); matrices are
fan-in normal, as the program's own initializer draws them. A tier's
token embedding is drawn at std ``d_model ** -0.5`` (about the 0.02 of
trained models' initialization), so that the embedding, which the model
scales by ``sqrt(d_model)``, enters the residual stream at unit scale:
drawn at std 1 it would outweigh every layer, and a tied head would
answer each position with its own input token whatever the layers
compute. The program never makes a weight of a benchmark run, and the
reference reads these same arrays."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp


def _normal(key, shape, std, dtype):
    return (std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def _norm(kind: str, shape):
    return {} if kind == "nonparametric_ln" else {
        "scale": jnp.zeros(shape, jnp.float32)}


def _dense(cfg: dict, key):
    D, H, KV, hd = cfg["d_model"], cfg["num_heads"], cfg["num_kv_heads"], \
        cfg["head_dim"]
    F, V, L = cfg["d_ff"], cfg["vocab_size"], cfg["num_layers"]
    dt = jnp.dtype(cfg.get("param_dtype", "bfloat16"))
    ks = jax.random.split(key, 9)
    p = {
        "embed": _normal(ks[0], (V, D), D ** -0.5, dt),
        "final_norm": _norm(cfg["norm_type"], (D,)),
        "layers": {
            "ln1": _norm(cfg["norm_type"], (L, D)),
            "ln2": _norm(cfg["norm_type"], (L, D)),
            "attn": {"wq": _normal(ks[1], (L, D, H, hd), D ** -0.5, dt),
                     "wk": _normal(ks[2], (L, D, KV, hd), D ** -0.5, dt),
                     "wv": _normal(ks[3], (L, D, KV, hd), D ** -0.5, dt),
                     "wo": _normal(ks[4], (L, H, hd, D), hd ** -0.5, dt)},
            "mlp": {"w_up": _normal(ks[5], (L, D, F), D ** -0.5, dt),
                    "w_gate": _normal(ks[6], (L, D, F), D ** -0.5, dt),
                    "w_down": _normal(ks[7], (L, F, D), F ** -0.5, dt)}},
    }
    if not cfg["tie_embeddings"]:
        p["unembed"] = _normal(ks[8], (V, D), D ** -0.5, dt)
    return p


def ssm_sizes(cfg: dict) -> dict:
    D = cfg["d_model"]
    di = cfg["ssm_expand"] * D
    H = di // cfg["ssm_head_dim"]
    GN = cfg["ssm_groups"] * cfg["ssm_state"]
    return {"D": D, "di": di, "H": H, "P": cfg["ssm_head_dim"],
            "N": cfg["ssm_state"], "G": cfg["ssm_groups"], "GN": GN,
            "conv_ch": di + 2 * GN, "in_proj": 2 * di + 2 * GN + H,
            "k": cfg["d_conv"]}


def _ssm(cfg: dict, key):
    s = ssm_sizes(cfg)
    L, V, D, di, H = cfg["num_layers"], cfg["vocab_size"], s["D"], s["di"], \
        s["H"]
    dt = jnp.dtype(cfg.get("param_dtype", "bfloat16"))
    ks = jax.random.split(key, 4)
    a_log = jnp.log(jnp.linspace(1.0, 16.0, H, dtype=jnp.float32))
    p = {
        "embed": _normal(ks[0], (V, D), D ** -0.5, dt),
        "final_norm": _norm("rmsnorm", (D,)),
        "layers": {
            "ln1": _norm("rmsnorm", (L, D)),
            "mixer": {
                "in_proj": _normal(ks[1], (L, D, s["in_proj"]), D ** -0.5,
                                   dt),
                "conv": {"w": _normal(ks[2], (L, s["k"], s["conv_ch"]),
                                      s["k"] ** -0.5, dt),
                         "b": jnp.zeros((L, s["conv_ch"]), dt)},
                "A_log": jnp.broadcast_to(a_log, (L, H)),
                "D": jnp.ones((L, H), jnp.float32),
                "dt_bias": jnp.zeros((L, H), jnp.float32),
                "norm": {"scale": jnp.zeros((L, di), jnp.float32)},
                "out_proj": _normal(ks[3], (L, di, D), di ** -0.5, dt)}},
    }
    if not cfg["tie_embeddings"]:
        p["unembed"] = _normal(jax.random.fold_in(key, 9), (V, D),
                               D ** -0.5, dt)
    return p


def _embedder(cfg: dict, key):
    d, h, L, F = cfg["d_model"], cfg["num_heads"], cfg["num_layers"], \
        cfg["d_ff"]
    hd, V, E = d // h, cfg["vocab_size"], cfg["embed_dim"]
    f32 = jnp.float32
    ks = jax.random.split(key, 9)
    return {
        "embed": _normal(ks[0], (V, d), 1.0, f32),
        "final_norm": {"scale": jnp.zeros((d,), f32)},
        "proj": _normal(ks[1], (d, E), d ** -0.5, f32),
        "layers": {
            "ln1": {"scale": jnp.zeros((L, d), f32)},
            "ln2": {"scale": jnp.zeros((L, d), f32)},
            "attn": {"wq": _normal(ks[2], (L, d, h, hd), d ** -0.5, f32),
                     "wk": _normal(ks[3], (L, d, h, hd), d ** -0.5, f32),
                     "wv": _normal(ks[4], (L, d, h, hd), d ** -0.5, f32),
                     "wo": _normal(ks[5], (L, h, hd, d), hd ** -0.5, f32)},
            "mlp": {"w_up": _normal(ks[6], (L, d, F), d ** -0.5, f32),
                    "w_gate": _normal(ks[7], (L, d, F), d ** -0.5, f32),
                    "w_down": _normal(ks[8], (L, F, d), F ** -0.5, f32)}},
    }


def _tier(cfg: dict, key):
    return _ssm(cfg, key) if cfg["family"] == "ssm" else _dense(cfg, key)


@partial(jax.jit, static_argnums=(0,))
def _make_tier(frozen_cfg, key):
    return _tier(dict(frozen_cfg), key)


@partial(jax.jit, static_argnums=(0,))
def _make_embedder(frozen_cfg, key):
    return _embedder(dict(frozen_cfg), key)


def _freeze(cfg: dict) -> tuple:
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if not isinstance(v, (dict, list))))


def make_tier(cfg: dict, key):
    """One tier's weights, in one jitted call on the default device."""
    return _make_tier(_freeze(cfg), key)


def make_embedder(cfg: dict, key):
    return _make_embedder(_freeze(cfg), key)
