"""Plain references of the models a cell serves, written from the
published descriptions and independent of the program: the dense decoder
(OLMo / DeepSeek-Coder: pre-norm, RoPE on split halves, grouped-query
causal attention, SwiGLU MLP), the Mamba-2 block run as its sequential
recurrence (not the chunked dual form), and the embedder (bidirectional
pre-norm encoder, mean-pooled, projected and L2-normalized).

They run in float32 with every matrix product at ``HIGHEST`` precision,
layer by layer (one layer's weights are upcast at a time). ``NUMERICS``
also holds the lower precisions that the controls put in the program's
place: every matrix product's operands rounded to int8 or fp8 (weights
per output channel, activations per row, as an int8 or fp8 matmul unit
takes them), and bfloat16 weights and activations at default precision.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
DEFAULT = jax.lax.Precision.DEFAULT

# name -> (weight rounding, activation dtype, matmul precision)
NUMERICS = {
    "f32": (None, jnp.float32, HIGHEST),
    "int8": ("int8", jnp.float32, HIGHEST),
    "fp8": ("fp8", jnp.float32, HIGHEST),
    "bf16": ("bf16", jnp.bfloat16, DEFAULT),
}


def _round_weight(w, how: str | None, in_axes: tuple[int, ...]):
    """``w`` as float32 after rounding to ``how`` with one scale per
    output channel (the max over the contracted ``in_axes``)."""
    w = w.astype(jnp.float32)
    if how is None:
        return w
    if how == "bf16":
        return w.astype(jnp.bfloat16)
    top = {"int8": 127.0, "fp8": 448.0}[how]
    scale = jnp.max(jnp.abs(w), axis=in_axes, keepdims=True) / top
    scale = jnp.where(scale > 0, scale, 1.0)
    q = w / scale
    q = jnp.round(q) if how == "int8" else \
        q.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return q * scale


class _Ops:
    def __init__(self, numerics: str):
        self.how, self.act, self.prec = NUMERICS[numerics]

    def w(self, w, in_axes=(0,)):
        return _round_weight(w, self.how, in_axes)

    def x(self, x, axes=(-1,)):
        """An activation entering a matrix product: rounded per row (over
        the contracted ``axes``) where the numerics round operands."""
        if self.how in ("int8", "fp8"):
            return _round_weight(x, self.how, axes)
        return x

    def mm(self, spec, x, w, x_axes=(-1,)):
        return jnp.einsum(spec, self.x(x, x_axes).astype(self.act),
                          w.astype(self.act), precision=self.prec,
                          preferred_element_type=jnp.float32
                          ).astype(self.act)

    def cast(self, x):
        return x.astype(self.act)


def _rms(x, scale, eps=1e-6):
    x = x.astype(jnp.float32)
    y = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
    return y * (1.0 + scale.astype(jnp.float32))


def _layernorm(x, eps=1e-5):
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps)


def _norm(kind, p, x):
    return _layernorm(x) if kind == "nonparametric_ln" else \
        _rms(x, p["scale"])


def _rope(x, theta: float):
    """x: (B, S, H, hd); rotation of the split halves by position."""
    S, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd)
    ang = np.arange(S, dtype=np.float64)[:, None] * inv[None]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None, :]
    a, b = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _attention(o: _Ops, q, k, v, key_ok, causal: bool):
    """q: (B, S, H, hd), k/v: (B, S, KV, hd); key_ok (B, S) bool."""
    B, S, H, hd = q.shape
    KV = k.shape[2]
    qg = o.cast(q).reshape(B, S, KV, H // KV, hd)
    s = jnp.einsum("bqkgh,bskh->bkgqs", qg, o.cast(k), precision=o.prec,
                   preferred_element_type=jnp.float32) / math.sqrt(hd)
    ok = key_ok[:, None, None, None, :]
    if causal:
        ok = ok & jnp.tril(jnp.ones((S, S), bool))[None, None, None]
    s = jnp.where(ok, s, -jnp.inf)
    p = o.cast(jax.nn.softmax(s, axis=-1))
    out = jnp.einsum("bkgqs,bskh->bqkgh", p, o.cast(v), precision=o.prec,
                     preferred_element_type=jnp.float32)
    return o.cast(out.reshape(B, S, H, hd))


def _attn_block(o: _Ops, lp, h, theta, key_ok, causal):
    a = lp["attn"]
    q = _rope(o.mm("bsd,dhk->bshk", h, o.w(a["wq"])), theta)
    k = _rope(o.mm("bsd,dhk->bshk", h, o.w(a["wk"])), theta)
    v = o.mm("bsd,dhk->bshk", h, o.w(a["wv"]))
    att = _attention(o, q, k, v, key_ok, causal)
    return o.mm("bshk,hkd->bsd", att, o.w(a["wo"], (0, 1)), (-2, -1))


def _mlp(o: _Ops, m, h):
    up = o.mm("bsd,df->bsf", h, o.w(m["w_up"]))
    gate = o.mm("bsd,df->bsf", h, o.w(m["w_gate"]))
    act = o.cast(jax.nn.silu(gate.astype(jnp.float32)) * up)
    return o.mm("bsf,fd->bsd", act, o.w(m["w_down"]))


def _head(o: _Ops, cfg, params, x):
    x = o.cast(_norm(cfg["norm_type"], params["final_norm"], x))
    w = params["embed"] if cfg["tie_embeddings"] else params["unembed"]
    return jnp.einsum("bsd,vd->bsv", o.x(x).astype(o.act),
                      o.w(w, (1,)).astype(o.act), precision=o.prec,
                      preferred_element_type=jnp.float32)


def _dense(cfg, params, tokens, n_last, o: _Ops):
    B, S = tokens.shape
    x = o.cast(o.w(params["embed"], (1,))[tokens] * math.sqrt(cfg["d_model"]))
    key_ok = jnp.ones((B, S), bool)

    def layer(x, lp):
        h = o.cast(_norm(cfg["norm_type"], lp["ln1"], x))
        x = o.cast(x + _attn_block(o, lp, h, cfg["rope_theta"], key_ok,
                                   True))
        h = o.cast(_norm(cfg["norm_type"], lp["ln2"], x))
        return o.cast(x + _mlp(o, lp["mlp"], h)), None

    x, _ = jax.lax.scan(layer, x, params["layers"])
    return _head(o, cfg, params, x[:, S - n_last:])


def _ssm(cfg, params, tokens, n_last, o: _Ops):
    from bench.weights import ssm_sizes
    z_ = ssm_sizes(cfg)
    B, S = tokens.shape
    di, H, P, N, G, GN, k = (z_["di"], z_["H"], z_["P"], z_["N"], z_["G"],
                             z_["GN"], z_["k"])
    x = o.cast(o.w(params["embed"], (1,))[tokens] * math.sqrt(cfg["d_model"]))

    def layer(x, lp):
        m = lp["mixer"]
        h = o.cast(_rms(x, lp["ln1"]["scale"]))
        zx = o.mm("bsd,de->bse", h, o.w(m["in_proj"]))
        z, xin, bm, cm, dt = jnp.split(
            zx, [di, 2 * di, 2 * di + GN, 2 * di + 2 * GN], axis=-1)
        xbc = jnp.concatenate([xin, bm, cm], -1).astype(jnp.float32)
        w = o.w(m["conv"]["w"]).astype(o.act).astype(jnp.float32)
        pad = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
        conv = sum(pad[:, i:i + S] * w[i] for i in range(k))
        conv = o.cast(jax.nn.silu(conv + m["conv"]["b"].astype(jnp.float32)))
        xin, bm, cm = jnp.split(conv, [di, di + GN], axis=-1)
        dt = jax.nn.softplus(dt.astype(jnp.float32) + m["dt_bias"])
        A = -jnp.exp(m["A_log"])
        xh = xin.astype(jnp.float32).reshape(B, S, H, P)
        rep = H // G
        bh = jnp.repeat(bm.astype(jnp.float32).reshape(B, S, G, N), rep, 2)
        ch = jnp.repeat(cm.astype(jnp.float32).reshape(B, S, G, N), rep, 2)

        def step(s, t):
            xt, dtt, bt, ct = t            # (B,H,P) (B,H) (B,H,N) (B,H,N)
            s = s * jnp.exp(dtt * A)[..., None, None] + \
                (dtt[..., None] * xt)[..., None] * bt[:, :, None, :]
            return s, jnp.einsum("bhpn,bhn->bhp", s, ct, precision=HIGHEST)

        s0 = jnp.zeros((B, H, P, N), jnp.float32)
        _, y = jax.lax.scan(step, s0, (jnp.moveaxis(xh, 1, 0),
                                       jnp.moveaxis(dt, 1, 0),
                                       jnp.moveaxis(bh, 1, 0),
                                       jnp.moveaxis(ch, 1, 0)))
        y = jnp.moveaxis(y, 0, 1) + m["D"][None, None, :, None] * xh
        y = o.cast(y.reshape(B, S, di))
        y = o.cast(y * o.cast(jax.nn.silu(z.astype(jnp.float32))))
        y = o.cast(_rms(y, m["norm"]["scale"]))
        return o.cast(x + o.mm("bse,ed->bsd", y, o.w(m["out_proj"]))), None

    x, _ = jax.lax.scan(layer, x, params["layers"])
    return _head(o, cfg, params, x[:, S - n_last:])


def _embed(cfg, params, tokens, o: _Ops):
    B, S = tokens.shape
    x = o.cast(o.w(params["embed"], (1,))[tokens] * math.sqrt(cfg["d_model"]))
    key_ok = tokens != 0

    def layer(x, lp):
        h = o.cast(_rms(x, lp["ln1"]["scale"]))
        x = o.cast(x + _attn_block(o, lp, h, cfg.get("rope_theta", 1e4),
                                   key_ok, False))
        h = o.cast(_rms(x, lp["ln2"]["scale"]))
        return o.cast(x + _mlp(o, lp["mlp"], h)), None

    x, _ = jax.lax.scan(layer, x, params["layers"])
    x = _rms(x, params["final_norm"]["scale"])
    w = key_ok.astype(jnp.float32)[..., None]
    pooled = jnp.sum(x * w, 1) / jnp.maximum(jnp.sum(w, 1), 1.0)
    out = jnp.einsum("bd,de->be", o.cast(pooled),
                     o.w(params["proj"]).astype(o.act), precision=o.prec,
                     preferred_element_type=jnp.float32)
    return out / jnp.maximum(jnp.linalg.norm(out, axis=-1, keepdims=True),
                             1e-9)


def _frozen(cfg: dict) -> tuple:
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if not isinstance(v, (dict, list))))


@partial(jax.jit, static_argnums=(0, 3, 4))
def _tier_logits(fcfg, params, tokens, n_last, numerics):
    cfg = dict(fcfg)
    fn = _ssm if cfg["family"] == "ssm" else _dense
    return fn(cfg, params, tokens, n_last, _Ops(numerics))


@partial(jax.jit, static_argnums=(0, 3))
def _embedder(fcfg, params, tokens, numerics):
    return _embed(dict(fcfg), params, tokens, _Ops(numerics))


def tier_logits(cfg: dict, params, tokens, n_last: int = 1,
                numerics: str = "f32"):
    """Logits (B, n_last, V) at the last ``n_last`` positions of
    ``tokens`` (B, S)."""
    return _tier_logits(_frozen(cfg), params, jnp.asarray(tokens, jnp.int32),
                        n_last, numerics)


def embed(cfg: dict, params, tokens, numerics: str = "f32"):
    """Unit embeddings (B, embed_dim) of ``tokens`` (B, S)."""
    return _embedder(_frozen(cfg), params, jnp.asarray(tokens, jnp.int32),
                     numerics)


def embed_many(cfg: dict, params, prompts: np.ndarray, block: int = 256,
               numerics: str = "f32") -> np.ndarray:
    """Embeddings of many equal-length prompts in fixed-size blocks (the
    last block padded by repetition), as a host float32 array."""
    n = len(prompts)
    out = []
    for i in range(0, n, block):
        chunk = np.asarray(prompts[i:i + block])
        pad = block - len(chunk)
        if pad:
            chunk = np.concatenate([chunk, np.repeat(chunk[:1], pad, 0)])
        out.append(np.asarray(embed(cfg, params, chunk, numerics))[
            :block - pad])
    return np.concatenate(out).astype(np.float32)


@partial(jax.jit, static_argnums=(3,))
def _store_topk(rows, ok, qs, k: int):
    """Exact top-k of ``qs`` (B, E) against the rows where ``ok`` holds,
    at HIGHEST precision: (sims (B, k), row ids (B, k))."""
    sims = jnp.einsum("ce,be->bc", rows, qs, precision=HIGHEST)
    return jax.lax.top_k(jnp.where(ok[None, :], sims, -jnp.inf), k)


def store_topk(rows, ok, qs: np.ndarray, k: int, lo: int,
               block: int = 1 << 16):
    """Top-``k`` over rows ``[lo, C)`` of a (C, E) device array, limited
    to rows where the (C,) bool device array ``ok`` holds, in row blocks
    so the sims never materialize whole. Ties go to the lower row.
    Returns host arrays (B, k)."""
    C = rows.shape[0]
    qs = jnp.asarray(qs, jnp.float32)
    best_s = np.full((len(qs), 0), -np.inf, np.float32)
    best_i = np.zeros((len(qs), 0), np.int64)
    starts = list(range(lo, C, block))
    for start in starts:
        stop = min(C, start + block)
        s, i = _store_topk(rows[start:stop], ok[start:stop], qs,
                           min(k, stop - start))
        best_s = np.concatenate([best_s, np.asarray(s)], 1)
        best_i = np.concatenate([best_i, np.asarray(i) + start], 1)
        order = np.lexsort((best_i, -best_s), axis=1)[:, :k]
        best_s = np.take_along_axis(best_s, order, 1)
        best_i = np.take_along_axis(best_i, order, 1)
    return best_s, best_i
