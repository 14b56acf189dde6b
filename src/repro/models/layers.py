"""Shared neural-net layers for the model zoo.

Everything is pure-functional: ``init_*`` builds param pytrees,
``*_apply``-style functions consume them.  Attention is implemented
query-block-wise (scan over query chunks) so the materialised score
tensor is ``[B, H, q_block, kv_len]`` — bounded VMEM/HBM footprint at
32k/500k context — with three masking regimes:

  * ``full``     — bidirectional (encoders)
  * ``causal``   — standard causal LM
  * ``window``   — causal sliding window (StarCoder2, RG-LRU attn layers);
                   prefill computes only the banded KV range, making it
                   genuinely sub-quadratic, and decode uses a ring-buffer
                   KV cache of ``window`` slots.
  * ``chunk``    — chunk-local causal (Llama-4 iRoPE local layers).

Shardings are applied by the caller via ``with_sharding_constraint``
(see ``repro.sharding.rules``); layers themselves are mesh-agnostic.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

# --------------------------------------------------------------- helpers

def _split(key, n):
    return jax.random.split(key, n)


def dense_init(key, in_dim, out_dim, dtype, scale=None):
    scale = scale if scale is not None else 1.0 / math.sqrt(in_dim)
    return (jax.random.normal(key, (in_dim, out_dim)) * scale).astype(dtype)


class Adapted(NamedTuple):
    """A frozen weight with its low-rank adapter (LoRA, Hu et al., arXiv
    2106.09685): ``linear`` computes ``x W + (x A) B``; ``b`` arrives
    already scaled by alpha / rank."""

    w: jax.Array
    a: jax.Array
    b: jax.Array


def linear(x, w):
    """``x @ w`` over the last axis, for a plain or an adapted weight."""
    if isinstance(w, Adapted):
        return linear(x, w.w) + linear(linear(x, w.a), w.b)
    return jnp.einsum("...d,df->...f", x, w)


def rms_norm(x, weight, eps: float = 1e-6):
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * weight.astype(jnp.float32)).astype(dt)


def layer_norm(x, weight, bias, eps: float = 1e-5):
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    x = (x - mu) * jax.lax.rsqrt(var + eps)
    return (x * weight.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dt)


# ------------------------------------------------------------------ RoPE

def rope_freqs(head_dim: int, theta: float = 10000.0):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x, positions, theta: float = 10000.0):
    """x: [B, S, H, D]; positions: [B, S] int32.  Rotates pairs (even, odd)."""
    d = x.shape[-1]
    inv = rope_freqs(d, theta)  # [D/2]
    ang = positions[..., None].astype(jnp.float32) * inv  # [B, S, D/2]
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., 0::2].astype(jnp.float32), x[..., 1::2].astype(jnp.float32)
    r1 = x1 * cos - x2 * sin
    r2 = x2 * cos + x1 * sin
    out = jnp.stack([r1, r2], axis=-1).reshape(x.shape)
    return out.astype(x.dtype)


# ------------------------------------------------------------- attention

NEG_INF = -1e30


def _attend(q, k, v, q_pos, kv_pos, *, kind: str, window: int):
    """Exact softmax attention for one query block against a KV view.

    q: [B, Q, H, D]; k/v: [B, K, Hkv(repeated to H), D];
    q_pos: [B, Q]; kv_pos: [B, K]  (kv_pos < 0 marks invalid slots).
    """
    depth = q.shape[-1]
    scores = jnp.einsum(
        "bqhd,bkhd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32)
    ) / math.sqrt(depth)
    dq = q_pos[:, None, :, None]  # [B,1,Q,1]
    dk = kv_pos[:, None, None, :]  # [B,1,1,K]
    valid = dk >= 0
    if kind == "full":
        mask = valid
    else:  # causal family
        mask = valid & (dk <= dq)
        if kind == "window":
            mask = mask & (dq - dk < window)
        elif kind == "chunk":
            mask = mask & (dq // window == dk // window)
    scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    # rows with no valid key (fully masked) produce uniform probs over
    # NEG_INF entries; zero them for safety.
    any_valid = jnp.any(mask, axis=-1, keepdims=True)
    probs = jnp.where(any_valid, probs, 0.0)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v.astype(jnp.float32))
    return out.astype(q.dtype)


def _repeat_kv(k, n_rep: int):
    if n_rep == 1:
        return k
    b, s, h, d = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (b, s, h, n_rep, d)).reshape(
        b, s, h * n_rep, d
    )


def _map_q_blocks(fn, n, unroll: bool):
    """Query-block loop.  ``unroll=True`` python-unrolls so XLA cost
    analysis (which counts while-loop bodies once) sees every block —
    used by the dry-run cost-correction lowerings."""
    if unroll:
        return jnp.stack([fn(jnp.int32(i)) for i in range(n)])
    return jax.lax.map(fn, jnp.arange(n))


def multihead_attention(
    q,
    k,
    v,
    q_pos,
    kv_pos,
    *,
    kind: str = "causal",
    window: int = 0,
    q_block: int = 1024,
    unroll: bool = False,
):
    """Block-wise exact attention.

    For ``kind == 'window'`` the KV tensor is front-padded by ``window``
    slots so each query block reads a static banded slice of length
    ``q_block + window`` — prefill cost O(S * window), not O(S^2).
    For ``kind == 'chunk'`` queries are reshaped into chunks of
    ``window`` and attend only within their chunk.
    """
    b, sq, h, d = q.shape
    n_rep = h // k.shape[2]
    k, v = _repeat_kv(k, n_rep), _repeat_kv(v, n_rep)
    skv = k.shape[1]
    d = v.shape[-1]  # the output's head size (MLA's v is narrower than q/k)

    if sq == 1:  # decode fast-path: single query against whole cache view
        return _attend(q, k, v, q_pos, kv_pos, kind=kind, window=window)

    if kind == "chunk" and window > 0 and sq % window == 0 and sq == skv:
        nc = sq // window
        qc = q.reshape(b * nc, window, h, q.shape[-1])
        kc = k.reshape(b * nc, window, h, k.shape[-1])
        vc = v.reshape(b * nc, window, h, d)
        qp = q_pos.reshape(b * nc, window)
        kp = kv_pos.reshape(b * nc, window)
        out = _attend(qc, kc, vc, qp, kp, kind="causal", window=0)
        return out.reshape(b, sq, h, d)

    qb = min(q_block, sq)
    if sq % qb != 0:
        qb = sq  # irregular sizes: single block
    nblk = sq // qb

    if kind == "window" and window > 0 and sq == skv:
        # banded prefill: pad KV by `window` in front, each block reads
        # a static slice [i*qb : i*qb + qb + window].
        pad = [(0, 0), (window, 0), (0, 0), (0, 0)]
        kp_ = jnp.pad(k, pad)
        vp_ = jnp.pad(v, pad)
        pos_pad = jnp.pad(kv_pos, [(0, 0), (window, 0)], constant_values=-1)

        def block(i):
            qs = i * qb
            qi = jax.lax.dynamic_slice_in_dim(q, qs, qb, axis=1)
            qpi = jax.lax.dynamic_slice_in_dim(q_pos, qs, qb, axis=1)
            ki = jax.lax.dynamic_slice_in_dim(kp_, qs, qb + window, axis=1)
            vi = jax.lax.dynamic_slice_in_dim(vp_, qs, qb + window, axis=1)
            kpi = jax.lax.dynamic_slice_in_dim(pos_pad, qs, qb + window, axis=1)
            return _attend(qi, ki, vi, qpi, kpi, kind="window", window=window)

        out = _map_q_blocks(block, nblk, unroll)  # [nblk, B, qb, H, D]
        return jnp.moveaxis(out, 0, 1).reshape(b, sq, h, d)

    def block(i):
        qs = i * qb
        qi = jax.lax.dynamic_slice_in_dim(q, qs, qb, axis=1)
        qpi = jax.lax.dynamic_slice_in_dim(q_pos, qs, qb, axis=1)
        return _attend(qi, k, v, qpi, kv_pos, kind=kind, window=window)

    out = _map_q_blocks(block, nblk, unroll)
    return jnp.moveaxis(out, 0, 1).reshape(b, sq, h, d)


def _flash_path(q, k, v, cfg):
    """Pallas flash-attention dispatch for the train/prefill path.

    Assumes positions == arange(S) per example (true for all training and
    prefill shapes in this framework; the decode path never routes here).
    ``chunk`` attention (iRoPE local layers) is block-diagonal: reshape
    chunks into the batch dim and run causal within each chunk.
    """
    from repro.kernels import ops as kops  # deferred: keep layers jnp-only

    b, s, h, d = q.shape
    qT = q.transpose(0, 2, 1, 3)
    kT = k.transpose(0, 2, 1, 3)
    vT = v.transpose(0, 2, 1, 3)
    if cfg.kind == "chunk" and cfg.window > 0 and s % cfg.window == 0:
        nc = s // cfg.window
        hkv = k.shape[2]
        qc = qT.reshape(b, h, nc, cfg.window, d).transpose(0, 2, 1, 3, 4).reshape(b * nc, h, cfg.window, d)
        kc = kT.reshape(b, hkv, nc, cfg.window, d).transpose(0, 2, 1, 3, 4).reshape(b * nc, hkv, cfg.window, d)
        vc = vT.reshape(b, hkv, nc, cfg.window, d).transpose(0, 2, 1, 3, 4).reshape(b * nc, hkv, cfg.window, d)
        oc = kops.flash_attention(qc, kc, vc, causal=True, window=None)
        out = oc.reshape(b, nc, h, cfg.window, d).transpose(0, 2, 1, 3, 4).reshape(b, h, s, d)
    else:
        causal = cfg.kind != "full"
        win = cfg.window if (cfg.kind == "window" and cfg.window > 0) else None
        out = kops.flash_attention(qT, kT, vT, causal=causal, window=win)
    return out.transpose(0, 2, 1, 3)


# --------------------------------------------------- attention (module)

@dataclasses.dataclass(frozen=True)
class AttnConfig:
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    rope_theta: float = 10000.0
    use_rope: bool = True
    qkv_bias: bool = False
    kind: str = "causal"  # full | causal | window | chunk
    window: int = 0
    q_block: int = 1024
    q_unroll: bool = False  # python-unroll the query-block loop (cost analysis)
    impl: str = "xla"  # "xla" | "flash" (Pallas online-softmax kernel)


def init_attention(key, cfg: AttnConfig, dtype):
    kq, kk, kv, ko = _split(key, 4)
    p = {
        "wq": dense_init(kq, cfg.d_model, cfg.n_heads * cfg.head_dim, dtype),
        "wk": dense_init(kk, cfg.d_model, cfg.n_kv_heads * cfg.head_dim, dtype),
        "wv": dense_init(kv, cfg.d_model, cfg.n_kv_heads * cfg.head_dim, dtype),
        "wo": dense_init(ko, cfg.n_heads * cfg.head_dim, cfg.d_model, dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((cfg.n_heads * cfg.head_dim,), dtype)
        p["bk"] = jnp.zeros((cfg.n_kv_heads * cfg.head_dim,), dtype)
        p["bv"] = jnp.zeros((cfg.n_kv_heads * cfg.head_dim,), dtype)
    return p


def attention_block(
    params,
    cfg: AttnConfig,
    x,
    positions,
    cache=None,
    shard=lambda t, name: t,
):
    """x: [B, S, d_model] -> ([B, S, d_model], new_cache).

    ``cache`` (decode): dict(k=[B,C,Hkv,D], v=[B,C,Hkv,D], pos=[B,C] int32
    (-1 invalid), index=[] int32 next write slot).  Ring-buffer semantics
    when cfg.kind == 'window' with C == window.
    """
    b, s, _ = x.shape
    h, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = jnp.einsum("bsd,df->bsf", x, params["wq"])
    k = jnp.einsum("bsd,df->bsf", x, params["wk"])
    v = jnp.einsum("bsd,df->bsf", x, params["wv"])
    if cfg.qkv_bias:
        q, k, v = q + params["bq"], k + params["bk"], v + params["bv"]
    q = q.reshape(b, s, h, hd)
    k = k.reshape(b, s, hkv, hd)
    v = v.reshape(b, s, hkv, hd)
    if cfg.use_rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    q, k, v = shard(q, "act_heads"), shard(k, "act_kv"), shard(v, "act_kv")

    new_cache = None
    if cache is None:
        if cfg.impl == "bypass" and s > 1:
            # measurement-only (see kernel_adjust): consume q/k/v at the
            # [B,S,H,dh] level without the O(Sq*Sk) score chain
            out = _repeat_kv(v, h // hkv) + 1e-6 * q + 1e-6 * _repeat_kv(k, h // hkv)
        elif cfg.impl == "flash" and s > 1:
            out = _flash_path(q, k, v, cfg)
        else:
            out = multihead_attention(
                q, k, v, positions, positions,
                kind=cfg.kind, window=cfg.window, q_block=cfg.q_block,
                unroll=cfg.q_unroll,
            )
    else:
        c = cache["k"].shape[1]
        slot = cache["index"] % c
        k_all = jax.lax.dynamic_update_slice_in_dim(cache["k"], k.astype(cache["k"].dtype), slot, axis=1)
        v_all = jax.lax.dynamic_update_slice_in_dim(cache["v"], v.astype(cache["v"].dtype), slot, axis=1)
        pos_all = jax.lax.dynamic_update_slice_in_dim(
            cache["pos"], positions.astype(jnp.int32), slot, axis=1
        )
        out = multihead_attention(
            q, k_all, v_all, positions, pos_all,
            kind=cfg.kind, window=cfg.window, q_block=cfg.q_block,
            unroll=cfg.q_unroll,
        )
        new_cache = {"k": k_all, "v": v_all, "pos": pos_all, "index": cache["index"] + s}

    out = out.reshape(b, s, h * hd)
    out = jnp.einsum("bsf,fd->bsd", out, params["wo"])
    return shard(out, "act_model"), new_cache


def init_attn_cache(cfg: AttnConfig, batch: int, cache_len: int, dtype):
    c = min(cache_len, cfg.window) if cfg.kind in ("window", "chunk") and cfg.window else cache_len
    return {
        "k": jnp.zeros((batch, c, cfg.n_kv_heads, cfg.head_dim), dtype),
        "v": jnp.zeros((batch, c, cfg.n_kv_heads, cfg.head_dim), dtype),
        "pos": -jnp.ones((batch, c), jnp.int32),
        "index": jnp.zeros((), jnp.int32),
    }


# ------------------------------------------------- multi-head latent attention

@dataclasses.dataclass(frozen=True)
class MLAConfig:
    d_model: int
    n_heads: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    rope_theta: float = 10000.0
    norm_eps: float = 1e-6
    q_block: int = 1024
    impl: str = "xla"  # "flash": the Pallas TPU flash-attention kernel on a TPU


def init_mla(key, cfg: MLAConfig, dtype):
    ks = _split(key, 4)
    h, dq = cfg.n_heads, cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    return {
        "wq": dense_init(ks[0], cfg.d_model, h * dq, dtype),
        "wkv_a": dense_init(ks[1], cfg.d_model, cfg.kv_lora_rank + cfg.qk_rope_head_dim, dtype),
        "kv_norm": jnp.ones((cfg.kv_lora_rank,), dtype),
        "wkv_b": dense_init(ks[2], cfg.kv_lora_rank,
                            h * (cfg.qk_nope_head_dim + cfg.v_head_dim), dtype),
        "wo": dense_init(ks[3], h * cfg.v_head_dim, cfg.d_model, dtype),
    }


def mla_block(params, cfg: MLAConfig, x, positions):
    """DeepSeek-V2 multi-head latent attention without query compression,
    training form (the full keys and values, no latent cache).
    x: [B, S, d_model] -> [B, S, d_model].

    q = x W_q -> [q_nope, q_rope] per head; [c_kv, k_rope] = x W_kva with
    c_kv RMS-normed; [k_nope, v] = c_kv W_kvb per head; RoPE on q_rope
    and on the one k_rope all heads share; causal softmax of
    (q_nope.k_nope + q_rope.k_rope) / sqrt(nope + rope); out = W_o v."""
    b, s, _ = x.shape
    h, dn, dr, dv = cfg.n_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    q = linear(x, params["wq"]).reshape(b, s, h, dn + dr)
    kv_a = linear(x, params["wkv_a"])
    c_kv = rms_norm(kv_a[..., : cfg.kv_lora_rank], params["kv_norm"], cfg.norm_eps)
    k_rope = apply_rope(kv_a[..., None, cfg.kv_lora_rank:], positions, cfg.rope_theta)
    kv = linear(c_kv, params["wkv_b"]).reshape(b, s, h, dn + dv)
    q = jnp.concatenate([q[..., :dn], apply_rope(q[..., dn:], positions, cfg.rope_theta)], -1)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(k_rope, (b, s, h, dr))], -1)
    if cfg.impl == "flash" and jax.default_backend() == "tpu":
        out = _tpu_flash_causal(q, k, kv[..., dn:])
    else:
        out = multihead_attention(q, k, kv[..., dn:], positions, positions,
                                  kind="causal", q_block=cfg.q_block)
    return linear(out.reshape(b, s, h * dv), params["wo"])


def _tpu_flash_causal(q, k, v, block: int = 512):
    """Causal attention through JAX's Pallas TPU flash-attention kernel
    (forward and backward; positions 0..S-1).  The kernel takes one head
    size for q, k and v, a multiple of 128: all three are zero-padded to
    it, which leaves the scores and the first ``v`` columns of the output
    as they are.  Inputs go in as bfloat16, the rounding a default-precision
    f32 product on the TPU applies to its inputs."""
    from jax.experimental.pallas.ops.tpu import flash_attention as fa

    dv = v.shape[-1]
    d = -(-max(q.shape[-1], dv) // 128) * 128
    bq = min(block, q.shape[1])

    def heads_first(t):
        t = jnp.pad(t, [(0, 0)] * 3 + [(0, d - t.shape[-1])])
        return t.astype(jnp.bfloat16).transpose(0, 2, 1, 3)

    sizes = fa.BlockSizes(block_q=bq, block_k_major=bq, block_k=bq, block_b=1,
                          block_q_major_dkv=bq, block_k_major_dkv=bq, block_k_dkv=bq,
                          block_q_dkv=bq, block_k_major_dq=bq, block_k_dq=bq, block_q_dq=bq)
    out = fa.flash_attention(heads_first(q), heads_first(k), heads_first(v), causal=True,
                             sm_scale=q.shape[-1] ** -0.5, block_sizes=sizes)
    return out.transpose(0, 2, 1, 3)[..., :dv].astype(q.dtype)


# ------------------------------------------------------------------- MLP

def init_swiglu(key, d_model, d_ff, dtype):
    k1, k2, k3 = _split(key, 3)
    return {
        "w_gate": dense_init(k1, d_model, d_ff, dtype),
        "w_up": dense_init(k2, d_model, d_ff, dtype),
        "w_down": dense_init(k3, d_ff, d_model, dtype),
    }


def swiglu(params, x, shard=lambda t, name: t):
    g = linear(x, params["w_gate"])
    u = linear(x, params["w_up"])
    h = shard(jax.nn.silu(g) * u, "act_ff")
    return shard(linear(h, params["w_down"]), "act_model")


def init_gelu_mlp(key, d_model, d_ff, dtype):
    k1, k2 = _split(key, 2)
    return {
        "w_in": dense_init(k1, d_model, d_ff, dtype),
        "b_in": jnp.zeros((d_ff,), dtype),
        "w_out": dense_init(k2, d_ff, d_model, dtype),
        "b_out": jnp.zeros((d_model,), dtype),
    }


def gelu_mlp(params, x, shard=lambda t, name: t):
    h = jnp.einsum("bsd,df->bsf", x, params["w_in"]) + params["b_in"]
    h = shard(jax.nn.gelu(h), "act_ff")
    return shard(jnp.einsum("bsf,fd->bsd", h, params["w_out"]) + params["b_out"], "act_model")


# ------------------------------------------------------------- embedding

def init_embedding(key, vocab, d_model, dtype):
    return {"table": (jax.random.normal(key, (vocab, d_model)) * 0.02).astype(dtype)}


def embed(params, tokens):
    return jnp.take(params["table"], tokens, axis=0)


def unembed(params, x):
    return jnp.einsum("bsd,vd->bsv", x, params["table"])
