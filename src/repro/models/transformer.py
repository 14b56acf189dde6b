"""Unified decoder/encoder stack for all 10 assigned architectures.

The stack is a repeating *pattern block* of ``P`` slots scanned over
``n_layers // P`` iterations (+ an optional tail stack for
``n_layers % P``), so the traced HLO contains each distinct layer type
once regardless of depth:

  * dense / audio / vlm : P=1, slot = [attn, mlp]
  * llama4 (iRoPE)      : P=global_every, local chunk-attn slots + one
                          global NoPE full-causal slot; MoE mlp
  * hybrid (griffin)    : P=pattern_len, rglru slots + attn slots
  * ssm (mamba)         : P=1, slot = [mamba] (no mlp)

Each slot owns its pre-norms; params for a slot are stacked with a
leading ``n_blocks`` axis and consumed by ``lax.scan``.  An MoE model
with ``first_k_dense`` leading dense-MLP layers (DeepSeek-V3, Moonlight)
runs them as a stack of their own (``params["dense"]``) before the MoE
stack; ``kv_lora_rank`` > 0 makes the attention slot MLA.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import layers as L
from repro.models import mamba as M
from repro.models import moe as MOE
from repro.models import rglru as R

ShardFn = Callable[[Any, str], Any]
_identity_shard: ShardFn = lambda t, name: t


# ------------------------------------------------------------- slot spec

@dataclasses.dataclass(frozen=True)
class SlotSpec:
    mixer: str  # attn | mla | mamba | rglru
    attn_kind: str = "causal"
    use_rope: bool = True
    has_mlp: bool = True
    dense: bool = False  # a leading dense-MLP layer of an MoE model


def pattern_of(cfg: ArchConfig) -> tuple[list[SlotSpec], list[SlotSpec]]:
    """Returns (pattern slots, tail slots)."""
    if cfg.arch_type == "ssm":
        return [SlotSpec("mamba", has_mlp=cfg.d_ff > 0)], []
    if cfg.arch_type == "hybrid":
        p = cfg.hybrid.pattern_len
        slots = [
            SlotSpec("attn", attn_kind="window")
            if j in cfg.hybrid.attn_slots
            else SlotSpec("rglru")
            for j in range(p)
        ]
        tail_n = cfg.n_layers % p
        return slots, slots[:tail_n]
    if cfg.global_every > 0:
        p = cfg.global_every
        slots = [
            SlotSpec("attn", attn_kind=cfg.attn_kind, use_rope=True)
            for _ in range(p - 1)
        ] + [SlotSpec("attn", attn_kind="causal", use_rope=False)]  # NoPE global
        assert cfg.n_layers % p == 0
        return slots, []
    if cfg.kv_lora_rank:
        return [SlotSpec("mla")], []
    kind = "full" if cfg.arch_type == "audio" else cfg.attn_kind
    return [SlotSpec("attn", attn_kind=kind)], []


def dense_slots(cfg: ArchConfig) -> list[SlotSpec]:
    """The leading dense-MLP layers (``first_k_dense``), run once each
    before the scanned stack."""
    pattern, _ = pattern_of(cfg)
    return [dataclasses.replace(pattern[0], dense=True)] if cfg.first_k_dense else []


def mla_config(cfg: ArchConfig) -> L.MLAConfig:
    return L.MLAConfig(
        d_model=cfg.d_model,
        n_heads=cfg.n_heads,
        kv_lora_rank=cfg.kv_lora_rank,
        qk_nope_head_dim=cfg.qk_nope_head_dim,
        qk_rope_head_dim=cfg.qk_rope_head_dim,
        v_head_dim=cfg.v_head_dim,
        rope_theta=cfg.rope_theta,
        norm_eps=cfg.norm_eps,
        q_block=cfg.q_block,
        impl=cfg.attn_impl,
    )


def attn_config(cfg: ArchConfig, spec: SlotSpec) -> L.AttnConfig:
    return L.AttnConfig(
        d_model=cfg.d_model,
        n_heads=cfg.n_heads,
        n_kv_heads=cfg.n_kv_heads,
        head_dim=cfg.head_dim,
        rope_theta=cfg.rope_theta,
        use_rope=cfg.use_rope and spec.use_rope,
        qkv_bias=cfg.qkv_bias,
        kind=spec.attn_kind,
        window=cfg.window if spec.attn_kind in ("window", "chunk") else 0,
        q_block=cfg.q_block,
        q_unroll=cfg.q_unroll,
        impl=cfg.attn_impl,
    )


# ----------------------------------------------------------------- init

def _init_slot(key, cfg: ArchConfig, spec: SlotSpec, dtype):
    ks = jax.random.split(key, 4)
    p: dict = {"norm1": jnp.ones((cfg.d_model,), dtype)}
    if cfg.norm == "layernorm":
        p["norm1_b"] = jnp.zeros((cfg.d_model,), dtype)
    if spec.mixer == "attn":
        p["attn"] = L.init_attention(ks[0], attn_config(cfg, spec), dtype)
    elif spec.mixer == "mla":
        p["attn"] = L.init_mla(ks[0], mla_config(cfg), dtype)
    elif spec.mixer == "mamba":
        p["mamba"] = M.init_mamba(ks[0], cfg, dtype)
    else:
        p["rglru"] = R.init_rglru(ks[0], cfg, dtype)
    if spec.has_mlp:
        p["norm2"] = jnp.ones((cfg.d_model,), dtype)
        if cfg.norm == "layernorm":
            p["norm2_b"] = jnp.zeros((cfg.d_model,), dtype)
        if cfg.arch_type == "moe" and not spec.dense:
            p["mlp"] = MOE.init_moe(ks[1], cfg, dtype)
        elif cfg.mlp == "gelu":
            p["mlp"] = L.init_gelu_mlp(ks[1], cfg.d_model, cfg.d_ff, dtype)
        else:
            p["mlp"] = L.init_swiglu(ks[1], cfg.d_model, cfg.d_ff, dtype)
    return p


def init_params(key, cfg: ArchConfig, dtype=jnp.float32):
    pattern, tail = pattern_of(cfg)
    p_len = len(pattern)
    n_blocks = (cfg.n_layers - cfg.first_k_dense) // p_len
    keys = jax.random.split(key, 8)

    params: dict = {}
    params["embed"] = L.init_embedding(keys[0], cfg.vocab, cfg.d_model, dtype)
    if not cfg.tied_embeddings:
        params["unembed"] = L.dense_init(keys[5], cfg.d_model, cfg.vocab, dtype)
    params["final_norm"] = jnp.ones((cfg.d_model,), dtype)
    if cfg.norm == "layernorm":
        params["final_norm_b"] = jnp.zeros((cfg.d_model,), dtype)
    if cfg.frontend_dim:
        k1, k2 = jax.random.split(keys[1])
        params["frontend_proj"] = {
            "w1": L.dense_init(k1, cfg.frontend_dim, cfg.d_model, dtype),
            "w2": L.dense_init(k2, cfg.d_model, cfg.d_model, dtype),
        }

    def init_stack(key, slots, n):
        out = {}
        for j, spec in enumerate(slots):
            ks = jax.random.split(jax.random.fold_in(key, j), n)
            out[f"slot{j}"] = jax.vmap(
                lambda k: _init_slot(k, cfg, spec, dtype)
            )(ks)
        return out

    if cfg.first_k_dense:
        params["dense"] = init_stack(keys[4], dense_slots(cfg), cfg.first_k_dense)
    params["stack"] = init_stack(keys[2], pattern, n_blocks)
    if tail:
        params["tail"] = init_stack(keys[3], tail, 1)
    return params


# --------------------------------------------------------------- caches

def init_cache(cfg: ArchConfig, batch: int, cache_len: int, dtype=jnp.float32):
    pattern, tail = pattern_of(cfg)
    n_blocks = cfg.n_layers // len(pattern)

    def slot_cache(spec: SlotSpec):
        if spec.mixer == "attn":
            return L.init_attn_cache(attn_config(cfg, spec), batch, cache_len, dtype)
        if spec.mixer == "mamba":
            return M.init_mamba_cache(cfg, batch, dtype)
        return R.init_rglru_cache(cfg, batch, dtype)

    def stack_cache(slots, n):
        return {
            f"slot{j}": jax.tree.map(
                lambda x: jnp.broadcast_to(x, (n,) + x.shape).copy(), slot_cache(s)
            )
            for j, s in enumerate(slots)
        }

    cache = {"stack": stack_cache(pattern, n_blocks)}
    if tail:
        cache["tail"] = stack_cache(tail, 1)
    return cache


# -------------------------------------------------------------- forward

def _norm(x, w, b, cfg: ArchConfig):
    if cfg.norm == "layernorm":
        return L.layer_norm(x, w, b)
    return L.rms_norm(x, w, cfg.norm_eps)


def _apply_slot(p, cfg: ArchConfig, spec: SlotSpec, x, positions, cache, shard):
    h = _norm(x, p["norm1"], p.get("norm1_b"), cfg)
    if spec.mixer == "attn":
        out, new_cache = L.attention_block(
            p["attn"], attn_config(cfg, spec), h, positions, cache, shard
        )
    elif spec.mixer == "mla":
        if cache is not None:
            raise NotImplementedError("MLA runs in training form only (no cache)")
        out, new_cache = L.mla_block(p["attn"], mla_config(cfg), h, positions), None
    elif spec.mixer == "mamba":
        out, new_cache = M.mamba_mixer(p["mamba"], cfg, h, cache, shard)
    else:
        out, new_cache = R.rglru_mixer(p["rglru"], cfg, h, cache, shard)
    x = x + out
    aux, counts = jnp.float32(0.0), {}
    if spec.has_mlp:
        h = _norm(x, p["norm2"], p.get("norm2_b"), cfg)
        if cfg.arch_type == "moe" and not spec.dense:
            out, aux, counts = MOE.moe_mlp(p["mlp"], cfg, h, shard)
        elif cfg.mlp == "gelu":
            out = L.gelu_mlp(p["mlp"], h, shard)
        else:
            out = L.swiglu(p["mlp"], h, shard)
        x = x + out
    return x, new_cache, aux, counts


def _run_stack(stack_params, slots, cfg, x, positions, stack_cache, shard, remat):
    """Scan a pattern stack.  Caches (if present) are scanned alongside.
    Returns (x, new caches, summed aux loss, counters per block and slot)."""

    def block(x, per_block):
        bp, bc = per_block
        aux_total = jnp.float32(0.0)
        new_bc, counts = {}, {}
        for j, spec in enumerate(slots):
            sc = bc.get(f"slot{j}") if bc is not None else None
            x, nc, aux, cnt = _apply_slot(bp[f"slot{j}"], cfg, spec, x, positions, sc, shard)
            if nc is not None:
                new_bc[f"slot{j}"] = nc
            if cnt:
                counts[f"slot{j}"] = cnt
            aux_total = aux_total + aux
        x = shard(x, "act_model")
        return x, (new_bc if new_bc else None, aux_total, counts)

    if remat:
        block = jax.checkpoint(block)

    xs = (stack_params, stack_cache)
    # cfg.q_unroll doubles as "cost-analysis mode": fully unroll the layer
    # scan so XLA cost analysis (which counts while bodies once) is exact.
    x, (new_caches, auxes, counts) = jax.lax.scan(block, x, xs, unroll=bool(cfg.q_unroll))
    return x, new_caches, jnp.sum(auxes), counts


def forward(
    params,
    cfg: ArchConfig,
    tokens: Optional[jax.Array] = None,
    *,
    positions: Optional[jax.Array] = None,
    embeds: Optional[jax.Array] = None,  # audio frames / extra inputs
    patch_embeds: Optional[jax.Array] = None,  # vlm image prefix
    cache=None,
    shard: ShardFn = _identity_shard,
    remat: bool = False,
):
    """Returns (logits [B,S,V], new_cache, aux_loss)."""
    return forward_counted(
        params, cfg, tokens, positions=positions, embeds=embeds,
        patch_embeds=patch_embeds, cache=cache, shard=shard, remat=remat,
    )[:3]


def forward_counted(
    params,
    cfg: ArchConfig,
    tokens: Optional[jax.Array] = None,
    *,
    positions: Optional[jax.Array] = None,
    embeds: Optional[jax.Array] = None,
    patch_embeds: Optional[jax.Array] = None,
    cache=None,
    shard: ShardFn = _identity_shard,
    remat: bool = False,
):
    """:func:`forward` plus the MoE layers' counters: (logits, new_cache,
    aux_loss, counts), ``counts`` holding per scanned stack and slot the
    held dispatch's ``assignments`` [n_blocks, experts_held] and
    ``expert_rows`` [n_blocks], the row capacity each block ran (empty
    for the capacity dispatches)."""
    pattern, tail = pattern_of(cfg)

    if cfg.arch_type == "audio":
        assert embeds is not None
        x = jnp.einsum("bsf,fd->bsd", embeds, params["frontend_proj"]["w1"])
        x = jax.nn.gelu(x)
        x = jnp.einsum("bsd,de->bse", x, params["frontend_proj"]["w2"])
    else:
        x = L.embed(params["embed"], tokens)
        if cfg.arch_type == "vlm" and patch_embeds is not None:
            pe = jnp.einsum("bpf,fd->bpd", patch_embeds, params["frontend_proj"]["w1"])
            pe = jax.nn.gelu(pe)
            pe = jnp.einsum("bpd,de->bpe", pe, params["frontend_proj"]["w2"])
            x = jnp.concatenate([pe.astype(x.dtype), x], axis=1)

    b, s, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    x = shard(x, "act_model")

    new_cache: dict = {}
    counts: dict = {}
    if cfg.first_k_dense:
        x, _, _, _ = _run_stack(params["dense"], dense_slots(cfg), cfg, x, positions,
                                None, shard, remat)
    x, nc, aux, counts["stack"] = _run_stack(
        params["stack"], pattern, cfg, x, positions,
        cache["stack"] if cache is not None else None, shard, remat,
    )
    if nc is not None:
        new_cache["stack"] = nc
    if tail:
        x, nct, aux_t, counts["tail"] = _run_stack(
            params["tail"], tail, cfg, x, positions,
            cache["tail"] if cache is not None else None, shard, remat,
        )
        aux = aux + aux_t
        if nct is not None:
            new_cache["tail"] = nct

    x = _norm(x, params["final_norm"], params.get("final_norm_b"), cfg)
    if cfg.tied_embeddings:
        logits = L.unembed(params["embed"], x)
    else:
        logits = jnp.einsum("bsd,dv->bsv", x, params["unembed"])
    logits = shard(logits, "act_vocab")
    return logits, (new_cache if cache is not None else None), aux, counts


# ----------------------------------------------------------------- loss

def cross_entropy(logits, targets, mask=None):
    logits = logits.astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    if mask is None:
        return jnp.mean(nll)
    mask = mask.astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def loss_fn(params, cfg: ArchConfig, batch, shard: ShardFn = _identity_shard, remat: bool = True):
    """Training loss for any arch.  Batch keys per arch type:

      decoders: tokens [B,S], targets [B,S]
      audio:    frames [B,S,F], targets [B,S], mask [B,S]
      vlm:      tokens [B,St], patch_embeds [B,P,F], targets [B,St]
                (loss on text positions only)
    """
    if cfg.arch_type == "audio":
        logits, _, aux = forward(
            params, cfg, embeds=batch["frames"], shard=shard, remat=remat
        )
        loss = cross_entropy(logits, batch["targets"], batch.get("mask"))
    elif cfg.arch_type == "vlm":
        logits, _, aux = forward(
            params, cfg, batch["tokens"],
            patch_embeds=batch["patch_embeds"], shard=shard, remat=remat,
        )
        n_p = batch["patch_embeds"].shape[1]
        text_logits = logits[:, n_p:, :]
        loss = cross_entropy(text_logits, batch["targets"])
    else:
        logits, _, aux = forward(params, cfg, batch["tokens"], shard=shard, remat=remat)
        loss = cross_entropy(logits, batch["targets"])
    if cfg.arch_type == "moe":
        loss = loss + cfg.moe.aux_loss_weight * aux
    return loss


# ------------------------------------------------------------- serving

def prefill(params, cfg: ArchConfig, tokens=None, *, embeds=None, patch_embeds=None,
            cache=None, shard: ShardFn = _identity_shard):
    """Prefill forward (no cache write needed for the benchmark shapes —
    logits only; a cache-writing variant is used by the decode driver)."""
    logits, nc, _ = forward(
        params, cfg, tokens, embeds=embeds, patch_embeds=patch_embeds,
        cache=cache, shard=shard, remat=False,
    )
    return logits, nc


def decode_step(params, cfg: ArchConfig, token, positions, cache, shard: ShardFn = _identity_shard):
    """One-token decode: token [B,1] int32, positions [B,1] int32."""
    logits, new_cache, _ = forward(
        params, cfg, token, positions=positions, cache=cache, shard=shard, remat=False
    )
    next_tok = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)[:, None]
    return next_tok, logits, new_cache
