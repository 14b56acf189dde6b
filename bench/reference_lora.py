"""Plain float32 reference of the LoRA-adapted MLA + MoE language model
(Moonlight-16B-A3B's family) and of one synchronous BR-DRAG round under
ALIE, written from the published equations and importing nothing of the
program.  The benchmark keeps a byte-for-byte copy beside its harness.

The model: DeepSeek-V2 multi-head latent attention without query
compression (arXiv 2405.04434) and DeepSeek-V3 routing (arXiv
2412.19437, §2.1.2): sigmoid scores over every routed expert, the top k
chosen on score + a selection bias, their scores renormalised and scaled.
Routed experts are computed densely, every held expert on every token,
each token's output masked to its chosen experts: no sort, no capacity.
LoRA (arXiv 2106.09685): ``h = W x + (alpha / r) B (A x)``.

Departures from the published model, as in the program: random weights
from a seed; RoPE rotates the pairs (2i, 2i + 1) of the rope part
(DeepSeek's checkpoint permutes q_rope and k_rope into halves first, the
same permutation on both, so the scores are the same); the cut of the
configuration (fewer layers, the experts [offset, offset + held) of each
MoE layer, a vocabulary slice), whose left-out experts add nothing here
either.

The round (paper arXiv 2601.06903, Alg. 2): every client and the root
run U plain SGD steps of ``jax.grad`` of this loss on their adapters;
the malicious rows are replaced by ALIE (Baruch et al. 2019: the benign
mean less z times the benign standard deviation, per coordinate); each
row g_m is calibrated by eq. (15), v_m = (1 - lam_m)(|r| / |g_m|) g_m +
lam_m r with lam_m = c (1 - cos(g_m, r)), and the mean of the v_m is
added to the adapters.

Parameters are the program's trees (weights are data): ``base`` as
``models/transformer.init_params`` lays it out and ``adapters`` as
``models/lora.init_adapters`` does.  ``cfg`` is a dict of numbers:
d_model, n_heads, kv_lora_rank, qk_nope_head_dim, qk_rope_head_dim,
v_head_dim, rope_theta, norm_eps, top_k, routed_scaling,
expert_offset and lora_scale.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

EPS = 1e-12


def _mm(x, w):
    return jnp.einsum("...d,df->...f", x, w.astype(x.dtype))


def dense(x, w, ad, scale):
    """x W, plus (alpha / r) (x A) B where the weight has an adapter."""
    y = _mm(x, w)
    if ad is not None:
        y = y + scale * _mm(_mm(x, ad["a"]), ad["b"])
    return y


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w.astype(x.dtype)


def rope(x, theta):
    """x: [L, H, D]; the pair (2i, 2i + 1) at position p turns by
    p * theta^(-2i / D)."""
    L, _, D = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = jnp.arange(L, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :].astype(x.dtype), jnp.sin(ang)[:, None, :].astype(x.dtype)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).reshape(x.shape)


def mla(p, ad, x, cfg):
    """Multi-head latent attention of one sequence x: [L, d]."""
    L = x.shape[0]
    h, dn, dr, dv = cfg["n_heads"], cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    rank, s = cfg["kv_lora_rank"], cfg["lora_scale"]
    ad = ad or {}
    q = dense(x, p["wq"], ad.get("wq"), s).reshape(L, h, dn + dr)
    kv_a = dense(x, p["wkv_a"], ad.get("wkv_a"), s)
    c_kv = rms_norm(kv_a[:, :rank], p["kv_norm"], cfg["norm_eps"])
    k_rope = rope(kv_a[:, None, rank:], cfg["rope_theta"])  # [L, 1, dr], shared by the heads
    kv = dense(c_kv, p["wkv_b"], ad.get("wkv_b"), s).reshape(L, h, dn + dv)
    q_nope, q_rope = q[..., :dn], rope(q[..., dn:], cfg["rope_theta"])
    k_nope, v = kv[..., :dn], kv[..., dn:]
    scores = (jnp.einsum("qhd,khd->hqk", q_nope, k_nope)
              + jnp.einsum("qhd,khd->hqk", q_rope, jnp.broadcast_to(k_rope, (L, h, dr))))
    scores = scores / jnp.sqrt(jnp.asarray(dn + dr, x.dtype))
    causal = jnp.arange(L)[None, :, None] >= jnp.arange(L)[None, None, :]
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    out = jnp.einsum("hqk,khd->qhd", probs, v).reshape(L, h * dv)
    return dense(out, p["wo"], ad.get("wo"), s)


def swiglu(p, ad, x, s):
    ad = ad or {}
    g = dense(x, p["w_gate"], ad.get("w_gate"), s)
    u = dense(x, p["w_up"], ad.get("w_up"), s)
    return dense(jax.nn.silu(g) * u, p["w_down"], ad.get("w_down"), s)


def route(p, x, cfg):
    """[L, E] weight of each routed expert for each token: the top k of
    sigmoid(x W_r) + b, weighted by their scores, renormalised, scaled."""
    scores = jax.nn.sigmoid(_mm(x, p["router"]))
    _, top = jax.lax.top_k(scores + p["router_bias"].astype(x.dtype), cfg["top_k"])
    chosen = jnp.sum(jax.nn.one_hot(top, scores.shape[-1], dtype=x.dtype), axis=1)  # [L, E]
    w = scores * chosen
    return w / jnp.sum(w, axis=-1, keepdims=True) * cfg["routed_scaling"]


def held_experts(p, x, cfg):
    """The held experts' share of the routed output, dense: every held
    expert on every token, weighted by the token's routing weight (zero
    unless the expert is among its top k)."""
    weights = route(p, x, cfg)
    n_held = p["w_gate"].shape[0]

    def expert(w_gate, w_up, w_down):
        return _mm(jax.nn.silu(_mm(x, w_gate)) * _mm(x, w_up), w_down)

    out = jax.vmap(expert)(p["w_gate"], p["w_up"], p["w_down"])  # [held, L, d]
    mine = weights[:, cfg["expert_offset"]: cfg["expert_offset"] + n_held]
    return jnp.einsum("le,eld->ld", mine, out)


def _layer(p, ad, x, cfg, moe: bool):
    ad = ad or {}
    x = x + mla(p["attn"], ad.get("attn"), rms_norm(x, p["norm1"], cfg["norm_eps"]), cfg)
    h = rms_norm(x, p["norm2"], cfg["norm_eps"])
    if moe:
        mad = ad.get("mlp", {})
        out = swiglu(p["mlp"]["shared"], mad.get("shared"), h, cfg["lora_scale"])
        out = out + held_experts(p["mlp"], h, cfg)
    else:
        out = swiglu(p["mlp"], ad.get("mlp"), h, cfg["lora_scale"])
    return x + out


def forward(base, adapters, tokens, cfg):
    """Logits [L, V] of one sequence ``tokens`` [L].  The layers of a
    stack are one scanned body, rematerialised under differentiation
    (the values are the same)."""
    with jax.default_matmul_precision("highest"):
        dt = adapters_dtype(adapters)
        x = base["embed"]["table"][tokens].astype(dt)
        for stack, moe in (("dense", False), ("stack", True)):
            if stack not in base:
                continue
            p, ad = base[stack]["slot0"], (adapters.get(stack) or {}).get("slot0")
            layer = jax.checkpoint(lambda x_, pa, moe=moe: (_layer(*pa, x_, cfg, moe), None))
            x, _ = jax.lax.scan(layer, x, (p, ad))
        x = rms_norm(x, base["final_norm"], cfg["norm_eps"])
        return _mm(x, base["unembed"])


def adapters_dtype(adapters):
    return jax.tree.leaves(adapters)[0].dtype


def loss(adapters, base, tokens, targets, cfg):
    """Mean next-token cross-entropy over the batch [B, L]."""
    def one(t, y):
        logp = jax.nn.log_softmax(forward(base, adapters, t, cfg), axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=-1))

    return sum(one(tokens[b], targets[b]) for b in range(tokens.shape[0])) / tokens.shape[0]


def sgd_step(theta, base, x, y, lr, cfg):
    """One plain SGD step on the batch x, y [B, L]."""
    g = jax.grad(loss)(theta, base, x, y, cfg)
    return jax.tree.map(lambda t, d: t - jnp.asarray(lr, t.dtype) * d, theta, g)


def local_sgd(adapters, base, xs, ys, lr, cfg, step=sgd_step):
    """U plain SGD steps on xs, ys [U, B, L]; returns theta_U - theta_0.
    ``step`` may be :func:`sgd_step` compiled on its own, so that a large
    model compiles one step and not U."""
    theta = adapters
    for u in range(xs.shape[0]):
        theta = step(theta, base, xs[u], ys[u], lr, cfg)
    return jax.tree.map(jnp.subtract, theta, adapters)


# ------------------------------------------------------------- the round
def _vdot(a, b):
    return sum(jnp.sum(x * y) for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def alie(rows: list, malicious, z: float):
    """Malicious rows replaced by the benign mean less z times the
    benign standard deviation, coordinate by coordinate."""
    benign = [r for r, m in zip(rows, malicious) if not m]
    if not benign or len(benign) == len(rows):
        return rows
    n = len(benign)
    mu = jax.tree.map(lambda *xs: sum(xs) / n, *benign)
    var = jax.tree.map(lambda m, *xs: sum((x - m) ** 2 for x in xs) / n, mu, *benign)
    crafted = jax.tree.map(lambda m, v: m - z * jnp.sqrt(v + EPS), mu, var)
    return [crafted if m else r for r, m in zip(rows, malicious)]


def br_drag(rows: list, r, c: float):
    """Eq. (15) per row, then the mean.  Returns (delta, lam [S])."""
    rn = jnp.sqrt(_vdot(r, r) + EPS)
    vs, lams = [], []
    for g in rows:
        gn = jnp.sqrt(_vdot(g, g) + EPS)
        lam = c * (1.0 - _vdot(g, r) / (gn * rn))
        vs.append(jax.tree.map(lambda gg, rr: (1.0 - lam) * (rn / gn) * gg + lam * rr, g, r))
        lams.append(lam)
    delta = jax.tree.map(lambda *xs: sum(xs) / len(xs), *vs)
    return delta, jnp.stack(lams)


def sync_round(adapters, client_rows: list, root_update, malicious, *, z: float, c: float):
    """The server's half of a round, given every client's honest update
    and the root's: ALIE on the malicious rows, BR-DRAG, the new
    adapters.  Returns (adapters', delta, lam)."""
    rows = alie(client_rows, malicious, z)
    delta, lams = br_drag(rows, root_update, c)
    return jax.tree.map(jnp.add, adapters, delta), delta, lams
