"""Plain reference of what the timed paths compute, written from the
paper (arXiv 2601.06903, Alg. 1 and eqs. 5-11) and the documented
semantics of the buffered-async server, importing nothing of the
program.

Everything works on parameter dicts (no flat plane, no kernels), in the
precision it is given: float32 with ``Precision.HIGHEST`` products for
the reference, bfloat16 for the control.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

EPS = 1e-12
HI = jax.lax.Precision.HIGHEST

#: trust-layer constants as the configuration runs them (the program's
#: documented defaults, ``TrustSpec(enabled=True)`` with no overrides)
TRUST = dict(decay=0.8, div_threshold=1.0, sensitivity=4.0, norm_cap=4.0,
             norm_sensitivity=1.0, warmup=2.0, quarantine_threshold=0.05)


# ------------------------------------------------------------- the model
def forward(params: dict, x, layers: list):
    """The configuration's CNN: SAME/VALID convolutions with ReLU, 2x2
    max pooling, dense layers (ReLU on all but the last)."""
    for layer in layers:
        if "conv" in layer:
            x = jax.lax.conv_general_dilated(
                x, params[layer["w"]].astype(x.dtype), (1, 1), layer["padding"],
                dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HI)
            x = jax.nn.relu(x + params[layer["b"]])
        elif "pool" in layer:
            p = layer["pool"]
            x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                      (1, p, p, 1), (1, p, p, 1), "VALID")
        else:
            x = x.reshape(x.shape[0], -1)
            x = jnp.dot(x, params[layer["w"]], precision=HI) + params[layer["b"]]
            if layer.get("relu", True):
                x = jax.nn.relu(x)
    return x


def loss(params, x, y, layers):
    logp = jax.nn.log_softmax(forward(params, x, layers), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=-1))


def local_sgd(params, xs, ys, lr, layers):
    """U plain SGD steps over xs [U, B, ...]; returns theta_U - theta_0."""
    theta = params
    grad = jax.grad(loss)
    for u in range(xs.shape[0]):
        g = grad(theta, xs[u], ys[u], layers)
        theta = {k: theta[k] - jnp.asarray(lr, theta[k].dtype) * g[k] for k in theta}
    return {k: theta[k] - params[k] for k in params}


# ------------------------------------------------------------- the flush
def _vdot(a: dict, b: dict):
    return sum(jnp.sum(a[k] * b[k]) for k in a)


def trust_weights(trust, cids):
    """Reputations of the buffered clients: 1 during warm-up, 0 once
    quarantined, else exp of the excess divergence and norm ratio."""
    rep = jnp.exp(-TRUST["sensitivity"] * jax.nn.relu(trust["div"] - TRUST["div_threshold"])
                  - TRUST["norm_sensitivity"] * jax.nn.relu(trust["nr"] - TRUST["norm_cap"]))
    rep = jnp.where(trust["seen"] >= TRUST["warmup"], rep, 1.0)
    rep = jnp.where(trust["quarantined"], 0.0, rep)
    return rep[cids]


def trust_observe(trust, cids, div, nr, gate, k: int):
    """Fold one flush's divergences into the per-client history, each
    slot from the history as it was before the flush; a client with two
    slots keeps the later one (one observation per flush)."""
    d0, n0, s0 = trust["div"], trust["nr"], trust["seen"]
    d, n, s = d0, n0, s0
    for i in range(k):
        c = cids[i]
        first = s0[c] == 0.0
        nd = jnp.where(first, div[i], TRUST["decay"] * d0[c] + (1 - TRUST["decay"]) * div[i])
        nn = jnp.where(first, nr[i], TRUST["decay"] * n0[c] + (1 - TRUST["decay"]) * nr[i])
        d = d.at[c].set(jnp.where(gate, nd, d0[c]))
        n = n.at[c].set(jnp.where(gate, nn, n0[c]))
        s = s.at[c].set(s0[c] + jnp.where(gate, 1.0, 0.0))
    rep = jnp.exp(-TRUST["sensitivity"] * jax.nn.relu(d - TRUST["div_threshold"])
                  - TRUST["norm_sensitivity"] * jax.nn.relu(n - TRUST["norm_cap"]))
    q = trust["quarantined"] | ((rep < TRUST["quarantine_threshold"]) & (s >= TRUST["warmup"]))
    return {"div": d, "nr": n, "seen": s, "quarantined": q}


def init_trust(n_clients: int, dtype):
    return {"div": jnp.zeros((n_clients,), dtype), "nr": jnp.ones((n_clients,), dtype),
            "seen": jnp.zeros((n_clients,), dtype),
            "quarantined": jnp.zeros((n_clients,), bool)}


def drag_flush(state, rows: list, cids, taus, *, c, alpha, discount_a):
    """One DRAG flush over K buffered updates (dicts), with staleness
    discounts phi = (1 + tau)^-a and trust weights.  The first flush
    applies the raw mean and seeds the reference direction (eq. 5a);
    later ones calibrate against it (eqs. 10-11), apply the
    trust-weighted mean and roll the reference (eq. 5b).

    ``state``: params, ref, initialized, trust.  Returns (state',
    metrics)."""
    k = len(rows)
    dt = rows[0][next(iter(rows[0]))].dtype
    r, init = state["ref"], state["initialized"]
    dots = jnp.stack([_vdot(g, r) for g in rows])
    gsq = jnp.stack([_vdot(g, g) for g in rows])
    rsq = _vdot(r, r)
    gn, rn = jnp.sqrt(gsq + EPS), jnp.sqrt(rsq + EPS)
    cos = dots / (gn * rn)
    phi = (1.0 + taus.astype(dt)) ** jnp.asarray(-discount_a, dt)
    lam = c * (1.0 - cos) * phi
    rep = trust_weights(state["trust"], cids)
    w = jnp.where(jnp.sum(rep) > EPS, rep / jnp.maximum(jnp.sum(rep), EPS), 1.0 / k)
    # v_m = (1 - lam) g_m + lam (|g_m| / |r|) r, weighted mean over m
    aw = jnp.where(init, w * (1.0 - lam), 1.0 / k).astype(dt)
    bw = jnp.where(init, w * lam * gn / rn, 0.0).astype(dt)
    delta = {key: sum(aw[m] * rows[m][key] for m in range(k)) + jnp.sum(bw) * r[key]
             for key in r}
    new_ref = {key: jnp.where(init, (1 - alpha) * r[key] + alpha * delta[key], delta[key])
               for key in r}
    trust = trust_observe(state["trust"], cids, 1.0 - cos, gn / rn, init, k)
    params = {key: state["params"][key] + delta[key] for key in delta}
    metrics = {
        "delta_norm": jnp.sqrt(_vdot(delta, delta)),
        "update_norm_mean": jnp.mean(jnp.sqrt(gsq)),
        "trust_weight_mean": jnp.mean(rep),
    }
    return ({"params": params, "ref": new_ref, "initialized": jnp.asarray(True),
             "trust": trust}, metrics)


def init_state(params: dict, n_clients: int):
    dt = params[next(iter(params))].dtype
    return {"params": params, "ref": {k: jnp.zeros_like(v) for k, v in params.items()},
            "initialized": jnp.asarray(False), "trust": init_trust(n_clients, dt)}


# ------------------------------------------------------------- arrivals
def _mix32(x):
    x = np.asarray(x, np.uint32)
    x = x ^ (x >> np.uint32(16))
    x = x * np.uint32(0x7FEB352D)
    x = x ^ (x >> np.uint32(15))
    x = x * np.uint32(0x846CA68B)
    return x ^ (x >> np.uint32(16))


def hash_u32(seed: int, salt: int, ctr):
    """The event plane's counter hash: two 32-bit finaliser rounds over
    a salted seed, keyed on a counter."""
    base = np.array([seed], np.uint32) ^ (np.array([salt], np.uint32)
                                          * np.array([0x9E3779B9], np.uint32))
    return _mix32(_mix32(base) ^ np.asarray(ctr, np.uint32))


def hash_unit(seed: int, salt: int, ctr):
    return (hash_u32(seed, salt, ctr) >> np.uint32(8)).astype(np.float32) * np.float32(2.0 ** -24)


SALT_CLIENT, SALT_LATENCY, SALT_BATCH = 0x5EED, 0x1A7E, 0xB47C


def event_schedule(*, seed: int, n_clients: int, concurrency: int, k: int, n_flushes: int,
                   table: np.ndarray):
    """The buffered-async arrival process, one flush at a time: W jobs
    in flight; the earliest completion (ties to the lower dispatch
    number) is popped and a fresh job, tagged with the current model
    version, takes its place; every K pops the server flushes.  A job's
    client is uniform over M, its latency an entry of ``table`` picked
    by a uniform draw, both keyed on its dispatch number.

    Returns seq, client, dispatch version arrays of shape [T, K]."""
    grid = len(table)

    def draw(seqs):
        cid = np.minimum((hash_unit(seed, SALT_CLIENT, seqs) * np.float32(n_clients))
                         .astype(np.int32), n_clients - 1)
        idx = (hash_unit(seed, SALT_LATENCY, seqs) * np.float32(grid)).astype(np.int32)
        return cid, table[idx]

    seq = np.arange(concurrency, dtype=np.int64)
    cid, dt = draw(seq)
    comp = np.float32(0.0) + dt
    disp = np.zeros(concurrency, np.int64)
    nxt = concurrency
    out = np.zeros((3, n_flushes, k), np.int64)
    for t in range(n_flushes):
        for i in range(k):
            tmin = comp.min()
            slot = int(np.argmin(np.where(comp == tmin, seq, np.iinfo(np.int64).max)))
            out[:, t, i] = seq[slot], cid[slot], disp[slot]
            now = comp[slot]
            c_new, dt_new = draw(np.array([nxt]))
            seq[slot], cid[slot], disp[slot] = nxt, c_new[0], t
            comp[slot] = np.float32(now) + dt_new[0]
            nxt += 1
    return out[0], out[1], out[2]


def batch_indices(*, seed: int, seqs, cids, parts_padded, part_len, ub: int):
    """Sample indices of each job's local batches: U*B draws with
    replacement from the client's partition, keyed on the dispatch
    number.  Returns [E, U*B] indices into the image set."""
    ctr = (np.asarray(seqs, np.uint32)[:, None] * np.uint32(ub)
           + np.arange(ub, dtype=np.uint32)[None, :])
    h = hash_u32(seed, SALT_BATCH, ctr)
    pos = (h % np.asarray(part_len, np.uint32)[np.asarray(cids)][:, None]).astype(np.int64)
    return parts_padded[np.asarray(cids)[:, None], pos]
