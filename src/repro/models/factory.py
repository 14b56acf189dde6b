"""The one model factory of the FL engines: a ``ModelSpec`` names either
a ``models/cnn.MODELS`` classifier or a ``repro.configs`` architecture
id, and :func:`build` returns what an engine needs of it.

- A CNN trains all of its parameters: ``init(key, data)`` returns
  ``(None, params)``, ``loss(params, batch)`` is the classification loss.
- An architecture trains low-rank adapters on a frozen base
  (``models/lora.py``): ``init(key, data)`` returns ``(base, adapters)``
  and ``loss(adapters, batch, base)`` returns ``(loss, counters)``: the
  next-token cross-entropy over the batch's ``x`` tokens and ``y``
  targets, and in-jit counters (``tokens_trained``, the MoE layers'
  ``expert_assignments`` [layers, held experts] and ``expert_rows``
  [layers], the row capacity each layer's held dispatch ran).
  The base is an argument, never a constant captured by the jitted round.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import cnn


@dataclasses.dataclass(frozen=True)
class Model:
    kind: str  # "cnn" | "arch"
    init: Callable  # (key, data) -> (frozen base | None, trainable params)
    loss: Callable  # cnn: (params, batch); arch: (params, batch, base) -> (loss, counters)
    accuracy: Callable  # cnn: (params, batch); arch: (params, batch, base)
    arch: object = None  # the ArchConfig of an architecture
    logits: Callable | None = None  # arch: (params, tokens [B, L], base) -> [B, L, V]


def is_arch(name: str) -> bool:
    from repro.configs import FL_ARCH_IDS

    return name in FL_ARCH_IDS


def arch_config(model_spec):
    """The ArchConfig a spec names, with its overrides applied (a dotted
    key such as ``moe.experts_held`` replaces a field of a nested config)."""
    from repro.configs import get_arch

    cfg = get_arch(model_spec.name, smoke=model_spec.smoke)
    for key, value in sorted(model_spec.overrides.items()):
        head, _, field = key.partition(".")
        if field:
            cfg = dataclasses.replace(
                cfg, **{head: dataclasses.replace(getattr(cfg, head), **{field: value})})
        else:
            cfg = dataclasses.replace(cfg, **{head: value})
    return cfg


def build(model_spec) -> Model:
    if not is_arch(model_spec.name):
        return _cnn(model_spec.name)
    return _arch(arch_config(model_spec), model_spec.adapters)


def _cnn(name: str) -> Model:
    init_fn, apply_fn = cnn.MODELS[name]

    def init(key, data):
        if name == "mlp":
            in_dim = int(np.prod(data.x.shape[1:]))
            return None, init_fn(key, in_dim, 64, data.n_classes)
        return None, init_fn(key)

    return Model(
        kind="cnn", init=init,
        loss=partial(cnn.classification_loss, apply_fn),
        accuracy=partial(cnn.accuracy, apply_fn),
    )


def _arch(cfg, adapters) -> Model:
    from repro.models import lora
    from repro.models import transformer as T

    scale = adapters.alpha / adapters.rank

    def init(key, data):
        del data
        k_base, k_lora = jax.random.split(key)
        base = jax.jit(partial(T.init_params, cfg=cfg))(k_base)
        return base, lora.init_adapters(k_lora, base, adapters.targets, adapters.rank)

    def logits_counted(params, tokens, base, remat):
        merged = lora.merge(base, params, scale)
        logits, _, _, counts = T.forward_counted(merged, cfg, tokens, remat=remat)
        return logits, counts

    def loss(params, batch, base):
        # no rematerialisation: a client's step keeps every layer's
        # activations (the TPU compiler puts Moonlight's round at 13.3 GB
        # of 16); its round took 3.43 s on a TPU v5e, 3.98 s when only
        # the matrix products were kept
        logits, counts = logits_counted(params, batch["x"], base, remat=False)
        moe = counts.get("stack", {}).get("slot0", {})
        counters = {
            "tokens_trained": jnp.int32(batch["y"].size),
            "expert_assignments": moe.get("assignments", jnp.zeros((0, 0), jnp.int32)),
            "expert_rows": moe.get("expert_rows", jnp.zeros((0,), jnp.int32)),
        }
        return T.cross_entropy(logits, batch["y"]), counters

    def accuracy(params, batch, base):
        logits, _ = logits_counted(params, batch["x"], base, remat=False)
        return jnp.mean(jnp.argmax(logits, -1) == batch["y"])

    def logits(params, tokens, base):
        return logits_counted(params, tokens, base, remat=False)[0]

    return Model(kind="arch", init=init, loss=loss, accuracy=accuracy, arch=cfg,
                 logits=logits)
