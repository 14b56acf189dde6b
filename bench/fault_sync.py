#!/usr/bin/env python3
"""``bench/run.py`` with the faults of the sync cells added to those of
``bench/faults.py``, planted the same way (the generator is built inside
the fault's context, so the program traces its rounds with it):

    python3 bench/fault_sync.py --workload <cell> --seed <n> --seconds <s> --fault <name>

Each patches ``repro.fl.round`` (``federated_round`` and what it calls)
or the MoE layer:

- ``sync_unchanged``: a round that returns the model as it was;
- ``sync_half_rows``: half of the clients' rows are lost after the
  stack is built: the second half is overwritten by the first, so the
  count of rows and their client ids stay as they were;
- ``sync_half_minibatch``: the clients and the root train on half of
  each local minibatch (half of its samples, or of its one sequence's
  tokens), the loss the mean over the rest;
- ``frozen_a``: the LoRA ``a`` factors are never updated (the round
  returns them as they were), only the ``b`` factors move;
- ``drop_routed``: every MoE layer returns nothing of its held experts,
  only its shared experts' output, as if the routed share were lost.
"""
from __future__ import annotations

import contextlib
import dataclasses
import os
import sys

sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.abspath(__file__)))]

from bench import faults, run  # noqa: E402

FAULTS = ("sync_unchanged", "sync_half_rows", "sync_half_minibatch", "frozen_a", "drop_routed")


def _keep_a(new, old):
    """``new`` with every LoRA ``a`` leaf taken from ``old``."""
    import jax

    def pick(path, n, o):
        return o if getattr(path[-1], "key", None) == "a" else n

    return jax.tree_util.tree_map_with_path(pick, new, old)


def _half(batches_u):
    """Half of each minibatch of a [U, B, ...] stack: its first B/2
    samples, or with B = 1 the first half of its sequence's tokens."""
    b = batches_u["y"].shape[1]
    if b > 1:
        return {key: v[:, : b // 2] for key, v in batches_u.items()}
    return {key: v[:, :, : v.shape[2] // 2] for key, v in batches_u.items()}


@contextlib.contextmanager
def planted(name: str, _planted=faults.planted):
    if name not in FAULTS:
        with _planted(name):
            yield
        return
    import jax
    import jax.numpy as jnp

    from repro.core import flat
    from repro.fl import round as rnd
    from repro.models import moe

    saved = (rnd.federated_round, rnd.local_update, flat.stack_updates, moe._dispatch_held)
    federated_round, local_update, stack_updates, held = saved

    def sync_unchanged(loss_fn, state, *args, **kw):
        new, metrics = federated_round(loss_fn, state, *args, **kw)
        return new._replace(params=state.params), metrics

    def frozen_a(loss_fn, state, *args, **kw):
        new, metrics = federated_round(loss_fn, state, *args, **kw)
        return new._replace(params=_keep_a(new.params, state.params)), metrics

    def sync_half_rows(*args, **kw):
        st = stack_updates(*args, **kw)
        k = st.data.shape[0]
        kept = st.data[: k - k // 2]
        return dataclasses.replace(st, data=jnp.concatenate([kept, kept[: k // 2]]))

    def sync_half_minibatch(loss_fn, params, batches_u, lr, **kw):
        return local_update(loss_fn, params, _half(batches_u), lr, **kw)

    def drop_routed(params, cfg, x):
        y, counts = held(params, cfg, x)
        return jnp.zeros_like(y), counts

    if name == "sync_unchanged":
        rnd.federated_round = sync_unchanged
    elif name == "frozen_a":
        rnd.federated_round = frozen_a
    elif name == "sync_half_rows":
        flat.stack_updates = sync_half_rows
    elif name == "sync_half_minibatch":
        rnd.local_update = sync_half_minibatch
    else:
        moe._dispatch_held = drop_routed
    jax.clear_caches()
    try:
        yield
    finally:
        rnd.federated_round, rnd.local_update, flat.stack_updates, moe._dispatch_held = saved
        jax.clear_caches()


def main(argv=None) -> int:
    faults.FAULTS = tuple(dict.fromkeys(faults.FAULTS + FAULTS))
    faults.planted = planted
    return run.main(argv)


if __name__ == "__main__":
    run.use_checkout_cache()
    sys.exit(main())
