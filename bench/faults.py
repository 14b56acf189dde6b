"""Faults planted under the timed path, to show that the check catches
them: each patches the program where the fault would arise, for as long
as the context is open.  Build the traffic generator inside the context,
so the program traces its steps with the fault in place.

- ``unchanged``: a flush that returns the model as it was;
- ``half_batch``: a flush that leaves half the buffered updates out and
  takes the mean over the rest;
- ``half_rows``: the same loss, after the stack is viewed: the left-out
  half of the rows is overwritten by the kept half, so the count and the
  staleness of every update stay as they were;
- ``half_minibatch``: each client trains on half of each local
  minibatch, its loss the mean over the rest;
- ``altered``: the model a flush produces is altered where it is made.
"""
from __future__ import annotations

import contextlib
import dataclasses

FAULTS = ("unchanged", "half_batch", "half_rows", "half_minibatch", "altered")


@contextlib.contextmanager
def planted(name: str):
    import jax
    import jax.numpy as jnp

    from repro.stream import buffer, megastep, server

    flush, as_stack, local_update = server.flush, buffer.as_stack, megastep.local_update

    def unchanged(loss_fn, cfg, params, *args, **kw):
        return (params,) + flush(loss_fn, cfg, params, *args, **kw)[1:]

    def altered(*args, **kw):
        out = flush(*args, **kw)
        first = sorted(out[0])[0]
        return ({**out[0], first: out[0][first] + 1e-3},) + out[1:]

    def half_stack(buf, spec, server_round):
        st = as_stack(buf, spec, server_round)
        h = st.data.shape[0] // 2
        return dataclasses.replace(st, data=st.data[:h], client_ids=st.client_ids[:h],
                                   staleness=st.staleness[:h])

    def half_rows(buf, spec, server_round):
        st = as_stack(buf, spec, server_round)
        k = st.data.shape[0]
        kept = st.data[: k - k // 2]
        return dataclasses.replace(st, data=jnp.concatenate([kept, kept[: k // 2]]))

    def half_minibatch(loss_fn, params, batches_u, lr, **kw):
        h = batches_u["y"].shape[1] // 2
        return local_update(loss_fn, params, {key: v[:, :h] for key, v in batches_u.items()},
                            lr, **kw)

    if name == "unchanged":
        server.flush = unchanged
    elif name == "altered":
        server.flush = altered
    elif name == "half_batch":
        buffer.as_stack = half_stack
    elif name == "half_rows":
        buffer.as_stack = half_rows
    elif name == "half_minibatch":
        megastep.local_update = half_minibatch
    else:
        raise KeyError(f"unknown fault {name!r}; have {FAULTS}")
    jax.clear_caches()
    try:
        yield
    finally:
        server.flush, buffer.as_stack, megastep.local_update = flush, as_stack, local_update
        jax.clear_caches()
