"""Training batches: seeded random token rows, made on the device.

A cell's file gives ``{"kind": "token_batches", "batch": 4, "seq": 2048}``.
Step ``t`` of a run with seed ``s`` trains on rows drawn from
``fold_in(key(s), t)``: every row of every step differs, and the same seed
gives the same batches.  Targets are the next token of each row.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


@functools.lru_cache(maxsize=None)
def _draw(batch: int, seq: int, vocab: int):
    def draw(key, step):
        x = jax.random.randint(jax.random.fold_in(key, step),
                               (batch, seq + 1), 0, vocab, jnp.int32)
        return {"tokens": x[:, :-1], "targets": x[:, 1:]}
    return jax.jit(draw)


def batch(spec: dict, key, step: int, vocab: int) -> dict:
    return _draw(spec["batch"], spec["seq"], vocab)(key, step)
