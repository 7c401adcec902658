"""Slot-pool KV cache for continuous batching.

One :class:`SlotCache` backs one function instance: a decode cache of width
``slots`` on the batch dim (the within-instance concurrency), with per-slot
insert (admission after prefill) and a shared decode step over all slots.
Inactive slots decode garbage that is never read — standard continuous
batching semantics.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation


class SlotCache:
    def __init__(self, model, slots: int, max_len: int):
        self.model = model
        self.slots = slots
        self.max_len = max_len
        self.cache = model.init_cache(slots, max_len)
        self.pos = np.zeros(slots, np.int32)           # next position per slot
        self.active = np.zeros(slots, bool)
        self.rid = np.full(slots, -1, np.int64)
        self.remaining = np.zeros(slots, np.int32)
        # set by admit and release: the device's copies of pos and active
        # are stale until the engine uploads them again
        self.changed = True

    def free_slots(self):
        return [i for i in range(self.slots) if not self.active[i]]

    def admit(self, slot: int, prefill_cache, prompt_len: int, rid: int,
              decode_steps: int):
        """Insert a prefilled (batch=1) sequence into `slot`; it finishes after
        ``decode_steps`` more decode steps."""
        def insert(c, p):
            # c: [K, slots, W, ...] or [K, slots, ...]; p batch dim = 1
            if c.ndim >= 3 and p.shape[2] != c.shape[2] and p.ndim == c.ndim:
                # attn cache: prefill width S0 <= W
                return jax.lax.dynamic_update_slice_in_dim(
                    c, jax.lax.dynamic_update_slice_in_dim(
                        jnp.zeros_like(c[:, slot:slot + 1]), p.astype(c.dtype),
                        0, axis=2),
                    slot, axis=1)
            return jax.lax.dynamic_update_slice_in_dim(
                c, p.astype(c.dtype), slot, axis=1)
        with TraceAnnotation("engine.insert", rid=rid):
            self.cache = jax.tree.map(insert, self.cache, prefill_cache)
            self.pos[slot] = prompt_len
            self.active[slot] = True
            self.rid[slot] = rid
            self.remaining[slot] = decode_steps
            self.changed = True

    def release(self, slot: int):
        self.active[slot] = False
        self.rid[slot] = -1
        self.changed = True

    def positions(self) -> jnp.ndarray:
        """The next position of every slot, as a copy: an :meth:`advance`
        after this call does not change what it returned."""
        return jnp.asarray(self.pos.copy())

    def advance(self):
        self.pos[self.active] += 1
        self.remaining[self.active] -= 1

    def finished_slots(self):
        return [i for i in range(self.slots)
                if self.active[i] and self.remaining[i] <= 0]
