"""Slot-pool decode cache for continuous batching.

One :class:`SlotCache` backs one function instance: a decode cache of width
``slots`` on the batch dim (the within-instance concurrency), with per-slot
insert (admission after prefill) and a shared decode step over all slots.
Inactive slots decode garbage that is never read — standard continuous
batching semantics.

A slot holds state of two kinds, which the model's cache specs tell apart:
keys and values of attention layers (``kv``, a leaf with a sequence axis,
``act_kv_seq``) and the recurrent state of Mamba layers (``recurrent``: the
conv window and the SSM state, a fixed size per slot).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

KV, RECURRENT = "kv", "recurrent"
SEQ_AXIS = "act_kv_seq"        # the logical axis of a KV leaf's positions


class SlotCache:
    def __init__(self, model, slots: int, max_len: int):
        self.model = model
        self.slots = slots
        self.max_len = max_len
        specs, axes = model.cache_specs(slots, max_len)
        self.cache = model.init_cache(slots, max_len)
        # per leaf, in the order of jax.tree.leaves(self.cache): the axis a
        # prompt's keys and values are padded along, None for recurrent state
        self._seq_axis = [ax.index(SEQ_AXIS) if SEQ_AXIS in ax else None
                          for ax in jax.tree.leaves(
                              axes, is_leaf=lambda a: isinstance(a, tuple))]
        # bytes of one slot's state, by kind
        self.state_bytes = {KV: 0, RECURRENT: 0}
        for spec, seq in zip(jax.tree.leaves(specs), self._seq_axis):
            size = math.prod(spec.shape) * jnp.dtype(spec.dtype).itemsize
            self.state_bytes[RECURRENT if seq is None else KV] += size // slots
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
        ``decode_steps`` more decode steps. A KV leaf takes the prompt's keys
        and values zero-padded along its sequence axis; a recurrent leaf is
        the slot's whole state and is replaced."""
        def insert(c, p, seq):
            # c: [K, slots, ...]; p: [K, 1, ...]
            p = p.astype(c.dtype)
            if seq is not None:
                p = jax.lax.dynamic_update_slice_in_dim(
                    jnp.zeros_like(c[:, slot:slot + 1]), p, 0, axis=seq)
            return jax.lax.dynamic_update_slice_in_dim(c, p, slot, axis=1)
        with TraceAnnotation("engine.insert", rid=rid):
            leaves, tree = jax.tree.flatten(self.cache)
            self.cache = jax.tree.unflatten(tree, [
                insert(c, p, seq) for c, p, seq in
                zip(leaves, jax.tree.leaves(prefill_cache), self._seq_axis)])
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
