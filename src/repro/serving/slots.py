"""Slot-based KV/SSM cache manager for the continuous-batching engine.

The decode batch is *persistent*: one pytree of model state with
``n_slots`` batch rows (see :func:`repro.models.init_slot_state` — KV cache
indices are per-row so every slot advances independently).  Requests are
prefilled on a detached batch-1 state and then *adopted* into a free slot
(a jitted per-row scatter); finished requests release their slot, which is
immediately reusable.  The jitted decode step therefore always sees the
same static shape — admission and eviction never trigger recompilation.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core import events as _ev
from repro.models import init_slot_state
from repro.models.attention import KVCache

__all__ = ["SlotCacheManager"]


@functools.partial(jax.jit, donate_argnums=(0,))
def _adopt(big, small, slot):
    """Scatter a batch-1 state pytree into row ``slot`` of the slot-batched
    state.  KV-cache ``idx`` leaves are (n_rep,) in ``small`` (scalar per
    repeat) but (n_rep, n_slots) in ``big``; every other leaf carries the
    batch axis at position 1."""

    def put(b, s):
        if s.ndim == b.ndim:
            return b.at[:, slot].set(s[:, 0])
        return b.at[:, slot].set(s)

    return jax.tree.map(put, big, small)


@functools.partial(jax.jit, donate_argnums=(0,))
def _reset_slot(big, slot):
    """Zero a released slot's cache index.  While the slot stays free its
    idx still drifts (+1 per decode step, like every row); that is
    harmless — cache writes past the buffer edge are dropped and the next
    adopt overwrites the whole row — but resetting here keeps the drift
    from accumulating across occupancies."""

    def fix(leaf):
        if isinstance(leaf, KVCache):
            return KVCache(k=leaf.k, v=leaf.v,
                           idx=leaf.idx.at[:, slot].set(0))
        return leaf

    return jax.tree.map(fix, big, is_leaf=lambda x: isinstance(x, KVCache))


class SlotCacheManager:
    """Owns the persistent decode-batch state plus per-slot host mirrors.

    ``pos[slot]`` is the number of valid context tokens in the slot (the
    rope/cache offset of the *next* token); ``last_token[slot]`` is the most
    recently sampled token, i.e. the next decode-step input.
    """

    def __init__(self, cfg: ModelConfig, n_slots: int, max_seq: int):
        if n_slots < 1:
            raise ValueError("need at least one slot")
        self.cfg = cfg
        self.n_slots = n_slots
        self.max_seq = max_seq
        self.state = init_slot_state(cfg, n_slots, max_seq)
        self.pos = np.zeros(n_slots, dtype=np.int32)
        self.last_token = np.zeros(n_slots, dtype=np.int32)
        self._free = list(range(n_slots - 1, -1, -1))  # pop() -> lowest id

    @property
    def n_free(self) -> int:
        return len(self._free)

    @property
    def n_active(self) -> int:
        return self.n_slots - self.n_free

    def allocate(self) -> Optional[int]:
        """Reserve a slot (lowest id first, deterministic); None when full."""
        if not self._free:
            return None
        return self._free.pop()

    def adopt(self, slot: int, small_state, n_context: int,
              last_token: int) -> None:
        """Install a prefilled batch-1 state into ``slot`` and arm the row
        for decoding (``n_context`` prompt tokens consumed, ``last_token``
        already sampled from the prefill logits)."""
        if not 0 <= slot < self.n_slots:
            raise IndexError(f"slot {slot} out of range")
        if n_context + 1 > self.max_seq:
            raise ValueError(
                f"context {n_context} leaves no room in max_seq {self.max_seq}")
        with _ev.span("engine.adopt"):
            self.state = _adopt(self.state, small_state,
                                jnp.asarray(slot, jnp.int32))
        self.pos[slot] = n_context
        self.last_token[slot] = last_token

    def release(self, slot: int) -> None:
        """Return a slot to the free list (its cache rows become dead)."""
        if slot in self._free:
            raise ValueError(f"slot {slot} is already free")
        with _ev.span("engine.release"):
            self.state = _reset_slot(self.state, jnp.asarray(slot, jnp.int32))
        self.pos[slot] = 0
        self.last_token[slot] = 0
        self._free.append(slot)
        self._free.sort(reverse=True)  # keep lowest-id-first determinism
