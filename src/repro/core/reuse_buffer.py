"""Reuse buffer: software cache of recently accessed KV groups (KVSwap §3.4.3).

Adjacent decode steps share 75-81 % of their critical groups (paper Fig. 8 /
Tab. 5), so retaining loaded groups in fixed memory slots avoids most disk
re-reads.  Implementation matches the paper: a fixed set of slots each holding
one group, a slot table mapping slot → group id, FIFO replacement.

Slots are keyed per (layer, batch row); capacity ``C`` counts groups.
"""

from __future__ import annotations

import collections
import dataclasses
import functools

import numpy as np


@dataclasses.dataclass
class ReuseStats:
    hits: int = 0
    misses: int = 0

    @property
    def ratio(self) -> float:
        tot = self.hits + self.misses
        return self.hits / tot if tot else 0.0


def _pad_bucket(n: int) -> int:
    """Pad scatter batches to power-of-two sizes with a floor of 8, so the
    full set of shape variants is tiny ({8, 16, 32, ...}) and can be
    pre-compiled up front (:meth:`DeviceReuseMirror.prewarm`) — an XLA
    compile for a fresh miss-count shape mid-decode would cost more than
    hundreds of steady-state steps."""
    nb = 8
    while nb < n:
        nb *= 2
    return nb


@functools.lru_cache(maxsize=None)
def _scatter_fn():
    """Jitted donated scatter of newly fetched groups into the device mirror.

    Lazy so importing this module never initializes a JAX backend (host-only
    users: prefetch worker threads, tuner).  Padding rows carry ``slot ==
    capacity`` which ``mode="drop"`` discards.
    """
    import jax

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def scatter(dev_k, dev_v, idx, kv):
        # idx [2, n] = (batch_idx, slot); kv [n, G, 2, H_kv, d] packed as the
        # disk layout, one upload per fetch
        dev_k = dev_k.at[idx[0], idx[1]].set(kv[:, :, 0], mode="drop")
        dev_v = dev_v.at[idx[0], idx[1]].set(kv[:, :, 1], mode="drop")
        return dev_k, dev_v

    return scatter


class DeviceReuseMirror:
    """Device-side mirror of a :class:`ReuseBuffer`'s slot storage.

    Holds ``k/v: [B, C, G, H_kv, d]`` device arrays addressed by the *same*
    slot indices the host slot table assigns, so a :class:`MappingTable`'s
    ``slots`` array is directly a gather index into device memory.  Only
    newly fetched groups cross the host→device boundary (one padded scatter
    per fetch, donated buffers); reuse hits move zero bytes.

    ``uploaded_bytes`` counts the *payload* bytes shipped host→device (the
    groups the delta actually contains) — the transfer-counting hook the
    tests assert against.
    ``padded_bytes`` additionally includes the zero rows the pow-2 bucket
    padding ships (a batching artifact: it buys a tiny, pre-compilable set
    of scatter shapes; the padding never exceeds one bucket of slack).
    """

    def __init__(self, slots: np.ndarray, slot_table: np.ndarray | None = None):
        import jax.numpy as jnp

        # slots: host [B, C, G, 2, H_kv, d] → split K/V device mirrors.
        # At attach time the reuse buffer is normally empty (first decode
        # step after prefill): allocate zeros on device instead of shipping
        # 2·B·C·G·H_kv·d bytes of host zeros across the boundary.
        shape = slots.shape[:3] + slots.shape[4:]
        if slot_table is not None and (slot_table == -1).all():
            self.k = jnp.zeros(shape, slots.dtype)
            self.v = jnp.zeros(shape, slots.dtype)
        else:
            self.k = jnp.asarray(np.ascontiguousarray(slots[:, :, :, 0]))
            self.v = jnp.asarray(np.ascontiguousarray(slots[:, :, :, 1]))
        self.capacity = slots.shape[1]
        self._dtype = slots.dtype
        self.uploaded_bytes = 0    # payload bytes (actual delta groups)
        self.padded_bytes = 0      # payload + pow-2 bucket padding
        self.uploaded_groups = 0
        self.scatter_calls = 0

    @property
    def nbytes(self) -> int:
        return int(np.prod(self.k.shape)) * self._dtype.itemsize * 2

    def prewarm(self, max_entries: int) -> None:
        """Compile every scatter bucket size up front (all-dropped writes).

        ``max_entries`` is the most groups one fetch can insert (B·M); the
        bucket set is {8, 16, ..., pad(max_entries)}.  Costs a few hundred
        ms once per process per shape — off the measured decode path.
        """
        import jax.numpy as jnp

        g, hk, d = self.k.shape[2:]
        sizes, nb = [], 8
        while nb < max(max_entries, 1):
            sizes.append(nb)
            nb *= 2
        sizes.append(nb)
        zeros = np.zeros((nb, g, 2, hk, d), self._dtype)
        for n in sizes:
            idx = np.full((2, n), self.capacity, np.int32)  # all rows dropped
            idx[0] = 0
            self.k, self.v = _scatter_fn()(
                self.k, self.v, jnp.asarray(idx), jnp.asarray(zeros[:n]))

    def scatter(self, entries: list) -> int:
        """Write ``entries = [(batch_idx, slot, kv [G, 2, H_kv, d]), ...]``
        into the mirror in one jitted scatter.  Returns payload bytes
        uploaded (what the delta contains; bucket padding is tracked
        separately in ``padded_bytes``)."""
        if not entries:
            return 0
        import jax.numpy as jnp

        n = len(entries)
        nb = _pad_bucket(n)
        g, _, hk, d = entries[0][2].shape
        idx = np.full((2, nb), self.capacity, np.int32)   # pad rows → dropped
        idx[0] = 0
        kv_up = np.zeros((nb, g, 2, hk, d), self._dtype)
        for i, (bi, slot, kv) in enumerate(entries):
            idx[0, i], idx[1, i] = bi, slot
            kv_up[i] = kv
        self.k, self.v = _scatter_fn()(
            self.k, self.v, jnp.asarray(idx), jnp.asarray(kv_up))
        nbytes = n * int(entries[0][2].nbytes)
        self.uploaded_bytes += nbytes
        self.padded_bytes += kv_up.nbytes
        self.uploaded_groups += n
        self.scatter_calls += 1
        return nbytes


class ReuseBuffer:
    """FIFO cache of KV groups for one layer of one batched sequence set."""

    def __init__(self, *, batch: int, capacity: int, group_size: int, n_kv_heads: int, head_dim: int, dtype=np.float32):
        self.batch = batch
        self.capacity = capacity
        self.group_size = group_size
        # slot storage: [B, C, G, 2, H_kv, d]
        self.slots = np.zeros((batch, capacity, group_size, 2, n_kv_heads, head_dim), dtype=dtype)
        # slot_table[b][slot] = group id or -1
        self.slot_table = np.full((batch, capacity), -1, dtype=np.int64)
        self._fifo: list[collections.deque] = [collections.deque() for _ in range(batch)]
        self._index: list[dict[int, int]] = [dict() for _ in range(batch)]  # gid -> slot
        self._free: list[list[int]] = [list(range(capacity - 1, -1, -1)) for _ in range(batch)]
        self.stats = ReuseStats()
        # device-side mirror (attached by the engine's device-resident path)
        self.device: DeviceReuseMirror | None = None
        # eviction hook (batch_idx, group_id, kv_view) → None, called with
        # the victim's slot contents *before* they are overwritten; the warm
        # tier (repro.tiers) registers here to admit evicted groups.  Not
        # called for clear_row/invalidate — those drop state, they don't
        # demote it.
        self.victim_sink = None

    def attach_device_mirror(self) -> DeviceReuseMirror:
        """(Re)build the device mirror from the current host slot contents.

        Called once per request at the first decode step; thereafter the
        mirror is kept coherent by delta scatters of fetch misses only
        (:meth:`KVCacheManager.sync_device`)."""
        self.device = DeviceReuseMirror(self.slots, self.slot_table)
        return self.device

    @property
    def nbytes(self) -> int:
        return self.slots.nbytes + self.slot_table.nbytes

    def lookup(self, batch_idx: int, group_ids) -> tuple[list[int], list[int]]:
        """Split requested ids into (hit ids, miss ids); updates hit stats."""
        idx = self._index[batch_idx]
        hits = [g for g in group_ids if g in idx]
        misses = [g for g in group_ids if g not in idx]
        self.stats.hits += len(hits)
        self.stats.misses += len(misses)
        return hits, misses

    def get(self, batch_idx: int, group_id: int) -> np.ndarray:
        """Return the slot contents ``[G, 2, H_kv, d]`` for a resident group."""
        slot = self._index[batch_idx][group_id]
        return self.slots[batch_idx, slot]

    def slot_of(self, batch_idx: int, group_id: int) -> int | None:
        """Slot index holding ``group_id``, or ``None`` if not resident.

        Does not count as a lookup for hit/miss stats — this is the address
        query the mapping-table rebuild uses after residency is settled.
        """
        return self._index[batch_idx].get(group_id)

    def insert(self, batch_idx: int, group_id: int, kv_group: np.ndarray,
               protected: set | None = None) -> int | None:
        """Insert a loaded group (``[G, 2, H_kv, d]``); FIFO-evicts if full.

        ``protected`` pins the current step's working set: those resident
        groups are never chosen as eviction victims (the preload buffer is
        merged into the reuse buffer — paper App. A.2).  Returns the slot
        index, or ``None`` if insertion would require evicting a protected
        group (caller stages the group transiently instead).
        """
        idx = self._index[batch_idx]
        fifo = self._fifo[batch_idx]
        if group_id in idx:  # refresh in place (idempotent insert)
            slot = idx[group_id]
            self.slots[batch_idx, slot] = kv_group
            return slot
        free = self._free[batch_idx]
        if free:
            slot = free.pop()
        else:
            victim = None
            if protected:
                for cand in fifo:
                    if cand not in protected:
                        victim = cand
                        break
                if victim is None:
                    return None
                fifo.remove(victim)
            else:
                victim = fifo.popleft()
            slot = idx.pop(victim)
            self.slot_table[batch_idx, slot] = -1
            if self.victim_sink is not None:
                # demote to the warm tier while the slot bytes are intact
                self.victim_sink(batch_idx, victim, self.slots[batch_idx, slot])
        idx[group_id] = slot
        fifo.append(group_id)
        self.slot_table[batch_idx, slot] = group_id
        self.slots[batch_idx, slot] = kv_group
        return slot

    def clear_row(self, batch_idx: int) -> None:
        """Retire a batch row: drop every resident group so the next tenant
        of the slot starts cold (its first fetch misses on everything and
        reads only its own groups — the slot-recycling contract).

        Slot *data* is left in place: with the row's slot table empty no
        mapping table can reference it, and the device mirror (if attached)
        is likewise unreachable until fresh inserts scatter over it.
        """
        self._index[batch_idx].clear()
        self._fifo[batch_idx].clear()
        self._free[batch_idx] = list(range(self.capacity - 1, -1, -1))
        self.slot_table[batch_idx, :] = -1

    def invalidate(self, batch_idx: int, group_id: int) -> None:
        """Drop a group (e.g. its on-disk contents were superseded)."""
        idx = self._index[batch_idx]
        if group_id in idx:
            slot = idx.pop(group_id)
            self.slot_table[batch_idx, slot] = -1
            self._fifo[batch_idx].remove(group_id)
            self._free[batch_idx].append(slot)

    def resident(self, batch_idx: int) -> set[int]:
        return set(self._index[batch_idx].keys())
