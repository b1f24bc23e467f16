"""Paged KV cache: fixed-size page pool + page-table scatter/gather.

The continuous-batching engine keeps ONE resident serving state per
accuracy tier — the pool.  Attention-cache leaves are stored as a pool of
fixed-size **pages** of ``page_size`` token positions each, and every
request holds a *page table* (a vector of physical page ids) instead of a
whole-``max_len`` contiguous slot: a 30-token request in a 4096-max_len
tier reserves ``ceil(30/page_size)`` pages, not 4096 rows.

Layout
------
Paged leaves are shaped ``(repeats, n_pages + 1, page_size, ...)`` — the
page id replaces the batch axis of ``transformer.init_state`` and the
sequence axis shrinks to one page.  Physical page ``n_pages`` is the
**null page**: page-table entries past a request's allocation point at
it, and decode scatters for inactive pool rows land in it, so garbage can
never reach a live page.  SSM/conv states carry no sequence axis and stay
per-slot (``(repeats, n_slots, ...)``); :func:`paged_layout` records
which phases page.

Host-side accounting is split over two cheap resources:

- :class:`SlotAllocator` — decode *rows* (the batch axis of the resident
  ``decode_step``); rows are cheap, they carry no KV storage anymore.
- :class:`PageAllocator` — KV *pages*, the real capacity.  A request's
  FULL worst-case need (``prompt + max_new - 1`` positions) is reserved
  at admission; physical pages are taken lazily as the write frontier
  advances.  Reserving up front keeps admission the only failure point —
  a request mid-decode can never hit pool exhaustion.

Device-side, the decode/prefill jits move data across the page boundary:

- :func:`gather_state` assembles the dense ``(rows, max_len)`` view the
  unmodified ``transformer.decode_step`` consumes (``leaf[:, tables]``
  is one XLA gather per leaf);
- :func:`scatter_token` / :func:`scatter_chunk` write the step's freshly
  produced cache rows back through the page tables;
- :func:`write_state` installs a whole prefilled batch-1 state into a
  request's pages (the fallback for archs whose SSM state cannot chunk);
- :func:`zero_pages` re-zeroes freed pages so a reused page carries no
  bits from its previous occupant.

Their operations carry the named scope ``kv_gather``, ``kv_scatter`` or
``kv_zero`` in their op metadata, so a device trace tells them from the
model's own cache slicing (scope ``model``, set by the runner).

Bit-transparency: paging only *relocates* cache rows; gather returns the
identical values a contiguous buffer would hold, so the decode math — and
therefore the token stream — is bit-identical to solo generation
(asserted in ``tests/test_serving_numerics.py``; the differential stub
rig in ``tests/test_serving_paging.py`` proves the indirection itself).
"""
from __future__ import annotations

import bisect
import dataclasses
from typing import List, Optional


class ServingError(RuntimeError):
    """A serving-layer error with a one-line message (queue/slot/engine
    misuse) — the serving analogue of ``repro.session.SessionError``."""


@dataclasses.dataclass
class SlotAllocator:
    """Fixed-size slot pool; allocation order is lowest-free-slot-first
    (deterministic, and keeps the active prefix of the pool dense-ish)."""

    n_slots: int

    def __post_init__(self):
        if self.n_slots < 1:
            raise ServingError(
                f"slot pool needs at least 1 slot, got {self.n_slots}")
        self._owner: dict[int, str] = {}

    @property
    def n_free(self) -> int:
        return self.n_slots - len(self._owner)

    @property
    def owners(self) -> dict[int, str]:
        """slot -> request id for every occupied slot (a copy)."""
        return dict(self._owner)

    def alloc(self, request_id: str) -> int:
        """Claim the lowest free slot for ``request_id``; raises
        :class:`ServingError` when the pool is exhausted."""
        for slot in range(self.n_slots):
            if slot not in self._owner:
                self._owner[slot] = request_id
                return slot
        raise ServingError(
            f"KV pool exhausted: all {self.n_slots} slots in use "
            f"(admitting {request_id!r}); retire a request or grow the pool")

    def free(self, slot: int) -> None:
        if slot not in self._owner:
            raise ServingError(f"slot {slot} is not allocated")
        del self._owner[slot]

    def owner(self, slot: int) -> Optional[str]:
        return self._owner.get(slot)


@dataclasses.dataclass
class PageAllocator:
    """Reservation-based page accounting (host-side, deterministic).

    ``reserve(rid, n)`` claims *capacity* for a request's full worst-case
    need at admission; ``take_page(rid)`` turns one unit of that
    reservation into a physical page id as the request's write frontier
    reaches it.  Because ``sum(held) <= sum(reserved) <= n_pages`` is an
    invariant, a ``take_page`` within a live reservation can never fail —
    exhaustion is an admission-time decision only.

    Pages are handed out lowest-id-first and returned to a sorted free
    list, so allocation is deterministic under identical schedules (the
    golden/differential tests rely on this).
    """

    n_pages: int

    def __post_init__(self):
        if self.n_pages < 1:
            raise ServingError(
                f"page pool needs at least 1 page, got {self.n_pages}")
        self._free: List[int] = list(range(self.n_pages))
        self._reserved: dict[str, int] = {}   # rid -> reserved page count
        self._held: dict[str, List[int]] = {}  # rid -> physical pages taken

    @property
    def n_free_pages(self) -> int:
        """Physically unallocated pages (>= ``n_unreserved``)."""
        return len(self._free)

    @property
    def n_unreserved(self) -> int:
        """Pages not promised to any live request — what admission has
        left to hand out."""
        return self.n_pages - sum(self._reserved.values())

    @property
    def owners(self) -> dict[int, str]:
        """page -> request id for every physically held page (a copy)."""
        return {p: rid for rid, pages in self._held.items() for p in pages}

    def can_reserve(self, n: int) -> bool:
        return 1 <= n <= self.n_unreserved

    def reserve(self, request_id: str, n: int) -> None:
        if n < 1:
            raise ServingError(
                f"request {request_id!r}: page reservation must be >= 1, "
                f"got {n}")
        if request_id in self._reserved:
            raise ServingError(
                f"request {request_id!r} already holds a page reservation")
        if n > self.n_unreserved:
            raise ServingError(
                f"page pool exhausted: {request_id!r} needs {n} pages but "
                f"only {self.n_unreserved} of {self.n_pages} are unreserved")
        self._reserved[request_id] = n
        self._held[request_id] = []

    def take_page(self, request_id: str) -> int:
        held = self._held.get(request_id)
        if held is None:
            raise ServingError(
                f"request {request_id!r} has no page reservation")
        if len(held) >= self._reserved[request_id]:
            raise ServingError(
                f"request {request_id!r} exceeded its reservation of "
                f"{self._reserved[request_id]} pages")
        if not self._free:  # unreachable while the invariant holds
            raise ServingError("page pool invariant violated: reservation "
                               "honored but no physical page is free")
        page = self._free.pop(0)
        held.append(page)
        return page

    def release(self, request_id: str) -> List[int]:
        """Drop the request's reservation; returns the physical pages it
        held (callers must re-zero them before reuse, see
        :func:`zero_pages`)."""
        if request_id not in self._reserved:
            raise ServingError(
                f"request {request_id!r} has no page reservation")
        pages = self._held.pop(request_id)
        del self._reserved[request_id]
        for p in pages:
            bisect.insort(self._free, p)
        return pages


# ---------------------------------------------------------------------------
# pool pytree scatter/gather (paged transformer serving state)
# ---------------------------------------------------------------------------

def pages_for(n_positions: int, page_size: int) -> int:
    """Pages needed to hold ``n_positions`` cache rows."""
    return -(-int(n_positions) // int(page_size))


def paged_layout(cfg):
    """Which cache phases page: per segment, the frozenset of pattern
    indices whose cache carries a sequence axis (every attention kind).
    SSM/conv states are recurrent — no sequence axis — and stay
    per-slot."""
    return tuple(
        frozenset(pi for pi, spec in enumerate(pattern)
                  if spec.kind != "ssm" and spec.attn != "none")
        for _, pattern in cfg.segments)


def paged_pool_init(cfg, n_slots: int, n_pages: int, page_size: int,
                    dtype=None):
    """The resident paged pool for ``cfg``: attention-cache leaves become
    ``(repeats, n_pages + 1, page_size, ...)`` (index ``n_pages`` is the
    null page), sequence-free leaves (SSM conv/state) stay per-slot
    ``(repeats, n_slots, ...)``."""
    import jax.numpy as jnp

    from repro.models import transformer

    if cfg.encoder_layers:
        raise ServingError(
            f"{cfg.arch_id}: encoder-decoder archs are not servable by the "
            f"token-only paged pool (requests carry no encoder inputs)")
    if page_size < 1:
        raise ServingError(f"page_size must be >= 1, got {page_size}")
    if n_pages < 1:
        raise ServingError(f"page pool needs at least 1 page, got {n_pages}")
    dt = jnp.dtype(dtype or cfg.dtype)
    layout = paged_layout(cfg)
    # templates: one init_state per storage granularity, picked per phase
    paged_tpl = transformer.init_state(cfg, n_pages + 1, page_size, dtype=dt)
    slot_tpl = transformer.init_state(cfg, n_slots, 1, dtype=dt)
    return {"layers": [
        {pi: (pseg[pi] if pi in layout[si] else sseg[pi]) for pi in pseg}
        for si, (pseg, sseg) in enumerate(zip(paged_tpl["layers"],
                                              slot_tpl["layers"]))
    ]}


def _map_pairs(pool, layout, dense, paged_fn, slot_fn):
    """Map ``paged_fn(pool_leaf, dense_leaf)`` over paged phases and
    ``slot_fn`` over per-slot phases, leaf-wise."""
    import jax

    return {"layers": [
        {pi: jax.tree.map(paged_fn if pi in layout[si] else slot_fn,
                          pseg[pi], dseg[pi])
         for pi in pseg}
        for si, (pseg, dseg) in enumerate(zip(pool["layers"],
                                              dense["layers"]))
    ]}


def gather_state(pool, layout, tables):
    """Assemble the dense decode view: for page tables ``(rows,
    max_pages)`` int32 the paged leaves become ``(repeats, rows,
    max_pages * page_size, ...)`` — exactly the contiguous state
    ``transformer.decode_step`` consumes.  Table entries pointing at the
    null page contribute zeros (causally masked away by the decode
    math).  Per-slot leaves pass through untouched (their batch axis IS
    the row set)."""
    import jax

    def g(leaf):
        x = leaf[:, tables]  # (repeats, rows, max_pages, page_size, ...)
        s = x.shape
        return x.reshape(s[0], s[1], s[2] * s[3], *s[4:])

    with jax.named_scope("kv_gather"):
        return {"layers": [
            {pi: (jax.tree.map(g, seg[pi]) if pi in layout[si] else seg[pi])
             for pi in seg}
            for si, seg in enumerate(pool["layers"])
        ]}


def scatter_token(pool, layout, dense, tables, pos, page_size: int):
    """Write one decode step back: for every row, the cache row the step
    produced at ``pos[row]`` of the dense state lands in page
    ``tables[row, pos // page_size]`` at offset ``pos % page_size``.
    Inactive rows carry null page tables, so their (garbage) row lands in
    the null page.  Per-slot leaves are replaced wholesale by the new
    dense leaves (``decode_step`` already advanced them in place)."""
    import jax
    import jax.numpy as jnp

    def upd(pl, dl):
        idx = pos.reshape((1, -1, 1) + (1,) * (dl.ndim - 3))
        val = jnp.take_along_axis(dl, idx, axis=2)[:, :, 0]
        return pl.at[:, pidx, off].set(val.astype(pl.dtype))

    with jax.named_scope("kv_scatter"):
        pidx = jnp.take_along_axis(tables, (pos // page_size)[:, None],
                                   axis=1)[:, 0]
        off = pos % page_size
        return _map_pairs(pool, layout, dense, upd, lambda pl, dl: dl)


def scatter_chunk(pool, layout, dense, table_row, start, length: int,
                  page_size: int):
    """Write one prefill chunk back (batch-1 path): dense positions
    ``[start, start + length)`` land through ``table_row`` (one page
    table, ``(max_pages,)`` int32).  ``length`` is static per compiled
    chunk shape; ``start`` may be traced.  Only valid for fully paged
    layouts (chunked prefill is disabled for SSM hybrids)."""
    import jax
    import jax.numpy as jnp

    def upd(pl, dl):
        val = jax.lax.dynamic_slice_in_dim(dl, start, length, axis=2)[:, 0]
        return pl.at[:, pidx, off].set(val.astype(pl.dtype))

    def slot_leaf(pl, dl):  # unreachable under chunked layouts
        return pl

    with jax.named_scope("kv_scatter"):
        pvec = start + jnp.arange(length)
        pidx = table_row[pvec // page_size]
        off = pvec % page_size
        return _map_pairs(pool, layout, dense, upd, slot_leaf)


def write_state(pool, layout, state, slot, table_row, page_size: int):
    """Install a whole prefilled batch-1 serving state: paged leaves
    scatter every buffered position ``[0, L_buf)`` through ``table_row``;
    per-slot leaves (SSM conv/state) write row ``slot``.  This is the
    whole-prompt fallback for archs whose recurrent state cannot be
    chunk-prefilled; ``L_buf`` must not exceed the positions covered by
    ``table_row``'s live entries."""
    import jax.numpy as jnp

    def upd(pl, dl):
        n_buf = dl.shape[2]
        pvec = jnp.arange(n_buf)
        return pl.at[:, table_row[pvec // page_size],
                     pvec % page_size].set(dl[:, 0].astype(pl.dtype))

    def srow(pl, dl):
        return pl.at[:, slot].set(dl[:, 0].astype(pl.dtype))

    return _map_pairs(pool, layout, state, upd, srow)


def zero_pages(pool, layout, pages):
    """Re-zero freed pages so the next occupant starts from the same
    all-zeros state a fresh pool would give it — no bits leak across
    requests (the stale-bit property of ``tests/test_serving_paging.py``)."""
    import jax
    import jax.numpy as jnp

    def z(leaf):
        return leaf.at[:, idx].set(jnp.zeros((), leaf.dtype))

    with jax.named_scope("kv_zero"):
        idx = jnp.asarray(pages, jnp.int32)
        return {"layers": [
            {pi: (jax.tree.map(z, seg[pi]) if pi in layout[si] else seg[pi])
             for pi in seg}
            for si, seg in enumerate(pool["layers"])
        ]}
