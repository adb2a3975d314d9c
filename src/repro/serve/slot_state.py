"""Per-slot decode-state adapters: one slot lifecycle, many state shapes.

The continuous-batching scheduler (serve/scheduler.py) manages *slots* — it
admits a request into a slot, advances it every tick, preempts it, audits it,
and evicts it.  What a slot's device state *is* differs by architecture:

===============  ==========================================================
adapter          per-slot device state
===============  ==========================================================
DenseKVState     a ``max_len`` slice of each layer's (B, S, H, D) K/V slab
                 plus a per-slot ``len`` scalar (``{"k","v","len"}`` nodes)
PagedKVState     a page-table row into a shared K/V pool plus ``len``
                 (``{"k","v","page_table","len"}`` nodes, serve/paging.py)
RecurrentState   a fixed-size recurrence row — Mamba ``{"h","conv"}``,
                 RWKV6 ``{"s","shift"}`` / channel-mix ``{"shift"}`` under
                 block-cache keys ``"ssm"``/``"cm"`` — constant in sequence
                 length (nn/ssm.py)
CrossAttnState   projected encoder K/V rows written once at admission —
                 ``{"xk","xv","xlen"}`` under block-cache key ``"xkv"``
                 (nn/attention.py init_cross_cache)
===============  ==========================================================

The scheduler never branches on architecture: the whole-cache-tree operations
below (``evict_cache_slot``, ``admit_cache_slot``, ``merge_inactive`` …) walk
the cache once and dispatch per node kind, so a hybrid model (jamba:
attention + mamba layers) gets every lifecycle event applied to every kind of
state it carries.  All operations are jit-friendly pure functions over the
cache pytree and ride the scheduler's existing donation paths — applying one
never changes the tree's structure, only leaf values.

Lifecycle contract (what each adapter must support):

* ``init_state`` — build the per-slot nodes (``model.init_cache`` with
  ``per_slot_len=True``; the adapters only *describe* the nodes).
* ``admit_write`` — install a prefilled batch-1 state into a slot (one-shot
  admission) or accept in-place chunk writes (chunked admission).
* ``evict`` — O(1) slot teardown: the slot's state becomes inert (KV ``len``
  and cross ``xlen`` to 0; recurrent rows zeroed) without touching other
  slots.
* ``preempt_pack`` / ``resume_unpack`` — park/restore state across a
  preemption.  Paged KV swaps page contents host-side; recurrent and dense
  states only support recompute preemption (re-prefill the continuation).
* ``audit_check`` — host-side invariants over the device state
  (serve/audit.py hosts the checkers; the per-tick auditor calls them).
* ``bytes_per_slot`` — the state's per-slot device footprint, the
  quality-vs-memory number serve_bench reports (recurrent state is constant
  in sequence length; KV grows linearly).

The scan-stacked layer axis (nn/transformer.py ``Stack``) is handled here,
outside the model: stacked leaves carry a leading layer dim, detected per
node (``len``/``xlen`` rank for KV/cross, leaf rank vs ``REC_BASE_RANK`` for
recurrent rows).
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.nn.attention import (copy_kv_page, gather_pool_pages,
                                reset_kv_slot, scatter_pool_pages,
                                set_kv_slot_len, set_page_entry, set_page_row,
                                write_kv_slot)

#: Unstacked rank of each recurrent-state leaf (nn/ssm.py ``init_state``):
#: ``h`` (B, d_inner, N), ``conv`` (B, K-1, d_inner), ``s`` (B, H, N, N),
#: ``shift`` (B, 1, D).  A leaf one rank higher carries the scan-stacked
#: layer axis in front and its slot axis is axis 1.
REC_BASE_RANK: Dict[str, int] = {"h": 3, "conv": 3, "s": 4, "shift": 3}


# --------------------------------------------------------------------------
# Node predicates
# --------------------------------------------------------------------------

def _is_kv(node) -> bool:
    return isinstance(node, dict) and "k" in node and "len" in node


def _is_xkv(node) -> bool:
    return isinstance(node, dict) and "xk" in node and "xlen" in node


def _is_recurrent(node) -> bool:
    if not isinstance(node, dict) or not node:
        return False
    return set(node) <= set(REC_BASE_RANK)


def _rec_slot_axis(key: str, leaf) -> int:
    """Slot axis of one recurrent leaf: 1 under a scan-stacked layer dim."""
    return 1 if jnp.ndim(leaf) == REC_BASE_RANK[key] + 1 else 0


def _find_paged_kv(cache):
    """First per-layer KV dict carrying a page table, or None (dense cache).

    Every layer shares one logical page assignment (the allocator hands out
    pool indices per request, not per layer), so auditing a single layer's
    table/lens audits them all."""
    found: List[Any] = []

    def rec(node):
        if found:
            return
        if _is_kv(node):
            if "page_table" in node:
                found.append(node)
            return
        if isinstance(node, dict):
            for v in node.values():
                rec(v)
        elif isinstance(node, (list, tuple)):
            for v in node:
                rec(v)

    rec(cache)
    return found[0] if found else None


def find_recurrent_nodes(cache) -> List[Dict[str, Any]]:
    """Every recurrent-state dict in a cache tree, in traversal order."""
    out: List[Dict[str, Any]] = []

    def rec(node):
        if _is_kv(node) or _is_xkv(node):
            return
        if _is_recurrent(node):
            out.append(node)
            return
        if isinstance(node, dict):
            for v in node.values():
                rec(v)
        elif isinstance(node, (list, tuple)):
            for v in node:
                rec(v)

    rec(cache)
    return out


def find_cross_nodes(cache) -> List[Dict[str, Any]]:
    """Every cross-attention ``xkv`` dict in a cache tree, traversal order."""
    out: List[Dict[str, Any]] = []

    def rec(node):
        if _is_kv(node):
            return
        if _is_xkv(node):
            out.append(node)
            return
        if isinstance(node, dict):
            for v in node.values():
                rec(v)
        elif isinstance(node, (list, tuple)):
            for v in node:
                rec(v)

    rec(cache)
    return out


# --------------------------------------------------------------------------
# Whole-cache-tree walkers (per-layer primitives live in nn/attention.py
# and nn/ssm.py; these apply one lifecycle event across every state node)
# --------------------------------------------------------------------------

def _map_slot_op(cache, fn, rec_fn=None, xkv_fn=None):
    """Apply ``fn(kv_dict, layer_axis)`` to every per-layer KV dict in a
    Stack cache tree ({'prelude': [...], 'body': [...]}, scan-stacked leaves
    carry a leading layer dim).  ``rec_fn(state_dict)`` / ``xkv_fn(node)``
    extend the walk to recurrent and cross-attention nodes (None leaves
    them untouched — the pre-adapter behavior)."""
    def rec(node):
        if _is_kv(node):
            return fn(node, jnp.ndim(node["len"]) == 2)
        if _is_xkv(node):
            return xkv_fn(node) if xkv_fn is not None else node
        if _is_recurrent(node):
            return rec_fn(node) if rec_fn is not None else node
        if isinstance(node, dict):
            return {k: rec(v) for k, v in node.items()}
        if isinstance(node, (list, tuple)):
            return type(node)(rec(v) for v in node)
        return node
    return rec(cache)


def _map_slot_op2(big, small, fn, rec_fn=None):
    """Same walk over two structurally identical cache trees."""
    def rec(b, s):
        if _is_kv(b):
            return fn(b, s, jnp.ndim(b["len"]) == 2)
        if _is_xkv(b):
            return b
        if _is_recurrent(b):
            return rec_fn(b, s) if rec_fn is not None else b
        if isinstance(b, dict):
            return {k: rec(v, s[k]) for k, v in b.items()}
        if isinstance(b, (list, tuple)):
            return type(b)(rec(bb, ss) for bb, ss in zip(b, s))
        return b
    return rec(big, small)


def _zero_recurrent_slot(state: Dict[str, Any], slot) -> Dict[str, Any]:
    """Zero one slot's row in every leaf of a recurrent-state dict.

    A zeroed row is the adapter's *inert* state: admission starts every
    recurrence from zeros (nn/ssm.py ``init_state``), so an evicted slot is
    indistinguishable from a never-used one — the auditor's dead-slot
    invariant (serve/audit.py ``check_recurrent_rows``).
    """
    out: Dict[str, Any] = {}
    for k, v in state.items():
        if v is None:
            out[k] = v
            continue
        ax = _rec_slot_axis(k, v)
        shape = list(v.shape)
        shape[ax] = 1
        out[k] = jax.lax.dynamic_update_slice_in_dim(
            v, jnp.zeros(shape, v.dtype), slot, axis=ax)
    return out


def _scatter_recurrent_slot(big: Dict[str, Any], small: Dict[str, Any],
                            slot) -> Dict[str, Any]:
    """Write a batch-1 recurrent state into ``slot`` of the per-slot state
    (the one-shot admission copy; chunked admission writes in place via the
    mixers' ``chunk`` path instead)."""
    out: Dict[str, Any] = {}
    for k, v in big.items():
        if v is None:
            out[k] = v
            continue
        ax = _rec_slot_axis(k, v)
        out[k] = jax.lax.dynamic_update_slice_in_dim(
            v, small[k].astype(v.dtype), slot, axis=ax)
    return out


def _reset_xkv_slot(node: Dict[str, Any], slot) -> Dict[str, Any]:
    """Evict one slot of a cross-attention cache: ``xlen[slot] = 0``.

    The projected ``xk``/``xv`` rows are left for overwrite (consumers mask
    on ``xlen``, exactly like KV ``len``) — eviction stays O(1)."""
    xl = node["xlen"]
    if jnp.ndim(xl) == 2:     # scan-stacked (L, slots)
        upd = jnp.zeros((xl.shape[0], 1), jnp.int32)
        xl = jax.lax.dynamic_update_slice(xl, upd, (jnp.int32(0), slot))
    else:
        xl = jax.lax.dynamic_update_slice(
            xl, jnp.zeros((1,), jnp.int32), (slot,))
    return dict(node, xlen=xl)


def admit_cache_slot(big_cache, small_cache, slot, length):
    """Write a batch-1 prefilled cache into ``slot`` of the per-slot cache.

    KV nodes block-copy ``length`` rows (``write_kv_slot``); recurrent nodes
    scatter the batch-1 state row (the whole recurrence fits one row, so
    ``length`` does not apply); cross-attention nodes pass through (EncDec
    one-shot admission is rejected at Scheduler construction).
    """
    return _map_slot_op2(
        big_cache, small_cache,
        lambda b, s, la: write_kv_slot(b, s, slot, length, layer_axis=la),
        rec_fn=lambda b, s: _scatter_recurrent_slot(b, s, slot))


def evict_cache_slot(cache, slot):
    """O(1) per-slot eviction across every state kind.

    KV: live length to zero, rows left for overwrite (paged caches
    additionally unmap the slot's page-table row; the host-side allocator
    reclaims the pages — Scheduler.run's ``finish``).  Recurrent: the slot's
    state rows are zeroed (a fresh admission must start its recurrence from
    zeros — there is no ``len`` mask to hide stale rows behind).
    Cross-attention: ``xlen`` to zero.
    """
    return _map_slot_op(
        cache, lambda kv, la: reset_kv_slot(kv, slot, layer_axis=la),
        rec_fn=lambda st: _zero_recurrent_slot(st, slot),
        xkv_fn=lambda node: _reset_xkv_slot(node, slot))


def merge_inactive(old_cache, new_cache, active):
    """Keep inactive slots' recurrent rows at their pre-step values.

    KV state tolerates batched steps running *every* row (junk appends land
    at rows >= ``len`` and are overwritten on admission), but a recurrence
    has no position axis to hide behind: one masked decode step through a
    dead or mid-prefill slot advances its state with a pad token and
    corrupts it.  This merge — ``where(active, stepped, previous)`` per slot
    row — restores every inactive row after the batched step, making the
    recurrent adapter's lifecycle identical to KV's.  KV and cross nodes
    pass through unchanged (structure preservation under donation).
    """
    act = jnp.asarray(active)

    def merge_rec(o: Dict[str, Any], n: Dict[str, Any]) -> Dict[str, Any]:
        out: Dict[str, Any] = {}
        for k, v in n.items():
            if v is None:
                out[k] = v
                continue
            ax = _rec_slot_axis(k, v)
            shape = [1] * v.ndim
            shape[ax] = v.shape[ax]
            out[k] = jnp.where(act.reshape(shape), v, o[k])
        return out

    def rec(o, n):
        if _is_kv(n) or _is_xkv(n):
            return n
        if _is_recurrent(n):
            return merge_rec(o, n)
        if isinstance(n, dict):
            return {k: rec(o[k], v) for k, v in n.items()}
        if isinstance(n, (list, tuple)):
            return type(n)(rec(oo, nn) for oo, nn in zip(o, n))
        return n
    return rec(old_cache, new_cache)


def set_cache_page_row(cache, slot, row):
    """Install a page-table row for ``slot`` in every layer of a paged cache
    tree (all layers share one logical page assignment — the allocator hands
    out pool indices once per request, not per layer)."""
    return _map_slot_op(
        cache, lambda kv, la: set_page_row(kv, slot, row, layer_axis=la))


def copy_cache_page(cache, src, dst):
    """Copy pool page ``src`` onto ``dst`` in every layer of a paged cache
    tree — the device half of copy-on-write (the host half is the refcount
    bookkeeping in serve/paging.py)."""
    return _map_slot_op(
        cache, lambda kv, la: copy_kv_page(kv, src, dst, layer_axis=la))


def set_cache_page_entry(cache, slot, idx, page):
    """``page_table[slot, idx] = page`` in every layer of a paged cache tree
    — the lazy decode-growth append (oversubscription)."""
    return _map_slot_op(
        cache, lambda kv, la: set_page_entry(kv, slot, idx, page,
                                             layer_axis=la))


def gather_cache_pages(cache, pages):
    """Swap-out gather: read pool pages ``pages`` out of every layer's K/V
    pools.  Returns a list of ``{"k", "v"}`` page stacks in the cache tree's
    deterministic traversal order (``scatter_cache_pages`` consumes the same
    order) — the cache itself is not modified."""
    out = []

    def op(kv, la):
        out.append(gather_pool_pages(kv, pages, layer_axis=la))
        return kv

    _map_slot_op(cache, op)
    return out


def scatter_cache_pages(cache, pages, data):
    """Swap-in restore: write ``gather_cache_pages`` data back into pool
    pages ``pages`` of every layer (same traversal order)."""
    it = iter(data)
    return _map_slot_op(
        cache, lambda kv, la: scatter_pool_pages(kv, pages, next(it),
                                                 layer_axis=la))


def set_cache_slot_len(cache, slot, length):
    """Set ``len[slot] = length`` in every layer of a per-slot cache tree.

    Prefix-sharing admission starts a slot at its shared-prefix length so
    the decode half's per-tick junk append for the still-prefilling slot
    lands in the slot's private divergence region — at len 0 it would write
    through the shared prefix mapping (see Scheduler admission).
    """
    def op(kv, la):
        ln = kv["len"]
        if la:
            upd = jnp.full((ln.shape[0], 1), length, jnp.int32)
            ln = jax.lax.dynamic_update_slice_in_dim(ln, upd, slot, axis=1)
        else:
            ln = set_kv_slot_len(ln, slot, length)
        return dict(kv, len=ln)

    return _map_slot_op(cache, op)


# --------------------------------------------------------------------------
# State-kind discovery and per-kind byte accounting
# --------------------------------------------------------------------------

def _model_blocks(model) -> List[Any]:
    """Every decode-path Block of a model (CausalLM stack / EncDec decoder)."""
    stacks = []
    if hasattr(model, "stack"):
        stacks.append(model.stack)
    if hasattr(model, "decoder"):
        stacks.append(model.decoder)
    blocks: List[Any] = []
    for st in stacks:
        blocks.extend(st.prelude)
        blocks.extend(st.body)
    return blocks


def mixer_kinds(model) -> Tuple[str, ...]:
    """The mixers of a model's decode blocks ("attn", "mamba", "rwkv")."""
    return tuple(sorted({b.mixer for b in _model_blocks(model)}))


def state_kinds(model) -> Tuple[str, ...]:
    """The per-slot state kinds a model serves with, in canonical order.

    ``"kv"`` — attention mixers (dense or paged self-attention K/V);
    ``"recurrent"`` — Mamba/RWKV mixers (fixed-size recurrence rows);
    ``"cross"`` — an EncDec decoder with a sized cross-attention cache
    (``enc_len`` set).  A hybrid (jamba) reports ``("kv", "recurrent")``.
    """
    blocks = _model_blocks(model)
    kinds: List[str] = []
    if any(b.mixer == "attn" for b in blocks):
        kinds.append("kv")
    if any(b.mixer in ("mamba", "rwkv") for b in blocks):
        kinds.append("recurrent")
    if hasattr(model, "encode") and getattr(model, "enc_len", None) \
            and any(getattr(b, "cross", False) for b in blocks):
        kinds.append("cross")
    return tuple(kinds)


def _bytes_where(cache, pred) -> int:
    """Total leaf bytes of the cache-tree nodes matching ``pred`` (runs on
    concrete arrays or ``jax.eval_shape`` structs alike)."""
    total = 0

    def rec(node):
        nonlocal total
        if pred(node):
            total += sum(l.size * l.dtype.itemsize
                         for l in jax.tree_util.tree_leaves(node))
            return
        if _is_kv(node) or _is_xkv(node) or _is_recurrent(node):
            return
        if isinstance(node, dict):
            for v in node.values():
                rec(v)
        elif isinstance(node, (list, tuple)):
            for v in node:
                rec(v)

    rec(cache)
    return total


def state_bytes_per_slot(cache, slots: int) -> Dict[str, int]:
    """Per-slot device bytes of each state kind present in ``cache``.

    The serving-memory comparison serve_bench's hetero bench reports:
    recurrent rows are constant in sequence length while KV slabs grow with
    ``max_len`` (paged pools amortize across slots — the pool's share is
    reported per slot).  ``cache`` may be a ``jax.eval_shape`` tree.
    """
    n = max(slots, 1)
    return {"kv": _bytes_where(cache, _is_kv) // n,
            "recurrent": _bytes_where(cache, _is_recurrent) // n,
            "cross": _bytes_where(cache, _is_xkv) // n}


# --------------------------------------------------------------------------
# Adapters: the documented per-kind lifecycle contract
# --------------------------------------------------------------------------

class SlotState:
    """Abstract per-slot state adapter: one state shape, full lifecycle.

    Concrete adapters bundle the walker operations above per state kind.
    The scheduler itself calls the *composite* walkers (one tree walk per
    lifecycle event handles every kind at once); the adapters are the
    contract surface — what tests pin down, what the auditor checks, and
    what docs/serving.md documents per architecture.
    """

    kind: str = "abstract"

    def evict(self, cache, slot):
        """Make ``slot`` inert without touching other slots (O(1))."""
        return evict_cache_slot(cache, slot)

    def admit_write(self, big_cache, small_cache, slot, length):
        """Install a batch-1 prefilled state into ``slot``."""
        return admit_cache_slot(big_cache, small_cache, slot, length)

    def preempt_pack(self, cache, pages):
        """Read the parkable device state out (swap preemption), or raise."""
        raise NotImplementedError(
            f"{self.kind} state does not support swap parking — use "
            f"recompute preemption (the continuation re-prefills)")

    def resume_unpack(self, cache, pages, data):
        """Restore ``preempt_pack`` data into the cache."""
        raise NotImplementedError(
            f"{self.kind} state does not support swap parking — use "
            f"recompute preemption (the continuation re-prefills)")

    def audit_check(self, cache, live: Dict[int, int]) -> None:
        """Assert this kind's device invariants (serve/audit.py checkers)."""

    def bytes_per_slot(self, cache, slots: int) -> int:
        """Per-slot device bytes of this kind's state in ``cache``."""
        return state_bytes_per_slot(cache, slots).get(
            self.kind.split("-")[0], 0)


class DenseKVState(SlotState):
    """Dense per-slot K/V slabs with a per-slot ``len`` vector."""

    kind = "kv"


class PagedKVState(DenseKVState):
    """Paged K/V: shared pool + per-slot page tables (serve/paging.py).

    The only adapter with a swap path: private page contents gather/scatter
    host-side while shared prefix pages stay resident under refcount.
    """

    kind = "kv-paged"

    def preempt_pack(self, cache, pages):
        """Gather pool pages ``pages`` (swap-out; cache unmodified)."""
        return gather_cache_pages(cache, pages)

    def resume_unpack(self, cache, pages, data):
        """Scatter swapped page data back into pool pages ``pages``."""
        return scatter_cache_pages(cache, pages, data)

    def audit_check(self, cache, live: Dict[int, int]) -> None:
        """Page-table invariants run via serve/audit.py check_page_tables
        (the scheduler wires allocator state in; nothing extra here)."""


class RecurrentState(SlotState):
    """Fixed-size recurrence rows (Mamba/RWKV): constant bytes per slot.

    Admission writes the whole row (one-shot scatter or in-place chunk
    scatter via the mixers' ``chunk`` path); eviction zeroes it; batched
    steps must run under ``merge_inactive`` so masked slots never advance.
    Preemption is recompute-only — the row is tiny but *sufficient*, so
    re-prefilling the continuation is cheaper than a swap protocol.
    """

    kind = "recurrent"

    def audit_check(self, cache, live: Dict[int, int]) -> None:
        """Dead slots' rows must be exactly zero (inert)."""
        from repro.serve.audit import check_recurrent_rows

        check_recurrent_rows(cache, set(live))


class CrossAttnState(SlotState):
    """Per-slot projected cross-attention K/V (EncDec serving).

    Written once per admission (``EncDecLM.write_cross_kv``) and read every
    decode step — the FLOPs trade that replaces re-projecting ``enc`` each
    tick.  Eviction zeroes ``xlen``; rows are overwritten on readmission.
    """

    kind = "cross"

    def audit_check(self, cache, live: Dict[int, int]) -> None:
        """Live slots' ``xlen`` must equal their encoder length; dead 0."""
        from repro.serve.audit import check_cross_lens

        check_cross_lens(cache, live)


def adapters_for(model, *, paged: bool = False,
                 cross_attn_cache: bool = True) -> Tuple[SlotState, ...]:
    """The adapter set a scheduler composes for ``model``.

    ``paged`` picks :class:`PagedKVState` over :class:`DenseKVState` for the
    ``"kv"`` kind; ``cross_attn_cache=False`` drops :class:`CrossAttnState`
    (the engine recomputes cross-attention from ``enc`` every step — the
    bench baseline).
    """
    out: List[SlotState] = []
    for kind in state_kinds(model):
        if kind == "kv":
            out.append(PagedKVState() if paged else DenseKVState())
        elif kind == "recurrent":
            out.append(RecurrentState())
        elif kind == "cross" and cross_attn_cache:
            out.append(CrossAttnState())
    return tuple(out)
