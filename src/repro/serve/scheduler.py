"""Continuous-batching scheduler: per-slot decode-state lifecycle over
jitted steps.

The lockstep ``ServeEngine.generate()`` runs every slot for a fixed horizon —
fine for tests, hopeless under traffic: a slot that finishes early idles until
the whole batch restarts.  This module adds the real serving policy on top of
the same jitted prefill/decode steps:

* a **request queue** (prompt, max_new, arrival order);
* **per-slot state** (live length, active flag, EOS hit) — the cache carries
  an int32 ``len`` *vector* (``per_slot_len=True``), so every slot advances
  and masks independently (nn/attention.py, kernels/qdecode_attn.py);
* **admission**, two policies:

  - *one-shot* (``chunk_size=None``): a freed slot is refilled by a
    slot-targeted prefill — the prompt runs through a fresh batch-1 cache,
    then ``write_kv_slot`` copies that cache into the slot's KV slice.  The
    prefill is a stop-the-world dispatch: every live decode slot stalls for
    the full prompt length, and each distinct (bucketed) prompt length costs
    a jit compile.
  - *chunked* (``chunk_size=C``): each tick runs ONE fused jitted mixed step
    (``engine.make_mixed_step``) = all live decode slots plus one C-token
    chunk of the oldest queued prompt, written **in place** into the target
    slot's KV slice (``append_kv_chunk`` / the fused ``qchunk_attn`` Pallas
    kernel for int8 caches).  No batch-1 scratch cache, no copy, one compile
    shape for every prompt length, and decode slots never stall more than
    one chunk — the admission-tail-latency fix.  ``token_budget`` caps the
    per-tick token count (live slots + C): when live decode alone exceeds
    it, the chunk waits (decode tokens are never dropped);

* **paged KV** (``ServeEngine(paged_kv=True)``): the per-slot cache becomes
  a shared page pool + per-slot page tables (nn/attention.py), and the
  scheduler runs a host-side block allocator (serve/paging.py): admission
  allocates ``ceil(extent / page_size)`` pages and installs the slot's
  page-table row; page exhaustion *defers* the admission in the queue
  (composing with the ``token_budget`` stall, decode never waits); eviction
  returns the pages.  Requires chunked admission — docs/serving.md has the
  full geometry;
* **prefix sharing** (paged, default on): an admission whose prompt prefix
  matches resident pages (serve/paging.py ``PrefixIndex``) maps them into
  its own table (refcounted), prefills only from the divergence point, and
  privatizes a shared divergence page by copy-on-write before any write —
  N same-system-prompt requests hold one copy of the prefix, the
  per-pool-byte capacity win serve_bench gates;
* **oversubscription** (``oversubscribe=True``, paged only): admission
  reserves only the prompt-covering pages instead of the full
  ``prompt+max_new`` extent; decode *grows* each slot's page-table row one
  page at a time as its live length crosses page boundaries (the
  ``set_page_entry`` jitted update).  When growth finds the pool empty the
  scheduler **preempts** a victim — least decode progress first, most
  recent admission breaking ties, with an aging bound so no request is
  starved by repeated eviction.  ``preempt_policy="recompute"`` harvests
  the victim's generated tokens and re-queues it as a continuation prompt
  (prompt + generated so far) re-prefilled through the chunked path;
  ``"swap"`` copies its *private* pages to a host-side ``SwapArea``
  (shared prefix pages stay resident under their refcount) and restores
  them as soon as a slot and pages free up.  Both policies keep greedy
  decode token-identical to the unpreempted run;
* **EncDec serving** (chunked only): each request carries its encoder
  output (``Request.enc``); the scheduler keeps a per-slot encoder buffer
  and threads it through the jitted decode/mixed steps, so every slot
  cross-attends its own context.  With ``engine.cross_attn_cache`` (the
  default) admission additionally projects the request's cross-attention
  K/V once into the slot's ``xkv`` rows (``EncDecLM.write_cross_kv``), so
  decode steps skip the per-tick re-projection entirely;
* **recurrent-state serving** (SSM/RWKV, chunked or one-shot): models whose
  layers carry fixed-size recurrence rows instead of (or alongside) KV
  serve through the same loop — the slot lifecycle is dispatched per state
  *kind* by the slot-state walkers (serve/slot_state.py), and batched steps
  run under an inactive-merge barrier so masked slots never advance their
  recurrence (a junk token through a dead KV row is masked by ``len``; a
  junk token through a recurrence corrupts it).  Mamba layers also ride the
  ragged tick, hybrids (jamba) with their KV layers on the paged pool: the
  mixer leaves inert rows' state untouched and starts a prompt's first
  chunk from zero state (the reset at admission).  Prefix sharing is off
  for any model with recurrent state;
* **termination**: per-slot EOS/length checks; finished slots are evicted
  with an O(1) ``reset_kv_slot`` and emit pad tokens under a sampling mask
  until readmission;
* a **stats tracker**: steady tok/s (compile excluded via ``warmup()``),
  p50/p99 per-request latency in decode steps (and in wall milliseconds
  under ``run(time_ticks=True)``), mean slot occupancy, jit-compile,
  admission-stall and page-allocator counters.

The jitted steps donate their cache (and, outside async-harvest mode, their
token) arguments, so per-tick cache updates are true in-place buffer reuse
at the XLA level rather than a whole-cache copy per tick.

Works for float *and* int8-quantized KV caches — the paper's memory win
(cache bytes ÷2 vs bf16, ÷4 vs f32) exercised under realistic traffic.
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Any, Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.policy import QuantPolicy
from repro.nn.module import Context
from repro.serve.admission import (AdmissionPlanner, Preempted, PrefillLane,
                                   pick_preemption_victim)
from repro.serve.audit import (check_allocator, check_cross_lens,
                               check_page_tables, check_recurrent_rows,
                               check_swap)
from repro.serve.engine import (make_decode_step, make_mixed_step,
                                make_prefill_step, make_ragged_step,
                                sample_tokens)
from repro.serve.faults import FaultPlan
from repro.serve.lanes import assemble_ragged_tick
from repro.serve.paging import (PageAllocator, PrefixIndex, SwapArea,
                                _tree_bytes)
from repro.serve.slot_state import (  # noqa: F401  (re-exported compat names)
    _find_paged_kv, _is_kv, _map_slot_op, _map_slot_op2, admit_cache_slot,
    copy_cache_page, evict_cache_slot, gather_cache_pages, merge_inactive,
    scatter_cache_pages, set_cache_page_entry, set_cache_page_row,
    mixer_kinds, set_cache_slot_len, state_kinds)
from repro.serve.trace import TickSpans

# Back-compat aliases: these used to be defined in this module.
_Prefill = PrefillLane
_Preempted = Preempted


# --------------------------------------------------------------------------
# Requests and results
# --------------------------------------------------------------------------

@dataclasses.dataclass
class Request:
    """One generation request. ``arrival`` is the decode-step tick at which
    the request becomes visible to the scheduler (0 = available at start).
    ``deadline_steps`` (optional) is a per-request latency bound in the
    same virtual clock: a request still unfinished ``deadline_steps`` ticks
    after arrival is evicted (or dropped from the queue/parked set) and
    returned with ``status="timeout"`` — tokens emitted so far included."""

    rid: int
    prompt: Any                 # (P,) int32 token ids (list / np / jnp)
    max_new: int
    arrival: int = 0
    enc: Any = None             # EncDec serving: this request's encoder
    #                             output (S_enc, D) or (1, S_enc, D); None
    #                             for decoder-only models
    deadline_steps: Optional[int] = None


#: Terminal request statuses: ``ok`` (ran to EOS/length), ``timeout``
#: (deadline_steps expired), ``cancelled`` (host-side cancel), ``rejected``
#: (bounded-queue backpressure), ``failed`` (unservable deadlock or a
#: NaN/Inf-poisoned slot evicted by the audit sentinel).
STATUSES = ("ok", "timeout", "cancelled", "rejected", "failed")


@dataclasses.dataclass
class RequestResult:
    """Everything the scheduler knows about one *terminal* request: the
    generated ids, the (arrival, admitted, finished) tick timeline the
    latency percentiles are computed from, and how it ended (``status``).

    Every request passed to ``run()`` gets exactly one result — degraded
    outcomes (timeout/cancelled/rejected/failed) carry whatever tokens were
    emitted before termination instead of vanishing into an exception.
    ``admitted_at`` is -1 for requests that never reached a slot, and
    ``started_at`` for requests whose prefill never began.
    """

    rid: int
    tokens: List[int]           # generated ids (includes EOS if hit)
    prompt_len: int
    arrival: int
    admitted_at: int            # ticks done when the first token was
    #                             sampled: the first-token step's tick + 1
    #                             (one-shot: the tick whose prefill ran)
    finished_at: int            # tick the last token was emitted
    eos: bool                   # True: stopped on EOS, False: length limit
    status: str = "ok"          # one of STATUSES
    started_at: int = -1        # tick of the step that carried the first
    #                             prefill chunk (one-shot: the admission
    #                             tick); same numbering as ``arrival``

    @property
    def latency_steps(self) -> int:
        """Queueing + service time in decode-step ticks."""
        return self.finished_at - self.arrival


class TickRecord(NamedTuple):
    """One executed step of ``Scheduler.run``: what the compiled step
    computed and how much of it was live."""

    tick: int                   # the step's tick (numbering of ``arrival``)
    decode_rows: int            # live decode slots
    chunks: Tuple[Tuple[int, int, int], ...]  # (rid, start, clen) per lane
    #                             that ran a prefill chunk
    step_rows: int              # rows the step computed: B + L*C ragged,
    #                             B + C mixed with a chunk, B decode
    queued: int                 # requests waiting in the queue


@dataclasses.dataclass
class ServeStats:
    """Aggregates the run; ``summary()`` is what serve_bench.py persists."""

    compile_s: float = 0.0      # warmup (jit compile) wall time, reported apart
    steady_s: float = 0.0       # post-warmup serving loop wall time
    decode_steps: int = 0
    tokens_out: int = 0
    occupancy_sum: float = 0.0
    latencies_steps: List[int] = dataclasses.field(default_factory=list)
    latencies_s: List[float] = dataclasses.field(default_factory=list)
    peak_cache_bytes: int = 0
    num_jit_compiles: int = 0   # compiled entries across the run's jitted steps
    prefill_chunks: int = 0     # chunked admission: mixed steps that carried a chunk
    stalled_chunks: int = 0     # chunked admission: ticks the pending chunk sat
    #                             out under token_budget (stall *duration*, not
    #                             a count of distinct deferred chunks)
    admission_stalls: int = 0   # one-shot admission: stop-the-world prefills
    #                             dispatched while >= 1 other slot was live
    page_stalls: int = 0        # paged KV: ticks the head-of-queue request
    #                             sat deferred because the allocator could not
    #                             serve its full page extent
    prefix_hits: int = 0        # prefix sharing: admissions that mapped >= 1
    #                             resident page instead of allocating it
    shared_pages_mapped: int = 0  # prefix sharing: total page mappings served
    #                             from the index (pool pages NOT allocated)
    cow_copies: int = 0         # prefix sharing: divergence pages privatized
    #                             by copy-on-write before their first write
    peak_pages_in_use: int = 0  # paged KV: allocator high-water mark
    peak_live_slots: int = 0    # max concurrent requests resident (live
    #                             decode slots + a mid-prefill reservation) —
    #                             the effective-capacity metric serve_bench
    #                             compares paged vs dense on
    page_util_sum: float = 0.0  # paged KV: per-tick live tokens / resident
    page_util_ticks: int = 0    # pool tokens (internal-fragmentation gauge)
    grown_pages: int = 0        # oversubscription: decode pages allocated
    #                             lazily, one per page-boundary crossing
    preemptions: int = 0        # oversubscription: slots evicted mid-decode
    #                             because growth/admission found the pool dry
    resumes: int = 0            # swap policy: preempted requests restored
    swapped_pages: int = 0      # swap policy: private pages copied to host
    swap_peak_bytes: int = 0    # swap policy: SwapArea high-water mark
    resume_stalls: int = 0      # swap policy: ticks the oldest preempted
    #                             request waited for a free slot + pages
    truncations: int = 0        # oversize="truncate": requests whose max_new
    #                             was clamped to the page-table width
    preempted_rids: Dict[int, int] = dataclasses.field(default_factory=dict)
    #                             rid -> times preempted (aging-bound audit)
    truncated_rids: Dict[int, int] = dataclasses.field(default_factory=dict)
    #                             rid -> granted max_new (per-request warning
    #                             record for oversize="truncate")
    ttft_steps: List[int] = dataclasses.field(default_factory=list)
    #                             per request: first-admission tick - arrival
    #                             (first leg only — a preempted request's
    #                             first token was already served)
    completed: int = 0          # requests that ended status="ok"
    rejections: int = 0         # bounded-queue backpressure: requests shed
    #                             (reject_policy) with status="rejected"
    timeouts: int = 0           # deadline_steps expiries (status="timeout")
    cancellations: int = 0      # host-side cancels (status="cancelled")
    failed: int = 0             # status="failed": deadlock conversions +
    #                             NaN-sentinel evictions
    deadlock_failures: int = 0  # failed subset: idle-branch unservable
    #                             requests (previously a RuntimeError)
    nan_evictions: int = 0      # failed subset: slots evicted by the
    #                             NaN/Inf logit sentinel (audit mode)
    swap_refusals: int = 0      # swap parks refused (SwapArea capacity or
    #                             an injected fault) -> recompute fallback
    fault_events: int = 0       # injected FaultPlan denials/poisons fired
    audited_ticks: int = 0      # ticks the invariant auditor ran clean
    state_kinds: str = ""       # the served model's slot-state kinds, "+"-
    #                             joined ("kv", "recurrent", "kv+recurrent",
    #                             "kv+cross", ...) — serve/slot_state.py
    ticks: List[TickRecord] = dataclasses.field(default_factory=list)
    #                             one record per executed step

    @property
    def completion_rate(self) -> float:
        """ok results / all terminal results (1.0 when nothing terminated —
        vacuously complete); the chaos gate's headline number."""
        total = (self.completed + self.rejections + self.timeouts
                 + self.cancellations + self.failed)
        return self.completed / total if total else 1.0

    @property
    def steady_tok_s(self) -> float:
        """Post-warmup tokens per wall second."""
        return self.tokens_out / self.steady_s if self.steady_s > 0 else 0.0

    @property
    def occupancy(self) -> float:
        """Mean fraction of batch slots live per decode step."""
        return self.occupancy_sum / max(self.decode_steps, 1)

    @property
    def page_occupancy(self) -> float:
        """Paged KV: mean live-token fill of the pages held by requests.

        1.0 = every resident pool token is a live K/V row; the gap is
        internal fragmentation (last-page waste + decode headroom reserved
        but not yet generated — oversubscription exists to close the
        latter).  0.0 when the run was not paged.  Sharing-aware: a pool
        page mapped by several slots counts once, filled to the *deepest*
        live row over its mappers, so the gauge stays a meaningful 0..1
        signal under prefix sharing (it used to double-count shared rows
        and read past 1.0).
        """
        return self.page_util_sum / max(self.page_util_ticks, 1)

    def summary(self) -> Dict[str, Any]:
        """The dict serve_bench.py persists (rates, percentiles, counters)."""
        lat = np.asarray(self.latencies_steps or [0])
        lat_ms = np.asarray(self.latencies_s or [0.0]) * 1e3
        return {
            "steady_tok_s": round(self.steady_tok_s, 2),
            "compile_s": round(self.compile_s, 3),
            "steady_s": round(self.steady_s, 4),
            "decode_steps": self.decode_steps,
            "tokens_out": self.tokens_out,
            "occupancy": round(self.occupancy, 4),
            "p50_latency_steps": float(np.percentile(lat, 50)),
            "p99_latency_steps": float(np.percentile(lat, 99)),
            "p50_latency_ms": round(float(np.percentile(lat_ms, 50)), 3),
            "p99_latency_ms": round(float(np.percentile(lat_ms, 99)), 3),
            "peak_cache_bytes": self.peak_cache_bytes,
            "num_jit_compiles": self.num_jit_compiles,
            "prefill_chunks": self.prefill_chunks,
            "stalled_chunks": self.stalled_chunks,
            "admission_stalls": self.admission_stalls,
            "page_stalls": self.page_stalls,
            "peak_pages_in_use": self.peak_pages_in_use,
            "peak_live_slots": self.peak_live_slots,
            "page_occupancy": round(self.page_occupancy, 4),
            "prefix_hits": self.prefix_hits,
            "shared_pages_mapped": self.shared_pages_mapped,
            "cow_copies": self.cow_copies,
            "grown_pages": self.grown_pages,
            "preemptions": self.preemptions,
            "resumes": self.resumes,
            "swapped_pages": self.swapped_pages,
            "swap_peak_bytes": self.swap_peak_bytes,
            "resume_stalls": self.resume_stalls,
            "truncations": self.truncations,
            "p50_ttft_steps": float(np.percentile(
                np.asarray(self.ttft_steps or [0]), 50)),
            "p99_ttft_steps": float(np.percentile(
                np.asarray(self.ttft_steps or [0]), 99)),
            "rejections": self.rejections,
            "timeouts": self.timeouts,
            "cancellations": self.cancellations,
            "failed": self.failed,
            "completion_rate": round(self.completion_rate, 4),
            "deadlock_failures": self.deadlock_failures,
            "nan_evictions": self.nan_evictions,
            "swap_refusals": self.swap_refusals,
            "fault_events": self.fault_events,
            "audited_ticks": self.audited_ticks,
            "state_kinds": self.state_kinds,
        }


@dataclasses.dataclass
class _Slot:
    req: Request
    admitted_at: int
    plen: int = 0                # this leg's prompt length (a recompute
    #                              continuation's includes carried tokens)
    emitted: int = 0
    tokens: List[int] = dataclasses.field(default_factory=list)  # sync mode
    first: Any = None            # async mode: (1,1) device first token
    cols: List[Tuple[int, int]] = dataclasses.field(default_factory=list)
    # async mode: per emitted decode token, its (slot row, column) in the
    # step matrix — the row is recorded per token because a swap-resumed
    # request may land in a different slot index


# --------------------------------------------------------------------------
# The scheduler.  Slot-state walkers live in serve/slot_state.py; admission
# planning and the preemption policy in serve/admission.py; ragged lane
# assembly in serve/lanes.py.
# --------------------------------------------------------------------------

class Scheduler:
    """Continuous batching over a ``ServeEngine``'s model/params/steps.

    ``eos_id``: generation stops when this id is sampled (None = length-only).
    ``pad_id``: emitted by masked (inactive) slots and used to pad prompts.
    ``prompt_bucket``: round prompt lengths up to a multiple, so distinct
    prompt lengths share jit compilations; the true last-token logits are
    gathered at the unpadded position and the slot's live length is set to
    the true prompt length, so bucket padding never changes semantics.
    ``chunk_size``: switch admission to chunked prefill (the mixed step);
    the chunk grid subsumes prompt bucketing, so ``prompt_bucket`` is
    ignored.  ``token_budget``: per-tick token cap for chunked admission
    (must fit at least one chunk; live decode slots always run).

    Paged engines (``engine.paged_kv``) require chunked admission: the
    one-shot path prefills into a dense batch-1 scratch cache and block-copies
    it, which has no paged analog (and no reason for one — the mixed step
    writes through the page table directly).

    ``prefix_sharing`` (paged only, default on): requests whose prompt
    prefix matches pages already resident map those pages into their own
    table (refcounted in serve/paging.py) and prefill only from the
    divergence point; a shared divergence page is privatized by
    copy-on-write before its first write.  Disable to measure the unshared
    baseline (serve_bench's shared-prefix gate does exactly that).

    ``oversubscribe`` (paged only): admission reserves only the
    prompt-covering (chunk-padded) pages; decode pages are allocated lazily,
    one page per boundary crossing, and pool exhaustion mid-decode preempts
    a victim under ``preempt_policy`` — ``"recompute"`` (re-queue the
    victim as a continuation prompt, re-prefilled through the chunked path)
    or ``"swap"`` (park its private pages host-side in a ``SwapArea`` and
    restore them when pages free up; shared prefix pages stay resident).
    ``preempt_aging`` bounds how often one request may be re-preempted
    before it becomes ineligible (starvation freedom).  Token streams stay
    identical to the unpreempted run under greedy decoding (temperature 0,
    the default); with sampling, preemption re-randomizes the tail of the
    victim's stream (documented, not asserted).

    ``oversize`` controls requests whose ``prompt+max_new`` extent exceeds
    the page-table width (``kv_max_pages * page_size``) or dense
    ``max_len``: ``"reject"`` (default) raises at ``run()``; ``"truncate"``
    clamps ``max_new`` to what the table can hold and records the clamp in
    ``ServeStats.truncated_rids``.  Either way the failure is *loud* — the
    silent page-plan clamp that used to drop KV rows past the table edge
    (decoding garbage attention) is gone.

    EncDec models (anything with an ``encode`` method) serve through the
    chunked path only, with every request carrying its own encoder output
    (``Request.enc``); the scheduler keeps a per-slot ``(slots, S_enc, D)``
    encoder buffer and threads it through the jitted steps — decoding
    without it silently drops the encoder context and emits garbage.

    All jitted steps donate their cache argument — and their token argument
    outside async-harvest mode (no ``eos_id``), where per-step token columns
    must stay alive until the end-of-run harvest — so on backends with
    donation support each tick updates the KV buffers in place instead of
    copying the whole cache through HBM.
    """

    def __init__(self, engine, *, eos_id: Optional[int] = None,
                 pad_id: int = 0, prompt_bucket: Optional[int] = None,
                 chunk_size: Optional[int] = None,
                 token_budget: Optional[int] = None,
                 prefix_sharing: bool = True,
                 oversubscribe: bool = False,
                 preempt_policy: str = "recompute",
                 preempt_aging: int = 2,
                 oversize: str = "reject",
                 ragged: bool = False,
                 prefill_lanes: int = 1,
                 max_queue: Optional[int] = None,
                 reject_policy: str = "reject",
                 swap_bytes: Optional[int] = None,
                 audit: bool = False):
        """Bind the scheduler's jitted steps to ``engine`` (see class doc).

        ``max_queue`` bounds the *arrived-and-waiting* queue (backpressure):
        an arrival past the bound is terminated with ``status="rejected"``
        under ``reject_policy="reject"``, or, under ``"shed_oldest"``, the
        oldest waiting request is shed in its favor (preemption
        continuations are never shed — they hold served tokens).
        ``swap_bytes`` caps the swap policy's host SwapArea; a victim whose
        pages do not fit falls back to recompute preemption
        (``ServeStats.swap_refusals``).  ``audit=True`` runs the invariant
        auditor (serve/audit.py) every tick and arms the NaN/Inf logit
        sentinel: a poisoned slot is evicted as ``failed`` instead of
        streaming garbage — the per-tick health readback costs pipeline
        overlap, so it is opt-in (CI keeps it always-on in the chaos lane).
        """
        self.engine = engine
        self.eos_id = eos_id
        self.pad_id = int(pad_id)
        self.prompt_bucket = prompt_bucket
        self.chunk_size = chunk_size
        self.token_budget = token_budget
        self.paged = bool(getattr(engine, "paged_kv", False))
        self.prefix_sharing = bool(prefix_sharing) and self.paged
        self.oversubscribe = bool(oversubscribe)
        self.preempt_policy = preempt_policy
        self.preempt_aging = int(preempt_aging)
        self.oversize = oversize
        self.ragged = bool(ragged)
        self.prefill_lanes = int(prefill_lanes)
        self.max_queue = max_queue
        self.reject_policy = reject_policy
        self.swap_bytes = swap_bytes
        self.audit = bool(audit)
        self._cancel_box: set = set()
        self.encdec = hasattr(engine.model, "encode")
        # Which per-slot state kinds this model serves with — the slot-state
        # walkers dispatch per cache node, so the loop below never branches
        # on architecture; these flags only gate policy validation, the
        # inactive-merge barrier, and the per-kind audit hooks.
        kinds = list(state_kinds(engine.model))
        if "cross" in kinds and not getattr(engine, "cross_attn_cache", True):
            kinds.remove("cross")   # engine recomputes from enc every step
        self.state_kinds: Tuple[str, ...] = tuple(kinds)
        self._has_recurrent = "recurrent" in kinds
        self._cross_cached = "cross" in kinds
        if self._has_recurrent:
            # a matched prefix skips the prefill that builds the recurrent
            # state: the slot would decode from the state of an empty
            # prompt.  Recurrent rows have no pages to share.
            self.prefix_sharing = False
        if max_queue is not None and max_queue < 1:
            raise ValueError(f"max_queue must be >= 1, got {max_queue}")
        if reject_policy not in ("reject", "shed_oldest"):
            raise ValueError(
                f"reject_policy must be 'reject' or 'shed_oldest', "
                f"got {reject_policy!r}")
        if swap_bytes is not None and swap_bytes < 0:
            raise ValueError(f"swap_bytes must be >= 0, got {swap_bytes}")
        if self.oversubscribe and not self.paged:
            raise ValueError(
                "oversubscribe=True requires a paged engine "
                "(ServeEngine(paged_kv=True)): lazy decode pages grow a "
                "page table, dense slabs have nothing to grow")
        if preempt_policy not in ("recompute", "swap"):
            raise ValueError(
                f"preempt_policy must be 'recompute' or 'swap', "
                f"got {preempt_policy!r}")
        if self.preempt_aging < 1:
            raise ValueError(
                f"preempt_aging must be >= 1, got {preempt_aging}")
        if oversize not in ("reject", "truncate"):
            raise ValueError(
                f"oversize must be 'reject' or 'truncate', got {oversize!r}")
        if chunk_size is not None and chunk_size < 1:
            raise ValueError("chunk_size must be >= 1")
        if self.paged and chunk_size is None:
            raise ValueError(
                "paged KV (engine.paged_kv) requires chunked admission: "
                "pass chunk_size=... (one-shot admission block-copies a "
                "dense scratch cache, which has no paged analog)")
        if self.encdec and chunk_size is None:
            raise ValueError(
                "EncDec serving requires chunked admission: pass "
                "chunk_size=... (e.g. Scheduler(engine, chunk_size=32)) — "
                "the one-shot slot prefill block-copies a scratch cache "
                "without the request's encoder output or its cross-attention "
                "K/V, so the slot would decode without encoder context")
        if self._has_recurrent:
            if self.ragged and "rwkv" in mixer_kinds(engine.model):
                raise ValueError(
                    "ragged=True cannot serve RWKV layers: the ragged tick "
                    "runs each lane's rows in order only through the Mamba "
                    "mixer (nn/ssm.py Mamba._apply_ragged) — use the mixed "
                    "step (chunk_size=... without ragged)")
            if self.paged and "kv" not in kinds:
                raise ValueError(
                    "paged KV (engine.paged_kv) on a pure recurrent-state "
                    "model: there is no KV cache to page — recurrent state "
                    "is a fixed-size per-slot row (drop paged_kv; its bytes "
                    "do not grow with sequence length)")
            if self.oversubscribe and preempt_policy == "swap":
                raise ValueError(
                    "preempt_policy='swap' cannot serve recurrent-state "
                    "layers: swap parks only KV pool pages, the victim's "
                    "recurrence rows would be zeroed by eviction and "
                    "resume would continue from corrupt state — use "
                    "preempt_policy='recompute' (re-prefill rebuilds the "
                    "recurrence exactly)")
            if prompt_bucket is not None and chunk_size is None:
                raise ValueError(
                    "prompt_bucket cannot serve recurrent-state layers "
                    "under one-shot admission: bucket padding would run "
                    "pad tokens through the recurrence and corrupt the "
                    "admitted state (KV slots mask on len; a recurrence "
                    "cannot) — drop prompt_bucket or use chunk_size=...")
        if token_budget is not None:
            if chunk_size is None:
                raise ValueError("token_budget requires chunked admission "
                                 "(chunk_size=...)")
            if token_budget < chunk_size:
                raise ValueError(
                    f"token_budget {token_budget} < chunk_size {chunk_size}: "
                    f"an idle batch could never admit a chunk")
        if self.ragged and chunk_size is None:
            raise ValueError(
                "ragged=True requires chunked admission (chunk_size=...): "
                "the ragged step's prefill lanes carry fixed-size chunks")
        if self.prefill_lanes < 1:
            raise ValueError(
                f"prefill_lanes must be >= 1, got {prefill_lanes}")
        if self.prefill_lanes > 1 and not self.ragged:
            raise ValueError(
                f"prefill_lanes={prefill_lanes} requires ragged=True: the "
                f"mixed step carries exactly one chunk per tick — only the "
                f"ragged forward flattens several lanes into one batch")

        model = engine.model
        vocab = engine.vocab
        temperature = engine.temperature
        health = self.audit     # audit mode threads per-row logit health
        decode = make_decode_step(
            model, mesh=engine.mesh, axis_rules=engine.axis_rules,
            temperature=temperature, with_health=health)
        pad = jnp.int32(self.pad_id)

        # Recurrent-state models: restore every inactive slot's recurrence
        # rows after the batched step (serve/slot_state.py merge_inactive) —
        # reading the donated input after the step is trace-safe (donation
        # is an aliasing hint, XLA copies where the value is still needed).
        merge = merge_inactive if self._has_recurrent else None

        def masked_decode(params, tok, cache, rng, active, enc=None,
                          poison=None):
            old = cache
            if health:
                nxt, ok, cache = decode(params, tok, cache, rng, enc,
                                        poison)
                if merge is not None:
                    cache = merge(old, cache, active)
                return jnp.where(active[:, None], nxt, pad), ok, cache
            nxt, cache = decode(params, tok, cache, rng, enc)
            if merge is not None:
                cache = merge(old, cache, active)
            return jnp.where(active[:, None], nxt, pad), cache

        def set_tok(tok, first, slot):
            # traced slot index: one compile serves every slot
            return jax.lax.dynamic_update_slice(tok, first, (slot, 0))

        # Donation: cache always; tok only in sync (EOS) mode — async mode
        # retains every step's token column until the end-of-run harvest, so
        # donating tok there would invalidate retained buffers.
        sync = eos_id is not None

        # The module-level tree ops get a per-instance closure before jit:
        # jax keys its compile cache on the underlying callable, so jitting
        # the shared function directly would make num_jit_compiles count
        # every OTHER engine's cache shapes too (the bucket-explosion
        # telltale must be per-scheduler to mean anything).
        def evict(cache, slot):
            return evict_cache_slot(cache, slot)

        self._masked_decode = jax.jit(masked_decode,
                                      donate_argnums=(1, 2) if sync else (2,))
        self._evict = jax.jit(evict, donate_argnums=(0,))
        self._set_tok = jax.jit(set_tok,
                                donate_argnums=(0,) if sync else ())
        self._jits = [self._masked_decode, self._evict, self._set_tok]
        if self.paged:
            def set_pages(cache, slot, row):
                return set_cache_page_row(cache, slot, row)

            def set_len(cache, slot, length):
                return set_cache_slot_len(cache, slot, length)

            def append_page(cache, slot, idx, page):
                return set_cache_page_entry(cache, slot, idx, page)

            self._set_pages = jax.jit(set_pages, donate_argnums=(0,))
            self._set_len = jax.jit(set_len, donate_argnums=(0,))
            self._append_page = jax.jit(append_page, donate_argnums=(0,))
            self._jits += [self._set_pages, self._set_len, self._append_page]
        if self.prefix_sharing:
            def copy_page(cache, src, dst):
                return copy_cache_page(cache, src, dst)

            self._copy_page = jax.jit(copy_page, donate_argnums=(0,))
            self._jits.append(self._copy_page)
        if self.oversubscribe and self.preempt_policy == "swap":
            def gather_pages(cache, pages):
                return gather_cache_pages(cache, pages)

            def scatter_pages(cache, pages, data):
                return scatter_cache_pages(cache, pages, data)

            # gather must NOT donate: the cache stays live (only page
            # contents are read out); scatter donates like every other
            # cache update
            self._gather_pages = jax.jit(gather_pages)
            self._scatter_pages = jax.jit(scatter_pages, donate_argnums=(0,))
            self._jits += [self._gather_pages, self._scatter_pages]
        if self.encdec:
            def set_enc(buf, row, slot):
                return jax.lax.dynamic_update_slice(
                    buf, row.astype(buf.dtype), (slot, jnp.int32(0),
                                                 jnp.int32(0)))

            self._set_enc = jax.jit(set_enc, donate_argnums=(0,))
            self._jits.append(self._set_enc)
        if self._cross_cached:
            # project + install one request's cross-attention K/V rows into
            # its slot, once, at admission/resume (EncDecLM.write_cross_kv)
            def write_xkv(params, cache, row, slot):
                ctx = Context(policy=QuantPolicy.float32(), train=False,
                              mesh=engine.mesh, axis_rules=engine.axis_rules)
                return model.write_cross_kv(params, cache, row, slot, ctx)

            self._write_xkv = jax.jit(write_xkv, donate_argnums=(1,))
            self._jits.append(self._write_xkv)
        # Host-side admission planning (paged sizing, prefix plans, COW) —
        # serve/admission.py; only chunked admission pages/plans anything.
        self._admission = AdmissionPlanner(
            page_size=engine.page_size, max_pages=engine.kv_max_pages,
            chunk_size=chunk_size, oversubscribe=self.oversubscribe) \
            if chunk_size is not None else None

        if chunk_size is None:
            # one-shot admission: batch-1 prefill + write_kv_slot copy
            prefill_full = make_prefill_step(
                model, mesh=engine.mesh, axis_rules=engine.axis_rules)

            def slot_prefill(params, tokens, plen, rng):
                """(1, P) prompt -> (first token (1,1), batch-1 cache).
                The LM head runs over the single true-last position only
                (logit_pos), not the whole padded bucket."""
                cache = model.init_cache(
                    1, engine.max_len, quantized_kv=engine.quantized_kv,
                    kv_dtype=getattr(model, "dtype", jnp.float32))
                logits, cache = prefill_full(params, tokens, cache,
                                             logit_pos=plen - 1)
                return sample_tokens(logits[:, 0], rng, vocab,
                                     temperature), cache

            def admit(big, small, slot, length):
                return admit_cache_slot(big, small, slot, length)

            self._slot_prefill = jax.jit(slot_prefill)
            self._admit = jax.jit(admit, donate_argnums=(0,))
            self._jits += [self._slot_prefill, self._admit]
        elif self.ragged:
            # ragged admission: ONE forward per tick — decode rows for every
            # slot plus up to prefill_lanes C-token chunks, flattened into a
            # single (1, B + L*C) token batch (engine.make_ragged_step).
            # Pure-decode ticks run the same step with all-inert lane rows:
            # one compile shape for the entire run.
            rag = make_ragged_step(
                model, mesh=engine.mesh, axis_rules=engine.axis_rules,
                temperature=temperature, with_health=health)
            nslots = engine.batch_slots

            def masked_ragged(params, tok, cache, rng, active, chunk_tok,
                              slot_ids, positions, logit_rows, enc=None,
                              poison=None):
                if health:
                    nxt, ok, cache = rag(params, tok, cache, rng, chunk_tok,
                                         slot_ids, positions, logit_rows,
                                         enc, poison)
                    dec = jnp.where(active[:, None], nxt[:nslots], pad)
                    return dec, nxt[nslots:], ok, cache
                nxt, cache = rag(params, tok, cache, rng, chunk_tok,
                                 slot_ids, positions, logit_rows, enc)
                dec = jnp.where(active[:, None], nxt[:nslots], pad)
                return dec, nxt[nslots:], cache

            self._masked_ragged = jax.jit(masked_ragged,
                                          donate_argnums=(1, 2) if sync
                                          else (2,))
            self._jits.append(self._masked_ragged)
        else:
            # chunked admission: one fused mixed step, one compile shape.
            # merge runs between the decode and chunk halves so the lane
            # slot's recurrence enters its chunk un-corrupted.
            mixed = make_mixed_step(
                model, mesh=engine.mesh, axis_rules=engine.axis_rules,
                temperature=temperature, with_health=health, merge=merge)

            def masked_mixed(params, tok, cache, rng, active, chunk_tok,
                             slot, start, length, enc=None, poison=None):
                if health:
                    nxt, first, dec_ok, first_ok, cache = mixed(
                        params, tok, cache, rng, chunk_tok, slot, start,
                        length, enc, poison, active)
                    return (jnp.where(active[:, None], nxt, pad), first,
                            dec_ok, first_ok, cache)
                nxt, first, cache = mixed(params, tok, cache, rng, chunk_tok,
                                          slot, start, length, enc, None,
                                          active)
                return jnp.where(active[:, None], nxt, pad), first, cache

            self._masked_mixed = jax.jit(masked_mixed,
                                         donate_argnums=(1, 2) if sync
                                         else (2,))
            self._jits.append(self._masked_mixed)

    def _count_jit_compiles(self) -> int:
        """Compiled-entry count across this scheduler's jitted steps — the
        bucket-explosion telltale: chunked admission stays O(1) no matter how
        many distinct prompt lengths a run serves."""
        return sum(f._cache_size() for f in self._jits
                   if hasattr(f, "_cache_size"))

    def cancel(self, rid: int) -> None:
        """Request host-side cancellation of ``rid`` (thread/callback-safe
        in the sense that it only mutates a host set): the running ``run()``
        drains the box at its next tick and terminates the request —
        wherever it is (queued, mid-prefill, parked, or live) — with
        ``status="cancelled"`` and its tokens emitted so far.  Cancelling an
        unknown or already-finished rid is a no-op."""
        self._cancel_box.add(int(rid))

    # ---- paged admission sizing (delegates to serve/admission.py) ---------
    def _pages_needed(self, plen: int, max_new: int) -> int:
        """A request's worst-case page footprint (AdmissionPlanner)."""
        return self._admission.pages_needed(plen, max_new)

    def _page_row(self, pages: List[int]) -> jax.Array:
        """A (max_pages,) device row: allocated pool indices then -1s."""
        return self._admission.page_row(pages)

    def _plan_admission(self, r: Request, plen: int, alloc: PageAllocator,
                        index: Optional[PrefixIndex],
                        keys: Optional[List[bytes]] = None):
        """Page plan for admitting ``r`` (AdmissionPlanner.plan), or None
        on a page stall."""
        return self._admission.plan(r, plen, alloc, index, keys=keys)

    def _assert_private_write(self, pages: List[int], lo: int, hi: int,
                              alloc: PageAllocator) -> None:
        """Shared-mapping write invariant (AdmissionPlanner)."""
        self._admission.assert_private_write(pages, lo, hi, alloc)

    # ---- prompt bucketing --------------------------------------------------
    def _bucket(self, plen: int) -> int:
        if self.prompt_bucket is None:
            return plen
        b = self.prompt_bucket
        return ((plen + b - 1) // b) * b

    def _pad_prompt(self, prompt) -> Tuple[jax.Array, int]:
        arr = np.asarray(prompt, np.int32).reshape(-1)
        plen = int(arr.shape[0])
        padded = np.full((1, self._bucket(plen)), self.pad_id, np.int32)
        padded[0, :plen] = arr
        return jnp.asarray(padded), plen

    # ---- warmup ------------------------------------------------------------
    def warmup(self, prompt_lens: Sequence[int], *, seed: int = 0,
               enc: Any = None) -> float:
        """Compile every step the run will need against throwaway state, so
        the measured loop is pure steady state. Returns compile seconds.

        One-shot admission compiles one slot-prefill per distinct (bucketed)
        prompt length; chunked admission compiles the mixed step once — its
        chunk shape is static, so ``prompt_lens`` is irrelevant.  ``enc`` is
        the run's per-slot encoder buffer shape-alike (EncDec serving).
        """
        eng = self.engine
        t0 = time.perf_counter()
        rng = jax.random.PRNGKey(seed)
        cache = eng.new_cache(per_slot=True)
        tok = jnp.full((eng.batch_slots, 1), self.pad_id, jnp.int32)
        active = jnp.ones((eng.batch_slots,), bool)
        slot0 = jnp.int32(0)
        # audit mode: the health-threading steps take a poison vector — an
        # all-zeros one is an exact no-op (see engine.make_decode_step)
        pz = jnp.zeros((eng.batch_slots,), jnp.float32) \
            if self.audit else None
        if enc is not None:
            enc = self._set_enc(jnp.zeros_like(enc), enc[:1], slot0)
            if self._cross_cached:
                cache = self._write_xkv(eng.params, cache, enc[:1], slot0)
        if self.chunk_size is not None:
            if self.paged:
                # throwaway page assignment for slot 0 (no allocator: warmup
                # state is discarded, only the compiles matter)
                n = min(self._pages_needed(self.chunk_size, 1),
                        eng.kv_num_pages)
                cache = self._set_pages(cache, slot0,
                                        self._page_row(list(range(n))))
                cache = self._append_page(cache, slot0, jnp.int32(n - 1),
                                          jnp.int32(n - 1))
                cache = self._set_len(cache, slot0, jnp.int32(0))
                if self.prefix_sharing:
                    cache = self._copy_page(cache, jnp.int32(0),
                                            jnp.int32(n - 1))
            if self.ragged:
                # one compile serves every tick: shapes depend only on
                # (slots, lanes, chunk) — values here are throwaway
                L, C = self.prefill_lanes, self.chunk_size
                T = eng.batch_slots + L * C
                ctok = jnp.full((L, C), self.pad_id, jnp.int32)
                sids = jnp.zeros((T,), jnp.int32)
                poss = jnp.full((T,), -1, jnp.int32)
                lrows = jnp.zeros((eng.batch_slots + L,), jnp.int32)
                if self.audit:
                    rp = jnp.zeros((eng.batch_slots + L,), jnp.float32)
                    tok, firsts, _ok, cache = self._masked_ragged(
                        eng.params, tok, cache, rng, active, ctok, sids,
                        poss, lrows, enc, rp)
                else:
                    tok, firsts, cache = self._masked_ragged(
                        eng.params, tok, cache, rng, active, ctok, sids,
                        poss, lrows, enc)
                tok = self._set_tok(tok, firsts[:1], slot0)
                cache = self._evict(cache, slot0)
                jax.block_until_ready((tok, cache))
                return time.perf_counter() - t0
            ctok = jnp.full((1, self.chunk_size), self.pad_id, jnp.int32)
            if self.audit:
                tok, first, _dok, _fok, cache = self._masked_mixed(
                    eng.params, tok, cache, rng, active, ctok, slot0,
                    jnp.int32(0), jnp.int32(self.chunk_size), enc, pz)
            else:
                tok, first, cache = self._masked_mixed(
                    eng.params, tok, cache, rng, active, ctok, slot0,
                    jnp.int32(0), jnp.int32(self.chunk_size), enc)
            tok = self._set_tok(tok, first, slot0)
        else:
            for p in sorted({self._bucket(int(p)) for p in prompt_lens}):
                toks = jnp.full((1, p), self.pad_id, jnp.int32)
                first, small = self._slot_prefill(eng.params, toks,
                                                  jnp.int32(p), rng)
                cache = self._admit(cache, small, slot0, jnp.int32(p))
                tok = self._set_tok(tok, first, slot0)
        if self.audit:
            tok, _ok, cache = self._masked_decode(eng.params, tok, cache,
                                                  rng, active, enc, pz)
        else:
            tok, cache = self._masked_decode(eng.params, tok, cache, rng,
                                             active, enc)
        cache = self._evict(cache, slot0)
        jax.block_until_ready((tok, cache))
        return time.perf_counter() - t0

    # ---- the serving loop --------------------------------------------------
    def run(self, requests: Sequence[Request], *, seed: int = 0,
            warmup: bool = True, time_ticks: bool = False,
            cancels: Optional[Dict[int, int]] = None,
            preempts: Optional[Dict[int, int]] = None,
            fault_plan: Optional[FaultPlan] = None,
            on_tick=None,
            ) -> Tuple[Dict[int, RequestResult], ServeStats]:
        """Serve all requests to a *terminal* status; ({rid: result}, stats).

        Time is discrete: one tick per batched step.  Queued requests become
        visible at their ``arrival`` tick and are admitted into the
        lowest-numbered free slot in (arrival, rid) order — one-shot (a
        stop-the-world batch-1 prefill between ticks) or, with
        ``chunk_size`` set, chunked (each tick's fused mixed step carries one
        prompt chunk alongside every live decode slot).

        Every request gets exactly one ``RequestResult`` — ``status="ok"``
        or a degraded terminal (``STATUSES``) carrying the tokens emitted so
        far: a ``deadline_steps`` expiry is a ``timeout`` wherever the
        request currently lives (queued, prefilling, parked, or decoding); a
        host cancel (``cancels={rid: tick}`` or :meth:`cancel` from a
        callback) is a ``cancelled``; a bounded-queue shed is a
        ``rejected``; an unservable request under a dry pool (previously a
        RuntimeError mid-run) is a ``failed``, as is a slot evicted by the
        audit-mode NaN/Inf logit sentinel.  ``run()`` itself only raises for
        invalid *inputs* (and :class:`~repro.serve.audit.AuditError` for
        genuine state corruption) — operational overload degrades per
        request instead of burning the whole batch.

        ``fault_plan`` (serve/faults.py) injects deterministic failures at
        the scheduler's seams for testing; ``on_tick(t)`` is a host hook
        called at the top of every tick (the cancellation tests drive
        :meth:`cancel` from it).

        ``preempts={rid: tick}`` forces a preemption of ``rid`` at the
        first tick >= ``tick`` where it holds a live slot (the entry stays
        pending until then, and is dropped if the request reaches a
        terminal status first).  The configured ``preempt_policy`` applies;
        on non-paged engines (dense KV, recurrent state) the preemption is
        always recompute — tokens so far are banked and the request
        re-queues as a continuation, so greedy token streams are unchanged.
        This is the preemption drill's deterministic trigger: it exercises
        the evict → carry → re-prefill lifecycle without needing a pool to
        exhaust.

        Without an ``eos_id`` termination is length-only, so scheduling never
        needs token *values* mid-flight: the loop runs fully async (device
        tokens harvested once at the end), keeping the dispatch pipeline as
        full as lockstep ``generate()``.  With EOS enabled each step syncs
        one (B, 1) readback — the price of data-dependent eviction.

        ``time_ticks=True`` blocks on each tick's tokens and records
        per-request wall-clock latency (summary p50/p99_latency_ms): the
        *step*-latency percentiles cannot see a stop-the-world prefill
        (virtual time does not advance during it), wall time can.
        """
        eng = self.engine
        nslots = eng.batch_slots
        C = self.chunk_size
        stats = ServeStats()
        stats.state_kinds = "+".join(self.state_kinds)
        preempts = {int(k): int(v) for k, v in (preempts or {}).items()}
        if fault_plan is not None:
            if fault_plan.nan and not self.audit:
                raise ValueError(
                    "FaultPlan.nan requires Scheduler(audit=True): the "
                    "NaN/Inf sentinel is audit mode's per-tick health "
                    "readback — without it the poison would stream garbage "
                    "tokens undetected")
            for tk, sj in fault_plan.nan.items():
                if not 0 <= sj < nslots:
                    raise ValueError(
                        f"FaultPlan.nan[{tk}] targets slot {sj} outside "
                        f"[0, {nslots})")
        plen_of: Dict[int, int] = {}
        checked: List[Request] = []
        for r in requests:
            plen = int(np.asarray(r.prompt).reshape(-1).shape[0])
            if r.max_new < 1:
                raise ValueError(f"request {r.rid}: max_new must be >= 1")
            if plen < 1:
                raise ValueError(f"request {r.rid}: empty prompt")
            if r.deadline_steps is not None and r.deadline_steps < 1:
                raise ValueError(
                    f"request {r.rid}: deadline_steps must be >= 1, got "
                    f"{r.deadline_steps}")
            if self.encdec and r.enc is None:
                raise ValueError(
                    f"request {r.rid}: EncDec serving needs the request's "
                    f"encoder output (Request.enc) — decoding without it "
                    f"drops the encoder context entirely")
            if not self.encdec and r.enc is not None:
                raise ValueError(
                    f"request {r.rid}: Request.enc given but the model has "
                    f"no encoder")
            if C is not None:
                rows = -(-plen // C) * C   # last (padded) chunk's extent
                # paged slots are bounded by their page-table capacity
                # (max_len rounded up to whole pages), not max_len itself —
                # chunk padding only has to fit allocatable pages
                cap = eng.kv_max_pages * eng.page_size if self.paged \
                    else eng.max_len
                if plen + r.max_new > cap and self.oversize == "truncate" \
                        and max(rows, plen + 1) <= cap:
                    granted = cap - plen
                    print(f"serve: request {r.rid}: truncating max_new "
                          f"{r.max_new} -> {granted} (prompt {plen} + "
                          f"horizon exceeds table capacity {cap})")
                    stats.truncations += 1
                    stats.truncated_rids[r.rid] = granted
                    r = dataclasses.replace(r, max_new=granted)
                if max(rows, plen + r.max_new) > cap:
                    # the loud half of the page-table-edge fix: rows past
                    # the table width would be sentinel-dropped on device
                    # and the request would silently decode garbage
                    raise ValueError(
                        f"request {r.rid}: prompt {plen} (chunk-padded to "
                        f"{rows}) + max_new {r.max_new} exceeds cache "
                        f"capacity {cap} (max_len {eng.max_len}); its KV "
                        f"rows past the table edge would be dropped and it "
                        f"would decode garbage — shrink the request, raise "
                        f"max_len, or use oversize='truncate'")
            elif self._bucket(plen) + r.max_new > eng.max_len:
                raise ValueError(
                    f"request {r.rid}: prompt {plen} (+bucket) + max_new "
                    f"{r.max_new} exceeds cache max_len {eng.max_len}")
            if self.paged:
                need = self._pages_needed(plen, r.max_new)
                if need > eng.kv_num_pages:
                    raise ValueError(
                        f"request {r.rid}: needs {need} pages but the pool "
                        f"holds {eng.kv_num_pages} — it could never be "
                        f"admitted (raise kv_pool_pages or shrink the "
                        f"request)")
            plen_of[r.rid] = plen
            checked.append(r)
        requests = checked
        orig_plen = dict(plen_of)   # recompute preemption moves plen_of

        enc_buf = None
        enc_of: Dict[int, jax.Array] = {}
        if self.encdec:
            for r in requests:
                row = jnp.asarray(r.enc)
                if row.ndim == 2:
                    row = row[None]
                if row.ndim != 3 or row.shape[0] != 1:
                    raise ValueError(
                        f"request {r.rid}: enc must be (S_enc, D) or "
                        f"(1, S_enc, D), got {row.shape}")
                enc_of[r.rid] = row
            shapes = {v.shape for v in enc_of.values()}
            if len(shapes) != 1:
                raise ValueError(
                    f"all requests must share one encoder shape per run "
                    f"(one jitted step signature), got {sorted(shapes)}")
            (one,) = shapes
            if self._cross_cached:
                el = int(getattr(eng.model, "enc_len"))
                if one[1] > el:
                    raise ValueError(
                        f"encoder output length {one[1]} exceeds the "
                        f"model's cross-attention cache capacity "
                        f"enc_len={el}: the cached xk/xv rows would "
                        f"truncate the encoder context — raise enc_len or "
                        f"shorten the encoder output")
            # keep the encoder's own dtype: an f32 buffer would silently
            # promote a bf16 model's cross-attention (and its residual
            # stream) and diverge from the generate() baseline
            enc_buf = jnp.zeros((nslots,) + one[1:],
                                next(iter(enc_of.values())).dtype)

        if warmup:
            stats.compile_s = self.warmup(
                [np.asarray(r.prompt).reshape(-1).shape[0]
                 for r in requests], seed=seed, enc=enc_buf)

        use_eos = self.eos_id is not None
        # pending: not yet arrived; queue: arrived and waiting.  The split
        # is what bounded-queue backpressure measures — max_queue bounds the
        # *waiting* set, not the future schedule.
        pending = deque(sorted(requests, key=lambda r: (r.arrival, r.rid)))
        queue: deque = deque()
        cont_rids: set = set()     # recompute continuations: never shed —
        #                            they hold already-served tokens
        cancels = {int(k): int(v) for k, v in (cancels or {}).items()}
        cancel_pending: set = set()
        has_deadlines = any(r.deadline_steps is not None for r in requests)
        fault = fault_plan
        poison_plan = deque(sorted(fault.nan.items())) \
            if fault is not None else deque()
        fault_hold = False         # this tick idled because of an injected
        #                            fault denial (not a genuine deadlock)
        zero_poison = None
        if self.audit:
            R = nslots + (self.prefill_lanes if self.ragged else 0)
            zero_poison = jnp.zeros((R,), jnp.float32)
        slots: List[Optional[_Slot]] = [None] * nslots
        results: Dict[int, RequestResult] = {}
        # (slot, j, finish tick, eos, status) per terminal leg
        finished: List[Tuple[_Slot, int, int, bool, str]] = []
        step_cols: List[jax.Array] = []    # async mode: one (B, 1) per step
        arrival_wall: Dict[int, float] = {}
        cache = eng.new_cache(per_slot=True)
        stats.peak_cache_bytes = sum(
            l.size * l.dtype.itemsize
            for l in jax.tree_util.tree_leaves(cache))
        tok = jnp.full((nslots, 1), self.pad_id, jnp.int32)
        rng = jax.random.PRNGKey(seed)
        active_host, active_dev = None, None
        # chunked admission state: the requests currently being prefilled
        # chunk-by-chunk into reserved slots.  The mixed step drives exactly
        # one lane; the ragged step drives up to prefill_lanes concurrently.
        lanes: List[_Prefill] = []
        max_lanes = self.prefill_lanes if self.ragged else 1
        alloc = PageAllocator(eng.kv_num_pages) if self.paged else None
        index = PrefixIndex(eng.page_size) if self.prefix_sharing else None
        slot_pages: Dict[int, List[int]] = {}
        prompt_keys: Dict[int, List[bytes]] = {}   # rid -> cached digests
        carry: Dict[int, List[int]] = {}     # recompute: earlier legs' tokens
        first_admit: Dict[int, int] = {}     # rid -> first admission tick
        first_start: Dict[int, int] = {}     # rid -> first prefill tick
        preempted: List[_Preempted] = []     # swap policy: parked requests
        swap = SwapArea(capacity_bytes=self.swap_bytes) \
            if (self.oversubscribe
                and self.preempt_policy == "swap") else None
        t = 0
        # host spans of each tick (serve/trace.py): recorded only while a
        # profiler trace is active; a tick's spans close at the top of the
        # next iteration, so ``continue`` paths close them too, and before
        # ``on_tick`` so ``serve.tick`` nests in a span the hook opens
        spans = TickSpans()

        def digests_of(r: Request) -> Optional[List[bytes]]:
            """Prompt page digests, hashed once per request (satellite #2)."""
            if index is None:
                return None
            keys = prompt_keys.get(r.rid)
            if keys is None:
                keys = index.digests(r.prompt)
                prompt_keys[r.rid] = keys
            return keys

        def bump(status: str) -> None:
            """Route a terminal status into its ServeStats counter."""
            if status == "ok":
                stats.completed += 1
            elif status == "timeout":
                stats.timeouts += 1
            elif status == "cancelled":
                stats.cancellations += 1
            elif status == "rejected":
                stats.rejections += 1
            else:
                stats.failed += 1

        def finish(j: int, slot: _Slot, eos: bool, status: str = "ok"):
            nonlocal cache
            finished.append((slot, j, t, eos, status))
            if status == "ok":
                # degraded terminals are excluded from the latency
                # percentiles: a timeout's latency is its deadline by
                # construction, and mixing it in would poison the p99
                stats.latencies_steps.append(t - slot.req.arrival)
                if time_ticks and slot.req.rid in arrival_wall:
                    stats.latencies_s.append(
                        time.perf_counter() - arrival_wall[slot.req.rid])
            bump(status)
            # ORDER MATTERS: enqueue the device-side page-table unmap
            # (evict_cache_slot) BEFORE returning the pages to the host
            # allocator.  The very next admission may be handed these pages
            # (LIFO free list) and install them in another slot's row; its
            # writes are sequenced after this unmap through the cache
            # value's data dependency — freeing first would let a reused
            # page be mapped by two rows at once (aliasing/double-free).
            cache = self._evict(cache, jnp.int32(j))
            if alloc is not None and j in slot_pages:
                released = alloc.free(slot_pages.pop(j))
                if index is not None:
                    # shared prefixes outlive their owner: only pages whose
                    # refcount hit zero leave the index
                    index.drop_pages(released)
            slots[j] = None

        def admit_live(j: int, r: Request, first):
            """Slot j goes live holding its freshly sampled first token."""
            slot = _Slot(req=r, admitted_at=t, plen=plen_of[r.rid],
                         emitted=1, first=first)
            slots[j] = slot
            stats.tokens_out += 1
            if r.rid not in first_admit:
                first_admit[r.rid] = t
                stats.ttft_steps.append(t - r.arrival)
            if index is not None and j in slot_pages:
                # prefill complete: this slot's full prompt pages become
                # donor candidates for later same-prefix admissions (the
                # digests were cached at admission — no re-hash here)
                index.insert_keys(digests_of(r),
                                  slot_pages[j][:plen_of[r.rid]
                                                // eng.page_size])
            if use_eos:
                with spans.child("serve.readback"):
                    first_id = int(np.asarray(first)[0, 0])
                slot.tokens.append(first_id)
                if first_id == self.eos_id or r.max_new == 1:
                    finish(j, slot, first_id == self.eos_id)
            elif r.max_new == 1:
                finish(j, slot, False)

        def requeue(r: Request) -> None:
            """Put a request back into the queue in (arrival, rid) order.

            Only preemption continuations come through here; they bypass the
            ``max_queue`` bound (they hold served tokens — shedding one
            would throw away completed work) and are marked shed-immune.
            """
            cont_rids.add(r.rid)
            items = list(queue)
            items.append(r)
            items.sort(key=lambda q: (q.arrival, q.rid))
            queue.clear()
            queue.extend(items)

        def terminal_queued(r: Request, status: str) -> None:
            """Emit the result for a request terminated outside a live slot
            (still queued / mid-prefill / parked): tokens are whatever
            earlier legs banked in ``carry`` (empty for a fresh request)."""
            results[r.rid] = RequestResult(
                rid=r.rid, tokens=carry.pop(r.rid, []),
                prompt_len=orig_plen[r.rid], arrival=r.arrival,
                admitted_at=first_admit.get(r.rid, -1), finished_at=t,
                eos=False, status=status,
                started_at=first_start.get(r.rid, -1))
            bump(status)

        def fail_slot_state(slot_j: int, r: Request, status: str) -> None:
            """Tear down a reserved/mid-prefill slot's device + pool state
            (same evict-before-free ordering as ``finish``) and emit the
            request's terminal result."""
            nonlocal cache
            cache = self._evict(cache, jnp.int32(slot_j))
            if alloc is not None and slot_j in slot_pages:
                released = alloc.free(slot_pages.pop(slot_j))
                if index is not None:
                    index.drop_pages(released)
            terminal_queued(r, status)

        def abort_lane(p: _Prefill, status: str) -> None:
            """Terminate a mid-prefill admission lane."""
            lanes.remove(p)
            fail_slot_state(p.slot, p.req, status)

        def terminal_parked(p: _Preempted, status: str) -> None:
            """Terminate a parked (swapped-out) request: free its kept
            prefix refs, drop its swapped bytes, harvest its tokens."""
            preempted.remove(p)
            finished.append((p.slot, -1, t, False, status))
            bump(status)
            released = alloc.free(p.kept)
            if index is not None:
                index.drop_pages(released)
            rid = p.slot.req.rid
            if rid in swap:
                swap.pop(rid)

        def reap_status(r: Request) -> Optional[str]:
            """Terminal status a live/waiting request must take this tick
            (cancellation beats timeout), or None to keep serving."""
            if r.rid in cancel_pending:
                return "cancelled"
            if r.deadline_steps is not None \
                    and t >= r.arrival + r.deadline_steps:
                return "timeout"
            return None

        def pool_alloc(n: int) -> Optional[List[int]]:
            """``alloc.alloc`` through the fault seam: a ``deny_alloc``
            tick answers None (pool exhausted) regardless of free pages."""
            nonlocal fault_hold
            if fault is not None and fault.deny_alloc(t):
                stats.fault_events += 1
                fault_hold = True
                return None
            return alloc.alloc(n)

        def harvest_slot_tokens(slot: _Slot) -> List[int]:
            """Tokens this leg emitted so far (device sync in async mode)."""
            if use_eos:
                return list(slot.tokens)
            out = [int(np.asarray(slot.first)[0, 0])]
            for row, c in slot.cols:
                out.append(int(np.asarray(step_cols[c])[row, 0]))
            return out

        def preempt(j: int) -> None:
            """Evict live slot j mid-decode to hand its pages to someone else.

            ``recompute``: the victim's generated tokens so far are banked in
            ``carry`` and the request re-queues as a continuation whose prompt
            is original-prompt + generated-tokens — the existing chunked
            prefill rebuilds its KV (and, under greedy decoding, continues
            the exact token stream).  ``swap``: its private pages are copied
            to the host SwapArea and restored verbatim on resume; shared
            prefix pages stay resident (refcount held) and are never moved.
            """
            nonlocal cache
            slot = slots[j]
            rid = slot.req.rid
            stats.preemptions += 1
            stats.preempted_rids[rid] = stats.preempted_rids.get(rid, 0) + 1
            # non-paged engines (dense KV, recurrent state) have no pages to
            # park or free — eviction + recompute covers every state kind
            pages = slot_pages.pop(j) if alloc is not None else None
            park = swap is not None
            if park and fault is not None and fault.deny_swap(t):
                # injected host-memory refusal: degrade to recompute
                stats.fault_events += 1
                stats.swap_refusals += 1
                park = False
            if park:
                # COW admission keeps shared mappings a contiguous row
                # prefix; split it from the private tail
                m = 0
                while m < len(pages) and alloc.refcount(pages[m]) > 1:
                    m += 1
                kept, priv = pages[:m], pages[m:]
                assert all(alloc.refcount(p) == 1 for p in priv), \
                    "shared page past the private tail — refcount layout bug"
                data, pad = None, 0
                if priv:
                    # pow2-pad the gather so swap traffic reuses a handful
                    # of compiled shapes instead of one per page count
                    pad = 1
                    while pad < len(priv):
                        pad *= 2
                    idx = jnp.asarray(priv + [priv[0]] * (pad - len(priv)),
                                      jnp.int32)
                    # device_get blocks: the host copy is complete before
                    # the pages re-enter the free list below
                    with spans.child("serve.readback"):
                        data = jax.device_get(self._gather_pages(cache, idx))
                if not swap.fits(_tree_bytes(data)):
                    # SwapArea capacity (swap_bytes) refusal: recompute
                    stats.swap_refusals += 1
                    park = False
            if park:
                stats.swapped_pages += len(priv)
                swap.put(rid, data)
                stats.swap_peak_bytes = swap.peak_bytes
                preempted.append(_Preempted(
                    slot=slot, kept=kept, n_priv=len(priv), data=data,
                    pad=pad, live_len=slot.plen + slot.emitted - 1,
                    last_tok=tok[j:j + 1]))
                cache = self._evict(cache, jnp.int32(j))
                released = alloc.free(priv)    # kept pages: refs retained
                if index is not None:
                    index.drop_pages(released)
            else:
                with spans.child("serve.readback"):
                    toks = harvest_slot_tokens(slot)
                carry[rid] = carry.get(rid, []) + toks
                remaining = slot.req.max_new - slot.emitted   # >= 1 here
                cont_prompt = np.concatenate(
                    [np.asarray(slot.req.prompt, np.int32).reshape(-1),
                     np.asarray(toks, np.int32)])
                plen_of[rid] = int(cont_prompt.shape[0])
                prompt_keys.pop(rid, None)     # digests are stale now
                cache = self._evict(cache, jnp.int32(j))
                if alloc is not None:
                    released = alloc.free(pages)
                    if index is not None:
                        index.drop_pages(released)
                requeue(dataclasses.replace(slot.req, prompt=cont_prompt,
                                            max_new=remaining))
            slots[j] = None

        def try_resume() -> None:
            """Restore parked (swap-policy) requests, FIFO, while room lasts."""
            nonlocal cache, tok, enc_buf
            while preempted:
                p = preempted[0]
                free = [j for j in range(nslots) if slots[j] is None
                        and all(p.slot != j for p in lanes)]
                if not free:
                    stats.resume_stalls += 1
                    return
                got = pool_alloc(p.n_priv)
                if got is None:
                    stats.resume_stalls += 1
                    return
                j = free[0]
                rid = p.slot.req.rid
                data = swap.pop(rid)
                if p.n_priv:
                    # dup-pad the scatter to the gather's pow2 shape; the
                    # duplicate indices rewrite the same page with the same
                    # contents, which is idempotent
                    idx = jnp.asarray(got + [got[0]] * (p.pad - p.n_priv),
                                      jnp.int32)
                    cache = self._scatter_pages(cache, idx, data)
                row = p.kept + got
                slot_pages[j] = row
                cache = self._set_pages(cache, jnp.int32(j),
                                        self._page_row(row))
                cache = self._set_len(cache, jnp.int32(j),
                                      jnp.int32(p.live_len))
                tok = self._set_tok(tok, p.last_tok, jnp.int32(j))
                if enc_buf is not None:
                    enc_buf = self._set_enc(enc_buf, enc_of[rid],
                                            jnp.int32(j))
                    if self._cross_cached:
                        cache = self._write_xkv(eng.params, cache,
                                                enc_of[rid], jnp.int32(j))
                if index is not None and rid in prompt_keys:
                    index.insert_keys(prompt_keys[rid],
                                      row[:p.slot.plen // eng.page_size])
                slots[j] = p.slot    # cols hold (row, col) pairs, so the
                preempted.pop(0)     # slot index change is harvest-safe
                stats.resumes += 1
                stats.peak_pages_in_use = alloc.peak_in_use

        def ensure_growth() -> None:
            """Lazy decode growth: extend any slot about to cross a page
            boundary; preempt a victim when the pool is dry."""
            nonlocal cache
            for j in range(nslots):
                slot = slots[j]
                if slot is None:
                    continue
                need_rows = slot.plen + slot.emitted   # next write position+1
                while slots[j] is not None \
                        and need_rows > len(slot_pages[j]) * eng.page_size:
                    if len(slot_pages[j]) >= eng.kv_max_pages:
                        raise RuntimeError(
                            f"slot {j} (rid {slot.req.rid}) needs row "
                            f"{need_rows} past its page table "
                            f"({eng.kv_max_pages} pages) — run() validation "
                            f"should have rejected this request")
                    got = pool_alloc(1)
                    if got is not None:
                        pos = len(slot_pages[j])
                        slot_pages[j].append(got[0])
                        cache = self._append_page(cache, jnp.int32(j),
                                                  jnp.int32(pos),
                                                  jnp.int32(got[0]))
                        stats.grown_pages += 1
                        stats.peak_pages_in_use = alloc.peak_in_use
                        continue
                    # pool dry mid-decode: preempt. Victims are picked
                    # starvation-free (aged slots become untouchable); each
                    # preemption removes a candidate, so this terminates.
                    cands = [(i, s.req.rid, s.emitted, s.admitted_at)
                             for i, s in enumerate(slots) if s is not None]
                    victim = pick_preemption_victim(
                        cands, stats.preempted_rids, self.preempt_aging)
                    preempt(victim)

        t0 = time.perf_counter()
        while pending or queue or lanes or preempted \
                or any(s is not None for s in slots):
            spans.close()
            if on_tick is not None:
                on_tick(t)
            spans.open(t)
            fault_hold = False

            # -- arrivals + bounded-queue backpressure ----------------------
            spans.phase("serve.arrivals")
            while pending and pending[0].arrival <= t:
                r = pending.popleft()
                if time_ticks:
                    arrival_wall.setdefault(r.rid, time.perf_counter())
                if self.max_queue is not None \
                        and len(queue) >= self.max_queue:
                    if self.reject_policy == "shed_oldest":
                        victim = next(
                            (q for q in queue if q.rid not in cont_rids),
                            None)
                        if victim is not None:
                            queue.remove(victim)
                            print(f"serve: queue full ({self.max_queue}) — "
                                  f"shedding oldest waiting request "
                                  f"{victim.rid} for arrival {r.rid}")
                            terminal_queued(victim, "rejected")
                            queue.append(r)
                            continue
                    print(f"serve: queue full ({self.max_queue}) — "
                          f"rejecting request {r.rid}")
                    terminal_queued(r, "rejected")
                    continue
                queue.append(r)

            # -- cancellation + deadline sweep, every residence state -------
            if cancels:
                for rid_, tk_ in cancels.items():
                    if tk_ <= t:
                        cancel_pending.add(rid_)
            if self._cancel_box:
                cancel_pending |= self._cancel_box
                self._cancel_box = set()
            if cancel_pending or has_deadlines:
                for r in list(queue):
                    st = reap_status(r)
                    if st:
                        queue.remove(r)
                        cancel_pending.discard(r.rid)
                        terminal_queued(r, st)
                for p in list(lanes):
                    st = reap_status(p.req)
                    if st:
                        cancel_pending.discard(p.req.rid)
                        abort_lane(p, st)
                for p in list(preempted):
                    st = reap_status(p.slot.req)
                    if st:
                        cancel_pending.discard(p.slot.req.rid)
                        terminal_parked(p, st)
                for j in range(nslots):
                    if slots[j] is not None:
                        st = reap_status(slots[j].req)
                        if st:
                            cancel_pending.discard(slots[j].req.rid)
                            finish(j, slots[j], False, status=st)

            # -- forced preemption drills (``preempts={rid: tick}``) --------
            # fire on the first tick >= the requested tick where the rid is
            # live; entries for already-finished rids are dropped
            if preempts:
                for rid_, tk_ in list(preempts.items()):
                    if tk_ > t:
                        continue
                    if rid_ in results:
                        preempts.pop(rid_)
                        continue
                    for j in range(nslots):
                        if slots[j] is not None \
                                and slots[j].req.rid == rid_:
                            preempt(j)
                            preempts.pop(rid_)
                            break

            spans.phase("serve.admit")
            # Oversubscription housekeeping runs before admission: parked
            # requests get first claim on freed pages (no starvation behind
            # a stream of fresh admissions), then live slots grow into
            # whatever remains before a new reservation can take it.
            if self.oversubscribe:
                if preempted:
                    try_resume()
                ensure_growth()

            chunk_job: Optional[_Prefill] = None
            if C is None:
                # -- one-shot admission: freed slots pull from the queue ----
                free = [j for j in range(nslots) if slots[j] is None]
                while free and queue:
                    if fault is not None and fault.deny_admission(t):
                        stats.fault_events += 1
                        fault_hold = True
                        break
                    j, r = free.pop(0), queue.popleft()
                    first_start.setdefault(r.rid, t)
                    if any(s is not None for s in slots):
                        stats.admission_stalls += 1
                    padded, plen = self._pad_prompt(r.prompt)
                    rng, sub = jax.random.split(rng)
                    first, small = self._slot_prefill(eng.params, padded,
                                                      jnp.int32(plen), sub)
                    cache = self._admit(cache, small, jnp.int32(j),
                                        jnp.int32(plen))
                    tok = self._set_tok(tok, first, jnp.int32(j))
                    admit_live(j, r, first)
                    if slots[j] is None:
                        # finished on its first token (EOS / max_new=1):
                        # the slot is free again this very tick — without
                        # this the queue head would meet an idle batch and
                        # be failed as a deadlock below
                        free.insert(0, j)
            else:
                # -- chunked admission: reserve a slot (and, when paged, the
                # request's full page extent) per open lane for the oldest
                # arrived requests; chunks ride the mixed/ragged step -------
                while len(lanes) < max_lanes and queue:
                    if fault is not None and fault.deny_admission(t):
                        # injected admission stall: nobody enters this tick
                        stats.fault_events += 1
                        fault_hold = True
                        break
                    free = [j for j in range(nslots) if slots[j] is None
                            and all(p.slot != j for p in lanes)]
                    if not free:
                        break
                    r = queue[0]
                    plan = None
                    if alloc is not None:
                        if fault is not None and fault.deny_alloc(t):
                            # injected pool exhaustion at the admission seam
                            stats.fault_events += 1
                            stats.page_stalls += 1
                            fault_hold = True
                            break
                        plan = self._plan_admission(r, plen_of[r.rid],
                                                    alloc, index,
                                                    keys=digests_of(r))
                        if plan is None:
                            # page exhaustion defers the admission in
                            # the queue; eviction frees pages, so the
                            # retry eventually lands (decode never waits).
                            # Head-of-queue blocking on purpose: skipping
                            # ahead would starve the big request behind an
                            # endless stream of small ones.
                            stats.page_stalls += 1
                            break
                    queue.popleft()
                    j = free[0]
                    start0 = 0
                    if plan is not None:
                        row_pages, copies, n_share, start0 = plan
                        slot_pages[j] = list(row_pages)
                        if n_share or copies:
                            stats.prefix_hits += 1
                            stats.shared_pages_mapped += n_share
                            stats.cow_copies += len(copies)
                        # device order: privatize divergence pages
                        # (COW copy) BEFORE installing the row that
                        # points at the copies, then park the slot's
                        # live length at the shared-prefix boundary
                        # so the decode half's junk append for this
                        # still-prefilling slot lands in the private
                        # region, never through a shared mapping
                        for src, dst in copies:
                            cache = self._copy_page(
                                cache, jnp.int32(src), jnp.int32(dst))
                        cache = self._set_pages(
                            cache, jnp.int32(j),
                            self._page_row(row_pages))
                        if start0:
                            cache = self._set_len(
                                cache, jnp.int32(j),
                                jnp.int32(start0))
                        stats.peak_pages_in_use = alloc.peak_in_use
                    if enc_buf is not None:
                        enc_buf = self._set_enc(
                            enc_buf, enc_of[r.rid], jnp.int32(j))
                        if self._cross_cached:
                            # project + cache the encoder K/V once, at
                            # admission — decode steps read the cached rows
                            # instead of re-projecting ``enc`` every tick
                            cache = self._write_xkv(
                                eng.params, cache, enc_of[r.rid],
                                jnp.int32(j))
                    lanes.append(_Prefill(
                        req=r, slot=j,
                        prompt=np.asarray(r.prompt, np.int32).reshape(-1),
                        next_start=start0))
                if lanes and not self.ragged:
                    n_live = sum(s is not None for s in slots)
                    if self.token_budget is not None \
                            and n_live + C > self.token_budget:
                        stats.stalled_chunks += 1   # decode never waits
                    else:
                        chunk_job = lanes[0]

            spans.phase(None)
            if not any(s is not None for s in slots) and chunk_job is None \
                    and not (self.ragged and lanes):
                if not lanes:
                    if fault_hold:
                        # this tick idled because an injected fault denial
                        # blocked admission/alloc — a transient stall, not a
                        # deadlock.  Fault windows are finite by contract
                        # (serve/faults.py), so just let time pass.
                        t += 1
                        continue
                    # With nothing live, no pages will ever be freed again —
                    # a blocked resume or a page-stalled head request is a
                    # genuine deadlock, not a transient stall.  Convert ONE
                    # victim to status="failed" (freeing whatever it pins)
                    # and retry: the remaining requests usually survive.
                    # This used to raise mid-run and burn the whole batch.
                    if preempted:
                        p = preempted[0]
                        stats.deadlock_failures += 1
                        print(f"serve: unservable deadlock — parked request "
                              f"{p.slot.req.rid} cannot resume (pool pages "
                              f"pinned by parked shared prefixes, nothing "
                              f"live to free any); failing it to unblock "
                              f"(raise kv_pool_pages to avoid this)")
                        terminal_parked(p, "failed")
                        continue
                    if queue:
                        r = queue.popleft()
                        stats.deadlock_failures += 1
                        print(f"serve: request {r.rid} can never be "
                              f"admitted — nothing is live yet its "
                              f"admission plan still cannot be served from "
                              f"the pool ({eng.kv_num_pages} pages); "
                              f"failing it (raise kv_pool_pages or shrink "
                              f"the request)")
                        terminal_queued(r, "failed")
                        continue
                    if pending:   # idle gap: jump to the next arrival
                        t = max(t + 1, pending[0].arrival)
                continue

            # -- one batched step; finished slots emit masked pads -----------
            spans.phase("serve.assemble")
            active = [s is not None for s in slots]
            stats.peak_live_slots = max(
                stats.peak_live_slots, sum(active) + len(lanes))
            if active != active_host:       # rebuild device mask only on change
                active_host, active_dev = active, jnp.asarray(active)
            rng, sub = jax.random.split(rng)
            poison_dev, ok_host = None, None
            if self.audit:
                # all-zeros poison is an exact logits no-op; a scheduled
                # FaultPlan.nan event poisons its target slot's row the
                # first tick >= its tick where that slot is live
                poison_dev = zero_poison
                if poison_plan and t >= poison_plan[0][0] \
                        and slots[poison_plan[0][1]] is not None:
                    _, sj_ = poison_plan.popleft()
                    stats.fault_events += 1
                    vec = np.zeros(zero_poison.shape, np.float32)
                    vec[sj_] = np.nan
                    poison_dev = jnp.asarray(vec)
            admitted = []               # (slot, request, first) on last chunks
            chunks = []                 # (rid, start, clen) per lane that ran
            if self.ragged:
                # -- ONE ragged forward: B decode rows + L lanes x C chunk
                # rows flatten into a single token batch; idle slots and
                # lane tails are inert pad rows (position -1), so every
                # tick — pure decode included — is the same compiled step.
                rt = assemble_ragged_tick(
                    slots, lanes, nslots=nslots, n_lanes=self.prefill_lanes,
                    chunk=C, pad_id=self.pad_id,
                    token_budget=self.token_budget, n_active=sum(active),
                    assert_private=(
                        (lambda sj, lo, hi: self._assert_private_write(
                            slot_pages[sj], lo, hi, alloc))
                        if alloc is not None else None))
                stats.stalled_chunks += rt.stalled  # decode never waits
                ran = rt.ran
                step_rows = nslots + self.prefill_lanes * C
                step_in = (jnp.asarray(rt.ctok), jnp.asarray(rt.sids),
                           jnp.asarray(rt.poss), jnp.asarray(rt.lrows))
                spans.phase("serve.dispatch")
                if self.audit:
                    tok, firsts, ok, cache = self._masked_ragged(
                        eng.params, tok, cache, sub, active_dev, *step_in,
                        enc_buf, poison_dev)
                    with spans.child("serve.readback"):
                        ok_host = np.asarray(ok).reshape(-1)
                else:
                    tok, firsts, cache = self._masked_ragged(
                        eng.params, tok, cache, sub, active_dev, *step_in,
                        enc_buf)
                spans.phase("serve.emit")
                done = []
                for li, clen in ran:
                    p = lanes[li]
                    stats.prefill_chunks += 1
                    chunks.append((p.req.rid, p.next_start, clen))
                    first_start.setdefault(p.req.rid, t)
                    p.next_start += clen
                    if p.next_start >= int(p.prompt.shape[0]):
                        if ok_host is not None \
                                and not bool(ok_host[nslots + li]):
                            # NaN/Inf first-token logits: evict the lane's
                            # poisoned slot state instead of admitting it
                            stats.nan_evictions += 1
                            fail_slot_state(p.slot, p.req, "failed")
                            done.append(li)
                            continue
                        first = firsts[li:li + 1]
                        tok = self._set_tok(tok, first, jnp.int32(p.slot))
                        admitted.append((p.slot, p.req, first))
                        done.append(li)
                for li in reversed(done):
                    lanes.pop(li)
            elif chunk_job is not None:
                start = chunk_job.next_start
                plen = int(chunk_job.prompt.shape[0])
                clen = min(C, plen - start)
                ctok = np.full((1, C), self.pad_id, np.int32)
                ctok[0, :clen] = chunk_job.prompt[start:start + clen]
                if alloc is not None:
                    # the fused chunk write covers C (padded) rows: none may
                    # go through a shared mapping (COW ran at admission)
                    self._assert_private_write(
                        slot_pages[chunk_job.slot], start, start + C, alloc)
                step_rows = nslots + C
                step_in = (jnp.asarray(ctok), jnp.int32(chunk_job.slot),
                           jnp.int32(start), jnp.int32(clen))
                first_ok = None
                spans.phase("serve.dispatch")
                if self.audit:
                    tok, first, dec_ok, first_ok, cache = self._masked_mixed(
                        eng.params, tok, cache, sub, active_dev, *step_in,
                        enc_buf, poison_dev)
                    with spans.child("serve.readback"):
                        ok_host = np.asarray(dec_ok).reshape(-1)
                else:
                    tok, first, cache = self._masked_mixed(
                        eng.params, tok, cache, sub, active_dev, *step_in,
                        enc_buf)
                spans.phase("serve.emit")
                stats.prefill_chunks += 1
                chunks.append((chunk_job.req.rid, start, clen))
                first_start.setdefault(chunk_job.req.rid, t)
                chunk_job.next_start = start + clen
                if chunk_job.next_start >= plen:
                    poisoned = False
                    if first_ok is not None:
                        with spans.child("serve.readback"):
                            poisoned = not bool(
                                np.asarray(first_ok).reshape(-1)[0])
                    if poisoned:
                        # NaN/Inf first-token logits: evict, don't admit
                        stats.nan_evictions += 1
                        fail_slot_state(chunk_job.slot, chunk_job.req,
                                        "failed")
                    else:
                        tok = self._set_tok(tok, first,
                                            jnp.int32(chunk_job.slot))
                        admitted.append((chunk_job.slot, chunk_job.req,
                                         first))
                    lanes.pop(0)
            else:
                step_rows = nslots
                spans.phase("serve.dispatch")
                if self.audit:
                    tok, ok, cache = self._masked_decode(
                        eng.params, tok, cache, sub, active_dev, enc_buf,
                        poison_dev)
                    with spans.child("serve.readback"):
                        ok_host = np.asarray(ok).reshape(-1)
                else:
                    tok, cache = self._masked_decode(eng.params, tok, cache,
                                                     sub, active_dev,
                                                     enc_buf)
                spans.phase("serve.emit")
            if time_ticks:
                with spans.child("serve.readback"):
                    jax.block_until_ready(tok)
            stats.ticks.append(TickRecord(t, sum(active), tuple(chunks),
                                          step_rows, len(queue)))
            t += 1
            stats.decode_steps += 1
            stats.occupancy_sum += sum(active) / nslots
            if alloc is not None and alloc.pages_in_use:
                # internal-fragmentation gauge: live K/V rows per resident
                # pool token.  Sharing-aware: a pool page mapped by several
                # slots counts ONCE, at the deepest live row any mapper
                # reaches — summing per-slot lengths would double-count
                # shared prefixes and report occupancy > 1.0.
                fill: Dict[int, int] = {}

                def _acc(pages: List[int], live: int) -> None:
                    for i, pg in enumerate(pages):
                        rows = min(max(live - i * eng.page_size, 0),
                                   eng.page_size)
                        if rows > fill.get(pg, 0):
                            fill[pg] = rows

                for s_j, s_ in enumerate(slots):
                    if s_ is not None:
                        _acc(slot_pages[s_j], s_.plen + s_.emitted)
                for p_ in lanes:
                    _acc(slot_pages.get(p_.slot, []), p_.next_start)
                for p_ in preempted:   # parked shared prefixes stay live
                    _acc(p_.kept, len(p_.kept) * eng.page_size)
                stats.page_util_sum += sum(fill.values()) / (
                    alloc.pages_in_use * eng.page_size)
                stats.page_util_ticks += 1
            if use_eos:
                with spans.child("serve.readback"):
                    tok_host = np.asarray(tok)
            else:
                tok_host = None
            if not use_eos:
                step_cols.append(tok)
            for j in range(nslots):
                slot = slots[j]
                if slot is None:
                    continue
                if ok_host is not None and not bool(ok_host[j]):
                    # NaN/Inf logits in row j: evict the poisoned slot as
                    # failed — its garbage token is never recorded (emitted
                    # is not bumped, so the harvest stops at the last
                    # healthy token)
                    stats.nan_evictions += 1
                    finish(j, slot, False, status="failed")
                    continue
                slot.emitted += 1
                stats.tokens_out += 1
                hit_eos = False
                if use_eos:
                    tid = int(tok_host[j, 0])
                    slot.tokens.append(tid)
                    hit_eos = tid == self.eos_id
                else:
                    # (row, col): a swap-resumed slot may land in a new row
                    slot.cols.append((j, len(step_cols) - 1))
                if hit_eos or slot.emitted >= slot.req.max_new:
                    finish(j, slot, hit_eos)
            for a in admitted:
                admit_live(*a)

            # -- invariant audit: allocator/table/swap agreement every tick -
            if self.audit:
                spans.phase("serve.audit")
                holders: Dict[Any, List[int]] = {
                    ("slot", j_): pgs for j_, pgs in slot_pages.items()}
                for p_ in preempted:
                    holders[("parked", p_.slot.req.rid)] = p_.kept
                if alloc is not None:
                    check_allocator(alloc, holders)
                    kv = _find_paged_kv(cache)
                    if kv is not None:
                        with spans.child("serve.readback"):
                            table = np.asarray(kv["page_table"])
                            lens = np.asarray(kv["len"])
                        if table.ndim == 3:    # scan-stacked layer axis
                            table = table[0]
                        if lens.ndim == 2:
                            lens = lens[0]
                        # live decode slots pin their device len exactly
                        # (plen + emitted - 1 rows written); mid-prefill
                        # lanes only lower-bound it — the fused mixed
                        # step's masked junk appends may run a lane's len
                        # ahead of its chunk cursor (see nn/attention.py
                        # append_kv_decode)
                        exact = {j_: s_.plen + s_.emitted - 1
                                 for j_, s_ in enumerate(slots)
                                 if s_ is not None}
                        mins = {p_.slot: p_.next_start for p_ in lanes}
                        check_page_tables(
                            table, lens, slot_pages, alloc.refcount,
                            exact_lens=exact, min_lens=mins,
                            page_size=eng.page_size)
                check_swap(swap, [(p_.slot.req.rid, p_.data)
                                  for p_ in preempted])
                if self._has_recurrent:
                    # dead slots must hold exactly-zero recurrent rows —
                    # any leak through merge_inactive decodes garbage for
                    # the NEXT occupant, so catch it the tick it happens
                    live_rec = {j_ for j_, s_ in enumerate(slots)
                                if s_ is not None}
                    live_rec |= {p_.slot for p_ in lanes}
                    check_recurrent_rows(cache, live_rec)
                if self._cross_cached:
                    want_xl = {j_: int(enc_of[s_.req.rid].shape[1])
                               for j_, s_ in enumerate(slots)
                               if s_ is not None}
                    for p_ in lanes:
                        want_xl[p_.slot] = int(
                            enc_of[p_.req.rid].shape[1])
                    check_cross_lens(cache, want_xl)
                stats.audited_ticks += 1
        spans.close()
        stats.steady_s = time.perf_counter() - t0
        stats.num_jit_compiles = self._count_jit_compiles()

        # -- harvest: one device->host sync for the whole run (async mode) --
        if step_cols:
            mat = np.asarray(jnp.concatenate(step_cols, axis=1))
        for slot, j, t_fin, eos, status in finished:
            r = slot.req
            if not use_eos:
                slot.tokens = [int(np.asarray(slot.first)[0, 0])] \
                    + [int(mat[row, c]) for row, c in slot.cols]
            # recompute preemption: tokens banked by earlier legs come
            # first; the result is keyed to the ORIGINAL prompt length and
            # first admission tick, so preemption is invisible downstream
            results[r.rid] = RequestResult(
                rid=r.rid, tokens=carry.pop(r.rid, []) + slot.tokens,
                prompt_len=orig_plen[r.rid],
                arrival=r.arrival,
                admitted_at=first_admit.get(r.rid, slot.admitted_at),
                finished_at=t_fin, eos=eos, status=status,
                started_at=first_start.get(r.rid, -1))
        return results, stats


# --------------------------------------------------------------------------
# Restart-the-batch baseline (what continuous batching replaces)
# --------------------------------------------------------------------------

def run_restart_batching(engine, requests: Sequence[Request], *, seed: int = 0,
                         warmup: bool = True, eos_id: Optional[int] = None,
                         ) -> Tuple[Dict[int, RequestResult], ServeStats]:
    """Serve via lockstep ``generate()`` restarts: gather whatever has
    arrived (≤ batch_slots), run the whole batch for the *longest* request's
    horizon, restart.  Late arrivals wait for the restart; short requests pad
    out the batch.  The bench's comparison point for the scheduler's
    steady-state throughput (benchmarks/serve_bench.py).
    """
    reqs = sorted(requests, key=lambda r: (r.arrival, r.rid))
    plens = {int(np.asarray(r.prompt).reshape(-1).shape[0]) for r in reqs}
    if len(plens) != 1:
        raise ValueError(f"restart baseline needs equal prompt lengths: {plens}")
    plen = plens.pop()
    nslots = engine.batch_slots
    stats = ServeStats()
    stats.peak_cache_bytes = engine.cache_bytes()
    max_horizon = max(r.max_new for r in reqs)

    if warmup:
        t0 = time.perf_counter()
        dummy = jnp.zeros((nslots, plen), jnp.int32)
        jax.block_until_ready(engine.generate(dummy, max_horizon, seed=seed))
        stats.compile_s = time.perf_counter() - t0

    queue = deque(reqs)
    results: Dict[int, RequestResult] = {}
    t = 0
    t0 = time.perf_counter()
    while queue:
        if queue[0].arrival > t:
            t = queue[0].arrival
        batch: List[Request] = []
        while queue and queue[0].arrival <= t and len(batch) < nslots:
            batch.append(queue.popleft())
        horizon = max(r.max_new for r in batch)
        prompts = np.zeros((nslots, plen), np.int32)
        for i, r in enumerate(batch):
            prompts[i] = np.asarray(r.prompt, np.int32).reshape(-1)
        out = np.asarray(engine.generate(jnp.asarray(prompts), horizon,
                                         seed=seed))
        for i, r in enumerate(batch):
            toks = [int(x) for x in out[i, :r.max_new]]
            eos = False
            if eos_id is not None and eos_id in toks:
                toks, eos = toks[:toks.index(eos_id) + 1], True
            results[r.rid] = RequestResult(
                rid=r.rid, tokens=toks, prompt_len=plen, arrival=r.arrival,
                admitted_at=t, finished_at=t + horizon, eos=eos)
            stats.tokens_out += len(toks)
            stats.latencies_steps.append(t + horizon - r.arrival)
        for step in range(horizon):
            stats.occupancy_sum += sum(
                1 for r in batch if r.max_new > step) / nslots
        stats.decode_steps += horizon
        t += horizon
    stats.steady_s = time.perf_counter() - t0
    stats.completed = len(results)    # the baseline serves everything "ok"
    return results, stats
