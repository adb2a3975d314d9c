"""Serving layer: jitted step engine, continuous-batching scheduler, paging.

``ServeEngine`` owns the jitted prefill/decode/mixed steps and the cache
geometry (dense slabs or a paged pool); ``Scheduler`` owns batch policy
(admission, eviction, page allocation) over the per-slot decode-state
adapters in ``serve/slot_state.py`` (paged/dense KV, recurrent SSM/RWKV
state, cached EncDec cross-attention); ``PageAllocator`` is the host-side
free list behind paged admission.  See docs/serving.md for the architecture.
"""
from repro.serve.admission import (AdmissionPlanner,  # noqa: F401
                                   pick_preemption_victim)
from repro.serve.audit import (AuditError, check_allocator,  # noqa: F401
                               check_cross_lens, check_page_tables,
                               check_recurrent_rows, check_swap)
from repro.serve.engine import (ServeEngine, make_decode_step,  # noqa: F401
                                make_mixed_step, make_prefill_step,
                                mask_vocab_tail, sample_tokens)
from repro.serve.faults import FaultPlan  # noqa: F401
from repro.serve.lanes import assemble_ragged_tick  # noqa: F401
from repro.serve.paging import (PageAllocator, PrefixIndex,  # noqa: F401
                                SwapArea)
from repro.serve.scheduler import (STATUSES, Request,  # noqa: F401
                                   RequestResult, Scheduler, ServeStats,
                                   TickRecord, run_restart_batching)
from repro.serve.slot_state import (CrossAttnState,  # noqa: F401
                                    DenseKVState, PagedKVState,
                                    RecurrentState, SlotState, adapters_for,
                                    state_bytes_per_slot, state_kinds)
