"""Serving driver: continuous batching under an arrival-schedule workload.

    PYTHONPATH=src python -m repro.launch.serve --arch smollm-135m-smoke \\
        --slots 4 --prompt-len 16 --requests 12 --max-new 32 --max-new-min 8 \\
        --arrival-spacing 2 [--wq] [--qkv] [--policy scheduler]

--wq   weight-only storage (bare = int8 → wq_matmul; int4[-block] /
       int2[-block] pack sub-int8 lanes → wq4_matmul; --wq-block sets the
       per-block scale granularity)
--qkv  int8 KV cache on the paper's Qm.n grid
Both reproduce the paper's deployment flow (train fp → quantize → deploy) at
the serving layer — now under realistic traffic instead of one lockstep batch.

Policies:
  chunked    continuous batching with chunked-prefill admission: every tick
             is ONE fused mixed step = all live decode slots + one
             --chunk-size prompt chunk written in place into its slot's KV
             slice.  Decode never stalls more than a chunk and every prompt
             length shares one compile shape.  --token-budget caps per-tick
             tokens (live slots + chunk; decode always runs)
  ragged     chunked, but every tick is ONE ragged forward over a flat token
             batch: all live decode tokens plus up to --prefill-lanes prompt
             chunks from *different* queued requests, routed by per-token
             slot/position vectors (one GEMM per layer per tick, one compile
             shape for the whole run).  --token-budget is split across lanes
             in admission order, so bursts drain --prefill-lanes times
             faster without stalling decode
  scheduler  continuous batching with one-shot admission: a freed slot is
             refilled by a stop-the-world batch-1 prefill + write_kv_slot
             copy (every live slot stalls for the full prompt)
  restart    restart-the-batch baseline: lockstep generate() per gathered
             batch, everyone waits for the longest request
  lockstep   the legacy single-batch generate() (no queue; --requests is
             clamped to --slots)

--paged (chunked/ragged) swaps the dense per-slot KV slabs for a shared page
pool + per-slot page tables: admission block-allocates ceil(extent /
--page-size) pages and defers on exhaustion instead of crashing;
--pool-pages sizes the pool (default dense parity).  Prefix sharing is on
by default in paged mode: requests whose prompt prefix matches resident
pages map them (refcounted, copy-on-write at the divergence page) instead
of allocating copies — --no-prefix-sharing measures the unshared baseline.
--oversubscribe switches admission to lazy decode pages (reserve the prompt
extent only, grow one page per crossed boundary) with --preempt-policy
{recompute,swap} deciding what happens when the pool runs dry mid-decode.
docs/serving.md walks the geometry and the knobs.

Hardening knobs (docs/serving.md "Failure semantics"): --deadline-steps puts
a per-request latency bound on the workload, --max-queue/--reject-policy
bound the waiting queue (backpressure), --audit runs the pool/state
invariant auditor every tick and arms the NaN/Inf logit sentinel, and
--fault-plan injects a deterministic failure schedule (serve/faults.py) for
chaos drills.  Every request always comes back with a terminal status.

Timing is reported as warmup/compile seconds and steady-state tok/s
*separately* — jit compile no longer pollutes the throughput figure.
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops as kops
from repro.launch.compile_cache import enable_compile_cache
from repro.models.registry import get_config
from repro.serve import Request, ServeEngine, run_restart_batching


def build_workload(args, vocab: int):
    """Arrival schedule: request i arrives at tick i*spacing with a prompt of
    --prompt-len tokens and max_new alternating across [min, max] (length
    spread is what continuous batching exploits)."""
    rng = np.random.default_rng(args.seed + 1)
    lo = args.max_new_min or args.max_new
    deadline = getattr(args, "deadline_steps", 0) or None
    reqs = []
    for i in range(args.requests):
        max_new = lo if (lo == args.max_new or i % 2 == 0) else args.max_new
        reqs.append(Request(
            rid=i,
            prompt=rng.integers(0, vocab, size=args.prompt_len,
                                dtype=np.int32),
            max_new=int(max_new),
            arrival=i * args.arrival_spacing,
            deadline_steps=deadline))
    return reqs


def report(name: str, stats) -> None:
    s = stats.summary()
    extra = ""
    if s.get("p99_latency_ms"):
        extra += (f" | latency p50/p99 {s['p50_latency_ms']:.1f}/"
                  f"{s['p99_latency_ms']:.1f} ms")
    if s.get("prefill_chunks"):
        extra += (f" | chunks {s['prefill_chunks']} "
                  f"(stalled {s['stalled_chunks']})")
    if s.get("num_jit_compiles"):
        extra += f" | jit shapes {s['num_jit_compiles']}"
    if s.get("peak_pages_in_use"):
        extra += (f" | pages peak {s['peak_pages_in_use']} "
                  f"(stalls {s['page_stalls']}, "
                  f"fill {s['page_occupancy']:.2f})")
    if s.get("prefix_hits"):
        extra += (f" | prefix hits {s['prefix_hits']} "
                  f"(shared {s['shared_pages_mapped']} pages, "
                  f"cow {s['cow_copies']})")
    if s.get("grown_pages"):
        extra += (f" | grown {s['grown_pages']} pages "
                  f"(preempt {s['preemptions']}, resume {s['resumes']}, "
                  f"swapped {s['swapped_pages']})")
    if s.get("p99_ttft_steps"):
        extra += (f" | ttft p50/p99 {s['p50_ttft_steps']:.0f}/"
                  f"{s['p99_ttft_steps']:.0f} steps")
    degraded = (s.get("rejections", 0) + s.get("timeouts", 0)
                + s.get("cancellations", 0) + s.get("failed", 0))
    if degraded:
        extra += (f" | completion {s['completion_rate']:.2f} "
                  f"(rej {s['rejections']}, timeout {s['timeouts']}, "
                  f"cancel {s['cancellations']}, failed {s['failed']})")
    if s.get("state_kinds"):
        extra += f" | state {s['state_kinds']}"
    if s.get("audited_ticks"):
        extra += f" | audited {s['audited_ticks']} ticks clean"
    if s.get("fault_events"):
        extra += (f" | faults {s['fault_events']} "
                  f"(swap refusals {s['swap_refusals']})")
    print(f"[{name}] warmup(compile) {s['compile_s']:.2f}s | "
          f"steady {s['steady_tok_s']:.1f} tok/s over {s['steady_s']:.3f}s | "
          f"occupancy {s['occupancy']:.2f} | "
          f"latency p50/p99 {s['p50_latency_steps']:.0f}/"
          f"{s['p99_latency_steps']:.0f} steps | "
          f"cache {s['peak_cache_bytes']/1024:.0f} KiB{extra}")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--slots", "--batch", type=int, default=4, dest="slots")
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--max-new-min", type=int, default=0,
                    help="alternate request horizons in [min, max] "
                         "(0 = uniform --max-new)")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--arrival-spacing", type=int, default=2,
                    help="decode-step ticks between request arrivals")
    ap.add_argument("--policy", default="scheduler",
                    choices=["chunked", "ragged", "scheduler", "restart",
                             "lockstep"])
    ap.add_argument("--prefill-lanes", type=int, default=2,
                    help="concurrent prompt-chunk lanes per ragged tick "
                         "(ragged policy; 1 reproduces chunked admission "
                         "order with the ragged kernel)")
    ap.add_argument("--chunk-size", type=int, default=16,
                    help="prefill chunk tokens per mixed/ragged step "
                         "(chunked and ragged policies; the last chunk's "
                         "padded rows must fit max_len, so keep it "
                         "<= --prompt-len)")
    ap.add_argument("--token-budget", type=int, default=0,
                    help="per-tick token cap for chunked admission "
                         "(0 = unbounded; must fit one chunk)")
    ap.add_argument("--paged", action="store_true",
                    help="paged KV cache: shared page pool + per-slot page "
                         "tables with block-allocated admission (chunked "
                         "policy only; see docs/serving.md)")
    ap.add_argument("--page-size", type=int, default=0,
                    help="tokens per KV page (paged mode; 0 = auto: 128 on "
                         "hardware Pallas dispatch, 16 elsewhere)")
    ap.add_argument("--pool-pages", type=int, default=0,
                    help="KV pool pages shared by all slots (0 = dense "
                         "parity: slots * ceil(max_len/page_size)); smaller "
                         "pools trade headroom for more slots per byte")
    ap.add_argument("--no-prefix-sharing", action="store_true",
                    help="disable prompt-prefix page sharing in paged mode "
                         "(on by default: same-prefix requests map the same "
                         "pool pages, COW at the divergence page)")
    ap.add_argument("--oversubscribe", action="store_true",
                    help="lazy decode pages (paged mode): admission reserves "
                         "only the prompt extent, decode grows one page per "
                         "crossed boundary and preempts a victim when the "
                         "pool runs dry (see --preempt-policy)")
    ap.add_argument("--preempt-policy", default="recompute",
                    choices=["recompute", "swap"],
                    help="mid-decode pool-exhaustion policy (with "
                         "--oversubscribe): 'recompute' re-queues the victim "
                         "as a continuation prompt re-prefilled later; "
                         "'swap' copies its private pages to host memory "
                         "and restores them bit-exactly on resume")
    ap.add_argument("--deadline-steps", type=int, default=0,
                    help="per-request deadline in decode-step ticks "
                         "(0 = none): a request unfinished this many ticks "
                         "after arrival is returned status='timeout' with "
                         "its tokens so far")
    ap.add_argument("--max-queue", type=int, default=0,
                    help="bound the arrived-and-waiting queue (0 = "
                         "unbounded): arrivals past the bound are shed "
                         "per --reject-policy as status='rejected'")
    ap.add_argument("--reject-policy", default="reject",
                    choices=["reject", "shed_oldest"],
                    help="bounded-queue backpressure: reject the new "
                         "arrival, or shed the oldest waiting request "
                         "in its favor")
    ap.add_argument("--audit", action="store_true",
                    help="run the pool/state invariant auditor every tick "
                         "and arm the NaN/Inf logit sentinel "
                         "(serve/audit.py; costs a per-tick host readback)")
    ap.add_argument("--fault-plan", default="",
                    help="deterministic fault injection: inline JSON "
                         "(starting '{') or a JSON file path — see "
                         "serve/faults.py FaultPlan.from_spec")
    ap.add_argument("--time-ticks", action="store_true",
                    help="block per tick and report wall-clock p50/p99 "
                         "request latency (ms)")
    ap.add_argument("--prompt-bucket", type=int, default=0,
                    help="round prompt lengths up to this multiple "
                         "(0 = exact lengths; one jit compile per length; "
                         "scheduler policy only)")
    ap.add_argument("--eos-id", type=int, default=-1,
                    help="stop a slot when this token is sampled (-1 = off)")
    ap.add_argument("--wq", nargs="?", const="int8", default=False,
                    choices=["int8", "int4", "int4-block", "int2",
                             "int2-block"],
                    help="weight-only storage format (bare --wq = int8; "
                         "int4/int2 pack two/four lanes per byte, -block "
                         "adds per-block scales)")
    ap.add_argument("--wq-block", type=int, default=32,
                    help="K rows per scale block for --wq *-block formats")
    ap.add_argument("--qkv", action="store_true")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    enable_compile_cache()
    dev = jax.devices()[0]
    print(f"[serve] device {dev.platform} ({dev.device_kind}) "
          f"x{len(jax.devices())}; kernels dispatch as {kops._mode()}")
    cfg = get_config(args.arch)
    model = cfg.build(dtype=jnp.float32, remat="off")
    params = model.init(jax.random.PRNGKey(args.seed))
    if args.paged and args.policy not in ("chunked", "ragged"):
        raise SystemExit("--paged requires --policy chunked or ragged "
                         "(block-allocated admission rides the fused step)")
    engine = ServeEngine(model=model, params=params,
                         max_len=args.prompt_len + args.max_new,
                         batch_slots=args.slots, quantized_kv=args.qkv,
                         weight_quant=args.wq, weight_block=args.wq_block,
                         temperature=args.temperature,
                         paged_kv=args.paged,
                         page_size=args.page_size or None,
                         kv_pool_pages=args.pool_pages or None)

    if args.policy == "lockstep":
        import time

        n = min(args.requests, args.slots)
        prompts = jax.random.randint(
            jax.random.PRNGKey(args.seed + 1), (args.slots, args.prompt_len),
            0, cfg.vocab, dtype=jnp.int32)
        t0 = time.perf_counter()
        jax.block_until_ready(engine.generate(prompts, args.max_new,
                                              seed=args.seed))
        warm = time.perf_counter() - t0
        t0 = time.perf_counter()
        out = engine.generate(prompts, args.max_new, seed=args.seed)
        out.block_until_ready()
        dt = time.perf_counter() - t0
        toks = n * args.max_new
        print(f"[lockstep] warmup(compile) {warm:.2f}s | "
              f"steady {toks/dt:.1f} tok/s over {dt:.3f}s")
        print(out[:n, :16])
        return out

    fault_plan = None
    if args.fault_plan:
        from repro.serve import FaultPlan

        fault_plan = FaultPlan.from_spec(args.fault_plan)
        if args.policy in ("restart", "lockstep"):
            raise SystemExit("--fault-plan requires a scheduler policy "
                             "(chunked/ragged/scheduler)")
        if fault_plan.nan and not args.audit:
            raise SystemExit("--fault-plan with nan events requires --audit "
                             "(the NaN sentinel is audit mode's health "
                             "readback)")
    reqs = build_workload(args, cfg.vocab)
    if args.policy == "restart":
        results, stats = run_restart_batching(
            engine, reqs, seed=args.seed,
            eos_id=None if args.eos_id < 0 else args.eos_id)
        report("restart", stats)
    else:
        sched = engine.scheduler(
            eos_id=None if args.eos_id < 0 else args.eos_id,
            prompt_bucket=args.prompt_bucket or None,
            chunk_size=(args.chunk_size
                        if args.policy in ("chunked", "ragged") else None),
            token_budget=(args.token_budget or None)
            if args.policy in ("chunked", "ragged") else None,
            ragged=args.policy == "ragged",
            prefill_lanes=(args.prefill_lanes
                           if args.policy == "ragged" else 1),
            prefix_sharing=not args.no_prefix_sharing,
            oversubscribe=args.oversubscribe,
            preempt_policy=args.preempt_policy,
            max_queue=args.max_queue or None,
            reject_policy=args.reject_policy,
            audit=args.audit)
        results, stats = sched.run(reqs, seed=args.seed,
                                   time_ticks=args.time_ticks,
                                   fault_plan=fault_plan)
        report(args.policy, stats)
    first = results[min(results)]
    print(f"request {first.rid}: {len(first.tokens)} tokens "
          f"({first.status}), first-10 {first.tokens[:10]}")
    return results


if __name__ == "__main__":
    main()
