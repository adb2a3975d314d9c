#!/usr/bin/env python3
"""Smoke run of the quantized serving path on a TPU, through the library.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # four chips: sharded training only

The model is smollm-135m at its published widths (30 layers, d_model 576,
9 query / 3 KV heads, head_dim 64, d_ff 1536, vocab 49152), bf16 params drawn
from ``--seed``; prompts are drawn from the same seed.  No weights, tokenizer
or network are needed.

One chip, one process, four phases:

1. device: JAX must find a TPU and the kernels must dispatch as compiled
   Pallas (no ``REPRO_KERNELS_FORCE``, no interpreter, no jnp oracle);
2. serve: ``ServeEngine`` + ``Scheduler`` (16 slots, max_len 2048, paged int8
   KV at the hardware page size, ragged ticks with 2 prefill lanes of 256
   tokens, an EOS id) serve 32 requests arriving one tick apart, once with
   bf16 weights and once with int8 weights; every request must end ``ok``;
3. kernels: the scheduler's compiled ragged step must hold the fused
   attention kernel (and, with int8 weights, the weight-only matmul) as TPU
   custom calls, not the jnp fallback;
4. correct: on the first ragged tick, compiled Pallas dispatch against the
   jnp oracle (``ops.FORCE = "ref"``) on the same chip: layer 0's int8 K/V
   bytes must be identical at the serving precision, and the logits must
   agree with both sides at "highest" matmul precision.

``--four-chips`` runs only the multi-chip path: three training steps on a
2x2 (data, model) mesh against the same steps on one device, both at
"highest" matmul precision.

The last line of stdout is ``{"ok": true, "device": {...}}``; any failure
exits non-zero before printing it.  Times printed on the way are smoke
output (compilation included), not measurements.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.core.policy import QuantPolicy  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models.registry import get_config  # noqa: E402
from repro.nn.attention import RaggedBatch  # noqa: E402
from repro.nn.module import Context  # noqa: E402
from repro.serve import Request, ServeEngine  # noqa: E402
from repro.serve.engine import HW_MIN_PAGE_SIZE  # noqa: E402
from repro.serve.slot_state import set_cache_page_row  # noqa: E402

ARCH = "smollm-135m"
SLOTS, MAX_LEN, CHUNK, LANES = 16, 2048, 256, 2
N_REQUESTS, MAX_NEW, EOS_ID = 32, 64, 2
PROMPT_LENS = (128, 1024)

# Pallas vs jnp oracle on the first ragged tick, both at "highest" matmul
# precision: at the default a TPU rounds f32 matmul operands to bf16, in
# XLA's attention einsums and in the kernel's, which alone moved the logits
# by 0.59 on a v5e.  At "highest" both sides quantize K/V onto the same int8
# grid and share every other op, so they differ only in the attention's
# accumulation order (online softmax over page blocks vs one full softmax),
# ~1e-6 relative in f32.  But the attention output is cast to bf16, and a
# one-ulp flip there (2^-8 relative) is carried through 30 bf16 layers into
# logits of magnitude ~5: with the CPU interpreter at full depth the gap is
# 0.086.  A wrong head or mask moves logits by their own magnitude (~5
# already at 2 layers).  0.5 sits above the rounding noise and well below a
# fault.
LOGIT_ATOL = 0.5


class PhaseError(RuntimeError):
    """A phase ran but its result is wrong."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise PhaseError(msg)


def check_device(chips: int):
    devices = jax.devices()
    dev = devices[0]
    print(f"[device] platform={dev.platform} kind={dev.device_kind!r} "
          f"count={len(devices)}", flush=True)
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, but JAX found platform "
                         f"{dev.platform!r}; refusing to run on it")
    if len(devices) < chips:
        raise SystemExit(f"chip_smoke: needs {chips} chips, JAX found "
                         f"{len(devices)}")
    if os.environ.get("REPRO_KERNELS_FORCE") or ops.FORCE is not None:
        raise SystemExit("chip_smoke: kernel dispatch is forced "
                         f"(REPRO_KERNELS_FORCE / ops.FORCE = {ops.FORCE!r}); "
                         "unset it — the chip run must dispatch compiled "
                         "Pallas")
    if not ops.is_hardware_dispatch():
        raise SystemExit("chip_smoke: kernels would not dispatch as compiled "
                         "Pallas on this backend")
    return dev, len(devices)


# --------------------------------------------------------------------------
# one chip: serving
# --------------------------------------------------------------------------

def workload(vocab: int, seed: int):
    rng = np.random.default_rng(seed)
    lens = rng.integers(PROMPT_LENS[0], PROMPT_LENS[1] + 1, size=N_REQUESTS)
    return [Request(rid=i, prompt=rng.integers(0, vocab, size=int(n),
                                               dtype=np.int32),
                    max_new=MAX_NEW, arrival=i)
            for i, n in enumerate(lens)]


def build_engine(model, params, weight_quant, page_size=None) -> ServeEngine:
    return ServeEngine(model=model, params=params, max_len=MAX_LEN,
                       batch_slots=SLOTS, weight_quant=weight_quant,
                       quantized_kv=True, paged_kv=True, page_size=page_size)


def serve_phase(model, params, reqs, weight_quant, seed: int):
    name = weight_quant or "bf16"
    engine = build_engine(model, params, weight_quant)
    check(engine.page_size == HW_MIN_PAGE_SIZE,
          f"page size {engine.page_size}, expected the hardware default "
          f"{HW_MIN_PAGE_SIZE}")
    check(engine.kv_num_pages == SLOTS * MAX_LEN // HW_MIN_PAGE_SIZE,
          f"pool of {engine.kv_num_pages} pages is not at dense parity")
    sched = engine.scheduler(eos_id=EOS_ID, chunk_size=CHUNK, ragged=True,
                             prefill_lanes=LANES)
    t0 = time.perf_counter()
    results, stats = sched.run(reqs, seed=seed)
    wall = time.perf_counter() - t0
    bad = {rid: r.status for rid, r in results.items() if r.status != "ok"}
    check(len(results) == len(reqs) and not bad,
          f"serve[{name}]: {len(results)}/{len(reqs)} results, not ok: {bad}")
    n_tok = sum(len(r.tokens) for r in results.values())
    n_eos = sum(bool(r.eos) for r in results.values())
    check(n_tok == stats.tokens_out, f"serve[{name}]: {n_tok} tokens "
          f"returned vs {stats.tokens_out} counted")
    print(f"[serve {name}] {len(results)} requests ok, {n_tok} tokens "
          f"({n_eos} ended on EOS), {stats.prefill_chunks} prefill chunks; "
          f"compile {stats.compile_s:.1f}s, run {wall:.1f}s", flush=True)
    return engine, sched


def ragged_step_hlo(engine: ServeEngine, sched) -> str:
    """Compiled HLO of the scheduler's ragged step at its serving shapes."""
    b, t = SLOTS, SLOTS + LANES * CHUNK
    i32 = jnp.int32
    sds = jax.ShapeDtypeStruct
    cache = jax.eval_shape(lambda: engine.new_cache(per_slot=True))
    lowered = sched._masked_ragged.lower(
        engine.params, sds((b, 1), i32), cache, jax.random.PRNGKey(0),
        sds((b,), jnp.bool_), sds((LANES, CHUNK), i32), sds((t,), i32),
        sds((t,), i32), sds((b + LANES,), i32))
    return lowered.compile().as_text()


def kernels_phase(engine: ServeEngine, sched, kernels) -> None:
    hlo = ragged_step_hlo(engine, sched)
    calls = [ln for ln in hlo.splitlines() if "tpu_custom_call" in ln]
    check(calls, "the compiled ragged step holds no tpu_custom_call: the "
          "jnp fallback ran instead of the fused kernels")
    for k in kernels:
        n = sum(k in ln for ln in calls)
        check(n > 0, f"no {k} custom call in the compiled ragged step")
    print(f"[kernels {engine.weight_quant or 'bf16'}] {len(calls)} "
          f"tpu_custom_call ops in the compiled ragged step; found "
          f"{', '.join(kernels)}", flush=True)


# --------------------------------------------------------------------------
# one chip: Pallas vs oracle on the first ragged tick
# --------------------------------------------------------------------------

def first_tick(model, params, prompts):
    """One ragged tick as the scheduler's first: each prompt's first chunk
    in its own lane (slot l, pages of its own), every decode row inert.
    Returns the logits at every valid chunk row and layer 0's int8 K/V
    pools after the tick.  A new engine and a new jit per call, so the
    kernel dispatch is traced afresh."""
    engine = build_engine(model, params, False, page_size=HW_MIN_PAGE_SIZE)
    b, t = SLOTS, SLOTS + LANES * CHUNK
    flat = np.zeros((t,), np.int32)
    slot_ids = np.concatenate([np.arange(b), np.zeros(t - b)]).astype(np.int32)
    positions = np.full((t,), -1, np.int32)
    rows = []
    cache = engine.new_cache(per_slot=True)
    pages_per_lane = CHUNK // engine.page_size
    for lane, p in enumerate(prompts):
        n = min(len(p), CHUNK)
        lo = b + lane * CHUNK
        flat[lo:lo + n] = p[:n]
        slot_ids[lo:lo + CHUNK] = lane
        positions[lo:lo + n] = np.arange(n)
        rows.extend(range(lo, lo + n))
        row = np.full((engine.kv_max_pages,), -1, np.int32)
        row[:pages_per_lane] = lane * pages_per_lane + np.arange(
            pages_per_lane)
        cache = set_cache_page_row(cache, jnp.int32(lane), jnp.asarray(row))

    @jax.jit
    def tick(params, cache):
        ctx = Context(policy=QuantPolicy.float32(), train=False)
        logits, cache = model.apply(
            params, jnp.asarray(flat)[None], ctx, cache=cache, decode=True,
            ragged=RaggedBatch(slots=jnp.asarray(slot_ids),
                               positions=jnp.asarray(positions)),
            logit_rows=jnp.asarray(rows, jnp.int32))
        kv = cache["body"][0]["kv"]              # layers stacked on axis 0
        return (logits[0, :, :model.vocab].astype(jnp.float32),
                kv["k"][0], kv["v"][0])

    return jax.device_get(tick(engine.params, cache))


def pallas_and_ref(model, params, prompts):
    pallas = first_tick(model, params, prompts)
    ops.FORCE = "ref"
    try:
        ref = first_tick(model, params, prompts)
    finally:
        ops.FORCE = None
    return pallas, ref


def correctness_phase(model, params, reqs) -> None:
    prompts = [r.prompt for r in reqs[:LANES]]
    # At the serving precision, layer 0's K/V rows come from the same ops on
    # both sides, so the kernel's quantize-on-write must store the bytes the
    # oracle stores.
    (_, pk, pv), (_, rk, rv) = pallas_and_ref(model, params, prompts)
    n_bad = int(np.sum(pk != rk) + np.sum(pv != rv))
    print(f"[correct] layer 0 int8 K/V after the first tick: {n_bad} of "
          f"{pk.size + pv.size} bytes differ from the oracle", flush=True)
    check(n_bad == 0, f"the kernel wrote {n_bad} K/V bytes the oracle "
          f"did not")

    with jax.default_matmul_precision("highest"):
        (pallas, _, _), (ref, _, _) = pallas_and_ref(model, params, prompts)
    check(np.all(np.isfinite(pallas)) and np.all(np.isfinite(ref)),
          "non-finite logits on the first ragged tick")
    diff = float(np.max(np.abs(pallas - ref)))
    agree = float(np.mean(pallas.argmax(-1) == ref.argmax(-1)))
    print(f"[correct] first ragged tick, {pallas.shape[0]} rows x "
          f"{pallas.shape[1]} logits: max |pallas - ref| = {diff!r} "
          f"(tolerance {LOGIT_ATOL}, max |ref| = "
          f"{float(np.max(np.abs(ref)))!r}); greedy tokens agree on "
          f"{agree:.4f} of rows", flush=True)
    check(diff < LOGIT_ATOL, f"Pallas logits differ from the oracle by "
          f"{diff} >= {LOGIT_ATOL}")


def one_chip(seed: int) -> None:
    cfg = get_config(ARCH)
    model = cfg.build(remat="off")                     # bf16 params
    t0 = time.perf_counter()
    params = model.init(jax.random.PRNGKey(seed))
    jax.block_until_ready(params)
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))
    print(f"[model] {ARCH}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
          f"{cfg.n_heads}/{cfg.n_kv_heads} heads, vocab {cfg.vocab}; "
          f"{n_params} params (bf16) in {time.perf_counter() - t0:.1f}s",
          flush=True)
    reqs = workload(cfg.vocab, seed)
    print(f"[workload] {len(reqs)} requests, prompt lengths "
          f"{min(len(r.prompt) for r in reqs)}-"
          f"{max(len(r.prompt) for r in reqs)}, max_new {MAX_NEW}, "
          f"eos_id {EOS_ID}", flush=True)
    for wq, kernels in ((False, ("qragged_attn",)),
                        ("int8", ("qragged_attn", "wq_matmul"))):
        engine, sched = serve_phase(model, params, reqs, wq, seed)
        kernels_phase(engine, sched, kernels)
        del engine, sched
    correctness_phase(model, params, reqs)


# --------------------------------------------------------------------------
# four chips: sharded training vs one device
# --------------------------------------------------------------------------

def four_chips(seed: int, steps: int = 3, batch: int = 8,
               seq: int = 512) -> None:
    from repro.data.pipeline import markov_batch_fn
    from repro.dist import sharding as shd
    from repro.launch.mesh import make_host_mesh
    from repro.optim import adamw
    from repro.train.trainer import make_train_step

    cfg = get_config(ARCH)
    model = cfg.build(dtype=jnp.float32, remat="none")    # as launch/train.py
    opt = adamw(weight_decay=0.01)
    lr = 3e-3
    params = jax.device_get(model.init(jax.random.PRNGKey(seed)))
    opt0 = jax.device_get(opt.init(params))
    batches = [markov_batch_fn(cfg.vocab, batch, seq, seed=seed)(i)
               for i in range(steps)]

    def run(step_fn, state, place_batch):
        losses = []
        for b in batches:
            state, mets = step_fn(state, place_batch(b))
            losses.append(float(mets["loss"]))
        return state, losses

    # Both sides at "highest" matmul precision: at the TPU's default (one
    # bf16 pass per f32 matmul) the sharded and single-device programs round
    # differently wherever their reduction orders differ, and AdamW turns
    # that into lr-sized updates on near-zero gradients (0.0095 apart after
    # three steps on a v5e).  At "highest" only f32 reduction order differs.
    with jax.default_matmul_precision("highest"):
        mesh = make_host_mesh(2, 2)
        rules = shd.make_axis_rules(mesh)
        pspecs = shd.param_pspecs(params, mesh, rules)
        gstate = {"params": jax.device_put(params, pspecs),
                  "opt": {"m": jax.device_put(opt0["m"], pspecs),
                          "v": jax.device_put(opt0["v"], pspecs),
                          "t": jnp.asarray(opt0["t"])},
                  "step": jnp.zeros((), jnp.int32)}
        t0 = time.perf_counter()
        step = jax.jit(make_train_step(model, opt, lr, mesh=mesh,
                                       axis_rules=rules), donate_argnums=(0,))
        gstate, sharded = run(step, gstate, lambda b: jax.device_put(
            b, shd.batch_pspecs(b, mesh, rules)))
        jax.block_until_ready(gstate)
        print(f"[train 2x2] {steps} steps, batch {batch}, seq {seq}: losses "
              f"{sharded!r} ({time.perf_counter() - t0:.1f}s incl. compile)",
              flush=True)
        for d in jax.devices():
            in_use = (d.memory_stats() or {}).get("bytes_in_use")
            print(f"[train 2x2] device {d.id}: bytes_in_use {in_use}",
                  flush=True)
        spread = [len(x.sharding.device_set)
                  for x in jax.tree_util.tree_leaves(gstate["params"])]
        check(max(spread) == 4,
              f"no parameter spans the four chips: {spread}")
        del gstate

        one = jax.devices()[0]
        state = jax.device_put({"params": params, "opt": opt0,
                                "step": np.zeros((), np.int32)}, one)
        t0 = time.perf_counter()
        step1 = jax.jit(make_train_step(model, opt, lr), donate_argnums=(0,))
        state, single = run(step1, state, lambda b: jax.device_put(b, one))
        print(f"[train 1 chip] losses {single!r} "
              f"({time.perf_counter() - t0:.1f}s incl. compile)", flush=True)
    diffs = [abs(a - b) for a, b in zip(sharded, single)]
    print(f"[train] max |sharded - single| loss = {max(diffs)!r} "
          f"(tolerance 1e-3)", flush=True)
    check(max(diffs) < 1e-3, f"sharded losses {sharded} differ from "
          f"single-device {single}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded-training path on a 2x2 mesh "
                         "(needs four chips)")
    args = ap.parse_args(argv)

    chips = 4 if args.four_chips else 1
    dev, count = check_device(chips)
    print(f"[cache] compile cache at {enable_compile_cache()}", flush=True)
    t0 = time.perf_counter()
    try:
        if args.four_chips:
            four_chips(args.seed)
        else:
            one_chip(args.seed)
    except PhaseError as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    print(f"[done] {time.perf_counter() - t0:.1f}s", flush=True)
    print(json.dumps({"ok": True, "device": {"platform": dev.platform,
                                             "kind": dev.device_kind,
                                             "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
