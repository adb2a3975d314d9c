"""Compile rehearsal for a TPU v5e: the serve path's kernels at real widths.

Interpret mode (the other kernel suites) does not check Mosaic's tiling or
VMEM limits; these tests hand the kernels to the TPU compiler for a
*described* ``v5e:2x2`` topology — no chip is attached, nothing runs — at
smollm-135m's published attention widths (9 query / 3 KV heads, head_dim 64,
page 128), the hybrid ragged step at Jamba2-3B's, and the ragged lane
blocks at the wider heads of the larger registry models.  A refusal here is
what the chip would raise.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library, and every pytest worker imports
every test file.
"""
from __future__ import annotations

import dataclasses
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.qchunk_attn import qchunk_attn_pallas
from repro.kernels.qdecode_attn import qdecode_attn_pallas
from repro.kernels.qpaged_attn import (qpaged_chunk_attn_pallas,
                                       qpaged_decode_attn_pallas)
from repro.kernels.qragged_attn import (lane_block, qragged_attn_pallas,
                                        qragged_attn_write)
from repro.kernels.wq_matmul import wq4_matmul_pallas, wq_matmul_pallas

HQ, HKV, D = 9, 3, 64          # configs/smollm_135m.py
D_MODEL, D_FF = 576, 1536
SLOTS, MAX_LEN, PAGE = 16, 2048, 128
CHUNK, LANES = 256, 2
T = SLOTS + LANES * CHUNK      # ragged tick tokens
POOL = SLOTS * MAX_LEN // PAGE  # dense-parity pool pages
# configs/jamba2_3b.py attention: 20 query heads on 1 KV head of 128, over
# 32 slots of 136 pages (17,408 rows)
J_HQ, J_HKV, J_D, J_SLOTS, J_PAGES = 20, 1, 128, 32, 136
J_T = J_SLOTS + LANES * CHUNK
# Registry models whose ragged step takes the lanes path at wider heads:
# (query heads, KV heads) at head_dim 128 (configs/*.py), over 8 slots of
# 32 pages.  The block rule must keep all their query rows within VMEM.
WIDE = {"jamba-v0.1-52b": (32, 8), "qwen2.5-14b": (40, 8),
        "command-r-plus-104b": (96, 8), "glm4-9b": (32, 2)}
W_D, W_SLOTS, W_PAGES = 128, 8, 32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # A persistent cache entry compiled for a described chip cannot be read
    # back without one; keep these compiles out of any configured cache.
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _ragged_specs(hq, hkv, d, slots, pages):
    i8, f32, i32 = jnp.int8, jnp.float32, jnp.int32
    t, pool = slots + LANES * CHUNK, (slots * pages, PAGE, hkv, d, i8)
    return [(t, hq, d, f32), (t, hkv, d, f32), (t, hkv, d, f32), pool, pool,
            (i32,), (i32,), (slots, pages, i32), (t, i32), (t, i32)]


def _kernel_cases():
    i8, f32, i32 = jnp.int8, jnp.float32, jnp.int32
    pool = (POOL, PAGE, HKV, D)
    dense = (SLOTS, MAX_LEN, HKV, D)
    ragged = _ragged_specs(HQ, HKV, D, SLOTS, MAX_LEN // PAGE)
    lanes = functools.partial(qragged_attn_pallas, lanes=LANES, chunk=CHUNK)
    cases = {
        "qragged_attn": (qragged_attn_pallas, ragged),
        # the tick's geometry: decode rows at bq = 1, the lanes in blocks
        # (smollm: one 256-row block a lane; Jamba2: 64-row blocks)
        "qragged_attn_lanes": (lanes, ragged),
        "qragged_attn_lanes_jamba": (lanes, _ragged_specs(
            J_HQ, J_HKV, J_D, J_SLOTS, J_PAGES)),
        # the write on the flat (P, ps, Hkv * D) pools the attention reads
        "qragged_attn_write": (qragged_attn_write, [
            (T, HKV, D, f32), (T, HKV, D, f32), (POOL, PAGE, HKV * D, i8),
            (POOL, PAGE, HKV * D, i8), (i32,), (i32,),
            (SLOTS, MAX_LEN // PAGE, i32), (T, i32), (T, i32)]),
        "qpaged_decode_attn": (qpaged_decode_attn_pallas, [
            (SLOTS, HQ, D, f32), pool + (i8,), pool + (i8,), (i32,), (i32,),
            (SLOTS, MAX_LEN // PAGE, i32), (SLOTS, i32)]),
        "qpaged_chunk_attn": (qpaged_chunk_attn_pallas, [
            (CHUNK, HQ, D, f32), (CHUNK, HKV, D, f32), (CHUNK, HKV, D, f32),
            pool + (i8,), pool + (i8,), (i32,), (i32,),
            (MAX_LEN // PAGE, i32), (i32,)]),
        "qdecode_attn": (qdecode_attn_pallas, [
            (SLOTS, HQ, D, f32), dense + (i8,), dense + (i8,), (i32,),
            (i32,), (SLOTS, i32)]),
        "qchunk_attn": (qchunk_attn_pallas, [
            (CHUNK, HQ, D, f32), (CHUNK, HKV, D, f32), (CHUNK, HKV, D, f32),
            dense + (i8,), dense + (i8,), (i32,), (i32,), (i32,), (i32,)]),
        "wq_matmul": (functools.partial(wq_matmul_pallas, out_dtype=jnp.bfloat16),
                      [(T, D_MODEL, jnp.bfloat16), (D_MODEL, D_FF, i8),
                       (D_FF, f32)]),
        "wq4_matmul": (functools.partial(wq4_matmul_pallas, k=D_MODEL,
                                         block_size=32,
                                         out_dtype=jnp.bfloat16),
                       [(T, D_MODEL, jnp.bfloat16), (D_MODEL // 2, D_FF, i8),
                        (D_MODEL // 32, D_FF, f32)]),
    }
    for arch, (hq, hkv) in WIDE.items():
        cases[f"qragged_attn_lanes_{arch}"] = (lanes, _ragged_specs(
            hq, hkv, W_D, W_SLOTS, W_PAGES))
    return cases


@pytest.mark.parametrize("name", sorted(_kernel_cases()))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, specs = _kernel_cases()[name]
    args = [_sds(one_chip, s[:-1], s[-1]) for s in specs]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    if name.startswith("qragged_attn_lanes"):
        # the lane blocks are the launch this case is for
        (_, hq, d), hkv = specs[0][:3], specs[3][2]
        assert lane_block(hkv, hq // hkv, d, PAGE, CHUNK) > 1
        assert "qragged_attn_lanes" in compiled.as_text()
    mem = compiled.memory_analysis()
    # the whole program (kernel + layout copies around it) fits one chip
    assert (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes) < 16 * 2**30


def test_ragged_serve_step_compiles_for_v5e(one_chip, monkeypatch):
    """The scheduler's per-tick program — a 2-layer model at smollm-135m
    widths over a paged int8 pool — compiles with the ragged write and
    attention kernels in it."""
    from repro.models.registry import get_config
    from repro.serve import ServeEngine
    from repro.serve.engine import make_ragged_step

    monkeypatch.setattr(ops, "FORCE", "pallas")
    cfg = dataclasses.replace(get_config("smollm-135m"), n_layers=2)
    model = cfg.build(remat="off")
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    engine = ServeEngine(model=model, params=params, max_len=MAX_LEN,
                         batch_slots=SLOTS, quantized_kv=True, paged_kv=True)
    assert engine.page_size == PAGE
    cache = jax.eval_shape(functools.partial(engine.new_cache, per_slot=True))

    def place(tree):
        return jax.tree_util.tree_map(
            lambda s: _sds(one_chip, s.shape, s.dtype), tree)

    i32 = jnp.int32
    step = jax.jit(make_ragged_step(model), donate_argnums=(2,))
    compiled = step.lower(
        place(params), _sds(one_chip, (SLOTS, 1), i32), place(cache),
        _sds(one_chip, (2,), jnp.uint32), _sds(one_chip, (LANES, CHUNK), i32),
        _sds(one_chip, (T,), i32), _sds(one_chip, (T,), i32),
        _sds(one_chip, (SLOTS + LANES,), i32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "qragged_attn_write" in text
    assert "qragged_attn_lanes" in text and "qragged_attn_decode" in text


def test_hybrid_ragged_serve_step_compiles_for_v5e(one_chip, monkeypatch):
    """The hybrid's per-tick program — one period of Jamba2-3B (13 Mamba
    layers and one attention layer at published widths, int8 weights) over
    32 slots, 2 x 256 lanes and an int8 paged pool of 17,408-token rows —
    compiles with the int8 matmul and the ragged attention kernels in it
    and fits one chip."""
    from repro.core.integerize import integerize_weights_only
    from repro.models.registry import get_config
    from repro.serve import ServeEngine
    from repro.serve.engine import make_ragged_step

    monkeypatch.setattr(ops, "FORCE", "pallas")
    slots, lanes, chunk, max_len = 32, 2, 256, 17408
    cfg = dataclasses.replace(get_config("jamba2-3b"), n_layers=14)
    model = cfg.build(remat="off")
    params = jax.eval_shape(
        lambda: integerize_weights_only(model.init(jax.random.PRNGKey(0))))
    engine = ServeEngine(model=model, params=params, max_len=max_len,
                         batch_slots=slots, quantized_kv=True, paged_kv=True)
    cache = jax.eval_shape(functools.partial(engine.new_cache, per_slot=True))

    def place(tree):
        return jax.tree_util.tree_map(
            lambda s: _sds(one_chip, s.shape, s.dtype), tree)

    i32, t = jnp.int32, slots + lanes * chunk
    step = jax.jit(make_ragged_step(model), donate_argnums=(2,))
    compiled = step.lower(
        place(params), _sds(one_chip, (slots, 1), i32), place(cache),
        _sds(one_chip, (2,), jnp.uint32), _sds(one_chip, (lanes, chunk), i32),
        _sds(one_chip, (t,), i32), _sds(one_chip, (t,), i32),
        _sds(one_chip, (slots + lanes,), i32)).compile()
    text = compiled.as_text()
    assert "qragged_attn_write" in text and "wq_matmul" in text
    assert "qragged_attn_lanes" in text and "qragged_attn_decode" in text
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes) < 16 * 2**30
