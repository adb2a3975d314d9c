"""Unit tests for the sharding-rule inference (divisibility, dedupe, prefix
fallback, serve orientation) — pure spec logic, no device mesh required
beyond the default 1-CPU (specs are constructed, not applied)."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.dist import sharding as shd

pytestmark = pytest.mark.skipif(
    len(jax.devices()) != 1, reason="spec-only tests assume default device")


def fake_mesh(shape=(16, 16), axes=("data", "model")):
    # AbstractMesh carries shapes/names without real devices
    return jax.sharding.AbstractMesh(tuple(shape), tuple(axes))


def test_divisibility_drops_axis():
    mesh = fake_mesh()
    rules = shd.make_axis_rules(mesh)
    # 9 heads can't shard 16 ways -> replicated; 1536 ff can
    spec = shd._spec_for_path("attn/wq/kernel", (576, 576), rules, mesh)
    assert spec == P("data", "model")
    spec = shd._spec_for_path("attn/wq/kernel", (576, 9), rules, mesh)
    assert spec == P("data", None)


def test_scan_stacked_leading_dims_replicate():
    mesh = fake_mesh()
    rules = shd.make_axis_rules(mesh)
    spec = shd._spec_for_path("stack/body/0/ffn/w_gate/kernel",
                              (30, 576, 1536), rules, mesh)
    assert spec == P(None, "data", "model")


def test_expert_orientation_train_vs_serve():
    mesh = fake_mesh()
    rules = shd.make_axis_rules(mesh)
    shape = (60, 384, 7168, 2048)
    train = shd._spec_for_path("ffn/experts/w_gate/kernel", shape, rules,
                               mesh, serve=False)
    serve = shd._spec_for_path("ffn/experts/w_gate/kernel", shape, rules,
                               mesh, serve=True)
    assert train == P(None, "model", None, "data")   # FSDP on F (train)
    assert serve == P(None, "model", "data", None)   # FSDP on D (decode)


def test_router_replicated():
    mesh = fake_mesh()
    rules = shd.make_axis_rules(mesh)
    spec = shd._spec_for_path("moe/router/kernel", (7168, 384), rules, mesh)
    assert spec == P()


def test_batch_prefix_fallback():
    mesh = fake_mesh((2, 16, 16), ("pod", "data", "model"))
    rules = shd.make_axis_rules(mesh, dp_only=True)
    # 256 % 512 != 0 -> longest divisible prefix ("data","model") = 256-way
    fit = shd._fit(mesh, rules["batch"], 256)
    assert fit == ("data", "model")
    # fully divisible batch uses all three axes
    assert shd._fit(mesh, rules["batch"], 512) == ("data", "model", "pod")
    # prime batch replicates
    assert shd._fit(mesh, rules["batch"], 7) is None


def test_dedupe_drops_second_use():
    assert shd._dedupe(("model", "model", None)) == ("model", None, None)
    assert shd._dedupe((("data", "model"), "model")) == (("data", "model"),
                                                         None)
    assert shd._dedupe((None, "data", "model")) == (None, "data", "model")


def test_cache_specs_kv_seq_sharded():
    mesh = fake_mesh()
    rules = shd.make_axis_rules(mesh)
    cache = {"kv": {"k": jax.ShapeDtypeStruct((64, 128, 32768, 8, 128),
                                              jnp.bfloat16),
                    "len": jax.ShapeDtypeStruct((), jnp.int32)}}
    specs = shd.cache_pspecs(cache, mesh, rules)
    assert specs["kv"]["k"].spec == P(None, "data", "model", None, None)
    assert specs["kv"]["len"].spec == P()


def test_qtensor_param_specs():
    from repro.core.qformat import QTensor

    mesh = fake_mesh()
    rules = shd.make_axis_rules(mesh)
    qt = QTensor(q=jax.ShapeDtypeStruct((7168, 2048), jnp.int8),
                 n=jax.ShapeDtypeStruct((2048,), jnp.int32),
                 width=8, channel_axis=1)
    specs = shd.param_pspecs({"ffn": {"w_gate": {"kernel": qt}}}, mesh, rules)
    out = specs["ffn"]["w_gate"]["kernel"]
    assert out.q.spec == P("data", "model")
    assert out.n.spec == P("model")   # per-channel exponents ride the N axis
