"""Distribution-layer tests on a virtual 8-device CPU mesh.

These run in subprocesses because the device count must be fixed before jax
initializes (the main test process keeps the default 1 device, per the
dry-run isolation rule).
"""
import os
import subprocess
import sys
import textwrap

import pytest

pytestmark = pytest.mark.slow  # subprocess-per-test: device count must be
#                                fixed before jax initializes

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_with_devices(body: str, n: int = 8) -> str:
    code = ("import os\n"
            f"os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count={n}'\n"
            + textwrap.dedent(body))
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=420)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    return r.stdout


def test_int8_gradient_compression_allreduce():
    """Compressed psum-mean ≈ exact mean; error feedback recovers the rest."""
    run_with_devices("""
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.dist.compress import compressed_psum_mean

    mesh = jax.make_mesh((8,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))

    def body(g, e):
        mean, new_e = compressed_psum_mean(g, "data", bits=8, error=e)
        exact = jax.lax.pmean(g, "data")
        return mean, new_e, exact

    fn = jax.jit(jax.shard_map(body, mesh=mesh,
                 in_specs=(P("data"), P("data")),
                 out_specs=(P("data"), P("data"), P("data"))))
    g = jax.random.normal(jax.random.PRNGKey(0), (8, 64)) * 0.01
    e = jnp.zeros_like(g)
    mean, new_e, exact = fn(g, e)
    # all shards agree on the mean
    m = np.asarray(mean)
    assert np.allclose(m, m[0:1], atol=0), "shards disagree"
    # int8 grid error is bounded by one quantization step of the shared grid
    ma = float(jnp.max(jnp.abs(g)))
    step = ma / 2**6   # n = frac bits for max|g| at 8 bits => resolution
    assert float(jnp.max(jnp.abs(m - np.asarray(exact)))) < step
    # error feedback: residual equals what quantization dropped
    re = np.asarray(new_e)
    assert np.all(np.abs(re) <= step)
    print("compress ok")
    """)


def test_error_feedback_converges():
    """Sum of compressed means over steps → sum of exact means (EF property)."""
    run_with_devices("""
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.dist.compress import compressed_grad_allreduce

    mesh = jax.make_mesh((8,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    G = {"w": jax.random.normal(jax.random.PRNGKey(1), (8, 32))}

    def body(g, e):
        cg, ne = compressed_grad_allreduce(g, "data", bits=8, error_state=e)
        return cg, ne, jax.tree_util.tree_map(
            lambda t: jax.lax.pmean(t, "data"), g)

    fn = jax.jit(jax.shard_map(body, mesh=mesh,
                 in_specs=(P("data"), P("data")),
                 out_specs=(P("data"), P("data"), P("data"))))
    e = {"w": jnp.zeros((8, 32))}
    tot_c = np.zeros((8, 32)); tot_x = np.zeros((8, 32))
    for step in range(20):
        cg, e, exact = fn(G, e)
        tot_c += np.asarray(cg["w"]); tot_x += np.asarray(exact["w"])
    # cumulative compressed mean tracks cumulative exact mean tightly
    denom = np.abs(tot_x).mean() + 1e-9
    rel = np.abs(tot_c - tot_x).mean() / denom
    assert rel < 0.02, rel
    print("EF ok", rel)
    """)


def test_pipeline_parallel_matches_sequential():
    """GPipe over 4 stages == sequential layer application."""
    run_with_devices("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.dist.pipeline import make_pipelined_fn

    n_stages, n_micro, mb, d = 4, 8, 2, 16
    mesh = jax.make_mesh((4,), ("pod",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    keys = jax.random.split(jax.random.PRNGKey(0), n_stages)
    Ws = jnp.stack([jax.random.normal(k, (d, d)) / np.sqrt(d) for k in keys])

    def stage_fn(W, x):
        return jnp.tanh(x @ W)

    run = make_pipelined_fn(stage_fn, mesh, axis_name="pod")
    x = jax.random.normal(jax.random.PRNGKey(1), (n_micro, mb, d))
    got = run(Ws, x)

    ref = x
    for i in range(n_stages):
        ref = jax.vmap(lambda xb: stage_fn(Ws[i], xb))(ref)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    print("pipeline ok")
    """, n=4)


def test_sharded_train_step_matches_single_device():
    """DP+TP pjit train step computes the same loss as single-device."""
    run_with_devices("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.models.registry import get_config
    from repro.dist import sharding as shd
    from repro.optim import sgd
    from repro.train.trainer import make_train_step
    from repro.data.pipeline import markov_batch_fn

    cfg = get_config("smollm-135m-smoke")
    model = cfg.build(dtype=jnp.float32, remat="none")
    opt = sgd(momentum=0.9)
    params = model.init(jax.random.PRNGKey(0))
    state = {"params": params, "opt": opt.init(params),
             "step": jnp.zeros((), jnp.int32)}
    batch = markov_batch_fn(cfg.vocab, 8, 32, seed=1)(0)

    # single device
    s1, m1 = jax.jit(make_train_step(model, opt, 0.01))(state, batch)

    # 4-data x 2-model mesh
    mesh = jax.make_mesh((4, 2), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    rules = shd.make_axis_rules(mesh)
    pspecs = shd.param_pspecs(params, mesh, rules)
    gstate = {"params": jax.device_put(params, pspecs),
              "opt": {"m": jax.device_put(opt.init(params)["m"],
                      shd.param_pspecs(opt.init(params)["m"], mesh, rules))},
              "step": jnp.zeros((), jnp.int32)}
    gbatch = jax.device_put(batch, shd.batch_pspecs(batch, mesh, rules))
    step = jax.jit(make_train_step(model, opt, 0.01, mesh=mesh,
                                   axis_rules=rules))
    s2, m2 = step(gstate, gbatch)
    assert abs(float(m1["loss"]) - float(m2["loss"])) < 1e-3, \
        (float(m1["loss"]), float(m2["loss"]))
    # params close after one step
    for a, b in zip(jax.tree_util.tree_leaves(s1["params"]),
                    jax.tree_util.tree_leaves(s2["params"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-4)
    print("sharded step ok", float(m1["loss"]))
    """)


def test_shardmap_dp_with_compression_trains():
    run_with_devices("""
    import jax, jax.numpy as jnp
    from repro.models.registry import get_config
    from repro.optim import sgd
    from repro.train.trainer import make_dp_shardmap_train_step
    from repro.data.pipeline import markov_batch_fn

    cfg = get_config("smollm-135m-smoke")
    model = cfg.build(dtype=jnp.float32, remat="none")
    opt = sgd(momentum=0.9)
    params = model.init(jax.random.PRNGKey(0))
    state = {"params": params, "opt": opt.init(params),
             "step": jnp.zeros((), jnp.int32)}
    mesh = jax.make_mesh((8,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    step = make_dp_shardmap_train_step(model, opt, 0.05, mesh,
                                       compress_bits=8)
    bf = markov_batch_fn(cfg.vocab, 16, 32, seed=2)
    losses = []
    for s in range(8):
        state, m = step(state, bf(s))
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0], losses   # it learns through int8 grads
    print("compressed training ok", losses[0], "->", losses[-1])
    """)


def test_elastic_checkpoint_across_meshes(tmp_path):
    """Checkpoint written on a 8-dev mesh restores onto 2-dev and 1-dev."""
    script = f"""
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.train.checkpoint import CheckpointManager

    mesh = jax.make_mesh((MESHN,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    ck = CheckpointManager({str(tmp_path)!r})
    tree = {{"w": jnp.arange(64.0).reshape(8, 8)}}
    if MESHN == 8:
        tree = jax.device_put(tree, NamedSharding(mesh, P("data")))
        ck.save(1, tree)
        print("saved")
    else:
        target = {{"w": jax.ShapeDtypeStruct((8, 8), jnp.float32,
                  sharding=NamedSharding(mesh, P("data")))}}
        out = ck.restore(1, target)
        np.testing.assert_array_equal(np.asarray(out["w"]),
                                      np.arange(64.0).reshape(8, 8))
        print("restored on", MESHN)
    """
    run_with_devices(script.replace("MESHN", "8"), n=8)
    run_with_devices(script.replace("MESHN", "2"), n=2)


def test_moe_weight_stationary_decode_matches_single_device():
    """The decode-step MoE dispatch (weight-stationary, §Perf kimi d1) must
    produce the same logits as the unsharded model, given the same cache.
    (Prefill routing *groups* differ by DP degree — capacity drops are
    group-local by design — so the comparison fixes the prefill cache.)"""
    run_with_devices("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.models.registry import get_config
    from repro.dist import sharding as shd
    from repro.nn.module import Context

    cfg = get_config("phi3.5-moe-42b-a6.6b-smoke")
    model = cfg.build(dtype=jnp.float32, remat="off")
    params = model.init(jax.random.PRNGKey(0))
    b, s_max = 4, 16
    toks = jnp.arange(b * 8, dtype=jnp.int32).reshape(b, 8) % cfg.vocab

    # single device: prefill once, then one decode step (the reference)
    cache0 = model.init_cache(b, s_max, quantized_kv=False,
                              kv_dtype=jnp.float32)
    ctx = Context(train=False)
    lg, cache = model.apply(params, toks, ctx, cache=cache0, decode=True)
    nxt = jnp.argmax(lg[:, -1:], -1).astype(jnp.int32)
    ref, _ = model.apply(params, nxt, ctx, cache=cache, decode=True)

    # 4x2 mesh, SAME cache, weight-stationary decode path active
    mesh = jax.make_mesh((4, 2), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    rules = shd.make_axis_rules(mesh)
    pp = jax.device_put(params, shd.param_pspecs(params, mesh, rules,
                                                 serve=True))
    cache_s = jax.device_put(cache, shd.cache_pspecs(cache, mesh, rules))
    ctx2 = Context(train=False, mesh=mesh, axis_rules=rules)

    @jax.jit
    def step(pp, cache_s, nxt):
        out, _ = model.apply(pp, nxt, ctx2, cache=cache_s, decode=True)
        return out

    got = step(pp, cache_s, nxt)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)
    print("moe ws decode ok")
    """)
