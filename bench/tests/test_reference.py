"""The plain references agree with the program's own forward pass (no cache,
float32) on the same seeded weights, at a small size on the CPU."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import harness
import tiny
from repro.core.policy import QuantPolicy
from repro.nn.module import Context


@pytest.mark.parametrize("workload", ["smollm-doc-long", "mamba-chat-burst"])
def test_reference_matches_program_forward(workload):
    cfg = tiny.cell(workload)["config"]
    pub = cfg["published"]
    # The program's RMSNorm fixes eps at 1e-6 where both published configs
    # say 1e-5; at this size that alone moves logits by ~1e-3 (the first
    # norm sees the raw embedding, mean square 1/d).  Pin the reference to
    # the program's eps here so the comparison can be tight.
    pub["rms_norm_eps" if "rms_norm_eps" in pub else "layer_norm_epsilon"] \
        = 1e-6
    ref = harness.load_module(harness.BENCH / "reference"
                              / f"{cfg['reference']}.py")
    adapter = harness.load_module(harness.BENCH / "adapters"
                                  / f"{cfg['reference']}.py")
    model = adapter.arch(cfg).build(dtype=jnp.float32, remat="off")
    w = ref.init_weights(jax.random.PRNGKey(7), pub)
    params = adapter.program_params(w, model.vocab_padded)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    assert jax.tree_util.tree_structure(params) \
        == jax.tree_util.tree_structure(shapes)
    for a, b in zip(jax.tree_util.tree_leaves(params),
                    jax.tree_util.tree_leaves(shapes)):
        assert a.shape == b.shape

    tokens = jax.random.randint(jax.random.PRNGKey(8), (96,), 0,
                                pub["vocab_size"])
    with jax.default_matmul_precision("highest"):
        ctx = Context(policy=QuantPolicy.float32(), train=False)
        got, _ = model.apply(params, tokens[None], ctx)
        want = ref.logits(w, ref.hidden(w, tokens, pub))
    got = np.asarray(got[0, :, :pub["vocab_size"]])
    want = np.asarray(want)
    # Both sides are float32 at "highest" and differ only in summation
    # order: ~3e-6 on logits of magnitude ~5.  A wrong head, rotation, mask
    # or state update moves logits by their own magnitude.
    assert np.max(np.abs(got - want)) < 1e-4
