"""The plain references compute what the trainer's model computes: at a
tiny size, in float32 on the CPU, each family's reference loss and
gradient equal the trainer's ``lm_loss`` on the same seeded weights."""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench_paths import BENCH

from reference import olmo, xlstm  # noqa: E402
from reference.numerics import Numerics  # noqa: E402

TINY = {
    "olmo-1b.d4.r2": (olmo, dict(hidden_size=64, intermediate_size=128,
                                 num_attention_heads=4,
                                 num_key_value_heads=4,
                                 num_hidden_layers=2, vocab_size=256)),
    # two mLSTM chunks of 256 positions exercise the carried state
    "xlstm-350m.d8.r2": (xlstm, dict(embedding_dim=64, num_heads=4,
                                     num_blocks=4, slstm_at=[3],
                                     vocab_size=256)),
}


@pytest.mark.parametrize("name", sorted(TINY))
def test_reference_matches_trainer_in_float32(name):
    from repro.configs import get_config
    from repro.models import model as M
    fam, small = TINY[name]
    c = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    c.update(small, dtypes={"params": "float32", "compute": "float32"})
    arch, fields = fam.program_config(c)
    mc = dataclasses.replace(get_config(arch).model, **fields)
    params = jax.jit(lambda k: fam.init_params(k, c))(jax.random.PRNGKey(3))
    seq = 512 if fam is xlstm else 64
    tokens = jnp.asarray(np.random.default_rng(0).integers(
        0, 256, (2, seq)), jnp.int32)
    mask = jnp.ones((2, seq - 1), jnp.float32)

    def trainer(p):
        return M.lm_loss(p, {"tokens": tokens}, mc)[0]

    def reference(p):
        return fam.nll_sum(p, tokens, c, Numerics(), mask) / jnp.sum(mask)

    with jax.default_matmul_precision("highest"):
        lt, gt = jax.jit(jax.value_and_grad(trainer))(params)
    lr, gr = jax.jit(jax.value_and_grad(reference))(params)
    assert float(lt) == pytest.approx(float(lr), rel=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(gt),
                    jax.tree_util.tree_leaves(gr)):
        np.testing.assert_allclose(a, b, rtol=2e-3, atol=1e-6)
