"""Flash-attention Pallas kernel: allclose vs the naive oracle across
shape/dtype/causality sweeps + the causal block-skip accounting."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import (causal_flops_saving,
                                           flash_attention_fwd)
from repro.kernels.ops import interpret_mode


def _naive(q, k, v, causal):
    s = q.shape[1]
    d = q.shape[-1]
    sc = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32),
                    k.astype(jnp.float32)) / jnp.sqrt(d)
    if causal:
        m = jnp.tril(jnp.ones((s, s), bool))
        sc = jnp.where(m[None], sc, -1e30)
    return jnp.einsum("bqk,bkd->bqd", jax.nn.softmax(sc, -1),
                      v.astype(jnp.float32))


@pytest.mark.parametrize("s,bq,bk", [(128, 32, 32), (256, 64, 64),
                                     (128, 64, 32), (192, 64, 64)])
@pytest.mark.parametrize("causal", [True, False])
def test_matches_naive(s, bq, bk, causal):
    if s % bq or s % bk:
        pytest.skip("non-divisible")
    key = jax.random.PRNGKey(s + bq)
    q, k, v = [jax.random.normal(jax.random.fold_in(key, i), (2, s, 16))
               for i in range(3)]
    out = flash_attention_fwd(q, k, v, causal=causal, bq=bq, bk=bk,
                              interpret=interpret_mode())
    ref = _naive(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_bf16_inputs():
    key = jax.random.PRNGKey(9)
    q, k, v = [jax.random.normal(jax.random.fold_in(key, i), (2, 128, 32)
                                 ).astype(jnp.bfloat16) for i in range(3)]
    out = flash_attention_fwd(q, k, v, causal=True, bq=64, bk=64,
                              interpret=interpret_mode())
    ref = _naive(q, k, v, True)
    np.testing.assert_allclose(np.asarray(out, dtype=np.float32),
                               np.asarray(ref), rtol=2e-2, atol=2e-2)


def test_causal_block_saving_approaches_half():
    # at 32k with 512-blocks the skip fraction is within 1% of the S^2/2 ideal
    assert causal_flops_saving(32768, 512, 512) == pytest.approx(0.5, abs=0.01)
    assert causal_flops_saving(4096, 1024, 1024) == pytest.approx(0.375,
                                                                  abs=0.01)


def test_numerical_stability_large_logits():
    key = jax.random.PRNGKey(11)
    q, k, v = [jax.random.normal(jax.random.fold_in(key, i), (1, 128, 16)) * 30
               for i in range(3)]
    out = flash_attention_fwd(q, k, v, causal=True, bq=32, bk=32,
                              interpret=interpret_mode())
    assert bool(jnp.all(jnp.isfinite(out)))
    ref = _naive(q, k, v, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)
