"""Property-based parity for the tiled, time-major fused macro kernel.

Three oracles pin the kernel down (``kernels/ref.py``):

* ``fused_macro_step_ref``  — composed single-step semantics;
* ``fused_macro_tiled_ref`` — explicit digital partial-sum tiling, must be
  bitwise-identical to the untiled oracle for ANY (bk, bn) because every
  MAC partial is a small exact integer (associativity-free in f32);
* ``fused_macro_seq_ref``   — left-fold of the step oracle over T.

The hypothesis strategies sweep modes (kwn/nld), ramp curves (linear / NLQ /
NL-activation), odd M/K/N/T (non-multiples of bm/bk/bn included) and the
T=1 degenerate; the seeded sweep below them re-runs a fixed sample of the
same space so the parity property is exercised even on images without
hypothesis (where ``@given`` tests skip via tests/_hypothesis_compat.py).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ima as ima_lib
from repro.kernels import ops, ref
from tests._hypothesis_compat import given, settings, st


def _tern(key, shape, rate=0.25):
    sparse = jax.random.uniform(jax.random.fold_in(key, 1), shape) < rate
    vals = jax.random.randint(key, shape, -1, 2)
    return (vals * sparse).astype(jnp.int8)


def _codebook(curve, mode):
    if mode == "nld":
        return ima_lib.activation_codebook(5, ima_lib.quadratic, -4.0, 4.0)
    if curve == "lin":
        return ima_lib.linear_codebook(5, -24.0, 24.0)
    return ima_lib.nlq_codebook(5, -24.0, 24.0)


def _operands(seed, t, m, n_in, n_out, mode, curve, j=2):
    keys = jax.random.split(jax.random.PRNGKey(seed), 7)
    nc = n_out if mode == "kwn" else j * n_out
    x = _tern(keys[0], (t, m, n_in))
    msb, lsb = _tern(keys[1], (n_in, nc)), _tern(keys[2], (n_in, nc))
    cb = _codebook(curve, mode)
    hi = 0.3 if mode == "kwn" else 0.05
    scale = jax.random.uniform(keys[3], (nc,), minval=0.01, maxval=hi)
    v = jax.random.normal(keys[4], (m, n_out)) * 0.5
    noise = 0.05 * jnp.sign(jax.random.normal(keys[5], (t, m, n_out)))
    w_dend = (None if mode == "kwn"
              else jax.random.normal(keys[6], (j, n_out)) / np.sqrt(j))
    return x, msb, lsb, cb, scale, v, noise, w_dend


def _assert_seq_matches_oracle(seed, t, m, n_in, n_out, mode, curve, k, j=2):
    x, msb, lsb, cb, scale, v, noise, w_dend = _operands(
        seed, t, m, n_in, n_out, mode, curve, j)
    kw = dict(mode=mode, k=min(k, n_out), drive_gain=0.25)
    out = ops.fused_macro_seq(x, msb, lsb, cb.boundaries, cb.levels, scale,
                              v, noise, w_dend=w_dend, **kw)
    want = jax.jit(functools.partial(ref.fused_macro_seq_ref, **kw))(
        x, msb, lsb, cb.boundaries, cb.levels, scale, v, noise, w_dend)
    want = list(want)
    want[4] = want[4][..., 0]
    for name, a, b in zip(("mac", "v_mem", "spikes", "mask", "adc_steps"),
                          out, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=f"{name} mismatch @ seed="
                                              f"{seed} t={t} m={m} "
                                              f"k_in={n_in} n={n_out} "
                                              f"{mode}/{curve}")
    if mode == "kwn":
        # KWN invariants: exactly min(k, n) winners; steps inside the ramp.
        mask = np.asarray(out[3])
        assert (mask.sum(-1) == min(k, n_out)).all()
        steps = np.asarray(out[4])
        assert ((steps >= 0) & (steps <= cb.n_codes - 1)).all()


# ---------------------------------------------------------------------------
# Hypothesis tier (runs where hypothesis is installed; skips elsewhere)
# ---------------------------------------------------------------------------

_shape_kwargs = dict(
    t=st.integers(min_value=1, max_value=4),
    m=st.integers(min_value=1, max_value=12),
    n_in=st.integers(min_value=1, max_value=320),
    n_out=st.integers(min_value=1, max_value=150),
    seed=st.integers(min_value=0, max_value=2 ** 16),
)


@settings(max_examples=12, deadline=None)
@given(curve=st.sampled_from(["lin", "nlq"]),
       k=st.integers(min_value=1, max_value=16), **_shape_kwargs)
def test_kwn_seq_matches_oracle_property(curve, k, t, m, n_in, n_out, seed):
    _assert_seq_matches_oracle(seed, t, m, n_in, n_out, "kwn", curve, k)


@settings(max_examples=8, deadline=None)
@given(j=st.integers(min_value=1, max_value=3), **_shape_kwargs)
def test_nld_seq_matches_oracle_property(j, t, m, n_in, n_out, seed):
    _assert_seq_matches_oracle(seed, t, m, n_in, min(n_out, 80), "nld",
                               "act", 12, j)


@settings(max_examples=16, deadline=None)
@given(seed=st.integers(min_value=0, max_value=2 ** 16),
       m=st.integers(min_value=1, max_value=8),
       n_in=st.integers(min_value=1, max_value=520),
       n_out=st.integers(min_value=1, max_value=260),
       bk=st.sampled_from([32, 64, 128, 256]),
       bn=st.sampled_from([16, 32, 64, 128]))
def test_tiled_oracle_equals_untiled_property(seed, m, n_in, n_out, bk, bn):
    """Digital partial-sum tiling is bitwise-invisible at f32 for any tile."""
    x, msb, lsb, cb, scale, v, noise, _ = _operands(
        seed, 1, m, n_in, n_out, "kwn", "nlq")
    kw = dict(mode="kwn", k=min(12, n_out), drive_gain=0.25)
    a = jax.jit(functools.partial(ref.fused_macro_step_ref, **kw))(
        x[0], msb, lsb, cb.boundaries, cb.levels, scale, v, noise[0])
    b = jax.jit(functools.partial(ref.fused_macro_tiled_ref, bk=bk, bn=bn,
                                  **kw))(
        x[0], msb, lsb, cb.boundaries, cb.levels, scale, v, noise[0])
    for name, aa, bb in zip(("mac", "v_mem", "spikes", "mask", "adc_steps"),
                            a, b):
        np.testing.assert_array_equal(np.asarray(aa), np.asarray(bb),
                                      err_msg=f"{name} tiling-variant")


# ---------------------------------------------------------------------------
# Seeded sweep (always runs; fixed sample of the same property space)
# ---------------------------------------------------------------------------

def _sweep_cases():
    """Fixed random sample over (T, M, K, N, mode, curve, k) incl. odd
    non-multiples of bm/bk/bn and the T=1 degenerate."""
    rng = np.random.RandomState(7)
    cases = [
        # pinned corners: T=1 degenerate, exact-tile, and maximal-oddness
        (1, 8, 256, 128, "kwn", "nlq", 12),
        (1, 16, 512, 256, "kwn", "lin", 12),
        (3, 9, 300, 130, "kwn", "nlq", 5),
        (2, 9, 300, 130, "nld", "act", 12),
        (1, 5, 100, 40, "nld", "act", 12),
    ]
    for _ in range(5):
        t = int(rng.randint(1, 5))
        m = int(rng.randint(1, 14))
        n_in = int(rng.randint(1, 400))
        n_out = int(rng.randint(1, 150))
        mode = rng.choice(["kwn", "nld"])
        curve = rng.choice(["lin", "nlq"]) if mode == "kwn" else "act"
        k = int(rng.randint(1, 17))
        cases.append((t, m, n_in, n_out, str(mode), str(curve), k))
    return cases


@pytest.mark.parametrize("t,m,n_in,n_out,mode,curve,k", _sweep_cases())
def test_seq_matches_oracle_sweep(t, m, n_in, n_out, mode, curve, k):
    _assert_seq_matches_oracle(m * 131 + n_in + n_out + t, t, m, n_in, n_out,
                               mode, curve, k)


@pytest.mark.parametrize("bk,bn", [(64, 32), (256, 128), (128, 64)])
def test_tiled_oracle_equals_untiled_sweep(bk, bn):
    x, msb, lsb, cb, scale, v, noise, _ = _operands(
        3, 1, 8, 384, 192, "kwn", "nlq")
    kw = dict(mode="kwn", k=12, drive_gain=0.25)
    a = jax.jit(functools.partial(ref.fused_macro_step_ref, **kw))(
        x[0], msb, lsb, cb.boundaries, cb.levels, scale, v, noise[0])
    b = jax.jit(functools.partial(ref.fused_macro_tiled_ref, bk=bk, bn=bn,
                                  **kw))(
        x[0], msb, lsb, cb.boundaries, cb.levels, scale, v, noise[0])
    for name, aa, bb in zip(("mac", "v_mem", "spikes", "mask", "adc_steps"),
                            a, b):
        np.testing.assert_array_equal(np.asarray(aa), np.asarray(bb),
                                      err_msg=f"{name} tiling-variant")


def test_seq_equals_iterated_step():
    """Time-major batching is bitwise-invisible: one T-step launch equals T
    T = 1 launches threading the membrane through HBM (the continuous
    engine's rounds split a stream the same way)."""
    t, m, n_in, n_out = 5, 8, 512, 256
    x, msb, lsb, cb, scale, v, noise, _ = _operands(
        11, t, m, n_in, n_out, "kwn", "nlq")
    kw = dict(mode="kwn", k=12, drive_gain=0.25)
    seq = ops.fused_macro_seq(x, msb, lsb, cb.boundaries, cb.levels, scale,
                              v, noise, **kw)
    v_c = v
    for step in range(t):
        mac, v_c, spk, mask, steps = ops.fused_macro_seq(
            x[step][None], msb, lsb, cb.boundaries, cb.levels, scale, v_c,
            noise[step][None], **kw)
        for name, a, b in zip(("mac", "spikes", "mask", "adc_steps"),
                              (mac[0], spk[0], mask[0], steps[0]),
                              (seq[0][step], seq[2][step], seq[3][step],
                               seq[4][step])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg=f"{name} @ t={step}")
    np.testing.assert_array_equal(np.asarray(v_c), np.asarray(seq[1]))
