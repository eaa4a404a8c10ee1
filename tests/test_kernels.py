"""Per-stage validation of the fused macro kernel: each stage of one time
step — the twin-cell MAC, the KWN ramp head, the LIF update, and the IMA
ramp conversion — checked against its ``ref.py`` oracle, with shape sweeps
and hypothesis property tests on the stage invariants.

Every case runs ``ops.fused_macro_seq`` for one step (T = 1) and reads the
stage off the kernel's outputs: the MAC from the raw-MAC telemetry, the
winner mask and early-stop steps from the KWN head, the membrane and spikes
from the LIF update, and the ramp codes through NLD mode (one branch, unit
dendrite weight, an identity LUT, no threshold), whose membrane after one
step from rest is exactly the looked-up level."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st

from repro.core import ima as ima_lib
from repro.kernels import ops, ref


def _tern(key, shape):
    return jax.random.randint(key, shape, -1, 2).astype(jnp.int8)


def _planes(seed, k, n):
    keys = jax.random.split(jax.random.PRNGKey(seed), 2)
    return _tern(keys[0], (k, n)), _tern(keys[1], (k, n))


def _kwn_step(x, msb, lsb, cb, *, k, scale=None, v=None, noise=None, **kw):
    """One KWN step through the sequence kernel; x (..., K)."""
    lead, n = x.shape[:-1], msb.shape[-1]
    scale = jnp.ones((n,)) if scale is None else scale
    v = jnp.zeros(lead + (n,)) if v is None else v
    mac, v_out, spk, mask, steps = ops.fused_macro_seq(
        x[None], msb, lsb, cb.boundaries, cb.levels, scale, v,
        None if noise is None else noise[None], mode="kwn", k=k, **kw)
    return mac[0], v_out, spk[0], mask[0], steps[0]


def _ramp(x, msb, lsb, scale, boundaries, levels):
    """Ramp conversion of ``(x @ planes) * scale`` read through NLD mode:
    one branch, w_dend = 1, drive_gain = 1, membrane from rest, threshold
    and saturation out of reach, so v_out is the LUT value of each code."""
    n = msb.shape[-1]
    _, v_out, spk, _, _ = ops.fused_macro_seq(
        x[None], msb, lsb, boundaries, levels, scale,
        jnp.zeros(x.shape[:-1] + (n,)), None, jnp.ones((1, n)), mode="nld",
        v_th1=1e6, v_lim=1e6)
    assert not bool(jnp.any(spk))
    return v_out


def _ramp_codes(x, msb, lsb, scale, cb):
    """(codes, reconstruction) of the ramp input, both read off the kernel:
    codes through an identity LUT, the reconstruction through the real one."""
    ident = jnp.arange(cb.n_codes, dtype=jnp.float32)
    codes = _ramp(x, msb, lsb, scale, cb.boundaries, ident)
    y = _ramp(x, msb, lsb, scale, cb.boundaries, cb.levels)
    return codes.astype(jnp.int32), y


class TestTernaryMac:
    """The raw-MAC telemetry equals the twin-cell GEMM oracle."""

    @pytest.mark.parametrize("m,k,n", [
        (8, 256, 128), (128, 256, 128), (64, 512, 256),
        (17, 300, 130),            # non-aligned: exercises padding
        (256, 1024, 384), (1, 256, 128),
    ])
    def test_matches_ref(self, m, k, n):
        keys = jax.random.split(jax.random.PRNGKey(m * 7 + n), 3)
        x = _tern(keys[0], (m, k))
        msb = _tern(keys[1], (k, n))
        lsb = _tern(keys[2], (k, n))
        cb = ima_lib.nlq_codebook(5, -64, 64)
        mac = _kwn_step(x, msb, lsb, cb, k=min(12, n), mac_telemetry=True)[0]
        want = ref.ternary_mac_ref(x, msb, lsb)
        np.testing.assert_allclose(mac, want, rtol=1e-6, atol=1e-6)

    def test_batched_leading_dims(self):
        keys = jax.random.split(jax.random.PRNGKey(0), 3)
        x = _tern(keys[0], (2, 5, 256))
        msb, lsb = _tern(keys[1], (256, 128)), _tern(keys[2], (256, 128))
        cb = ima_lib.nlq_codebook(5, -64, 64)
        mac = _kwn_step(x, msb, lsb, cb, k=12)[0]
        want = ref.ternary_mac_ref(x.reshape(-1, 256), msb,
                                   lsb).reshape(2, 5, 128)
        assert mac.shape == (2, 5, 128)
        np.testing.assert_allclose(mac, want, rtol=1e-6)

    def test_nondefault_ratio(self):
        keys = jax.random.split(jax.random.PRNGKey(1), 3)
        x, msb, lsb = (_tern(keys[0], (16, 256)), _tern(keys[1], (256, 128)),
                       _tern(keys[2], (256, 128)))
        cb = ima_lib.nlq_codebook(5, -64, 64)
        mac = _kwn_step(x, msb, lsb, cb, k=12, ratio=3.0)[0]
        want = ref.ternary_mac_ref(x, msb, lsb, ratio=3.0)
        np.testing.assert_allclose(mac, want, rtol=1e-6)

    @settings(max_examples=5, deadline=None)
    @given(st.integers(1, 40), st.integers(1, 300), st.integers(1, 200),
           st.integers(0, 2 ** 31 - 1))
    def test_property_exact_integer_gemm(self, m, k, n, seed):
        # Ternary x ternary-plane GEMM is exact in f32 for any shape, so
        # any tiling and padding the planner picks gives the oracle's bits.
        keys = jax.random.split(jax.random.PRNGKey(seed), 3)
        x = _tern(keys[0], (m, k))
        msb, lsb = _tern(keys[1], (k, n)), _tern(keys[2], (k, n))
        cb = ima_lib.nlq_codebook(5, -64, 64)
        mac = _kwn_step(x, msb, lsb, cb, k=min(12, n))[0]
        np.testing.assert_array_equal(
            np.asarray(mac), np.asarray(ref.ternary_mac_ref(x, msb, lsb)))


class TestKwnTopk:
    """The KWN head's winner mask and early-stop ramp steps equal the
    descending-ramp top-K oracle on the same MAC."""

    @pytest.mark.parametrize("m,n,k,bits", [
        (8, 128, 3, 5), (64, 128, 12, 5), (16, 128, 1, 5),
        (9, 128, 12, 5),           # padding rows
        (32, 256, 16, 6), (8, 128, 127, 5),
    ])
    def test_matches_ref(self, m, n, k, bits):
        cb = ima_lib.nlq_codebook(bits, -64, 64)
        x = _tern(jax.random.PRNGKey(m + n + k), (m, 256))
        msb, lsb = _planes(bits, 256, n)
        _, _, _, mask, steps = _kwn_step(x, msb, lsb, cb, k=k)
        want_mask, want_steps = ref.kwn_topk_ref(
            ref.ternary_mac_ref(x, msb, lsb), cb.boundaries, k)
        np.testing.assert_array_equal(mask, want_mask)
        np.testing.assert_array_equal(steps, want_steps[..., 0])

    def test_batched(self):
        cb = ima_lib.nlq_codebook(5, -64, 64)
        x = _tern(jax.random.PRNGKey(5), (3, 7, 256))
        msb, lsb = _planes(5, 256, 128)
        _, _, _, mask, steps = _kwn_step(x, msb, lsb, cb, k=12)
        assert mask.shape == (3, 7, 128) and steps.shape == (3, 7)
        np.testing.assert_array_equal(mask.sum(-1), 12.0)

    @settings(max_examples=5, deadline=None)
    @given(st.integers(1, 64), st.integers(1, 64), st.integers(0, 2 ** 31 - 1))
    def test_property_k_winners_and_dominance(self, m, k, seed):
        cb = ima_lib.nlq_codebook(5, -64, 64)
        keys = jax.random.split(jax.random.PRNGKey(seed), 2)
        x = _tern(keys[0], (m, 256))
        msb, lsb = _planes(seed % 1000, 256, 128)
        _, _, _, mask, steps = _kwn_step(x, msb, lsb, cb, k=k)
        assert bool(jnp.all(mask.sum(-1) == k))
        # Every winner's code >= every loser's code (ramp dominance).
        codes = ima_lib.ima_convert(ref.ternary_mac_ref(x, msb, lsb), cb)
        wmin = jnp.min(jnp.where(mask > 0, codes, 10 ** 6), -1)
        lmax = jnp.max(jnp.where(mask == 0, codes, -1), -1)
        assert bool(jnp.all(lmax <= wmin))
        # Early stop: steps = distance from top code down to the K-th winner.
        assert bool(jnp.all((steps >= 0) & (steps <= cb.n_codes - 1)))


class TestLifStep:
    """The LIF update equals the LIF oracle driven by the oracle head's
    winner drive (LUT value x column scale x mask x gain)."""

    @pytest.mark.parametrize("shape", [(8, 128), (64, 128), (33, 100),
                                       (256, 512)])
    @pytest.mark.parametrize("use_snl", [True, False])
    def test_matches_ref(self, shape, use_snl):
        m, n = shape
        keys = jax.random.split(jax.random.PRNGKey(m), 5)
        x = _tern(keys[0], (m, 256))
        msb, lsb = _planes(n, 256, n)
        cb = ima_lib.nlq_codebook(5, -24, 24)
        scale = jax.random.uniform(keys[1], (n,), minval=0.05, maxval=0.3)
        v = jax.random.normal(keys[2], shape)
        noise = 0.05 * jnp.sign(jax.random.normal(keys[3], shape))
        k = max(1, n // 4)
        _, out_v, out_s, _, _ = _kwn_step(
            x, msb, lsb, cb, k=k, scale=scale, v=v, noise=noise,
            drive_gain=0.25, use_snl=use_snl)
        mac = ref.ternary_mac_ref(x, msb, lsb)
        mask, _ = ref.kwn_topk_ref(mac, cb.boundaries, k)
        _, recon = ref.nlq_convert_ref(mac, cb.boundaries, cb.levels)
        drive = recon * scale * mask * 0.25
        want_v, want_s = ref.lif_step_ref(v, drive, mask, noise,
                                          use_snl=use_snl)
        np.testing.assert_allclose(out_v, want_v, atol=1e-6)
        np.testing.assert_array_equal(out_s, want_s)

    @settings(max_examples=5, deadline=None)
    @given(st.integers(1, 50), st.integers(2, 200),
           st.integers(0, 2 ** 31 - 1))
    def test_property_hold_and_reset(self, m, n, seed):
        keys = jax.random.split(jax.random.PRNGKey(seed), 2)
        x = _tern(keys[0], (m, 256))
        msb, lsb = _planes(seed % 1000, 256, n)
        cb = ima_lib.nlq_codebook(5, -24, 24)
        v = jax.random.normal(keys[1], (m, n)) * 0.3 - 0.5  # below SNL band
        _, out_v, out_s, mask, _ = _kwn_step(
            x, msb, lsb, cb, k=max(1, n // 4), scale=jnp.full((n,), 0.2),
            v=v, noise=jnp.zeros((m, n)))
        # Losers below the SNL band must hold their state exactly.
        lose = np.asarray(mask) == 0
        hold = np.where(np.asarray(v) >= 1.0, 0.0, np.asarray(v))
        np.testing.assert_allclose(np.asarray(out_v)[lose], hold[lose],
                                   atol=1e-6)
        # Spiked neurons are reset.
        assert bool(jnp.all(jnp.where(out_s > 0, out_v == 0.0, True)))


class TestNlq:
    """The IMA ramp conversion (read through NLD mode) equals the
    boundary-compare + LUT oracle."""

    @pytest.mark.parametrize("m,n,bits,kind", [
        (8, 128, 5, "nlq"), (64, 128, 5, "lin"), (16, 256, 6, "nlq"),
        (9, 130, 5, "nlq"), (128, 128, 4, "act"),
    ])
    def test_matches_ref(self, m, n, bits, kind):
        if kind == "nlq":
            cb, full = ima_lib.nlq_codebook(bits, -64, 64), 64.0
        elif kind == "lin":
            cb, full = ima_lib.linear_codebook(bits, -64, 64), 64.0
        else:
            cb = ima_lib.activation_codebook(bits, ima_lib.quadratic, -8, 8)
            full = 8.0
        keys = jax.random.split(jax.random.PRNGKey(m * 3 + n), 2)
        x = _tern(keys[0], (m, 256))
        msb, lsb = _planes(bits, 256, n)
        scale = jax.random.uniform(keys[1], (n,), minval=0.5,
                                   maxval=1.5) * (full / 48.0)
        codes, y = _ramp_codes(x, msb, lsb, scale, cb)
        want_c, want_y = ref.nlq_convert_ref(
            ref.ternary_mac_ref(x, msb, lsb) * scale, cb.boundaries,
            cb.levels)
        np.testing.assert_array_equal(codes, want_c)
        np.testing.assert_allclose(y, want_y, rtol=1e-6)

    def test_matches_core_ima(self):
        cb = ima_lib.nlq_codebook(5, -64, 64)
        x = _tern(jax.random.PRNGKey(9), (32, 256))
        msb, lsb = _planes(9, 256, 128)
        scale = jnp.full((128,), 64.0 / 48.0)
        codes, y = _ramp_codes(x, msb, lsb, scale, cb)
        inp = ref.ternary_mac_ref(x, msb, lsb) * scale
        np.testing.assert_array_equal(codes, ima_lib.ima_convert(inp, cb))
        np.testing.assert_allclose(y, ima_lib.ima_quantize(inp, cb),
                                   rtol=1e-6)

    @settings(max_examples=10, deadline=None)
    @given(st.integers(2, 7), st.integers(0, 2 ** 31 - 1))
    def test_property_monotone_and_bounded(self, bits, seed):
        # Row r sums a_r in {1, 2, 3} unit events onto all-ones columns, so
        # the ramp input is a_r * scale[c]: ascending along every row.
        cb = ima_lib.nlq_codebook(bits, -64, 64)
        keys = jax.random.split(jax.random.PRNGKey(seed), 2)
        a = jax.random.randint(keys[0], (4, 1), 1, 4)
        x = (jnp.arange(3)[None, :] < a).astype(jnp.int8)
        ones = jnp.ones((3, 64), jnp.int8)
        scale = jnp.sort(jax.random.normal(keys[1], (64,)) * 20.0)
        codes, _ = _ramp_codes(x, jnp.zeros_like(ones), ones, scale, cb)
        assert bool(jnp.all(jnp.diff(codes, axis=-1) >= 0))  # monotone codes
        assert bool(jnp.all((codes >= 0) & (codes < cb.n_codes)))
