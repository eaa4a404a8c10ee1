"""Counter-based PRNG shared by the fused Pallas kernel and its oracles.

The Fig. 7 IMA error model needs a fresh Gaussian per (time step, row,
column) *inside* the fused kernel.  A stateful generator (the TPU hardware
PRNG behind ``pltpu.prng_random_bits``, or the PRBS LFSR) cannot serve here:
its stream depends on how the launch iterates the grid, so changing the tile
plan — or comparing against a pure-JAX reference — changes the draws.  A
*counter-based* generator makes the draw a pure function of
``(seed, step, row, column)``: the same element gets the same noise for any
(bm, bk, bn) tiling, any batch padding, and in the jnp oracle, which is what
lets noisy fused output stay **bitwise-equal** to ``kernels/ref.py`` and
lets a re-run with the same seed reproduce spikes exactly.

The block cipher is Threefry-2x32 with the standard 20-round schedule (the
same construction ``jax.random`` uses; implemented here by hand so the
identical uint32 ops run both inside the Pallas kernel body and in the
oracle).  Gaussians come from one cipher call per element via Box–Muller on
the two output words; SNL sign noise uses the low bit of the first word.
Distinct consumers are domain-separated through the key's second word
(``tag ^ step``), so the IMA and SNL streams never collide.

Everything here is plain ``jnp`` uint32/f32 arithmetic — no host callbacks,
no Pallas-specific primitives — so the same function object is traceable
inside a kernel body (interpret or compiled) and in ordinary jitted code.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# Key-lane tags: domain separation between the noise consumers.
TAG_IMA = 0x494D4101   # IMA conversion error (Fig. 7a/b)
TAG_SNL = 0x534E4C01   # SNL probabilistic-firing sign noise (Eq. 1 n(t))

_PARITY = 0x1BD11BDA   # Threefry key-schedule parity constant
_ROT_A = (13, 15, 26, 6)
_ROT_B = (17, 29, 16, 24)


def _rotl(x: jax.Array, r: int) -> jax.Array:
    return (x << jnp.uint32(r)) | (x >> jnp.uint32(32 - r))


def _mix(x0: jax.Array, x1: jax.Array, rots) -> tuple[jax.Array, jax.Array]:
    for r in rots:
        x0 = x0 + x1
        x1 = _rotl(x1, r) ^ x0
    return x0, x1


def threefry2x32(k0, k1, c0, c1) -> tuple[jax.Array, jax.Array]:
    """Threefry-2x32-20: key (k0, k1), counter (c0, c1) -> two uint32 words.

    All inputs broadcast; arithmetic is mod-2^32 (uint32 wraparound).
    """
    k0 = jnp.asarray(k0).astype(jnp.uint32)
    k1 = jnp.asarray(k1).astype(jnp.uint32)
    ks2 = k0 ^ k1 ^ jnp.uint32(_PARITY)
    x0 = jnp.asarray(c0).astype(jnp.uint32) + k0
    x1 = jnp.asarray(c1).astype(jnp.uint32) + k1
    ks = (k0, k1, ks2)
    for i in range(5):
        x0, x1 = _mix(x0, x1, _ROT_A if i % 2 == 0 else _ROT_B)
        x0 = x0 + ks[(i + 1) % 3]
        x1 = x1 + ks[(i + 2) % 3] + jnp.uint32(i + 1)
    return x0, x1


def _unit_open(bits: jax.Array) -> jax.Array:
    """uint32 -> f32 uniform on the open interval (0, 1).

    Uses the top 24 bits (exact in f32) shifted off zero by half an ulp so
    ``log`` in Box–Muller never sees 0.  The 24-bit word fits int32, and
    the cast goes through it: the TPU kernel compiler has no uint32 -> f32
    conversion.
    """
    hi24 = (bits >> jnp.uint32(8)).astype(jnp.int32).astype(jnp.float32)
    return (hi24 + jnp.float32(0.5)) * jnp.float32(2.0 ** -24)


def counter_normal(seed, step, rows: jax.Array, cols: jax.Array,
                   tag: int) -> jax.Array:
    """One standard-normal draw per (row, col) element.

    seed:  uint32/int32 scalar (traced or Python int).
    step:  time-step index (traced or Python int) — folded into the key.
    rows/cols:  broadcastable int32 arrays of *global* element coordinates
                (absolute row index, logical column index), so padding and
                tiling cannot shift the stream.
    """
    k0 = jnp.asarray(seed).astype(jnp.uint32)
    k1 = jnp.uint32(tag) ^ jnp.asarray(step).astype(jnp.uint32)
    b0, b1 = threefry2x32(k0, k1, rows, cols)
    r = jnp.sqrt(jnp.float32(-2.0) * jnp.log(_unit_open(b0)))
    theta = jnp.float32(2.0 * 3.141592653589793) * _unit_open(b1)
    return r * jnp.cos(theta)


def counter_sign(seed, step, rows: jax.Array, cols: jax.Array,
                 tag: int) -> jax.Array:
    """±1.0 f32 per element — the PRBS-equivalent two-level noise source."""
    k0 = jnp.asarray(seed).astype(jnp.uint32)
    k1 = jnp.uint32(tag) ^ jnp.asarray(step).astype(jnp.uint32)
    b0, _ = threefry2x32(k0, k1, rows, cols)
    bit = (b0 & jnp.uint32(1)).astype(jnp.int32)    # no uint32 -> f32 on TPU
    return bit.astype(jnp.float32) * 2.0 - 1.0


def noisy_ima_codes(ideal_codes: jax.Array, x: jax.Array,
                    rows: jax.Array, cols: jax.Array, seed, step,
                    params, n_codes: int) -> jax.Array:
    """Fig. 7 error injection in code space, shared by kernel and oracle.

    Mirrors ``ima.ima_convert_noisy`` operation-for-operation: a slow
    sinusoidal INL profile over the normalized input range, a constant
    comparator offset, and Gaussian thermal noise — all in code LSBs — then
    round and clip to the ripple-counter range.  ``params`` is any object
    with ``offset_lsb / sigma_lsb / inl_lsb / in_lo / in_hi`` floats
    (``ima.IMAKernelNoise``).
    """
    u = (x - jnp.float32(params.in_lo)) / jnp.float32(
        params.in_hi - params.in_lo + 1e-9)
    inl = jnp.float32(params.inl_lsb) * jnp.sin(
        jnp.float32(2.0 * 3.141592653589793) * u)
    g = counter_normal(seed, step, rows, cols, TAG_IMA)
    eps = jnp.float32(params.offset_lsb) + jnp.float32(params.sigma_lsb) * g
    code = jnp.round(ideal_codes.astype(jnp.float32) + inl + eps)
    return jnp.clip(code.astype(jnp.int32), 0, n_codes - 1)
