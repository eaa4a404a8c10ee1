"""The time-major fused macro kernel in NLD mode (``kernels/fused_macro.py``
``_seq_nld_kernel``): one launch runs T steps of the ternary MAC over J
branch columns per soma, the activation ramp on every branch column, the
soma combine and the dense LIF update for M rows.

Operations: the dense ternary MAC, 2 T M K (J N), every block counted
whether or not activity gating skips it; the ramp, combine and LIF head is
not counted.  Bytes: each operand and result once, at the dtypes the
launch passes: the events int8 (the kernel's operand; the cast from f32
runs before it), the two twin-cell planes int8 over J N columns, the J N
scales, the ramp codebook (``codes`` levels and the boundaries between
them), the (J, N) dendritic
weights, the two stream-control words, the membrane in and out, the
all-zero noise block NLD still streams, and the spikes, mask and ramp
steps it writes.

In the trace the kernel is the custom call named after its jitted
wrapper, ``%fused_macro_seq.N``, as in KWN serving.
"""

MATCH = r"^%(jvp_jit_)?fused_macro_seq(__)?(\.\d+)? = .*custom-call\("


def ops(s: dict) -> float:
    return 2.0 * s["t"] * s["m"] * s["k"] * s["branches"] * s["n"]


def nbytes(s: dict) -> float:
    t, m, k, n, j = s["t"], s["m"], s["k"], s["n"], s["branches"]
    b = t * m * k + 2 * k * j * n                    # events, planes
    b += 4 * j * n + 4 * (2 * s["codes"] - 1)       # scales, codebook
    b += 4 * j * n + 4 * 2                           # w_dend, stream control
    b += 4 * m * n * 2                               # membrane in and out
    b += 4 * t * m * n                               # zero noise block
    b += 4 * t * m * n * 2 + 4 * t * m               # spikes, mask, steps
    return float(b)
