"""The time-major fused macro kernel (``kernels/fused_macro.py``
``_seq_kwn_kernel``): one launch runs T steps of the ternary MAC, the
ramp, KWN and the LIF update for M rows.

Operations: the dense ternary MAC, 2 T M K N, every block counted
whether or not activity gating skips it; the ramp, KWN and LIF head is
not counted.  Bytes: each operand and result once, at the dtypes the
launch passes (events f32, the two twin-cell planes int8).

In the trace the kernel is the custom call named after its jitted
wrapper: ``%fused_macro_seq.N`` in serving, ``%jvp_jit_fused_macro_seq__.N``
in training.
"""

MATCH = r"^%(jvp_jit_)?fused_macro_seq(__)?(\.\d+)? = .*custom-call\("


def ops(s: dict) -> float:
    return 2.0 * s["t"] * s["m"] * s["k"] * s["n"]


def nbytes(s: dict) -> float:
    t, m, k, n = s["t"], s["m"], s["k"], s["n"]
    b = 4 * t * m * k + 2 * k * n + 4 * 3 * n        # events, planes, scale
    b += 4 * m * n * 2                               # membrane in and out
    b += 4 * t * m * n * 2 + 4 * t * m               # spikes, mask, steps
    if s.get("noise"):
        b += 4 * t * m * n                           # pre-drawn SNL noise
    if s.get("train"):
        b += 4 * t * m * n * 2                       # MAC and V_mem traces
    return float(b)
