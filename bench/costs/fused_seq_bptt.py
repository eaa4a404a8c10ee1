"""The surrogate BPTT kernel (``kernels/fused_macro_grad.py``
``_seq_kwn_bwd_kernel``): the time-reversed backward of ``fused_seq``.

Operations: the dW contraction x^T g, 2 T M K N (the per-step surrogate
chain is not counted).  Bytes: events f32, the four saved (T, M, N)
stacks (spike cotangent, V_mem trace, winner mask, MAC), the scale, the
final-membrane cotangent, dW and dv0 once each.

In the trace the kernel is the custom call named after its jitted
wrapper: ``%transpose_jvp_jit_fused_macro_seq_grad___.N``.
"""

MATCH = r"^%(transpose_)?(jvp_jit_)?fused_macro_seq_grad_*(\.\d+)? = .*custom-call\("


def ops(s: dict) -> float:
    return 2.0 * s["t"] * s["m"] * s["k"] * s["n"]


def nbytes(s: dict) -> float:
    t, m, k, n = s["t"], s["m"], s["k"], s["n"]
    return float(4 * t * m * k + 4 * 4 * t * m * n + 4 * n
                 + 4 * m * n * 2 + 4 * k * n)
