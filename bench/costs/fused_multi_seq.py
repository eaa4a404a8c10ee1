"""The stacked fused kernel (``kernels/fused_macro.py``
``_multi_seq_kwn_kernel``): T steps of every layer of a KWN stack in one
launch, the spikes between layers kept on chip.

Operations: the dense ternary MACs of every layer, sum 2 T M K_l N_l,
every block counted (the heads are not).  Bytes: the events f32, each
layer's two int8 planes and membranes, the pre-drawn SNL noise of each
layer, and the last layer's spikes and mask.

In the trace the kernel is the custom call named after its jitted
wrapper: ``%fused_macro_multi_seq.N``.
"""

MATCH = r"^%fused_macro_multi_seq(\.\d+)? = .*custom-call\("


def ops(s: dict) -> float:
    return sum(2.0 * s["t"] * s["m"] * k * n for k, n in s["layers"])


def nbytes(s: dict) -> float:
    t, m = s["t"], s["m"]
    b = 4 * t * m * s["layers"][0][0]
    for k, n in s["layers"]:
        b += 2 * k * n + 4 * 3 * n + 4 * m * n * 2 + 4 * t * m * n
        b += 4 * t * m * 2                           # steps, spike counts
    n_last = s["layers"][-1][1]
    return float(b + 4 * t * m * n_last * 2)
