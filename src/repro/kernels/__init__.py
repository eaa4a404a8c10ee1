"""Pallas TPU kernels for the paper's compute hot-spots.

ternary_mac — packed twin-cell ternary GEMM (C1): 2x int8 planes decoded
              in-kernel, MXU f32 accumulation.
kwn_topk    — descending-ramp top-K with early stop (C3): emits mask +
              per-row ADC step counts for the latency/energy model.
lif_step    — fused leak/update/compare + SNL noise (C5): one VMEM pass.
nlq_lut     — NLQ boundary compare + one-hot LUT map-back (C2/C6).
fused_macro — the whole macro step (MAC -> IMA ramp -> KWN/NLD head -> LIF)
              in one kernel, VMEM-resident end to end: the inference hot
              path; bitwise-equal to the composed chain at f32.
flash_attention — online-softmax attention fwd with causal block skipping
              (beyond-paper: removes the 2x causal flops waste the roofline
              table shows for train/prefill attention; serving-prefill use).

``ops``  — jit'd wrappers (padding, batching, interpret_mode()).
``ref``  — pure-jnp oracles used by the allclose test sweeps.
"""

from repro.kernels import ops, ref  # noqa: F401
