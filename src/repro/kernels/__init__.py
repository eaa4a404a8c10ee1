"""Pallas TPU kernels for the paper's compute hot-spots.

fused_macro — the whole macro step (MAC -> IMA ramp -> KWN/NLD head -> LIF)
              in one kernel, VMEM-resident end to end: the inference hot
              path, and its stacked multi-layer form; bitwise-equal to the
              ``ref`` oracles at f32.
fused_macro_grad — the time-reversed surrogate backward (BPTT) of the
              fused sequence, for silicon-in-the-loop training.
flash_attention — online-softmax attention fwd with causal block skipping
              (beyond-paper: removes the 2x causal flops waste the roofline
              table shows for train/prefill attention; serving-prefill use).

``ops``  — jit'd wrappers (padding, batching, interpret_mode()).
``ref``  — pure-jnp oracles used by the allclose test sweeps.
"""

from repro.kernels import ops, ref  # noqa: F401
