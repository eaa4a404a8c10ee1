"""Share of the roofline of ``fused_seq_nld`` (%): the larger of its dense
MAC operations over the int8 peak and its bytes over the HBM bandwidth
(``bench/costs/fused_seq_nld.py``), over its device time in the trace."""


def read(rec):
    return rec.roofline("fused_seq_nld")
