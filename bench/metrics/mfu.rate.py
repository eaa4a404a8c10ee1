"""The window's share of the chip's int8 peak (%): the MAC operations the
completed work needs (forward MACs, plus the dW contraction in training)
over the window's length times the peak."""


def read(rec):
    return rec.mfu()
