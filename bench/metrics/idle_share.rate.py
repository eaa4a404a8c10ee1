"""Share of the window in which no operation ran on the device (%): one
minus the union of device-busy intervals over the traced window."""


def read(rec):
    return rec.idle_share()
