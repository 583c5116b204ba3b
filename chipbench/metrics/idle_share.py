"""Share of the traced window in which no operation ran on the device.
Read for ``idle_share.<part>``, one name for each end-to-end metric it
moves."""


def read(r):
    return r.idle_share()
