"""Seconds from process start to the window's start: imports, inputs from
the seed onto the device, and the traffic's warm-up calls, which compile
or read back every program the window runs."""


def read(win):
    return win.setup_s
