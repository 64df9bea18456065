"""Set-up time: process start to the first step of the window (imports,
device start, plan, state on the device, step compile or cache load, the
first steps that warm up and check the program)."""


def read(run):
    return run.setup_s
