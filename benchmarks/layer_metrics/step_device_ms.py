"""Device-busy time per step: the union of the device's operation
intervals over the traced window, divided by the steps traced."""


def read(ctx):
    return ctx["reduced"]["step_device_s"] * 1e3
