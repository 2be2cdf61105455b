"""device_idle_share (%): share of the traced window with no operation
running on the device."""

from harness import layers


def read(run):
    return layers.device_idle_share(run)
