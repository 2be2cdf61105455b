"""decode_roofline (%): bytes a decode step must move at peak HBM
bandwidth over the mean device time of the decode program in the trace."""

from harness import layers


def read(run):
    return layers.decode_roofline(run)
