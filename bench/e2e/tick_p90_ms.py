"""90th percentile of the window's tick() wall times (harness clock), ms."""
import numpy as np


def read(window):
    return float(np.percentile(window.tick_s, 90) * 1e3)
