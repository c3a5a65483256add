"""95th percentile of the window's scenario() wall times (harness clock), ms."""
import numpy as np


def read(window):
    return float(np.percentile(window.scenario_s, 95) * 1e3)
