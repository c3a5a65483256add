"""Median wall time of the window's tick() calls (harness clock), ms."""
import numpy as np


def read(window):
    return float(np.percentile(window.tick_s, 50) * 1e3)
