"""Harness clock around each loop's ingest_many, mean per loop, ms."""
import numpy as np


def read(ctx):
    s = ctx.traced.get("ingest_s") or []
    return float(np.mean(s) * 1e3) if s else None
