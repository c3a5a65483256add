"""Telemetry samples ingested and flushed into the device ring, over the
window's whole wall time (ingest, ticks and queries alike)."""


def read(window):
    return float(window.samples / window.seconds)
