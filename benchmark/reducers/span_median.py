"""Median of one of the benchmark's spans, scaled (`scale` 1000: ms)."""

import statistics


def read(spec: dict, reading) -> float | None:
    seconds = reading.spans.get(spec["span"])
    if not seconds:
        return None
    return statistics.median(seconds) * spec.get("scale", 1.0)
