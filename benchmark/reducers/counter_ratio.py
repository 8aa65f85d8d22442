"""A window delta of one counter over another (`denominator`) or over a
count of work the traffic reports (`per_unit`, e.g. "blocks"); alone, the
delta itself."""


def read(spec: dict, reading) -> float | None:
    top = reading.counters.get(spec["counter"], 0)
    if "denominator" in spec:
        bottom = reading.counters.get(spec["denominator"], 0)
    elif "per_unit" in spec:
        bottom = reading.units.get(spec["per_unit"], 0)
    else:
        return float(top)
    if not bottom:
        return None
    return top / bottom
