"""Mean of a per-block (or per-request) reading the traffic reports."""


def read(spec: dict, reading) -> float | None:
    values = reading.units.get(spec["unit_list"])
    if not values:
        return None
    return sum(values) / len(values) * spec.get("scale", 1.0)
