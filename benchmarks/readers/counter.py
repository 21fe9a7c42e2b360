"""One counter the driver observed, as it is."""


def read(obs, key):
    value = obs.get("counters", {}).get(key)
    return None if value is None else float(value)
