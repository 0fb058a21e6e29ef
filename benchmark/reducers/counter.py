"""A counter the driver kept, by name."""


def read(obs, *, name: str, scale: float = 1.0):
    value = obs.counters.get(name)
    return None if value is None else value * scale
