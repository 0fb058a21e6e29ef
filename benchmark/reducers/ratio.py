"""One counter over another."""


def read(obs, *, over: str, under: str, scale: float = 1.0):
    a, b = obs.counters.get(over), obs.counters.get(under)
    if a is None or not b:
        return None
    return scale * a / b
