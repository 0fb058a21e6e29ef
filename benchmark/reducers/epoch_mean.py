"""Mean over the measured epochs of a sum and difference of telemetry fields."""


def read(obs, *, plus, minus=(), scale: float = 1.0):
    rows = [e for e in obs.epochs
            if all(e.get(k) is not None for k in list(plus) + list(minus))]
    if not rows:
        return None
    per = [sum(e[k] for k in plus) - sum(e[k] for k in minus) for e in rows]
    return scale * sum(per) / len(per)
