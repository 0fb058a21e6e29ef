"""Share of the roofline on the device: the least time the chip could take for
the traced units' work (work over peak) over the time an op ran on it."""


def read(obs, *, work: str, units: str, peak: str):
    if not obs.trace or not obs.trace["devices"] or not obs.trace["busy_s"]:
        return None
    n = obs.trace_units.get(units)
    if not n or work not in obs.shapes:
        return None
    least_s = n * obs.shapes[work] / (obs.chips * obs.peaks[peak])
    return 100.0 * least_s / obs.trace["busy_s"]
