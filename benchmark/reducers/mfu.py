"""End-to-end utilisation: work per unit x units per second over chips x peak.
Host clock: the rate is the end-to-end metric itself."""


def read(obs, *, rate: str, work: str, peak: str = "flops_per_s"):
    if rate not in obs.end_to_end or work not in obs.shapes:
        return None
    return 100.0 * obs.shapes[work] * obs.end_to_end[rate] / (obs.chips * obs.peaks[peak])
