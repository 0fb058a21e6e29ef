"""Share of the compute roofline of a set of device ops, named in the metric's
file: the work a counter holds for the traced units, over the peak, over the self
time of those ops in the trace (``trace["ops"]``, summed by name)."""


def read(obs, *, ops, work: str, peak: str = "flops_per_s"):
    if not obs.trace or not obs.trace.get("ops") or work not in obs.counters:
        return None
    seconds = sum(obs.trace["ops"].get(name, 0.0) for name in ops)
    if not seconds:
        return None
    return 100.0 * obs.counters[work] / (obs.chips * obs.peaks[peak] * seconds)
