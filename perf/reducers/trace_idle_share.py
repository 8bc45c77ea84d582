"""The device's idle share of the traced slice, in per cent: 1 minus the
union of the intervals in which an operation ran on the device, over the
slice, averaged over the chips used."""


def reduce(obs):
    trace = obs.get("trace")
    if not trace or not trace["window_s"]:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
