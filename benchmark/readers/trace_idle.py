"""Reader `trace_idle`: the share of the traced window in which no
operation ran on the device: 100 x (1 - busy / window)."""


def read(params, obs):
    trace = obs.get("trace")
    if not trace or trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
