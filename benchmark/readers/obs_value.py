"""Reader `obs_value`: a number the driver took itself and left in the
window's `obs` (a phase of set-up timed on the host's clock, a quantile
of one of the program's histograms over the window).
params: {"key": "install_s"}."""


def read(params, obs):
    value = obs.get(params["key"])
    return None if value is None else float(value)
