"""Reader `bulk_stats`: a stage time of `RepoBackend.last_bulk_stats`
(host-clock busy seconds the loader keeps per open), as the median over
the window's opens. params: {"key": "t_io"}."""

import statistics


def read(params, obs):
    vals = [s[params["key"]] for s in obs.get("bulk_stats", ())
            if params["key"] in s]
    return float(statistics.median(vals)) if vals else None
