"""Reader `compile_events`: compile requests of this process counted
from jax.monitoring events (harness.CacheWatch), split at the start of
the window. params: {"phase": "setup"|"window", "field":
"requests"|"hits"|"misses"}."""


def read(params, obs):
    rep = obs.get("compile")
    if not rep:
        return None
    return float(rep[params["phase"]][params["field"]])
