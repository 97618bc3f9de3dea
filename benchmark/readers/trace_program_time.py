"""Reader `trace_program_time`: device seconds of the XLA programs
whose name holds one of `match`, from the trace's "XLA Modules" line,
divided by obs[`per`] where given (e.g. per traced open).
params: {"match": ["materialize_full"], "per": "traced_opens"}."""


def read(params, obs):
    trace = obs.get("trace")
    if not trace:
        return None
    hit = [s for n, s in trace["programs"].items()
           if any(m in n for m in params["match"])]
    if not hit:
        return None
    per = obs.get(params["per"], 1) if params.get("per") else 1
    return sum(hit) / max(1, per)
