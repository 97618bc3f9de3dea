"""Reader `span_rate`: the seconds of the program's spans named `names`
in the traced part of the window (`span_tree.load` of the newest
trace), on one of their two clocks, over the seconds traced: how much
of a traced second a stage takes. `clock` is "wall" (default) or "cpu"
(the stat `cpu_us`, PR 34; spans without it count for neither). Spans on
several threads add up, so a rate may pass 1. `per` names the number in
`obs` to divide by (the driver's `traced_s`); without it the plain sum.

A trace with program spans but none of these reads 0; a trace without
any program span, one in which none of the named spans carries `cpu_us`
where that clock is asked for, or a missing or zero `per`, gives None.
params: {"names": ["net.repl.rx"], "clock": "cpu", "per": "traced_s"}.

Why a reader of its own: `span_stats` sums one clock and divides by
nothing, `span_cpu` divides by a count of spans only; a stage's CPU
seconds a traced second is neither.
"""

from benchmark.readers import span_tree
from benchmark.readers.span_cpu import cpu_s


def read(params, obs):
    path = span_tree.newest_trace()
    if path is None or not obs.get("trace"):
        return None
    spans, _busy = span_tree.load(path)
    if not spans:
        return None
    hit = [s for s in spans if s.name in params["names"]]
    clock = params.get("clock", "wall")
    if clock == "cpu":
        secs = [cpu_s(s) for s in hit]
        secs = [c for c in secs if c is not None]
        if hit and not secs:
            return None
    elif clock == "wall":
        secs = [s.dur for s in hit]
    else:
        raise SystemExit(f"benchmark: span_rate has no clock {clock!r}")
    total = float(sum(secs))
    if "per" not in params:
        return total
    per = obs.get(params["per"])
    return total / per if per else None
