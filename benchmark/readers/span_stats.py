"""Reader `span_stats`: the program's own spans named `names` in the
traced part of the window (the newest trace under benchmark/.cache/,
read by `span_tree.load`), with no open to hang them on: `total`
seconds, `mean` seconds a span, or their `count`. A trace that holds
program spans but none of these reads 0 (no full collection fell into
the traced seconds; a mean of nothing is None); one without any program
span (the program before PR 24) gives None.
params: {"names": ["serve.batch"], "measure": "mean"}."""

from benchmark.readers import span_tree


def read(params, obs):
    path = span_tree.newest_trace()
    if path is None or not obs.get("trace"):
        return None
    spans, _busy = span_tree.load(path)
    durs = [s.dur for s in spans if s.name in params["names"]]
    measure = params["measure"]
    if not spans or (not durs and measure == "mean"):
        return None
    if measure == "total":
        return float(sum(durs))
    if measure == "mean":
        return sum(durs) / len(durs)
    if measure == "count":
        return float(len(durs))
    raise SystemExit(f"benchmark: span_stats has no measure {measure!r}")
