"""Reader `serve_roofline`: the least time the chip could take for the
read tier's query dispatches of one kind in the trace (bytes of
counts/serve_query.py over the peak of peaks.json; an unknown device is
an error) over the device time they took, in percent. A dispatch's
shape is in its XLA program's name, `jit_serve_<kind>_b<B>_n<N>`, so
every call on device 0's "XLA Modules" line is counted at its own
shape. A trace without such programs gives None.
params: {"kind": "seq_order", "peak": "hbm_bytes_per_s"}."""

import re

from benchmark import trace_reduce
from benchmark.counts import serve_query
from benchmark.readers import span_tree


def read(params, obs):
    path = span_tree.newest_trace()
    if path is None or not obs.get("trace"):
        return None
    name = re.compile(
        r"^jit_serve_" + re.escape(params["kind"]) + r"_b(\d+)_n(\d+)"
    )
    work = took = 0.0
    for plane, lines in trace_reduce.load(path):
        if not span_tree.DEVICE0.match(plane):
            continue
        for line, events in lines:
            if line != "XLA Modules":
                continue
            for event, _start, dur in events:
                m = name.match(event)
                if m:
                    work += serve_query.bytes_moved(
                        params["kind"], int(m.group(1)), int(m.group(2))
                    )
                    took += dur / 1e9
    if took <= 0:
        return None
    kind = obs["device_kind"]
    if kind not in obs["peaks"]:
        raise SystemExit(f"benchmark: no peaks for device kind {kind!r}")
    return 100.0 * (work / obs["peaks"][kind][params["peak"]]) / took
