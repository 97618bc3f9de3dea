"""Reader `roofline`: the least time the chip could take for the work
the traced programs did (bytes from counts/<counts>.py over the peak of
peaks.json for this device kind; an unknown device is an error) over
the device time they took in the trace, in percent.
params: {"match": [...], "counts": "bulk_slab", "shapes": "bulk_slabs",
"peak": "hbm_bytes_per_s", "per": "traced_opens"}."""

from benchmark.harness import load_module


def read(params, obs):
    trace = obs.get("trace")
    shapes = obs.get(params["shapes"])
    if not trace or not shapes:
        return None
    took = sum(s for n, s in trace["programs"].items()
               if any(m in n for m in params["match"]))
    if took <= 0:
        return None
    kind = obs["device_kind"]
    if kind not in obs["peaks"]:
        raise SystemExit(f"benchmark: no peaks for device kind {kind!r}")
    peak = obs["peaks"][kind][params["peak"]]
    work = load_module("counts", params["counts"]).bytes_moved(shapes)
    work *= obs.get(params["per"], 1) if params.get("per") else 1
    return 100.0 * (work / peak) / took
