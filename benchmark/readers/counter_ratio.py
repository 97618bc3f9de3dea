"""Reader `counter_ratio`: the program's counters (telemetry snapshot at
both ends of the window), summed over `num`, over the sum of `den` (or
as they are, with no `den`). `at` picks the reading: "window" (default:
after minus before) or "before" (the totals at the window's start: what
set-up did). A counter the program does not have, or a zero `den`,
gives None. params: {"num": [...], "den": [...], "at": "window"}."""


def read(params, obs):
    before = obs.get("counters_before")
    after = obs.get("counters_after")
    if before is None or after is None:
        return None

    def total(names):
        if any(n not in after for n in names):
            return None
        if params.get("at") == "before":
            return sum(before.get(n, 0) for n in names)
        return sum(after[n] - before.get(n, 0) for n in names)

    num = total(params["num"])
    if num is None:
        return None
    if not params.get("den"):
        return float(num)
    den = total(params["den"])
    return float(num) / den if den else None
