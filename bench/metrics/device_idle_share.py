"""Share of the traced rounds in which no operation ran on the device:
1 - (device busy inside the `round` annotations) / (their union). Waiting
for arrivals happens outside every round and is not counted."""


def read(run):
    if run.trace is None or not run.trace["round_ns"] or not run.trace["n_ops"]:
        return None
    t = run.trace
    return 100.0 * (1.0 - t["busy_in_rounds_ns"] / t["round_ns"])
