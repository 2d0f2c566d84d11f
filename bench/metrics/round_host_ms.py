"""Mean over the window's rounds of the program's `round` span less the
time under its `decode_wait` and `prefill_wait` spans: what the synchronous
host loop adds to each round (admission, staging, dispatch, emission, the
scans and syncs after a chunk), stalls included. None where the program
records no wait spans."""
import bisect

WAITS = ("decode_wait", "prefill_wait")


def read(run):
    rounds = sorted((s.start, s.end) for s in run.spans if s.name == "round")
    waits = sorted((s.start, s.end) for s in run.spans if s.name in WAITS)
    if not rounds or not waits:
        return None
    starts = [a for a, _ in waits]
    host = 0.0
    for a, b in rounds:
        host += b - a
        i = bisect.bisect_left(starts, a)
        while i < len(waits) and waits[i][0] < b:
            if waits[i][1] <= b:
                host -= waits[i][1] - waits[i][0]
            i += 1
    return 1e3 * host / len(rounds)
