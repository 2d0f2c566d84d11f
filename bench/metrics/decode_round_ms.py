"""Mean wall time of the program's `decode_round` spans in the window; a
span ends after the round's tokens are fetched to the host."""


def read(run):
    d = [s.end - s.start for s in run.spans if s.name == "decode_round"]
    return 1e3 * sum(d) / len(d) if d else None
