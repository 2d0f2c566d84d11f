"""Mean rows of the decode rounds in the window (the `width` the program's
`decode_round` spans carry): how full the scheduler keeps a round."""


def read(run):
    rows = [s.args["width"] for s in run.spans if s.name == "decode_round"]
    return sum(rows) / len(rows) if rows else None
