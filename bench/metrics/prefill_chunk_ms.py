"""Mean wall time of the program's `prefill_chunk` spans in the window; a
chunk blocks until its logits are ready, so this is host plus device."""


def read(run):
    d = [s.end - s.start for s in run.spans if s.name == "prefill_chunk"]
    return 1e3 * sum(d) / len(d) if d else None
