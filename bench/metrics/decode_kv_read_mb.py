"""Mean K+V megabytes (1e6 bytes) the decode program gathers a round, over
the window's `decode_round` spans (their `kv_bytes`: every layer, every
row of the round's padded width; a windowed layer its window's blocks, a
full layer the whole table). None where the program's spans carry no
`kv_bytes`."""


def read(run):
    kv = [s.args["kv_bytes"] for s in run.spans
          if s.name == "decode_round" and "kv_bytes" in s.args]
    return sum(kv) / len(kv) / 1e6 if kv else None
