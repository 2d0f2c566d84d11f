"""Share of its roofline that the decode program reaches on the device.

For every traced round that ran `paged_decode_round`: the least time the
round's work needs (`bench/work.py`: every bf16 weight once, the keys and
values of each row's live tokens read and one row written, at the chip's
peak FLOP/s or HBM bandwidth, whichever binds), over the device time of the
program's events in the trace. Padding the tables to the widest request
is not work, so a decode that reads only live pages raises this share."""

from bench import work


def read(run):
    if run.trace is None:
        return None
    per_round = run.trace["program_rounds"].get("paged_decode_round", {})
    need = dev = 0.0
    for n, ns in per_round.items():
        ctx = run.driver.rounds[n].decode_ctx
        if not ctx or ns <= 0:
            continue
        need += work.least_seconds(work.decode_flops(run.sizes, ctx),
                                   work.decode_bytes(run.sizes, ctx), run.peaks)
        dev += ns / 1e9
    return 100.0 * need / dev if dev else None
