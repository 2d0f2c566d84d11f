"""99th percentile (nearest rank) of every inter-token gap whose later token
lands in the window, read beside a backlog's throughput. With every row
busy the tail is set by the rounds that carry a second prefill chunk (the
token budget left after the decode rows holds one whole chunk and part of
another), so it swings between the one-chunk and the two-chunk round time
with the share of such rounds near 1%: a reading of the scheduler's
packing, not a user's end-to-end number in an offline job."""


def read(run):
    return run.driver.end_to_end().get("tbt_p99_ms")
