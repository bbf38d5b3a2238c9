"""Client-side time from sending a request to the last byte of its reply,
75th percentile over the replies that arrived inside the window (hundreds;
the count is `attempted`). A failed reply has no latency and fails the run.

Why the 75th: in a closed loop of 32 over batches of 8 a reply waits three or
four batch periods, about half of them each, and 4-7% wait a second longer
for a dropped connection attempt (PERF.md, PR 22). The median sits on the
step between the two modes, the 95th percentile on the step to the slow
replies, and the 90th is reached by the tail in some runs: each flips
between runs of the same code. The 75th lies in the middle of the
four-period mode and leaves it only if the mix of the modes shifts by a
quarter of all replies."""

from benchmark.harness import percentile


def read(run):
    value = percentile(run.records.get("latency_s", []), 0.75)
    return None if value is None else 1e3 * value
