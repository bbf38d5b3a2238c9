"""Share of the window in which chip 0 was idle while the worker ran its
own Python between batches: `stack` (`np.stack`), `dispatch` (the call of
the compiled bucket) and `deliver` (resolving the futures), the phases
`batch_handoff_ms_p50` times.

The device's timeline lies on the host's clock to about 1 ms only, and
`dispatch` borders `wait` where the device starts: that moves up to 0.9
points between this and `serve_idle_wait_pct` from run to run. Only their
sum is reliable; judge a change by the sum."""

from benchmark import spans


def read(run):
    return spans.idle_pct(run, "stack", "dispatch", "deliver")
