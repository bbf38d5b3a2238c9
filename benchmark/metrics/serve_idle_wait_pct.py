"""Share of the window in which chip 0 was idle while the worker was
blocked on the outputs (`wait`: `t_wait` to `t_deliver`): the input still on
its way to the device, or the output on its way back.

The device's timeline lies on the host's clock to about 1 ms only, and
`wait` borders `dispatch` where the device starts: that moves up to 0.9
points between this and `serve_idle_handoff_pct` from run to run. Only their
sum is reliable; judge a change by the sum."""

from benchmark import spans


def read(run):
    return spans.idle_pct(run, "wait")
