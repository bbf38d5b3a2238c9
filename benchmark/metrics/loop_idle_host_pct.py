"""Share of the window in which chip 0 was idle while the loop thread ran
its own and telemetry's Python after a step (`host` of
benchmark/loop_spans.py): at a log step the device has drained, and stands
still through the record's write. The six `loop_idle_*` shares add up to
`device_idle_pct`."""

from benchmark import loop_spans


def read(run):
    return loop_spans.idle_pct(run, "host")
