"""Share of the window in which chip 0 was idle and no phase of a
`serve_batch` event covers the time: the guard on the spans' coverage (a
batch in flight when the window closes has written no event yet)."""

from benchmark import spans


def read(run):
    return spans.idle_pct(run, spans.UNNAMED)
