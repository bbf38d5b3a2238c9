"""Valid (non-padding) tokens trained per second and chip in a packed cell:
the step's own `tokens` counter x steps completed / window seconds / chips."""


def read(run):
    counts = run.records.get("packed_counts")
    if counts is None or run.records.get("window_s", 0) <= 0:
        return None
    return (counts["tokens"] * run.records["steps"]
            / run.records["window_s"] / run.chips)
