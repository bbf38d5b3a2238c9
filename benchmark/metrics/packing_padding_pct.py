"""Share of a packed step's token slots that hold padding, from the step's
own counters (`padding_tokens` and `tokens` in its metrics, counted on the
device from the segment ids), not from the generator's arrays."""


def read(run):
    counts = run.records.get("packed_counts")
    if counts is None:
        return None
    slots = counts["tokens"] + counts["padding_tokens"]
    return 100.0 * counts["padding_tokens"] / slots if slots else None
