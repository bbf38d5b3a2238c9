"""Images trained per second and chip: global batch x optimizer steps
completed in the window / window seconds / chips. The window opens and
closes on a fence. Host clock."""


def read(run):
    if "steps" not in run.records or run.records["window_s"] <= 0:
        return None
    return run.records["images"] / run.records["window_s"] / run.chips
