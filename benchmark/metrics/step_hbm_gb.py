"""The compiler's own accounting of the step for one chip
(`memory_analysis()`): arguments + temporaries + outputs that alias no
argument. The runtime's `peak_bytes_in_use` leaves the temporaries out."""


def read(run):
    if "steps" not in run.records or "step_bytes" not in run.program:
        return None
    return run.program["step_bytes"] / 1e9
