"""Traces and backend compiles inside the measured window, counted through
`jax.monitoring`. Must be 0; anything else also fails `correct`."""


def read(run):
    if "steps" not in run.records:
        return None
    return float(run.records["compiles_in_window"])
