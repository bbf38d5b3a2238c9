"""Share of the window in which chip 0 was idle while the worker laid the
uint8 batch out and handed it to the device (`put`: `t_put` to `t_dispatch`:
fault hook, padding, `jax.device_put`)."""

from benchmark import spans


def read(run):
    return spans.idle_pct(run, "put")
