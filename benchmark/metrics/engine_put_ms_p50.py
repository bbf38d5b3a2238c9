"""Host side of handing a batch to the device (`t_dispatch - t_put` of the
server's `serve_batch` events: fault hook, padding, `jax.device_put`),
median."""

from benchmark import spans


def read(run):
    return spans.median_ms(run, "serve_batch",
                           lambda e: e["t_dispatch"] - e["t_put"])
