"""The worker's own Python around a batch (`serve_batch` events), the three
phases `serve_idle_handoff_pct` covers: `stack` (`np.stack`,
`t_put - t_stack`), `dispatch` (the call of the compiled bucket,
`t_wait - t_dispatch`) and `deliver` (resolving the futures,
`t_end - t_deliver`), median of their sum. With `batch_collect_ms_p50`,
`engine_put_ms_p50` and `engine_wait_ms_p50` it covers the batch period."""

from benchmark import spans


def read(run):
    return spans.median_ms(
        run, "serve_batch",
        lambda e: ((e["t_put"] - e["t_stack"])
                   + (e["t_wait"] - e["t_dispatch"])
                   + (e["t_end"] - e["t_deliver"])))
