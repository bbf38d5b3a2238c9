"""The worker's own Python around a batch (`serve_batch` events inside the
window), median of the sum of three stretches: `stack` (`np.stack`,
`t_put - t_stack`), the call of the compiled bucket, and `deliver`
(resolving the futures, `t_end - t_deliver`).

The batch's own marks do not close the call: after `t_dispatch` the worker
makes the call and then turns to the batch AHEAD (its `t_wait`, where
`result()` begins to block), so the `dispatch` phase `t_wait - t_dispatch`
of an overlapped batch stretches over the whole of the batch ahead's wait: a
period, not the worker's time. The call of batch n therefore ends at
`t_wait` of batch n - 1:

    (t_put - t_stack)(n) + (t_wait(n - 1) - t_dispatch(n)) + (t_end - t_deliver)(n)

which holds for a batch dispatched behind one in flight (`overlapped` 1:
`_deliver` of the batch ahead follows its `_dispatch` at once); for the
others the worker collects the next batch before it turns to any answer and
no mark tells the call from that wait, so they are left out. With
`batch_collect_ms_p50`, `engine_put_ms_p50` and `engine_wait_ms_p50` it
covers the worker's part of a batch period."""

from benchmark import spans


def read(run):
    return spans.median_ms_of(
        (e["t_put"] - e["t_stack"]) + (ahead["t_wait"] - e["t_dispatch"])
        + (e["t_end"] - e["t_deliver"])
        for e, ahead in spans.batches_with_the_one_ahead(run)
        if e.get("overlapped") == 1 and ahead["t_wait"] >= e["t_dispatch"])
