"""The batch period by the server's own marks: how long the engine had each
batch to itself, without the wait behind the batch ahead (`serve_batch`
events inside the window), median. Host marks, not device time: the device's
own time is the trace's (`serve_device_idle_pct`, the breakdown).

The batcher keeps one batch queued on the device behind the one that runs
(vitax/serve/batcher.py), so a batch's `infer_s` (`t_deliver - t_put`) holds
the rest of its predecessor's run as well: two periods. The engine turns to
batch n when its inputs are there and its compiled call is made
(`t_dispatch`, the last mark before the call) and the batch ahead has been
delivered (its `t_deliver`, on the worker's clock), whichever is later, and
is done with it at its own `t_deliver`:

    t_deliver(n) - max(t_dispatch(n), t_deliver(n - 1))

Under saturation that is `t_deliver(n) - t_deliver(n - 1)`, the delivery
period; with nothing ahead it is what `infer_s` was before batches
overlapped, less the `device_put`. A batch whose predecessor's event lies
outside the window is left out."""

from benchmark import spans


def read(run):
    return spans.median_ms_of(
        e["t_deliver"] - max(e["t_dispatch"], ahead["t_deliver"])
        for e, ahead in spans.batches_with_the_one_ahead(run))
