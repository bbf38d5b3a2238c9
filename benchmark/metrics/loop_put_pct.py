"""Share of the window's wall time the loop thread spent handing the host
batch to the device (`put`: `t_got` to `t_batch`:
`make_array_from_process_local_data` up to the batch reaching the loop)."""

from benchmark import loop_spans


def read(run):
    return loop_spans.wall_pct(run, "put")
