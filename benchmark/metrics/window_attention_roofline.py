"""Roofline share of the decoder's sliding-layer attention kernels
(`flash_window_*` events of chip 0): the FLOPs and bytes attention over the
`sliding_window` latest keys of each document needs
(benchmark/roofline_laguna.py) over their summed device time. The part of a
block pair outside the window, and the forward that remat runs again below
PR 30's span, are in the time and not in the need."""

from benchmark import roofline_laguna


def read(run):
    return roofline_laguna.attention_share(run, "flash_window_",
                                           "sliding_attention", "window_pairs")
