"""Roofline share of the decoder's full-layer attention kernels
(`flash_causal_*` events of chip 0): the FLOPs and bytes causal attention
within each document needs (benchmark/roofline_laguna.py: the sum of
n (n + 1) / 2 pairs, grouped key/value heads read once) over their summed
device time. Reads what the step itself counted."""

from benchmark import roofline_laguna


def read(run):
    return roofline_laguna.attention_share(run, "flash_causal_",
                                           "full_attention", "causal_pairs")
