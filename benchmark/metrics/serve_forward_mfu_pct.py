"""Forward-only model FLOP/s utilisation of a serve cell: a third of the
training FLOPs an image x images answered a second over chips x peak."""

from benchmark import flops


def read(run):
    if run.peaks is None or "answered" not in run.records:
        return None
    rate = run.records["answered_work"] / run.records["window_s"]
    return 100.0 * flops.model_flops_per_image(run.config) / 3.0 * rate \
        / (run.chips * run.peaks["bf16_flops"])
