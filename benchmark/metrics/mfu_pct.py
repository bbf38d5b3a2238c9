"""Model FLOP/s utilisation of the traced run: useful forward+backward FLOPs
an image (benchmark/flops.py; recomputation not counted) x images a second
over chips x the bf16 peak."""

from benchmark import flops


def read(run):
    if run.peaks is None or "steps" not in run.records:
        return None
    rate = run.records["images"] / run.records["window_s"]
    return 100.0 * flops.model_flops_per_image(run.config) * rate \
        / (run.chips * run.peaks["bf16_flops"])
