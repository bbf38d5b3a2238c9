"""The benchmark's copy of the FLOPs arithmetic equals the program's, for
every configuration the manifest names."""

import pytest

from benchmark import flops
from benchmark import manifest as mf

MANIFEST = mf.Manifest()
CONFIGS = [c["name"] for c in MANIFEST.data["configs"]]


@pytest.mark.parametrize("name", CONFIGS)
def test_flops_equal_the_programs(name):
    from vitax.config import Config
    from vitax.models.vit import expected_param_count
    from vitax.telemetry.flops import model_flops_per_image
    config = MANIFEST.config(name)
    cfg = Config(**mf.config_kwargs(config)).validate()
    assert flops.model_flops_per_image(config) == model_flops_per_image(cfg)
    assert flops.num_patches(config) == cfg.num_patches
    assert flops.param_count(config) == expected_param_count(cfg)


def test_peaks_table():
    row = mf.peaks_for("TPU v5 lite")
    assert row["bf16_flops"] == 197e12 and row["hbm_bytes_per_s"] == 819e9
    with pytest.raises(SystemExit):
        mf.peaks_for("TPU v9 imaginary")
