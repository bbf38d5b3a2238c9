"""The benchmark's copies of the FLOPs and parameter arithmetic equal the
program's, for every cell: each through the module its own generator names
(`arithmetic`), on the `Config` that generator builds."""

import pytest

from benchmark import manifest as mf

MANIFEST = mf.Manifest()
CELLS = [w["name"] for w in MANIFEST.data["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_flops_equal_the_programs(name):
    cell = MANIFEST.cell(name)
    config = MANIFEST.config(cell["config"])
    traffic = MANIFEST.traffic(cell["traffic"])
    gen = mf.generator(traffic["kind"])
    cfg = gen.build_config(MANIFEST.config_kwargs(config), traffic,
                           cell["chips"], 0)
    pairs = gen.arithmetic.against_program(config, traffic, cfg)
    assert len(pairs) >= 2
    for what, ours, programs in pairs:
        assert ours == programs, what
    if "parameters" in config:      # where the file states them
        assert gen.arithmetic.param_count(config) == config["parameters"]


def test_peaks_table():
    row = mf.peaks_for("TPU v5 lite")
    assert row["bf16_flops"] == 197e12 and row["hbm_bytes_per_s"] == 819e9
    with pytest.raises(SystemExit):
        mf.peaks_for("TPU v9 imaginary")
