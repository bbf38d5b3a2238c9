"""`python -m vitax.train` — the module-form training entry point.

Identical surface to run_vit_training.py (parse_config's full flag set,
--preset_file included, so a committed autotune winner drives a real run:
`python -m vitax.train --fake_data --preset_file presets/l14_v5e-1.json`).
"""

from vitax.config import parse_config
from vitax.train.loop import train


def main(argv=None):
    cfg = parse_config(argv)
    train(cfg)


if __name__ == "__main__":
    main()
