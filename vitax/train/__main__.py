"""`python -m vitax.train` — the module-form training entry point.

Identical surface to run_vit_training.py (parse_config's full flag set).
"""

from vitax.config import parse_config
from vitax.train.loop import train


def main(argv=None):
    cfg = parse_config(argv)
    train(cfg)


if __name__ == "__main__":
    main()
