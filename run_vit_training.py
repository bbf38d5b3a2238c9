#!/usr/bin/env python3
"""vitax training entry point — CLI-compatible with the reference's
run_vit_training.py (same 26 flags, same defaults; reference :327-364).

Launch (single host; each pod host runs the same command — see README):
    python3 run_vit_training.py --fake_data ...
"""

from vitax.config import parse_config
from vitax.train.loop import train


def main(argv=None):
    cfg = parse_config(argv)
    train(cfg)
    # multi-process runs must also EXIT together: a rank that wins the
    # teardown race kills the coordination service under its peers and a
    # clean drain reads as dirty (see vitax/distributed.orderly_shutdown)
    from vitax.distributed import orderly_shutdown
    orderly_shutdown()


if __name__ == "__main__":
    main()
