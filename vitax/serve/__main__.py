"""CLI entry: python -m vitax.serve — load params, warm up, serve HTTP.

Shares the training CLI surface (vitax/config.py build_parser — the model
shape flags MUST match the checkpoint being served) plus two source flags:

    # serve the latest Orbax epoch checkpoint
    python -m vitax.serve --ckpt_dir /ckpts --embed_dim 5120 ... --serve_port 8000

    # serve a consolidated single-file export (vitax.checkpoint.consolidate)
    python -m vitax.serve --npz full.npz --embed_dim 5120 ...
"""

from __future__ import annotations

import sys

from vitax.config import Config, build_parser, config_fields_from_namespace


def main(argv=None) -> int:
    parser = build_parser()
    src = parser.add_argument_group("vitax serve source")
    src.add_argument("--npz", type=str, default="",
                     help="consolidated .npz export to serve (overrides "
                          "--ckpt_dir/--epoch)")
    src.add_argument("--epoch", type=int, default=-1,
                     help="epoch checkpoint to serve (-1 = latest under "
                          "--ckpt_dir)")
    ns = parser.parse_args(argv)
    cfg = Config(**config_fields_from_namespace(ns)).validate()
    from vitax.platform import setup_compile_cache
    setup_compile_cache()  # restarts re-use the AOT buckets' compiles

    # the registry's engine constructor (vitax/programs/builder.py):
    # scenario-checked, then npz export or Orbax checkpoint exactly as the
    # flags say — arbiter-provisioned replicas boot through the same path
    from vitax.programs.builder import build_engine
    from vitax.serve.server import serve_forever
    engine = build_engine(cfg, npz=ns.npz,
                          epoch=None if ns.epoch < 0 else ns.epoch)
    # serve_forever binds first, THEN warms: /healthz answers (live,
    # ready: false) while the AOT buckets compile, so a fleet router can
    # watch the replica warm without routing to it; SIGTERM drains cleanly
    # (in-flight answered, batcher flushed) and we exit 0
    serve_forever(cfg, engine)
    return 0


if __name__ == "__main__":
    sys.exit(main())
