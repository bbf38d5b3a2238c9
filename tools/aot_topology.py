"""AOT-compile whole programs against described TPU topologies — no hardware.

Two uses, one mechanism (`jax.experimental.topologies` hands the XLA TPU
compiler a topology description, and the FULL program compiles for it,
GSPMD-partitioned with all collectives; nothing runs):

- the pod-scale flagship configs (ViT-10B on v5p-128, ViT-60B on v5p-256:
  BASELINE.json configs 4 and 5), which no machine here can hold — the
  per-device memory_analysis() is the compiler's own accounting for the pod;
- the rehearsal before a chip run (the `on-chip-measurement` guide, section
  2.3): the `smoke_*` configs are chip_smoke.py's programs — the l14 and
  10B-width train steps on ONE described v5e chip, the 10B-width step on
  four (fsdp=4), and the l14 serve bucket — compiled with the production
  Pallas kernels in real Mosaic lowering and checked against 16 GB, so what
  the chip's compiler would refuse costs no chip time.

Usage:
    JAX_PLATFORMS=cpu python tools/aot_topology.py [--configs 10b 60b]
    JAX_PLATFORMS=cpu python tools/aot_topology.py --configs smoke_l14 \
        smoke_10b_width smoke_10b_width_fsdp4 smoke_serve_l14
    JAX_PLATFORMS=cpu python tools/aot_topology.py --configs 10b \
        --remat_policy dots_saveable      # any trainer flag, on every config

Writes one JSON object per config with the compiled per-device argument /
temp / output bytes and the HBM bound checked. A compile that passes is a
compile, never a run: it says nothing about results or times. Keep
JAX_PLATFORMS=cpu set (the topology compile client is independent of the
default backend) and the persistent compile cache off — a described-topology
entry cannot be read back without a chip. libtpu allows ONE process at a
time (/tmp/libtpu_lockfile) — don't run two topology compiles concurrently.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# HBM bytes per chip by topology-name prefix (Google Cloud TPU documentation)
HBM_BY_PREFIX = {"v5p": 95e9, "v5e": 16e9}


def config_for(cfg_kw: dict, flags) -> "Config":
    """A config entry as a `Config`, with the trainer's own flags
    (vitax/config.py) applied on top: the entry becomes the parser's
    defaults, so an explicit flag wins, as on the trainer's command line."""
    import dataclasses

    from vitax.config import (Config, build_parser,
                              config_fields_from_namespace)
    parser = build_parser()
    parser.set_defaults(**dataclasses.asdict(Config(
        **{"num_classes": 1000, "warmup_steps": 0, **cfg_kw})))
    return Config(**config_fields_from_namespace(
        parser.parse_args(flags))).validate()


def compile_for_topology(tag: str, topo_name: str, cfg_kw: dict,
                         kernels: bool = False, flags=(),
                         n_devices: int = 0,
                         serve_bucket: int = 0) -> dict:
    """Compile one whole program for `topo_name` (its first `n_devices`
    devices; 0 = all): the train step, or with `serve_bucket` the serving
    engine's predict program for that batch bucket."""
    import jax
    from jax.experimental import topologies

    from chip_smoke import program_facts
    from vitax.models import count_params
    from vitax.programs.builder import (Geometry, abstract_batch,
                                        build_program)

    td = topologies.get_topology_desc(topo_name, "tpu")
    devices = list(td.devices)[:n_devices or None]
    n_dev = len(devices)
    cfg = config_for(cfg_kw, flags)
    # the trainer's own assembly, on the described devices; `kernels`
    # compiles the PRODUCTION program: real Mosaic kernels against the TPU
    # target (VITAX_FORCE_MOSAIC set in main; force_tpu_kernels runs the
    # selection logic despite the CPU host backend)
    geom = Geometry.assemble(cfg, devices=devices, force_tpu_kernels=kernels)
    state = geom.abstract_state
    n_params = count_params(state.params)
    t0 = time.perf_counter()
    if serve_bucket:
        # the engine over ABSTRACT params: _lower_bucket needs only shapes
        from vitax.serve.engine import InferenceEngine
        engine = InferenceEngine(cfg, geom.mesh, geom.model, state.params)
        lowered, _ = engine._lower_bucket(serve_bucket)
        state_bytes = sum(x.size * x.dtype.itemsize
                          for x in jax.tree.leaves(state.params))
    else:
        lowered = build_program("train", geom).lower(
            state, abstract_batch(cfg, geom.mesh),
            jax.eval_shape(lambda: jax.random.key(0)))
        state_bytes = sum(x.size * x.dtype.itemsize
                          for x in jax.tree.leaves(state))
    t_lower = time.perf_counter() - t0
    t0 = time.perf_counter()
    compiled = lowered.compile()
    t_compile = time.perf_counter() - t0
    ma = compiled.memory_analysis()
    hbm = HBM_BY_PREFIX[topo_name.split(":", 1)[0]]
    facts = program_facts(compiled)
    rec = {
        "config": tag,
        "program": (f"serve bucket {serve_bucket}" if serve_bucket
                    else "train step"),
        "topology": topo_name,
        "kernels": bool(kernels),
        "n_devices": n_dev,
        "device_kind": str(devices[0].device_kind),
        "params": n_params,
        "batch_size": serve_bucket or cfg.batch_size,
        "global_state_bytes": state_bytes,
        "per_device_argument_bytes": ma.argument_size_in_bytes,
        "per_device_temp_bytes": ma.temp_size_in_bytes,
        "per_device_output_bytes": ma.output_size_in_bytes,
        "alias_bytes": ma.alias_size_in_bytes,
        "hbm_bound_bytes": int(hbm),
        # donation aliases outputs onto arguments: resident = args + temps
        "per_device_resident_bytes": (ma.argument_size_in_bytes
                                      + ma.temp_size_in_bytes),
        "fits_hbm": (ma.argument_size_in_bytes
                     + ma.temp_size_in_bytes) < hbm,
        # kernels and collectives the compiler put in (chip_smoke.py reads
        # the same facts off the program it ran)
        **{k: facts[k] for k in (
            "tpu_custom_call", "tpu_custom_call_attention",
            "tpu_custom_call_fused_optimizer", "all_gather",
            "reduce_scatter")},
        "lower_seconds": round(t_lower, 1),
        "compile_seconds": round(t_compile, 1),
    }
    return rec


CONFIGS = {
    # BASELINE config 4: the 10.078B flagship on a v5p-128 pod, pure ZeRO-3
    "10b": ("v5p:4x4x8", dict(
        image_size=224, patch_size=14, embed_dim=5120, num_heads=32,
        num_blocks=32, batch_size=1024, fsdp_size=-1,
        remat_policy="none_saveable")),
    # BASELINE config 5: ViT-60B (8192d / 80L) on v5p-256
    "60b": ("v5p:8x8x4", dict(
        image_size=224, patch_size=14, embed_dim=8192, num_heads=64,
        num_blocks=80, batch_size=1024, fsdp_size=-1,
        remat_policy="none_saveable")),
    # config 4 variant: pp2 composed with fsdp64 (the GPipe body's gathers)
    "10b_pp": ("v5p:4x4x8", dict(
        image_size=224, patch_size=14, embed_dim=5120, num_heads=32,
        num_blocks=32, batch_size=1024, pp_size=2, fsdp_size=-1, dp_size=1,
        remat_policy="none_saveable")),
    # GPipe at the 10B shape on 8 chips (pp2 x fsdp4)
    "10b_pp8": ("v5p:2x2x2", dict(
        image_size=224, patch_size=14, embed_dim=5120, num_heads=32,
        num_blocks=32, batch_size=64, pp_size=2, fsdp_size=4, dp_size=1,
        remat_policy="none_saveable")),
    # MoE under pp x ep at ViT-L width (round-5 composition): the manual
    # tiled all-to-alls inside the pipeline body must compile for a REAL
    # TPU target, not just the CPU interpret mesh (~1.3B params: dense L/14
    # + 8 experts per block)
    "moe_pp_ep": ("v5p:2x2x2", dict(
        image_size=224, patch_size=14, embed_dim=1024, num_heads=16,
        num_blocks=24, batch_size=64, moe_experts=8, pp_size=2, ep_size=2,
        dp_size=2, fsdp_size=1, remat_policy="none_saveable")),
}

# configs compiled WITH the production Pallas kernels (real Mosaic lowering
# against the TPU target — not interpret mode): --configs entries here get
# kernels=True automatically
KERNEL_CONFIGS = {
    # the 10B flagship's actual production program (4D whole-N kernel at
    # h32/dh160 grouped-padded geometry) on the v5p-128 pod target
    "10b_kernels": ("v5p:4x4x8", dict(
        image_size=224, patch_size=14, embed_dim=5120, num_heads=32,
        num_blocks=32, batch_size=1024, fsdp_size=-1,
        remat_policy="none_saveable")),
    # ring attention over sp with Mosaic block kernels + ppermute ring —
    # the multi-chip Pallas composition the CPU interpret mesh cannot prove
    "l14_ring_sp": ("v5p:2x2x2", dict(
        image_size=224, patch_size=14, embed_dim=1024, num_heads=16,
        num_blocks=24, batch_size=32, sp_size=2, fsdp_size=4, dp_size=1,
        remat_policy="none_saveable")),
    # long-context streaming kernel WITH in-kernel dropout at N=4096 on the
    # v5e target the real bench chip matches — Mosaic-validates the round-5
    # streaming dropout before any chip window
    "longctx_dropout": ("v5e:2x4", dict(
        image_size=896, patch_size=14, embed_dim=1024, num_heads=16,
        num_blocks=4, batch_size=16, att_dropout=0.1, fsdp_size=-1,
        remat_policy="none_saveable")),
    # l14 with the 4D whole-N dropout kernel (the measured -2.9% path)
    "l14_dropout": ("v5e:2x4", dict(
        image_size=224, patch_size=14, embed_dim=1024, num_heads=16,
        num_blocks=24, batch_size=64, att_dropout=0.1, fsdp_size=-1,
        remat_policy="none_saveable")),
}
CONFIGS.update(KERNEL_CONFIGS)


def _smoke_kw(model: str, batch: int) -> dict:
    """chip_smoke.py's trainer command line as Config kwargs (the model
    shapes live there, in one place)."""
    from chip_smoke import MODELS, train_argv
    from vitax.config import parse_config
    cfg = parse_config(train_argv(model, batch, "/nonexistent", seed=0))
    return {k: getattr(cfg, k) for k in (*MODELS[model], "batch_size",
                                         "warmup_steps")}


# chip_smoke.py's programs against the chip it runs on: name ->
# (model, batch, devices of v5e:2x2 used, serve bucket or 0). Always with
# the production kernels.
SMOKE_CONFIGS = {
    "smoke_l14": ("l14", 32, 1, 0),
    "smoke_10b_width": ("10b_width", 8, 1, 0),
    "smoke_10b_width_fsdp4": ("10b_width", 32, 4, 0),
    "smoke_10b_width_b32_1chip": ("10b_width", 32, 1, 0),  # comparison arm
    "smoke_serve_l14": ("l14", 32, 1, 8),
}


def main():
    ap = argparse.ArgumentParser(
        description="Flags this parser does not know are the trainer's "
                    "(vitax/config.py) and override every config compiled.")
    ap.add_argument("--configs", nargs="+", default=["10b", "60b"],
                    choices=[*CONFIGS, *SMOKE_CONFIGS])
    ap.add_argument("--out", default=os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "AOT_TOPOLOGY.json"))
    args, flags = ap.parse_known_args()
    if flags:
        print(f"[aot_topology] trainer flags: {flags}", flush=True)

    results = []
    for tag in args.configs:
        n_devices = serve_bucket = 0
        if tag in SMOKE_CONFIGS:
            model, batch, n_devices, serve_bucket = SMOKE_CONFIGS[tag]
            topo, kw, kernels = "v5e:2x2", _smoke_kw(model, batch), True
        else:
            topo, kw = CONFIGS[tag]
            kernels = tag in KERNEL_CONFIGS
        if kernels:
            os.environ["VITAX_FORCE_MOSAIC"] = "1"
        print(f"[aot_topology] compiling {tag} for {topo} "
              f"(kernels={kernels}) ...", flush=True)
        rec = compile_for_topology(tag, topo, kw, kernels=kernels,
                                   flags=flags, n_devices=n_devices,
                                   serve_bucket=serve_bucket)
        os.environ.pop("VITAX_FORCE_MOSAIC", None)
        print(json.dumps(rec), flush=True)
        results.append(rec)

    existing = {}
    if os.path.exists(args.out):
        try:
            with open(args.out) as f:
                existing = {r["config"]: r for r in json.load(f)}
        except (json.JSONDecodeError, KeyError, TypeError):
            existing = {}
    for r in results:
        existing[r["config"]] = r
    with open(args.out, "w") as f:
        json.dump(list(existing.values()), f, indent=1)
    print(f"[aot_topology] wrote {args.out}")


if __name__ == "__main__":
    main()
