"""Which kernels a program runs: the one place that chooses.

`choose_kernels(cfg, mesh)` makes the `Kernels` record a model is built with
(`build_model_for`, vitax/programs/builder.py): the attention core (that
family's own chooser, `make_attention_impl` of vitax/ops/attention.py) and,
for a decoder's recurrent layers, the state-space scan, the delta rule and the
mixers' short convolution. A member is the callable the model calls in place
of its plain `jax.numpy` form, or None for that form; `kernel_lines` says at
start-up what was chosen and, where it is the plain form, why.

One rule for the three recurrent families: on a TPU (or forced: interpret mode
on the CPU, real Mosaic under VITAX_FORCE_MOSAIC), where the family's
`*_tiling` (vitax/ops/: pure functions of shapes) tiles every layer of the
model, the fused entry, under shard_map over the batch axes on a mesh of
several devices; the plain form otherwise. The model layer says which shapes a
configuration gives a family; the kernel layer sees shapes, never `cfg`.
"""

from __future__ import annotations

from typing import Callable, List, NamedTuple, Optional, Tuple

from jax.sharding import Mesh, PartitionSpec as P

from vitax.config import Config
from vitax.models.decoder import GATED_CONV, delta_shapes, mixer_shape
from vitax.models.kda import KDAShape
from vitax.ops.attention import make_attention_impl
from vitax.ops.common import LANES
from vitax.ops.conv import conv_silu, conv_tiling
from vitax.ops.kda import chunk_tiling, kda_fused, kda_tiling
from vitax.ops.ssd import scan_tiling, ssd_fused
from vitax.parallel.mesh import BATCH_AXES, shard_map
from vitax.platform import backend_platform


class Kernels(NamedTuple):
    attention: Optional[Callable] = None
    scan: Optional[Callable] = None     # a mamba layer's, as `ssd`
    rule: Optional[Callable] = None     # a kda layer's, as `kda`
    conv: Optional[Callable] = None     # a recurrent mixer's, as `conv_silu`


# None where the model has no such layer; else (whether the kernels tile every
# layer's shapes, the start-up line's words)
Words = Optional[Tuple[bool, str]]


def _scan_words(cfg: Config) -> Words:
    mixer = mixer_shape(cfg)
    if mixer is None:
        return None
    tiling = scan_tiling(mixer.heads, mixer.head_size, mixer.state_size,
                         mixer.groups, mixer.chunk)
    if isinstance(tiling, str):
        return False, f"plain ({tiling})"
    return True, (f"fused kernel (chunk {mixer.chunk}, {tiling[0]} heads a "
                  f"grid step)")


def _rule_words(cfg: Config) -> Words:
    shapes = delta_shapes(cfg)
    if not shapes:
        return None
    kda = [s for s in shapes if isinstance(s, KDAShape)]
    if not kda:     # Gated DeltaNet: `GatedDeltaMixer` runs the plain rule
        return False, (
            f"plain (a {shapes[0].key_size} x {shapes[0].value_size} state "
            f"under one decay a head: the kernels tile a square state of "
            f"multiples of {LANES} under a decay a channel)")
    chunk, sub = chunk_tiling(cfg.pack_tokens, kda[0].gate_bound)
    for s in kda:
        hb = kda_tiling(s.heads, s.head_size, chunk, sub)
        if isinstance(hb, str):
            return False, f"plain ({hb})"
    return True, (f"fused kernel (chunk {chunk}, sub-chunks of {sub}, {hb} "
                  f"heads a grid step)")


def _conv_words(cfg: Config) -> Words:
    mixer = mixer_shape(cfg)
    shapes = ([] if mixer is None else [mixer]) + delta_shapes(cfg)
    if not shapes:      # a conv layer's mixer never asks for the member
        return (False, "plain (a gated convolution, C * conv(B * x) without "
                "an activation: the kernel pair has a silu behind its taps "
                "and no gate)") if GATED_CONV in cfg.layer_kinds else None
    tilings = [conv_tiling(channels, cfg.pack_tokens, taps, norm,
                           2 if cfg.dtype == "bfloat16" else 4)
               for channels, taps, norm in (s.conv for s in shapes)]
    for tiling in tilings:
        if isinstance(tiling, str):
            return False, f"plain ({tiling})"
    return True, "fused kernel (" + ", ".join(
        f"{lanes} channels a grid step in blocks of {rows} tokens"
        for lanes, rows in tilings) + ")"


class _Family(NamedTuple):
    member: str         # of `Kernels`
    line: str           # what the start-up line calls it
    words: Callable[[Config], Words]
    fused: Callable     # the kernel layer's entry, called as the plain form
    # the entry's array arguments in order, "r": rows (sharded over the batch
    # axes), "-": replicated; what follows them is static
    operands: str


FAMILIES = (
    _Family("scan", "state-space scan", _scan_words, ssd_fused, "rr-rr-r"),
    _Family("rule", "delta rule", _rule_words, kda_fused, "rrrrrr"),
    _Family("conv", "mixer convolution", _conv_words, conv_silu, "rr--"),
)


def _member(family: _Family, words: str, mesh: Optional[Mesh]) -> Callable:
    """The family's fused entry as the model calls it, under shard_map on a
    mesh of several devices, named for the start-up line."""
    sharded = mesh is not None and mesh.size > 1
    count = len(family.operands)

    def impl(*args):
        if not sharded:
            return family.fused(*args)
        rows = P(BATCH_AXES)
        return shard_map(
            lambda *arrays: family.fused(*arrays, *args[count:]), mesh=mesh,
            in_specs=tuple(rows if o == "r" else P() for o in family.operands),
            out_specs=rows, check_vma=False)(*args[:count])
    impl.vitax_name = words + (" + shard_map" if sharded else "")
    return impl


def choose_kernels(cfg: Config, mesh: Optional[Mesh] = None,
                   force_tpu_kernels: bool = False) -> Kernels:
    """The kernels of the program `cfg` and `mesh` give. `force_tpu_kernels`
    chooses as on a TPU off it (a compile for a described topology; interpret
    mode on the CPU)."""
    chosen = {}
    on_tpu = force_tpu_kernels or backend_platform() == "tpu"
    for family in FAMILIES if cfg.decoder and on_tpu else ():
        tiles, words = family.words(cfg) or (False, "")
        if tiles:
            chosen[family.member] = _member(family, words, mesh)
    return Kernels(attention=make_attention_impl(
        cfg, mesh, force_tpu_kernels=force_tpu_kernels), **chosen)


def kernel_lines(cfg: Config, kernels: Kernels) -> List[str]:
    """The start-up lines: the attention core's, and one for each family the
    model has a layer of: the chosen kernel's name, or why the plain form
    runs."""
    lines = ["attention core: "
             + getattr(kernels.attention, "vitax_name", "dense jnp")]
    for family in FAMILIES if cfg.decoder else ():
        described = family.words(cfg)
        if described is None:
            continue
        why = described[1] if backend_platform() == "tpu" else "plain (no TPU)"
        lines.append(f"{family.line}: " + getattr(
            getattr(kernels, family.member), "vitax_name", why))
    return lines
