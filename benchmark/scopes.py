"""Device time by `jax.named_scope`: joins a trace's op events to the scopes
of the program through the compiled HLO text's `op_name` metadata.

`trace_reduce` knows an op event by its HLO instruction name and text, which
carry no scope. The compiled module's text gives every instruction an
`op_name` such as `jit(train_step)/.../moe_route/top_k`: the path of named
scopes it was traced under (a fusion carries its root's; what the backward
and remat make of a scope keeps the scope's name inside `transpose(...)` or
`checkpoint`). `index` keeps, of a compiled text, the instructions that lie
under one of the given scopes; `seconds` sums their own time in a trace. A
kernel the TPU compiler makes itself (`ragged-dot-*`) carries no path: find
it by name (`ReducedTrace.seconds_matching`).
"""

from __future__ import annotations

import re
from typing import Dict, Iterable

_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*?op_name=\"([^\"]*)\"")


def index(hlo_text: str, scopes: Iterable[str]) -> Dict[str, str]:
    """{instruction name: the first of `scopes` in its op_name path}."""
    scopes = tuple(scopes)
    out: Dict[str, str] = {}
    for line in hlo_text.splitlines():
        found = _INSTRUCTION.match(line)
        if not found:
            continue
        parts = re.split(r"[/()]", found.group(2))
        for scope in scopes:
            if scope in parts:
                out[found.group(1)] = scope
                break
    return out


def seconds(trace, op_scopes: Dict[str, str], *scopes: str,
            device: int = 0) -> float:
    """Own time of the ops of one device that lie under one of `scopes`."""
    return trace.self_seconds(
        lambda o: op_scopes.get(o.name) in scopes, device)
