"""The rules of form a configuration file is held to, whatever its model
family: how it states its cut, and that it sets no performance knob.

A configuration is cut to size in two ways only (the `model-configs` guide,
section 4). In depth; and to the share one chip holds of a stated
deployment, in which several chips share each layer: its experts, its
heads, its rows of the vocabulary. Both are COUNTS held here, and every one
is listed in `reduced` with the published value in `source_values`. A width
is never cut. The rules are data this package owns (`form_rules.json`); what
belongs to one family (which key counts the layers, which the experts, which
blocks reach `Config`) is its `shapes/<family>.json`, which a later PR adds.

`problems(config, family, rules, config_fields)` returns every breach as a
line of text, each rule with a message of its own; nothing is raised, so a
test can ask for the one it broke.
"""

from __future__ import annotations

import os
from typing import Any, List, Optional

from benchmark import manifest as mf

RULES_FILE = os.path.join(mf.BENCH_DIR, "form_rules.json")


def rules() -> dict:
    return mf.read_json(RULES_FILE)


def is_width(key: str, family: dict, widths: dict) -> bool:
    return (key in widths["keys"] or key in family.get("widths", [])
            or key.endswith(tuple(widths["suffixes"]))
            or any(part in key for part in widths["contains"]))


def _number(value: Any) -> bool:
    return isinstance(value, (int, float)) and not isinstance(value, bool)


def cut_problems(key: str, here: Any, source: Any, family: dict,
                 widths: dict) -> List[str]:
    """`key` is reduced from `source` to `here`: it is no width, a number
    got smaller, a per-layer list is a shorter prefix. Nothing else is a
    cut: a changed group would hide which of its keys moved."""
    if is_width(key, family, widths):
        return [f"`{key}` is a width: a width is never reduced"]
    if here == source:
        return [f"`{key}` is listed as reduced but equals the source's"]
    if _number(here) and _number(source):
        return [] if here < source else [
            f"`{key}`: {here} is not smaller than the source's {source}"]
    if isinstance(here, list) and isinstance(source, list):
        return [] if len(here) < len(source) and source[:len(here)] == here \
            else [f"`{key}`: the list is no shorter prefix of the source's"]
    return [f"`{key}`: {here!r} against the source's {source!r} is neither "
            f"a smaller number nor a shorter list"]


def period_of(layers: list) -> int:
    """The shortest period of a per-layer list."""
    for p in range(1, len(layers) + 1):
        if all(layers[i] == layers[i + p] for i in range(len(layers) - p)):
            return p
    return max(len(layers), 1)


def share_problems(config: dict, family: dict, rules: dict) -> List[str]:
    """A file that reduces anything but depth stands for one chip of a
    deployment whose chips share each layer: it says of how many, every
    reduced key has a role the floors can be held by, and what is left is
    still the model."""
    floors = rules["floors"]
    roles = family.get("roles", {})
    depth_key = roles.get("depth")
    # a width in `reduced` is refused as a width, and has no role to ask for
    reduced = [k for k in config["reduced"]
               if not is_width(k, family, rules["widths"])]
    if set(reduced) <= {depth_key}:
        return []
    out = []
    chips = config.get("chips_sharing_a_layer")
    if not (isinstance(chips, int) and not isinstance(chips, bool)
            and chips >= floors["chips_sharing_a_layer"]
            and str(config.get("deployment", "")).strip()):
        out.append("reduces more than depth but states no deployment: "
                   "`chips_sharing_a_layer` (a whole number >= "
                   f"{floors['chips_sharing_a_layer']}) and `deployment`")
    known = {depth_key} | {k for role in ("experts_held", "vocabulary_rows",
                                          "heads", "per_layer")
                           for k in roles.get(role, [])}
    for key in reduced:
        if key not in known:
            out.append(f"`{key}` is reduced but has no role in the family's "
                       f"`roles`: no floor can be held for it")
    source = config.get("source_values", {})
    if isinstance(chips, int) and chips > 0:
        # heads have no floor of their own in the guide: what one of the
        # chips that share a layer would hold of them, and no less
        for key in roles.get("heads", []):
            if (key in reduced and _number(config.get(key))
                    and _number(source.get(key))
                    and config[key] * chips < source[key]):
                out.append(f"`{key}`: {config[key]} heads held, under the "
                           f"share one of {chips} chips holds of the "
                           f"source's {source[key]}")
    for key in roles.get("experts_held", []):
        if key in reduced and config[key] < floors["experts_held"]:
            out.append(f"`{key}`: {config[key]} experts held, under the "
                       f"floor of {floors['experts_held']}")
    for key in roles.get("vocabulary_rows", []):
        if key in reduced and key in source and (
                config[key] < floors["vocabulary_share"] * source[key]):
            out.append(f"`{key}`: {config[key]} rows, under "
                       f"{floors['vocabulary_share']} of the source's "
                       f"{source[key]}")
    if depth_key in config:
        lead = config.get(roles.get("leading_dense", ""), 0)
        lead = lead if _number(lead) else 0
        period = 1
        for key in roles.get("per_layer", []):
            if key in config:
                if len(config[key]) != config[depth_key]:
                    out.append(f"`{key}` has {len(config[key])} entries for "
                               f"{config[depth_key]} layers")
                period = max(period,
                             period_of(source.get(key, config[key])[lead:]))
        need = max(floors["layers_after_leading_dense"], period)
        if config[depth_key] - lead < need:
            out.append(f"`{depth_key}`: {config[depth_key] - lead} layers "
                       f"after the {lead} leading ones, under the floor of "
                       f"{need} (a whole period of {period}, and at least "
                       f"{floors['layers_after_leading_dense']})")
    return out


def _lookup(config: dict, path: str) -> Optional[Any]:
    node: Any = config
    for part in path.split("."):
        if not isinstance(node, dict) or part not in node:
            return None
        node = node[part]
    return node


def knob_keys(rules: dict) -> set:
    """The knobs no family may declare: `form_rules.json`'s, and whatever a
    committed family file adds under `knobs` (a PR that gives `Config` a new
    knob lists it in a file of its own; nothing takes one off)."""
    keys = set(rules["knobs"]["keys"])
    shapes = os.path.join(mf.BENCH_DIR, "shapes")
    for name in sorted(os.listdir(shapes)):
        if name.endswith(".json"):
            keys |= set(mf.read_json(os.path.join(shapes, name))
                        .get("knobs", []))
    return keys


def declared_keys(family: dict) -> set:
    """Every key a family lets through to `Config`."""
    return set(family["shape_keys"]).union(
        family["mesh_keys"], *family.get("nested", {}).values())


def knob_problems(config: dict, family: dict, rules: dict,
                  config_fields: set) -> List[str]:
    """Only what the family declares reaches `Config`; the family declares
    no performance knob; a declared nested block holds declared keys only;
    every width inside a nested block is held equal to a key outside the
    nested blocks, under the source's name, so that no size is cut out of
    sight; and where the family says two keys state one size, they agree."""
    out = []
    nested = family.get("nested", {})
    declared = set(family["shape_keys"]) | set(family["mesh_keys"])
    for key in sorted(declared_keys(family) & knob_keys(rules)):
        out.append(f"the family declares `{key}`, a performance knob")
    pairs = [(a.split(".")[0] in nested, a, b.split(".")[0] in nested, b)
             for a, b in family.get("equal", [])]
    held = {a for _, a, b_in, _ in pairs if not b_in} | {
        b for a_in, _, _, b in pairs if not a_in}
    for block, keys in nested.items():
        for key in keys:
            if (is_width(key, family, rules["widths"])
                    and f"{block}.{key}" not in held):
                out.append(f"the family declares `{block}.{key}`, a width, "
                           f"but its `equal` holds it to no key outside the "
                           f"nested blocks")
    for key in sorted((set(config) & config_fields) - declared):
        out.append(f"sets `{key}`, a `Config` field its family does not "
                   f"declare")
    for block, keys in nested.items():
        for key in sorted(set(config.get(block, {})) - set(keys)):
            out.append(f"`{block}.{key}` is not declared by the family")
    for a_in, a, b_in, b in pairs:
        if (a_in and a.split(".")[0] not in config) or (
                b_in and b.split(".")[0] not in config):
            continue    # the file has no such block: nothing of it reaches Config
        if _lookup(config, a) != _lookup(config, b):
            out.append(f"`{a}` = {_lookup(config, a)!r} but `{b}` = "
                       f"{_lookup(config, b)!r}: the family holds them equal")
    return out


def problems(config: dict, family: dict, rules: dict,
             config_fields: set) -> List[str]:
    out = []
    source = config.get("source_values", {})
    for key in config["reduced"]:
        if key not in config or key not in source:
            out.append(f"`{key}` is reduced but the file lacks it or its "
                       f"`source_values` entry")
        else:
            out += cut_problems(key, config[key], source[key], family,
                                rules["widths"])
    out += share_problems(config, family, rules)
    out += knob_problems(config, family, rules, config_fields)
    return out


def manifest_problems(man: mf.Manifest) -> dict:
    """{configuration: its breaches} over a manifest's configurations, with
    the manifest entry held equal to the file; empty where all is well."""
    import dataclasses

    from vitax.config import Config
    fields = {f.name for f in dataclasses.fields(Config)}
    held_to, found = rules(), {}
    for entry in man.data["configs"]:
        config = man.config(entry["name"])
        out = problems(config, man.family(config["family"]), held_to, fields)
        for key in ("reduced", "source"):
            if config[key] != entry[key]:
                out.append(f"`{key}` differs between the manifest's entry "
                           f"and the file")
        if out:
            found[entry["name"]] = out
    return found
