"""Share of the held experts' hidden units that a ReLU gate leaves above 0:
the step's own `expert_hidden_live` (vitax/models/experts.py: the pairs of a
live sorted row and a hidden unit whose gate is > 0, counted in the forward
loop's blocks and summed over the layers) over the slots held x the expert
width, at the last warm-up step. What a ReGLU is for: the rest of `up` and
`down`'s work multiplies zeros, which the grouped products compute all the
same. None where the step sows no such count (experts gated by silu)."""


def read(run):
    counts = run.records.get("packed_counts") or {}
    units = counts.get("expert_slots_here", 0) * run.config.get(
        "moe_ffn_hidden_size", 0)
    if "expert_hidden_live" not in counts or not units:
        return None
    return 100.0 * counts["expert_hidden_live"] / units
