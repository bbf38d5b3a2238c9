"""The fullest held expert's load over the mean load, the worst sparse layer
(the step's own per-layer per-expert counter `expert_load`, at the last
warm-up step): 1 is perfect balance; the grouped products' time follows the
sum, a deployment's slowest chip the maximum."""


def read(run):
    load = run.records.get("expert_load")
    if not load:
        return None
    return max(max(row) * len(row) / max(sum(row), 1) for row in load)
