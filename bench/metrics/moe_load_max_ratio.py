"""Load imbalance of the held experts: per SGD step and MoE layer, the
largest number of tokens any held expert received over the mean of the
held experts, averaged over the window's steps and layers (the program's
``expert_tokens`` counter).  1 is an even load; the busiest expert sets
the length of the grouped matmul's longest group."""


def read(run):
    return run.counters.get("moe_load_max_ratio")
