"""How unevenly the router loads the experts held here: the picks of the
fullest held expert over the mean of the held experts, summed over every
routed layer of every prefill and decode step of the window (the program's
counters; 1 = even). A grouped product's time follows its fullest group."""


def read(run):
    c = run.get("counters", {})
    local = c.get("dl4j_serving_moe_picks_local_total")
    most = c.get("dl4j_serving_moe_expert_load_max_total")
    if not local or not most:
        return None
    return most * run["cfg"]["num_experts"] / local
