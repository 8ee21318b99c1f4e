"""How much of the expert layer a decode step streams, %: held experts with
at least one pick, a routed layer a decode step (the program's counters
``serving.moe_decode_experts_touched_total`` over
``serving.moe_decode_layer_steps_total``), over the experts held
(``num_experts``). A step reads the weights of the experts its picks touch,
so this is the routed part of ``decode_step_bytes``; it also says how far
the seed's router is from an even one (which reads 64 (1 - e^-2) of 64, 86%,
at 32 rows of 4 picks)."""


def read(run):
    c = run.get("counters", {})
    touched = c.get("dl4j_serving_moe_decode_experts_touched_total")
    layer_steps = c.get("dl4j_serving_moe_decode_layer_steps_total")
    held = run["cfg"].get("num_experts")
    if not touched or not layer_steps or not held:
        return None
    return touched / layer_steps / held * 100.0
