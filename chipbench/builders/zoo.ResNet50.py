"""Builder ``zoo.ResNet50``: the program's ResNet-50 with the benchmark's
weights put in. The only place that knows how the program names its leaves."""

import jax.numpy as jnp


def build(cfg: dict):
    from deeplearning4j_tpu.zoo import ResNet50

    size = cfg["image_size"]
    return ResNet50(num_classes=cfg["num_classes"],
                    input_shape=(size, size, cfg["num_channels"]),
                    compute_dtype=cfg["compute_dtype"]).init()


def load(net, weights: dict) -> None:
    """Copies, because the train step donates its parameters and the
    reference needs ``weights`` afterwards."""
    copy = lambda a: jnp.array(a, copy=True)
    for name, arr in weights.items():
        stem, leaf = name.rsplit("_", 1)
        if name == "fc_w":
            net.params["output"]["W"] = copy(arr)
        elif name == "fc_b":
            net.params["output"]["b"] = copy(arr)
        elif leaf == "conv":
            net.params[name]["W"] = copy(arr)
        else:
            net.params[f"{stem}_bn"][leaf] = copy(arr)


def export(tree: dict) -> dict:
    """A tree shaped like ``net.params`` (the parameters, or one of Adam's
    moments) -> {reference leaf name: array}."""
    out = {}
    for layer, leaves in tree.items():
        for leaf, arr in leaves.items():
            if layer == "output":
                out[f"fc_{leaf.lower()}"] = arr
            elif layer.endswith("_conv"):
                out[layer] = arr
            else:
                out[f"{layer[:-3]}_{leaf}"] = arr
    return out


def adam_m(net) -> dict:
    return export({k: v["m"] for k, v in net.opt_states.items() if v["m"]})


def step_memory(net, x, y):
    """What the compiled train step takes on the device beside its
    arguments, from its ``memory_analysis()``: the step is lowered as
    ``_fit_batch`` calls it for this batch (nothing runs, nothing is
    donated; the jit's own cache holds it). None where the step is not a
    jitted function."""
    if not hasattr(net._train_step, "lower"):
        return None
    n = x.shape[0]
    compiled = net._train_step.lower(
        net.params, net.states, net.opt_states, net._it_dev, net._rng_key,
        dict(zip(net.conf.inputs, [x])), dict(zip(net.conf.outputs, [y])),
        net._dev_weights(n, n), None, None).compile()
    m = compiled.memory_analysis()
    return {"argument_bytes": int(m.argument_size_in_bytes),
            "output_bytes": int(m.output_size_in_bytes),
            "alias_bytes": int(m.alias_size_in_bytes),
            "temp_bytes": int(m.temp_size_in_bytes)}
