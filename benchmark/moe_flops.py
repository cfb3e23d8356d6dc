"""Operations and bytes of a dropless expert layer's grouped products, from
shapes alone, and of the grouped-query flash kernels' calls: the count
functions of the ``lfm2-8b-a1b`` cells' roofline metrics. Like ``flops.py``
(whose choices these follow: a multiply-add is two operations, training is
the forward product and the two of the backward pass, nothing recomputed is
counted), nothing here looks at the program or at a trace.
"""


def expert_layers(config):
    """How many of the configuration's layers have experts."""
    return len(config["layer_types"]) - config["num_dense_layers"]


def even_rows(config, tokens):
    """Assignments an even routing sends to the experts held here, a layer:
    experts per token x tokens x the share of the published experts held."""
    share = config["num_experts"] / config["published"]["num_experts"]
    return config["num_experts_per_tok"] * tokens * share


def grouped_products(config, rows, bytes_per_elem=2):
    """(operations, bytes) of ONE expert layer's grouped products in a
    training step over ``rows`` assignments: gate and up as one product of
    hidden x 2 expert-width, down as expert-width x hidden, each three
    times (forward, the rows' gradient, the weights' gradient). Bytes: each
    of the six reads or writes the held experts' weights once and its rows
    in and out."""
    d, m, held = (
        config["hidden_size"], config["moe_intermediate_size"],
        config["num_experts"],
    )
    ops = nbytes = 0
    for k, n in ((d, 2 * m), (m, d)):
        ops += 3 * 2 * rows * k * n
        nbytes += 3 * (held * k * n + rows * (k + n)) * bytes_per_elem
    return ops, nbytes
