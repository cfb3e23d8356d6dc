"""Layer: experts (``models/moe.py``). The grouped products' share of their
roofline: the least time the chip could take for the assignments the
routing sent to the experts held here (``moe_flops.grouped_products``:
every expert layer's gate+up and down products, forward and the two
backward products, nothing recomputed) over the device time under scope
``moe_experts`` a step, in per cent. It reads the scope and not a kernel's
name, so that it measures the same work whatever implements the product.

The rows are the routing's own, from the program's counters (the window's
``moe_counters`` rows: ``held_share`` x experts per token x tokens, the
layers' mean), not the even share that ``step_mfu`` assumes: the selection
bias is drawn from the seed, a seed's held experts may be chosen for 0.15
or 0.35 of the assignments where even is 0.25, and a share of a roofline
counted for rows that were never multiplied would pass 100%. A window in
which the routing sent nothing here (a share's experts starved: PERF.md
section 6, PR 29) reads 0: no product was due, and the seconds under the
scope are passes over empty rows. A program without the counters or the
scope gives None."""

import flops
import moe_flops
from layer_metrics import moe_counters, named_scopes


def read(run):
    trace = run["trace"]
    under = named_scopes.seconds(run, ("moe_experts",))
    held_share = moe_counters.mean(run, "held_share")
    if not under or held_share is None or trace.steps() < 1:
        return None
    config, tokens = run["config"], run["tokens_per_step"]
    rows = held_share * config["num_experts_per_tok"] * tokens
    least, bound = 0.0, "nothing"
    if rows:
        ops, nbytes = moe_flops.grouped_products(config, rows)
        least, bound = flops.roofline_seconds(ops, nbytes, run["peak"])
    layers = moe_flops.expert_layers(config)
    a_step = under / trace.steps()
    run.setdefault("notes", []).append(
        f"moe_experts_roofline: {layers} expert layers, {rows:.0f} rows a "
        f"layer as routed (even routing: "
        f"{moe_flops.even_rows(config, tokens):.0f}), least "
        f"{least * 1e3:.3f} ms a layer ({bound}-bound), "
        f"{a_step * 1e3:.3f} ms a step under scope moe_experts"
    )
    return 100.0 * layers * least / a_step
