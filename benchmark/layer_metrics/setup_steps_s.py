"""Layer: set-up (``setup_parts.py``). Seconds of the set-up under the
``fit`` calls that closed before the window's (``train.main``'s own, where
``init_state`` runs; the three compared calls; the 12-step warm-up), less
the ``compile:`` spans inside them. Notes: each call's wall seconds and the
seconds by innermost span (``init_state``, ``record_compile``, ``step``,
``data_load``, the wait spans, ``epoch_drain``, ...)."""

from layer_metrics import program_spans as ps
from layer_metrics import setup_parts


def read(run):
    parts = setup_parts.read_parts(run)
    if parts is None:
        return None
    value = parts["ns"]["steps"] / 1e9
    ps.say(run, "setup_steps_s: %d fit calls of %s s; by innermost span: %s; "
           "compile spans inside them %.3f s" % (
               len(parts["fits"]), ", ".join(
                   f"{(r.end_ns - r.start_ns) / 1e9:.3f}" for r in parts["fits"]
               ), setup_parts.largest_first(parts["names"]["steps"]),
               parts["compile_in"].get("steps", 0) / 1e9,
           ))
    return value
