"""Layer: set-up (``setup_parts.py``). Seconds of the set-up under
``train.py::main``'s five ``main_*`` spans (distributed init, mesh and
compile cache; datasets; model and partitioner; loaders, optimizer and
Trainer), less the ``compile:`` spans inside them. Notes: seconds by
innermost span."""

from layer_metrics import program_spans as ps
from layer_metrics import setup_parts


def read(run):
    parts = setup_parts.read_parts(run)
    if parts is None:
        return None
    value = parts["ns"]["main"] / 1e9
    ps.say(run, "setup_main_s: by innermost span: %s; compile spans inside "
           "them %.3f s" % (
               setup_parts.largest_first(parts["names"]["main"]),
               parts["compile_in"].get("main", 0) / 1e9,
           ))
    return value
