"""Layer: set-up (``setup_parts.py``). Seconds of the set-up under no
program span before ``train.py::main`` opens ``main_args``: under
``benchmark/run.py`` mostly the backend's start (``jax.devices()`` in
``harness.find_chips``) and the driver's own imports; in a user's
``python train.py`` run the backend starts inside ``main_runtime``. Notes:
where the set-up's start was taken from, and the seconds before the first
``import`` row and after it."""

from layer_metrics import program_spans as ps
from layer_metrics import setup_parts


def read(run):
    parts = setup_parts.read_parts(run)
    if parts is None:
        return None
    value = parts["ns"]["before_main"] / 1e9
    before = parts["before_import"] / 1e9
    ps.say(run, "setup_before_main_s: from %s: %.3f s before the first import "
           "row, %.3f s between the imports and main_args" % (
               parts["whence"], before, value - before,
           ))
    return value
