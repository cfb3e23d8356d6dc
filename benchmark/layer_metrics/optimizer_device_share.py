"""Layer: train step (``train/step.py``). Device time of the ops under the
program's ``optimizer`` scope (``optimizer.update`` and
``optax.apply_updates``), as self time on device 0 over the traced window
of whole steps, in per cent."""

from layer_metrics import scope_ops


def read(run):
    return scope_ops.share(run, "optimizer")
