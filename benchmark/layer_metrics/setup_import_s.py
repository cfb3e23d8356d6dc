"""Layer: set-up (``setup_parts.py``). Seconds of the set-up under the
``import`` rows: the package's import, JAX's among it
(``distributed_pytorch_example_tpu/__init__.py``), and ``train.py``'s
module body, less the ``compile:`` spans inside them. Notes: each row's
wall and process CPU seconds."""

from layer_metrics import program_spans as ps
from layer_metrics import setup_parts


def read(run):
    parts = setup_parts.read_parts(run)
    if parts is None:
        return None
    value = parts["ns"]["import"] / 1e9
    rows = sorted(parts["imports"], key=lambda r: r.start_ns)
    ps.say(run, "setup_import_s: %d import rows, wall / process CPU s: %s; "
           "compile spans inside them %.3f s" % (
               len(rows), ", ".join(
                   "%.3f / %.3f" % (
                       (r.end_ns - r.start_ns) / 1e9,
                       r.args["cpu_close_s"] - r.args["cpu_open_s"],
                   ) for r in rows
               ), parts["compile_in"].get("import", 0) / 1e9,
           ))
    return value
