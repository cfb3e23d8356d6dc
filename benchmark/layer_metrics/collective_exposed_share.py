"""Layer: parallelism (``parallel/``). Time in collective operations during
which no other operation runs on that chip, over the traced window, on the
chip where it is largest, in per cent. Nothing to read where no collective
ran (one chip)."""


def read(run):
    share = run["trace"].collective_exposed_share_worst()
    return None if share is None else 100.0 * share
