"""Layer: device. One minus the union of the op intervals over the traced
window of whole steps, on the chip where that is largest, in per cent."""


def read(run):
    return 100.0 * run["trace"].idle_share_worst()
