"""Layer: input (``data/loader.py``). Share of the traced window that the
training thread spent inside ``next()`` on the loader, in per cent, from the
benchmark's own wrapper around the ``DeviceLoader``."""


def read(run):
    if not run.get("traced_host_s"):
        return None
    return 100.0 * run["traced_wait_s"] / run["traced_host_s"]
