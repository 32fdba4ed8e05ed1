"""Seconds from the start of the process until the gateway is ready to
serve: weights made on the device, the router loaded (or, in a
configuration's first run, its support set embedded and the router
fitted), every shape of the cell compiled or loaded from the compile cache,
and one request served."""


def read(run):
    return run.setup_s
