"""Process start to window start: imports, device set-up, weights made on
the device, compilation or cache load, tuning, warm prompts and every
client's first request."""


def read(run):
    return run.setup_s
