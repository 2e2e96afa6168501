"""Process start to the first request of the window: data generation,
deployment, staging, placement and warm-up, with any compiling."""


def read(rec):
    return rec["setup_s"]
