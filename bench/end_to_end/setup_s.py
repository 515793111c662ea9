"""Process start to the first timed request, in s: content generation,
ingest, broker start, warm-up and any compilation."""


def read(run):
    return run.setup_s
